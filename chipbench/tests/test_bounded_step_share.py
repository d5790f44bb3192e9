"""moe.bounded_step_share (PR 38) on registries as a window records them
(`run["registry"]`: close minus open, series named as the program's registry
renders them): two routed layers over 36 steps.

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q
"""

import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LAYERS = ("nemotron_h.h1.moe", "nemotron_h.h3.moe")
HELD = {'pt_moe_held_pairs_total{expert="%d",layer="%s"}' % (e, layer): 900.0
        for layer in LAYERS for e in range(8)}
EVERY = {'pt_moe_expert_tokens_total{expert="%d",layer="%s"}' % (e, layer):
         13824.0 for layer in LAYERS for e in range(128)}


def _paths(bounded, whole):
    series = 'pt_moe_row_path_total{layer="%s",path="%d"}'
    return {series % (layer, path): steps for layer in LAYERS
            for path, steps in ((0, bounded), (1, whole))}


def _reader():
    path = os.path.join(HERE, "layer_metrics", "moe.bounded_step_share.py")
    spec = importlib.util.spec_from_file_location("t_bounded_step_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("registry,share,steps", [
    # every step of both layers in one chunk of the bounded rows
    ({**EVERY, **HELD, **_paths(36.0, 0.0)}, 1.0, (72.0, 0.0)),
    # both layers' routing needed a second chunk in 9 of their 36 steps
    ({**EVERY, **HELD, **_paths(27.0, 9.0)}, 0.75, (54.0, 18.0)),
    # a share whose bound reaches all its rows publishes path 1 alone
    ({**EVERY, **HELD, **_paths(0.0, 36.0)}, 0.0, (0.0, 72.0)),
    # a share of the experts from before the path counter (the parent of
    # PR 38): all its rows in every step
    ({**EVERY, **HELD}, 0.0, (0.0, 0.0)),
    # every expert held (olmoe), no routed op (gpt2), no registry: nothing
    (EVERY, None, (0.0, 0.0)),
    ({"pt_executor_donated_bytes": 8.0e9}, None, (0.0, 0.0)),
    (None, None, (0.0, 0.0)),
], ids=["bounded", "spilled_a_quarter", "whole", "held_pairs_alone",
        "every_expert_held", "no_routed_op", "no_registry"])
def test_bounded_step_share_reads_the_path_counter(registry, share, steps):
    reader = _reader()
    run = {"registry": registry, "steps": 36}
    assert reader.compute(run) == share
    assert reader.info(run) == {"bounded_steps": steps[0],
                                "spilled_steps": steps[1]}
