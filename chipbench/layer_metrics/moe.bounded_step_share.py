"""moe.bounded_step_share (layer: Routed experts). The share of the window's
(routed layer, step) pairs in which a chip's share of the experts computed its
live rows in ONE chunk of its bound (`ops/moe_ops.py:row_bound`: two even
shares of the T x k (token, slot) pairs) and not in more, as a step does whose
live pairs exceed the bound: `pt_moe_row_path_total{layer,path}` from the
program's metrics registry (`run["registry"]`: close minus open over the
window), path 0 over paths 0 + 1, summed over the routed layers. 1.0: the
routing's gathers, masks and combine ran on R rows in every step. 0.0 where
the registry has `pt_moe_held_pairs_total` (a share of the experts) but no
path counter: a program whose shares run all their rows in every step.
Nothing to read where it has neither (every expert held, no routed op)."""

import re

PATH, HELD = "pt_moe_row_path_total", "pt_moe_held_pairs_total"
_LABEL = re.compile(r'(\w+)="([^"]*)"')


def by_path(registry):
    """{path: (layer, step) readings in the window}."""
    out = {}
    for series, value in (registry or {}).items():
        if series.startswith(PATH + "{"):
            path = dict(_LABEL.findall(series))["path"]
            out[path] = out.get(path, 0.0) + value
    return out


def compute(run):
    registry = run.get("registry") or {}
    steps = by_path(registry)
    if sum(steps.values()) > 0:
        return steps.get("0", 0.0) / sum(steps.values())
    if any(series.startswith(HELD + "{") for series in registry):
        return 0.0
    return None


def info(run):
    steps = by_path(run.get("registry"))
    return {"bounded_steps": steps.get("0", 0.0),
            "spilled_steps": steps.get("1", 0.0)}
