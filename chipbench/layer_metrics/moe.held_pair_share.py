"""moe.held_pair_share (layer: Routed experts). The share of the window's
(token, slot) pairs that this chip's share of the routed layers computed:
`pt_moe_held_pairs_total{layer,expert}` (the pairs that chose a held expert)
over `pt_moe_expert_tokens_total{layer,expert}` (all pairs, over every expert
the router scores), both from the program's metrics registry
(`run["registry"]`: close minus open over the window), summed over the routed
layers. With even routing it is held / experts (8 / 128 = 0.0625); the
grouped matmuls' work follows it, the sort's, gathers' and combine's rows do
not (they span all pairs). Also the check that nothing was dropped: each
layer's pairs must sum to steps x tokens a step x `num_experts_per_tok`, else
the reader raises. Nothing to read where the registry has no held-pairs
counter (every expert held, or a program from before the counter)."""

import re

ALL, HELD = "pt_moe_expert_tokens_total", "pt_moe_held_pairs_total"
_LABEL = re.compile(r'(\w+)="([^"]*)"')


def per_layer(registry, family):
    """{layer: pairs in the window}."""
    out = {}
    for series, value in (registry or {}).items():
        if series.startswith(family + "{"):
            layer = dict(_LABEL.findall(series))["layer"]
            out[layer] = out.get(layer, 0.0) + value
    return out


def compute(run):
    held = per_layer(run.get("registry"), HELD)
    if not held:
        return None
    every = per_layer(run.get("registry"), ALL)
    cfg, cell = run["config"], run["cell"]
    want = (run["steps"] * int(cell["batch"]) * int(cell["seqlen"])
            * int(cfg["num_experts_per_tok"]))
    for layer in held:
        if every.get(layer) != want:
            raise ValueError(
                f"layer {layer}: {every.get(layer)} (token, slot) pairs "
                f"counted in the window, {want} routed ({run['steps']} "
                f"steps): tokens were dropped or counted twice")
    return sum(held.values()) / sum(every[layer] for layer in held)


def info(run):
    held = per_layer(run.get("registry"), HELD)
    every = per_layer(run.get("registry"), ALL)
    return {"by_layer": {layer: held[layer] / every[layer] for layer in held},
            "held_pairs_per_step": sum(held.values()) / run["steps"]}
