"""moe.load_max_over_mean (layer: Routed experts). How uneven the routing
was over the window: the busiest expert's (token, slot) pairs over the mean
expert's, from the `pt_moe_expert_tokens_total{layer,expert}` counters of
the program's metrics registry (`run["registry"]`: close minus open over
the window), the worst routed layer's. 1.0 is an even load; the grouped
matmul's time follows the sum, the tail of an expert-parallel layout would
follow the maximum. Also the check that nothing was dropped: each layer's
counters must sum to steps x tokens a step x `num_experts_per_tok`, else the
reader raises. Nothing to read where the registry has no such counter."""

import re

FAMILY = "pt_moe_expert_tokens_total"
_LABEL = re.compile(r'(\w+)="([^"]*)"')


def per_layer(registry):
    """{layer: {expert: pairs in the window}}."""
    out = {}
    for series, value in (registry or {}).items():
        if not series.startswith(FAMILY + "{"):
            continue
        labels = dict(_LABEL.findall(series))
        out.setdefault(labels["layer"], {})[labels["expert"]] = value
    return out


def compute(run):
    layers = per_layer(run.get("registry"))
    if not layers:
        return None
    cfg, cell = run["config"], run["cell"]
    want = (run["steps"] * int(cell["batch"]) * int(cell["seqlen"])
            * int(cfg["num_experts_per_tok"]))
    worst = 0.0
    for layer, counts in layers.items():
        total = sum(counts.values())
        if total != want:
            raise ValueError(
                f"layer {layer}: {total} (token, slot) pairs counted in the "
                f"window, {want} routed ({run['steps']} steps): tokens were "
                f"dropped or counted twice")
        mean = total / int(cfg["num_experts"])   # experts never chosen count
        worst = max(worst, max(counts.values()) / mean)
    return worst
