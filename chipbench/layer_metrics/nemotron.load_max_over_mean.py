"""nemotron.load_max_over_mean (layer: Routed experts). `moe.load_max_over_mean`
where the router scores more experts than the chip holds: the busiest
expert's (token, slot) pairs over the mean expert's, over the window, the
worst routed layer's, from `pt_moe_expert_tokens_total{layer,expert}` (all
`router_experts` the router scores, held or not: the counter is of the
choice, not of the work). 1.0 is an even load. The wrapped reader divides by
the config's `num_experts`, a key this configuration does not have (its
published keys are `n_routed_experts`, here the 8 held, and `router_experts`
the 128 scored), so the count is this file's; the check that nothing was
dropped is the wrapped reader's to the letter: each layer's counters must sum
to steps x tokens a step x `num_experts_per_tok`, else the reader raises.
Nothing to read where the registry has no such counter."""

from chipbench.readers import load_reader

WRAPS = "moe.load_max_over_mean"


def compute(run):
    layers = load_reader(WRAPS).per_layer(run.get("registry"))
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
        mean = total / int(cfg["router_experts"])  # experts never chosen count
        worst = max(worst, max(counts.values()) / mean)
    return worst
