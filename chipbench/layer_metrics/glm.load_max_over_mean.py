"""glm.load_max_over_mean (layer: Routed experts). The busiest expert's (token,
slot) pairs over the mean expert's, over the window, the worst routed layer's,
over ALL the `router_experts` (64) the router scores, held or not: what
`nemotron.load_max_over_mean` computes for a chip's share of the experts (the
count by `router_experts`, the check that each layer's counters sum to steps x
tokens x `num_experts_per_tok`), under a name of its own because that reader's
manifest entry lists the nemotron cell and a `model_config` PR may not edit an
entry that is there (PERF.md section 7). This file loads
`nemotron.load_max_over_mean.py` by path and returns what its `compute(run)`
returns. 1.0 is an even load."""

from chipbench.readers import load_reader

WRAPS = "nemotron.load_max_over_mean"


def compute(run):
    return load_reader(WRAPS).compute(run)
