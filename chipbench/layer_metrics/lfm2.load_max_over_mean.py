"""lfm2.load_max_over_mean: `nemotron.load_max_over_mean` on the lfm2-24b-a2b cell, under a name of its own:
the busiest expert's (token, slot) pairs over the mean expert's, the worst
routed layer's, over ALL the `router_experts` (64) the router scores, held or
not (that reader counts by `router_experts` and checks that each layer's
counters sum to steps x tokens x `num_experts_per_tok`); 1.0 is an even load. That reader's manifest entry lists the cells that were there, and a
`model_config` PR may not edit an entry that is there (PERF.md section 7): this
file only loads `nemotron.load_max_over_mean.py` by path and returns what it returns. A later
`benchmark` PR that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "nemotron.load_max_over_mean"


def compute(run):
    return load_reader(WRAPS).compute(run)
