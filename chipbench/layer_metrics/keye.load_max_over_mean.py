"""keye.load_max_over_mean: `nemotron.load_max_over_mean` on the keye-
vl-2.0-30b-a3b cell, under a name of its own: the busiest expert's tokens over
the mean over the 128 scored, per layer, from the window's
`pt_moe_expert_tokens_total`. That reader's manifest entry lists the cells that
were there, and a `model_config` PR may not edit an entry that is there
(PERF.md section 7 item 3): this file only loads
`nemotron.load_max_over_mean.py` by path and returns what it returns. A later
`benchmark` PR that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "nemotron.load_max_over_mean"


def compute(run):
    return load_reader(WRAPS).compute(run)
