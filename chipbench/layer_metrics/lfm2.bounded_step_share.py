"""lfm2.bounded_step_share: `moe.bounded_step_share` on the lfm2-24b-a2b cell, under a name of its own:
the share of the window's (routed layer, step) pairs in which the share's live
rows ran in ONE chunk of their bound (2 x 65 536 / 8 = 16 384 rows) and not in
more. That reader's manifest entry lists the cells that were there, and a
`model_config` PR may not edit an entry that is there (PERF.md section 7): this
file only loads `moe.bounded_step_share.py` by path and returns what it returns. A later
`benchmark` PR that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "moe.bounded_step_share"


def compute(run):
    return load_reader(WRAPS).compute(run)


def info(run):
    return load_reader(WRAPS).info(run)
