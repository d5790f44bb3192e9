"""lfm2.held_pair_share: `moe.held_pair_share` on the lfm2-24b-a2b cell, under a name of its own:
the share of the window's (token, slot) pairs that chose an expert this chip
holds: 0.125 under even routing (8 of 64); glm's drifted to 0.22 over a window. That reader's manifest entry lists the cells that were there, and a
`model_config` PR may not edit an entry that is there (PERF.md section 7): this
file only loads `moe.held_pair_share.py` by path and returns what it returns. A later
`benchmark` PR that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "moe.held_pair_share"


def compute(run):
    return load_reader(WRAPS).compute(run)


def info(run):
    return load_reader(WRAPS).info(run)
