"""keye.donated_gib: `step.donated_gib` on the keye-vl-2.0-30b-a3b cell, under a
name of its own: the bytes the step program's donated arguments hold (weights
and Adam state, rebound in place), GiB. That reader's manifest entry lists the
cells that were there, and a `model_config` PR may not edit an entry that is
there (PERF.md section 7 item 3): this file only loads `step.donated_gib.py` by
path and returns what it returns. A later `benchmark` PR that drops the
`workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "step.donated_gib"


def compute(run):
    return load_reader(WRAPS).compute(run)
