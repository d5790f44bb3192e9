"""phi4.donated_gib: `step.donated_gib` on the phi-4-mini-flash-reasoning cell, under a name of its own:
what the step program takes by donation: the weights and Adam's state, 8.37
GB. That reader's manifest entry lists the cells that were there, and a
`model_config` PR may not edit an entry that is there (PERF.md section 7): this
file only loads `step.donated_gib.py` by path and returns what it returns. A later
`benchmark` PR that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "step.donated_gib"


def compute(run):
    return load_reader(WRAPS).compute(run)
