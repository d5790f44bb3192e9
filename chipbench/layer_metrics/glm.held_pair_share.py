"""glm.held_pair_share: `moe.held_pair_share` on the glm-4.7-flash cells, under a name of its own. That
reader's manifest entry lists the cells of the configurations that were
there, and a `model_config` PR may not edit an entry that is there (PERF.md
section 7): this file only loads `moe.held_pair_share.py` by path and returns what its
`compute(run)` returns, so the shared code (see that file's docstring for
what is measured) is seen on this configuration too. A later `benchmark` PR
that drops the `workloads` list of `moe.held_pair_share` retires this file."""

from chipbench.readers import load_reader

WRAPS = "moe.held_pair_share"


def compute(run):
    return load_reader(WRAPS).compute(run)


def info(run):
    return load_reader(WRAPS).info(run)
