"""olmoe.flash_roofline: `kernel.flash_roofline` on the olmoe-1b-7b cells, under a name of its own. That
reader's manifest entry lists the gpt2-small cells, and a `model_config` PR
may not edit an entry that is there (PERF.md section 7): this file only
loads `kernel.flash_roofline.py` by path and returns what its `compute(run)` returns, so
the shared code (see that file's docstring for what is measured) is seen on
this configuration too. A later `benchmark` PR that drops the `workloads`
list of `kernel.flash_roofline` retires this file."""

from chipbench.readers import load_reader

WRAPS = "kernel.flash_roofline"


def compute(run):
    return load_reader(WRAPS).compute(run)


def info(run):
    return load_reader(WRAPS).info(run)
