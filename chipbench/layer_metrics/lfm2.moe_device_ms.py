"""lfm2.moe_device_ms: `nemotron.moe_device_ms` on the lfm2-24b-a2b cell, under a name of its own:
the leaf rows under a routed-FFN op's scope, forward and backward, ms a step,
with that reader's `info` (the inner scopes `route`, `dispatch`, `experts`,
`combine`, the kernels' part, the passes): the op is the same op, here at T x 4
= 65 536 (token, slot) rows with three stacks of width 1536 and no shared
expert. That reader's manifest entry lists the cells that were there, and a
`model_config` PR may not edit an entry that is there (PERF.md section 7): this
file only loads `nemotron.moe_device_ms.py` by path and returns what it returns. A later
`benchmark` PR that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "nemotron.moe_device_ms"


def compute(run):
    return load_reader(WRAPS).compute(run)


def info(run):
    return load_reader(WRAPS).info(run)
