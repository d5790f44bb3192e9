"""keye.moe_device_ms: `nemotron.moe_device_ms` on the keye-vl-2.0-30b-a3b cell,
under a name of its own: the leaf rows under a routed-FFN op's scope, forward
and backward, ms a step, with that reader's `info` (the inner scopes `route`,
`dispatch`, `experts`, `combine`, the kernels' part, the passes): the op is the
same op, here a float32 softmax router over 128 with the top 8 renormalised, T
x 8 = 131 072 (token, slot) rows of which an eighth is live, three stacks of
width 768 and no shared expert. That reader's manifest entry lists the cells
that were there, and a `model_config` PR may not edit an entry that is there
(PERF.md section 7 item 3): this file only loads `nemotron.moe_device_ms.py` by
path and returns what it returns. A later `benchmark` PR that drops the
`workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "nemotron.moe_device_ms"


def compute(run):
    return load_reader(WRAPS).compute(run)


def info(run):
    return load_reader(WRAPS).info(run)
