"""glm.moe_device_ms: `moe.device_ms` on the glm-4.7-flash cells, under a name
of its own (that reader's manifest entry lists the olmoe cell; PERF.md section
7): the leaf rows under a routed-FFN op's scope, forward and backward, ms a
step, as `nemotron.moe_device_ms` reads them, whose `compute` and `info` (the
inner scopes `route`, `dispatch`, `experts`, `combine` and `shared`, the
kernels' part, the passes) this file returns by path: the op is the same op,
here with three stacks and a SwiGLU shared expert of three matrices."""

from chipbench.readers import load_reader

WRAPS = "nemotron.moe_device_ms"


def compute(run):
    return load_reader(WRAPS).compute(run)


def info(run):
    return load_reader(WRAPS).info(run)
