"""keye.moe_dispatch_ms: `nemotron.moe_dispatch_ms` on the keye-vl-2.0-30b-a3b
cell, under a name of its own: of the routed-FFN ops' rows, those that are
neither the grouped-matmul kernels nor the router: the sort, the gathers, the
chunks' masks and the combine (no shared expert to leave out here), ms a step.
That reader's manifest entry lists the cells that were there, and a
`model_config` PR may not edit an entry that is there (PERF.md section 7 item
3): this file only loads `nemotron.moe_dispatch_ms.py` by path and returns what
it returns. A later `benchmark` PR that drops the `workloads` lists retires
this file."""

from chipbench.readers import load_reader

WRAPS = "nemotron.moe_dispatch_ms"


def compute(run):
    return load_reader(WRAPS).compute(run)
