"""lfm2.moe_dispatch_ms: `nemotron.moe_dispatch_ms` on the lfm2-24b-a2b cell, under a name of its own:
what routing costs beyond the matmuls: of the routed-FFN op's rows everything
that is neither a grouped-matmul kernel nor under the op's inner scope `shared`
(there is no shared expert here): the float32 router, sigmoid and top-4, the
sort of the T x 4 pairs with the held ones first, the gathers there and back,
the masks, the casts of the stacks, silu x up and the gate-weighted sum, forward
and backward, ms a step. That reader's manifest entry lists the cells that were there, and a
`model_config` PR may not edit an entry that is there (PERF.md section 7): this
file only loads `nemotron.moe_dispatch_ms.py` by path and returns what it returns. A later
`benchmark` PR that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "nemotron.moe_dispatch_ms"


def compute(run):
    return load_reader(WRAPS).compute(run)
