"""nemotron.moe_dispatch_ms (layer: Routed experts). `moe.dispatch_ms` on the
nemotron-3-nano-30b-a3b cells: what routing costs beyond the matmuls. Of
`moe.device_ms`'s rows (leaf rows under a routed-FFN op's scope) everything
that is neither a grouped-matmul kernel nor under the op's inner scope
`shared` (the shared expert's two dense matmuls, which this configuration's
op runs beside the routed ones and which are no part of routing): the
float32 router, sigmoid and top-k, the sort of the T x k (token, slot) pairs
with the held ones first, the gathers there and back, the masks on both
sides of the kernels, the casts and pads of the stacks, relu^2 and the
gate-weighted sum; forward (every emission) and backward, ms a step. Its
rows span ALL T x k pairs where a sixteenth are live: the lever PERF.md
section 7 names. `moe.dispatch_ms`'s manifest entry lists the olmoe cell, so
this configuration reads through a name of its own (PERF.md section 7).
Nothing to read where `moe.device_ms` has nothing."""

from chipbench.readers import load_reader


def compute(run):
    moe = load_reader("moe.device_ms")
    mine = moe.rows(run)
    if not mine:
        return None
    return sum(r["ns"] for r in mine if not moe.is_kernel(r)
               and "shared" not in r["op_name"].split("/")
               ) / 1e6 / run["steps"]
