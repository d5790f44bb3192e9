"""glm.moe_dispatch_ms (layer: Routed experts). What routing costs beyond the
matmuls on the glm-4.7-flash cells: of `moe.device_ms`'s rows everything that
is neither a grouped-matmul kernel nor under the op's inner scope `shared`
(the shared expert's three dense matmuls, no part of routing): the float32
router, sigmoid and top-4, the sort of the T x 4 (token, slot) pairs with the
held ones first, the gathers there and back, the masks on both sides of the
kernels, the casts of the stacks, silu x up and the gate-weighted sum; forward
and backward (the share's recomputed forward with it), ms a step. Its rows
span ALL T x 4 pairs where an eighth are live. The count is
`nemotron.moe_dispatch_ms`'s, returned by path under a name of its own (that
entry lists the nemotron cell; PERF.md section 7)."""

from chipbench.readers import load_reader

WRAPS = "nemotron.moe_dispatch_ms"


def compute(run):
    return load_reader(WRAPS).compute(run)
