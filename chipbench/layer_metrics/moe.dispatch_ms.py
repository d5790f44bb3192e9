"""moe.dispatch_ms (layer: Routed experts). What routing costs beyond the
matmuls: of `moe.device_ms`'s rows (leaf rows under a routed-FFN op's scope),
everything that is NOT a grouped-matmul kernel: the float32 router, softmax
and top-k, the sort of the (token, slot) pairs, the gathers there and back,
the casts of the expert weights to the compute type, silu * up and the
gate-weighted sum; forward (both emissions) and backward, ms a step.
Nothing to read where `moe.device_ms` has nothing."""

from chipbench.readers import load_reader


def compute(run):
    moe = load_reader("moe.device_ms")
    mine = moe.rows(run)
    if not mine:
        return None
    return sum(r["ns"] for r in mine if not moe.is_kernel(r)) / 1e6 / run["steps"]
