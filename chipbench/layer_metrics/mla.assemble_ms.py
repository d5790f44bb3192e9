"""mla.assemble_ms (layer: Latent attention). Of `mla.device_ms`'s rows, what
is neither a GEMM (a row under one of the layer's five projections: `q_down`,
`q_up`, `kv_down`, `kv_up`, `out`) nor a `tpu_custom_call` (the attention
kernels): the two latent norms, the rotary passes over Q's last 64 lanes and
the one-head key, the split of the down-projection into latent and rotary
key, the split of the up-projection's output into k_n and v, k_r laid beside
20 heads, and whatever XLA runs around the kernels under their scope; forward
and backward, ms a step. This is what a later `perf_opt` would fold into the
kernels' index maps (K as two operands, V a strided view). `info` splits it by
part. Nothing to read where `mla.device_ms` has nothing."""

from chipbench.readers import load_reader

GEMMS = ("q_down", "q_up", "kv_down", "kv_up", "out")


def rows(run):
    return [(r, part) for r, part in load_reader("mla.device_ms").rows(run)
            if part not in GEMMS and r["target"] != "tpu_custom_call"]


def compute(run):
    if not load_reader("mla.device_ms").rows(run):
        return None
    return sum(r["ns"] for r, _ in rows(run)) / 1e6 / run["steps"]


def info(run):
    by_part = {}
    for r, part in rows(run):
        by_part[part] = by_part.get(part, 0.0) + r["ns"] / 1e6 / run["steps"]
    return {"by_part_ms": by_part}
