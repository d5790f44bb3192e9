"""attn.assemble_ms (layer: Window attention). Of the gated attention layers'
rows (`attn.window_ms.py:layers`: each layer's ops found by structure from its
`flash_attention` op), what is neither a GEMM (a row under one of the layer's
five projections: `q_proj`, `k_proj`, `v_proj`, `gate_proj`, `out_proj`) nor a
`tpu_custom_call` (the attention kernels): the per-head norms of Q and K, the
rotary passes of the window layers, the gate's sigmoid and its multiply, and
whatever XLA runs around the kernels under their scope (the sum of a group's
dK and dV over its 8 query heads, reshapes and copies); forward and backward,
window and global layers alike, ms a step. This is what a later `perf_opt`
would fuse. `info` splits it by part. Nothing to read where `attn.window_ms`
finds no gated attention layer."""

from chipbench.readers import load_reader

GEMMS = ("q_proj", "k_proj", "v_proj", "gate_proj", "out_proj")


def rows(run):
    """[(row, part)] of the leaf rows of the gated attention layers that are
    neither a projection's nor a kernel."""
    ops = (run.get("trace") or {}).get("ops")
    if not ops or not run.get("program_ops"):
        return None
    part_of = {}
    for layer in load_reader("attn.window_ms").layers(run["program_ops"]):
        part_of.update(layer["parts"])
    if not part_of:
        return None
    return [(r, part_of[r["scope"]]) for r in ops
            if not r["container"] and r["scope"] in part_of
            and part_of[r["scope"]] not in GEMMS
            and r["target"] != "tpu_custom_call"]


def compute(run):
    mine = rows(run)
    if mine is None:
        return None
    return sum(r["ns"] for r, _ in mine) / 1e6 / run["steps"]


def info(run):
    by_part = {}
    for r, part in rows(run):
        part = "around_kernels" if part == "kernels" else part
        by_part[part] = by_part.get(part, 0.0) + r["ns"] / 1e6 / run["steps"]
    return {"by_part_ms": by_part}
