"""trinity.flash_roofline (layer: Kernels). The flash-attention kernels' share
of their roofline where window and global layers sit in one model: the rows
`kernel.flash_roofline` reads (the `tpu_custom_call`s under a
`flash_attention.*` scope, all five layers; that reader is loaded by path)
over the operations and bytes of `kernels/trinity_flash_attention.py` (a
layer's query-key pairs by its kind: T (T + 1) / 2 for a global layer, W (W +
1) / 2 + (T - W) W for a window layer; K/V bytes at the 4 K/V heads), where
`kernels/flash_attention.py` would count every layer causal. `info` gives the
share of each kind on its own kernels (`attn.window_ms`'s rows). Never
clamped: over 100 the count is wrong. Nothing to read where the step holds no
such kernel."""

from chipbench import roofline
from chipbench.kernels import trinity_flash_attention
from chipbench.readers import load_reader

ROWS_OF = "kernel.flash_roofline"


def _rows_and_need(run):
    mine = load_reader(ROWS_OF).rows(run)
    return mine, trinity_flash_attention.flops_and_bytes(
        run["config"], run["cell"])


def share(run):
    """(percent, bound) or None."""
    mine, (flops, bytes_) = _rows_and_need(run)
    if not mine:
        return None
    seconds = sum(r["ns"] for r in mine) / 1e9 / run["steps"]
    return roofline.share(flops, bytes_, seconds, run["device"]["kind"])


def compute(run):
    got = share(run)
    return None if got is None else got[0]


def info(run):
    mine, (flops, bytes_) = _rows_and_need(run)
    out = {"bound": share(run)[1], "flops_per_step": flops,
           "bytes_per_step": bytes_, "kernels_per_step":
           sum(r["count"] for r in mine) / run["steps"],
           "kernel_ms_per_step":
           sum(r["ns"] for r in mine) / 1e6 / run["steps"]}
    by_kind = load_reader("attn.window_ms").kernel_rows(run)
    names = {"window": "sliding_attention", "global": "full_attention"}
    for kind, rows in by_kind.items():
        kinds = [k for k in run["config"]["layer_types"] if k == names[kind]]
        need = trinity_flash_attention.flops_and_bytes(
            run["config"], run["cell"], kinds)
        seconds = sum(r["ns"] for r in rows) / 1e9 / run["steps"]
        out[f"{kind}_layers_pct"] = roofline.share(
            *need, seconds, run["device"]["kind"])[0]
    return out
