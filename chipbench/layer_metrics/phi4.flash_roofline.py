"""phi4.flash_roofline (layer: Kernels). The flash-attention kernels' share of
their roofline where three layers of six are differential-attention layers:
the rows `kernel.flash_roofline` reads (the `tpu_custom_call`s under a
`flash_attention.*` scope; that reader is loaded by path) over the operations
and bytes of `kernels/phi4_flash_attention.py` (per attention layer four
launches of 20 query heads over 10 K/V heads of 64, the window's pairs on the
windowed layer, T from the cell), where `kernels/flash_attention.py` would
count six layers of 40 heads once. Never clamped: over 100 the count is wrong.
Nothing to read where the step holds no such kernel."""

from chipbench import roofline
from chipbench.kernels import phi4_flash_attention
from chipbench.readers import load_reader

ROWS_OF = "kernel.flash_roofline"


def _rows_and_need(run):
    mine = load_reader(ROWS_OF).rows(run)
    return mine, phi4_flash_attention.flops_and_bytes(run["config"], run["cell"])


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
    return {"bound": share(run)[1], "flops_per_step": flops,
            "bytes_per_step": bytes_, "kernels_per_step":
            sum(r["count"] for r in mine) / run["steps"],
            "kernel_ms_per_step":
            sum(r["ns"] for r in mine) / 1e6 / run["steps"]}
