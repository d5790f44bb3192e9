"""lfm2.flash_roofline (layer: Kernels). The flash-attention kernels' share of
their roofline where one layer of five is an attention layer: the rows
`kernel.flash_roofline` reads (the `tpu_custom_call`s under a
`flash_attention.*` scope; that reader is loaded by path) over the operations
and bytes of `kernels/lfm2_flash_attention.py` (the "full_attention" layers of
`layer_types` alone, 32 query heads over 8 K/V heads of 64, causal, T from the
cell), where `kernels/flash_attention.py` would count all five layers. Never
clamped: over 100 the count is wrong. Nothing to read where the step holds no
such kernel."""

from chipbench import roofline
from chipbench.kernels import lfm2_flash_attention
from chipbench.readers import load_reader

ROWS_OF = "kernel.flash_roofline"


def _rows_and_need(run):
    mine = load_reader(ROWS_OF).rows(run)
    return mine, lfm2_flash_attention.flops_and_bytes(run["config"], run["cell"])


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
