"""nemotron.flash_roofline (layer: Kernels). The flash-attention kernels'
share of their roofline on a configuration whose depth is a pattern of block
kinds and whose K/V heads are fewer than its query heads: the rows
`kernel.flash_roofline` reads (the `tpu_custom_call`s under a
`flash_attention.*` scope; that reader is loaded by path) over the
operations and bytes of `kernels/nemotron_flash_attention.py`, which counts
the pattern's attention blocks and the K/V heads' bytes where
`kernels/flash_attention.py` would multiply by `num_hidden_layers`. Never
clamped. Nothing to read where the step holds no such kernel."""

from chipbench import roofline
from chipbench.kernels import nemotron_flash_attention
from chipbench.readers import load_reader

ROWS_OF = "kernel.flash_roofline"


def share(run):
    """(percent, bound) or None."""
    mine = load_reader(ROWS_OF).rows(run)
    if not mine:
        return None
    seconds = sum(r["ns"] for r in mine) / 1e9 / run["steps"]
    flops, bytes_ = nemotron_flash_attention.flops_and_bytes(
        run["config"], run["cell"])
    return roofline.share(flops, bytes_, seconds, run["device"]["kind"])


def compute(run):
    got = share(run)
    return None if got is None else got[0]


def info(run):
    mine = load_reader(ROWS_OF).rows(run)
    flops, bytes_ = nemotron_flash_attention.flops_and_bytes(
        run["config"], run["cell"])
    return {"bound": share(run)[1], "flops_per_step": flops,
            "bytes_per_step": bytes_, "kernels_per_step":
            sum(r["count"] for r in mine) / run["steps"],
            "kernel_ms_per_step":
            sum(r["ns"] for r in mine) / 1e6 / run["steps"]}
