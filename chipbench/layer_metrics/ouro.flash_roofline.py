"""ouro.flash_roofline (layer: Kernels). The flash-attention kernels' share of
their roofline in a looped model: the least time the chip could take for what
one step's attention needs (`kernels/ouro_flash_attention.py`: K x L layer
applications, one forward and one backward each) over the time per step the
device spent in the `tpu_custom_call`s whose innermost Program scope
(`repeat.body_device_ms.py:body_rows`) is a `flash_attention` op of the
`repeat` op's body: forward, RECOMPUTED forward and backward, all of it. The
recomputed forward's time is in the seconds and its FLOPs and bytes count
nothing, so a sound reading stands under a model's whose layers run once by
about the recomputed share. Which bound it is is on the `info` line. Never
clamped: over 100 the count is wrong. Nothing to read where the loop's body
holds no such kernel."""

from chipbench import roofline
from chipbench.kernels import ouro_flash_attention
from chipbench.readers import load_reader

ATTN = "ouro.attn_device_ms"


def rows(run):
    return [(r, which) for r, which in load_reader(ATTN).rows(run)
            if r["target"] == "tpu_custom_call"]


def share(run):
    """(percent, bound) or None."""
    mine = rows(run)
    if not mine:
        return None
    seconds = sum(r["ns"] for r, _ in mine) / 1e9 / run["steps"]
    flops, bytes_ = ouro_flash_attention.flops_and_bytes(run["config"], run["cell"])
    return roofline.share(flops, bytes_, seconds, run["device"]["kind"])


def compute(run):
    got = share(run)
    return None if got is None else got[0]


def info(run):
    mine = rows(run)
    flops, bytes_ = ouro_flash_attention.flops_and_bytes(run["config"], run["cell"])
    by_pass = {}
    for r, which in mine:
        by_pass[which] = by_pass.get(which, 0.0) + r["ns"] / 1e6 / run["steps"]
    return {"bound": share(run)[1], "flops_per_step": flops,
            "bytes_per_step": bytes_, "kernels_per_step":
            sum(r["count"] for r, _ in mine) / run["steps"],
            "kernel_ms_per_step": sum(by_pass.values()),
            "kernel_ms_by_pass": by_pass}
