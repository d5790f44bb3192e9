"""kernel.gmm_roofline (layer: Kernels). The grouped-matmul kernels' share of
their roofline: the least time the chip could take for what one step's
routed experts need (`kernels/moe_grouped_matmul.py`, by `roofline.share`
from `peaks.json`) over the time per step the device spent in the
`tpu_custom_call`s under a routed-FFN op's scope (the forward gmm, a forward
emitted twice, the backward's gmm against the transposed weights and its
tgmm: all of it). Which bound it is is printed on the run's `info` line.
Never clamped: over 100 the count is wrong. Nothing to read where the step
holds no such kernel (on XLA:CPU the grouped matmul is `ragged_dot`)."""

from chipbench import roofline
from chipbench.kernels import moe_grouped_matmul
from chipbench.readers import load_reader


def rows(run):
    moe = load_reader("moe.device_ms")
    return [r for r in moe.rows(run) if moe.is_kernel(r)]


def share(run):
    """(percent, bound) or None."""
    mine = rows(run)
    if not mine:
        return None
    seconds = sum(r["ns"] for r in mine) / 1e9 / run["steps"]
    flops, bytes_ = moe_grouped_matmul.flops_and_bytes(run["config"], run["cell"])
    return roofline.share(flops, bytes_, seconds, run["device"]["kind"])


def compute(run):
    got = share(run)
    return None if got is None else got[0]


def info(run):
    mine = rows(run)
    flops, bytes_ = moe_grouped_matmul.flops_and_bytes(run["config"], run["cell"])
    return {"bound": share(run)[1], "flops_per_step": flops,
            "bytes_per_step": bytes_, "kernels_per_step":
            sum(r["count"] for r in mine) / run["steps"],
            "kernel_ms_per_step":
            sum(r["ns"] for r in mine) / 1e6 / run["steps"]}
