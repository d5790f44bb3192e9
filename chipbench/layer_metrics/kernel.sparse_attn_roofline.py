"""kernel.sparse_attn_roofline (layer: Kernels). The sparse attention's
kernels' share of their roofline: the least time the chip could take for what
one step's attention over the KEPT keys needs
(`kernels/sparse_attention.py`: six matmuls over the kept pairs of 32 heads
of 128, every layer; by `roofline.share` from `peaks.json`) over the time per
step the device spent in the `tpu_custom_call`s under a `sparse_attention` op's
scope (`dsa.device_ms`'s rows of part `kernels`: the forward and the fused
backward launches). The count is of the kept pairs whatever computes them, so
kernels that compute every causal pair and mask read about the kept share
(`dsa.kept_pair_share`) of their dense reading. Never clamped: over 100 the
count is wrong. Nothing to read where the step holds no such kernel."""

from chipbench import roofline
from chipbench.kernels import sparse_attention
from chipbench.readers import load_reader

LAYER = "dsa.device_ms"


def rows(run):
    return [r for r, part, _ in load_reader(LAYER).rows(run)
            if part == "kernels" and r["target"] == "tpu_custom_call"]


def share(run):
    """(percent, bound) or None."""
    mine = rows(run)
    if not mine:
        return None
    flops, bytes_ = sparse_attention.flops_and_bytes(run["config"],
                                                     run["cell"])
    seconds = sum(r["ns"] for r in mine) / 1e9 / run["steps"]
    return roofline.share(flops, bytes_, seconds, run["device"]["kind"])


def compute(run):
    got = share(run)
    return None if got is None else got[0]


def info(run):
    mine = rows(run)
    flops, bytes_ = sparse_attention.flops_and_bytes(run["config"],
                                                     run["cell"])
    return {"bound": share(run)[1], "flops_per_step": flops,
            "bytes_per_step": bytes_,
            "kernels_per_step": sum(r["count"] for r in mine) / run["steps"],
            "kernel_ms_per_step":
            sum(r["ns"] for r in mine) / 1e6 / run["steps"]}
