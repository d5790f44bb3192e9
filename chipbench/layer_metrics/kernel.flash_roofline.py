"""kernel.flash_roofline (layer: Kernels). The flash-attention kernels'
share of their roofline: the least time the chip could take for what one
step's causal attention needs (`kernels/flash_attention.py`, by
`roofline.share` from `peaks.json`) over the time per step the device spent
in the `tpu_custom_call`s under a `flash_attention.*` scope (forward, a
forward emitted twice, dQ, dK/dV: all of it). Which bound it is is printed
on the run's `info` line. Never clamped: over 100 the count is wrong.
Nothing to read where the step holds no such kernel."""

from chipbench import roofline
from chipbench.kernels import flash_attention

SCOPE = "flash_attention."


def rows(run):
    ops = (run.get("trace") or {}).get("ops") or ()
    return [r for r in ops if r["target"] == "tpu_custom_call"
            and r["scope"].startswith(SCOPE)]


def share(run):
    """(percent, bound) or None."""
    mine = rows(run)
    if not mine:
        return None
    seconds = sum(r["ns"] for r in mine) / 1e9 / run["steps"]
    flops, bytes_ = flash_attention.flops_and_bytes(run["config"], run["cell"])
    return roofline.share(flops, bytes_, seconds, run["device"]["kind"])


def compute(run):
    got = share(run)
    return None if got is None else got[0]


def info(run):
    mine = rows(run)
    flops, bytes_ = flash_attention.flops_and_bytes(run["config"], run["cell"])
    return {"bound": share(run)[1], "flops_per_step": flops,
            "bytes_per_step": bytes_, "kernels_per_step":
            sum(r["count"] for r in mine) / run["steps"],
            "kernel_ms_per_step":
            sum(r["ns"] for r in mine) / 1e6 / run["steps"]}
