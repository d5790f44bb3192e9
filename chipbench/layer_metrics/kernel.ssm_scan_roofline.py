"""kernel.ssm_scan_roofline (layer: Kernels). The state-space scans' share
of their roofline: the least time the chip could take for what one step's
Mamba-2 scans need (`kernels/mamba2_scan.py`, by `roofline.share` from
`peaks.json`) over the time per step the device spent under the mixer ops'
inner `scan` scope (`ssm.scan_ms`: forward, a forward emitted twice, the
backward and its recomputation: all of it). It rates those rows whether XLA
runs them or a kernel does; `info` says which, and which bound it is. Never
clamped: over 100 the count is wrong. Nothing to read where the step holds
no such scope."""

from chipbench import roofline
from chipbench.kernels import mamba2_scan
from chipbench.readers import load_reader

SCAN = "ssm.scan_ms"


def share(run):
    """(percent, bound) or None."""
    ms = load_reader(SCAN).compute(run)
    if ms is None:
        return None
    flops, bytes_ = mamba2_scan.flops_and_bytes(run["config"], run["cell"])
    return roofline.share(flops, bytes_, ms / 1e3, run["device"]["kind"])


def compute(run):
    got = share(run)
    return None if got is None else got[0]


def info(run):
    flops, bytes_ = mamba2_scan.flops_and_bytes(run["config"], run["cell"])
    return {"bound": share(run)[1], "flops_per_step": flops,
            "bytes_per_step": bytes_,
            "run_by": load_reader(SCAN).info(run)["run_by"]}
