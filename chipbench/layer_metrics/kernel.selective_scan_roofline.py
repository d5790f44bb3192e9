"""kernel.selective_scan_roofline (layer: Kernels). The Mamba-1 selective
scans' share of their roofline: the least time the chip could take for what
one step's scans need (`kernels/selective_scan.py`: the op's operands and
results and 27 C N operations a token; by `roofline.share` from `peaks.json`)
over the time per step the device spent under the mixer ops' inner `scan`
scope (`ssm1.scan_ms`: forward, the forward a checkpoint runs again, the
backward: all of it). Rows are picked by scope and not by `target`: it rates
those rows whether XLA runs them or a kernel does, and `info` says which. The
bound it reads against is MEMORY's, while the work is the vector unit's, for
which `peaks.json` has no row (`kernels/selective_scan.py` says what that
makes of the share). Never clamped: over 100 the count is wrong. Nothing to
read where the step holds no such scope."""

from chipbench import roofline
from chipbench.kernels import selective_scan
from chipbench.readers import load_reader

SCAN = "ssm1.scan_ms"


def share(run):
    """(percent, bound) or None."""
    ms = load_reader(SCAN).compute(run)
    if ms is None:
        return None
    flops, bytes_ = selective_scan.flops_and_bytes(run["config"], run["cell"])
    return roofline.share(flops, bytes_, ms / 1e3, run["device"]["kind"])


def compute(run):
    got = share(run)
    return None if got is None else got[0]


def info(run):
    flops, bytes_ = selective_scan.flops_and_bytes(run["config"], run["cell"])
    return {"bound": share(run)[1], "operations_per_step": flops,
            "bytes_per_step": bytes_,
            "program_counted_bytes":
            (run.get("registry") or {}).get("pt_selective_scan_bytes"),
            "run_by": load_reader(SCAN).info(run)["run_by"]}
