"""A kernel's share of its roofline: the least time the chip could take for
the operations and bytes the algorithm needs, over the time the kernel took.

    share(flops, bytes, seconds, device_kind) -> (percent, "compute" | "memory")

The least time is the larger of `flops / bf16_flops` and `bytes /
hbm_bytes_per_s` of `peaks.json` (an unknown `device_kind` is an error); the
second value says which of the two it was. Never clamped: a share over 100
means the operations or bytes are counted too high, or the time leaves out
part of the kernel's work, and has to be seen. The operations and bytes come
from `kernels/<kernel>.py:flops_and_bytes(config, cell)`, the seconds from
the device trace (`xplane.reduce`'s `ops`)."""

from __future__ import annotations

from chipbench import flops as _flops


def share(flops: float, bytes_: float, seconds: float, device_kind: str):
    if not seconds > 0:
        raise ValueError(f"a kernel that took {seconds} s has no share")
    peak = _flops.peaks(device_kind)
    compute_s = flops / float(peak["bf16_flops"])
    memory_s = bytes_ / float(peak["hbm_bytes_per_s"])
    bound = "compute" if compute_s >= memory_s else "memory"
    return 100.0 * max(compute_s, memory_s) / seconds, bound
