"""kernel.short_conv_roofline (layer: Kernels). The gated short convolutions'
share of their roofline: the least time the chip could take for what one
step's operators need between their two GEMMs (`kernels/gated_short_conv.py`:
the op's operands and results, 11 T d bf16 elements an operator; by
`roofline.share` from `peaks.json`) over the time per step the device spent
under the operators' inner `mix` scope (`conv.mix_ms`: the forward kernel, the
backward kernel and XLA's sum of dw's partial sums: all of it). Reported only
where kernels run those rows (`tpu_custom_call`s among them): a plain form that
XLA fuses into its neighbours has no boundary to count bytes at, and the metric
is then left out. `info` says which bound it is. Never clamped: over 100 the
count is wrong. Nothing to read where the step holds no such scope."""

from chipbench import roofline
from chipbench.kernels import gated_short_conv
from chipbench.readers import load_reader

MIX = "conv.mix_ms"


def share(run):
    """(percent, bound) or None."""
    mix = load_reader(MIX)
    if not any(r["target"] == "tpu_custom_call" for r in mix.rows(run)):
        return None
    flops, bytes_ = gated_short_conv.flops_and_bytes(run["config"],
                                                     run["cell"])
    return roofline.share(flops, bytes_, mix.compute(run) / 1e3,
                          run["device"]["kind"])


def compute(run):
    got = share(run)
    return None if got is None else got[0]


def info(run):
    flops, bytes_ = gated_short_conv.flops_and_bytes(run["config"],
                                                     run["cell"])
    return {"bound": share(run)[1], "flops_per_step": flops,
            "bytes_per_step": bytes_,
            "program_counted_bytes":
            (run.get("registry") or {}).get("pt_short_conv_bytes")}
