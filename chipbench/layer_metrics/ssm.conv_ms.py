"""ssm.conv_ms (layer: State-space mixers). Device time per step in the short
causal convolution in front of the scans, its bias and its `silu`: of
`ssm.device_ms`'s rows, those under the mixer op's inner `conv` scope
(forward, the forward again inside the checkpoint's backward, and the backward
with the sums that give the taps' and the bias's gradients), over the window's
steps. Its `info` gives the passes, whether a kernel (`tpu_custom_call`) or XLA
runs them, and the path the program's registry counted
(`pt_ssm_conv_dispatch_total{path}`, where the program has that counter).
Nothing to read where `ssm.device_ms` finds nothing."""

from chipbench.readers import load_reader

MIXER = "ssm.device_ms"


def rows(run):
    mixer = load_reader(MIXER)
    return [r for r in mixer.rows(run) if mixer.inner_scope(r) == "conv"]


def compute(run):
    mine = rows(run)
    if not mine:
        return None
    return sum(r["ns"] for r in mine) / 1e6 / run["steps"]


def info(run):
    mixer = load_reader(MIXER)
    by_pass, kernels = {}, 0.0
    for r in rows(run):
        ms = r["ns"] / 1e6 / run["steps"]
        which = mixer.which_pass(r)
        by_pass[which] = by_pass.get(which, 0.0) + ms
        if r["target"] == "tpu_custom_call":
            kernels += ms
    registry = run.get("registry") or {}
    return {"by_pass_ms": by_pass, "kernels_ms": kernels,
            "run_by": "kernels" if kernels else "xla",
            "dispatch": {k: v for k, v in registry.items()
                         if k.startswith("pt_ssm_conv_dispatch_total")}}
