"""conv.mix_ms (layer: Short-conv operators). Device time per step in what lies
between an operator's two GEMMs: of `conv.device_ms`'s rows, those under the
op's inner `mix` scope (the gate B * X, the K taps, the gate C *, forward; the
backward that forms them again and gives dbcx and dw), over the window's steps.
Its `info` gives the passes, whether a kernel (`tpu_custom_call`) or XLA runs
them, and the path and bytes the program's registry counted
(`pt_short_conv_dispatch_total{path}`, `pt_short_conv_bytes`). Nothing to read
where `conv.device_ms` finds nothing."""

from chipbench.readers import load_reader

OPERATOR = "conv.device_ms"


def rows(run):
    operator = load_reader(OPERATOR)
    return [r for r in operator.rows(run)
            if operator.inner_scope(r) == "mix"]


def compute(run):
    mine = rows(run)
    if not mine:
        return None
    return sum(r["ns"] for r in mine) / 1e6 / run["steps"]


def info(run):
    operator = load_reader(OPERATOR)
    by_pass, kernels = {}, 0.0
    for r in rows(run):
        ms = r["ns"] / 1e6 / run["steps"]
        which = operator.which_pass(r)
        by_pass[which] = by_pass.get(which, 0.0) + ms
        if r["target"] == "tpu_custom_call":
            kernels += ms
    registry = run.get("registry") or {}
    return {"by_pass_ms": by_pass, "kernels_ms": kernels,
            "run_by": "kernels" if kernels else "xla",
            "dispatch": {k: v for k, v in registry.items()
                         if k.startswith("pt_short_conv_dispatch_total")},
            "bytes_per_step": registry.get("pt_short_conv_bytes")}
