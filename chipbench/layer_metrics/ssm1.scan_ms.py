"""ssm1.scan_ms (layer: Mamba-1 mixers). Device time per step in the selective
scans alone: of `ssm1.device_ms`'s rows, those under the mixer op's inner
`scan` scope (the forward, the forward the mixer's checkpoint runs again, the
backward), over the window's steps. Its `info` gives the passes, whether a
kernel (`tpu_custom_call`) or XLA runs them, and what the program's registry
counted (`pt_selective_scan_dispatch_total{path}`, `pt_selective_scan_bytes`,
`pt_selective_scan_saved_state_bytes`). Nothing to read where `ssm1.device_ms`
finds nothing."""

from chipbench.readers import load_reader

MIXER = "ssm1.device_ms"


def rows(run):
    mixer = load_reader(MIXER)
    return [r for r in mixer.rows(run) if mixer.inner_scope(r) == "scan"]


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
        by_pass[mixer.which_pass(r)] = by_pass.get(mixer.which_pass(r), 0.0) + ms
        if r["target"] == "tpu_custom_call":
            kernels += ms
    registry = run.get("registry") or {}
    return {"by_pass_ms": by_pass, "kernels_ms": kernels,
            "run_by": "kernels" if kernels else "xla",
            "dispatch": {k: v for k, v in registry.items()
                         if k.startswith("pt_selective_scan_dispatch_total")},
            "bytes_per_step": registry.get("pt_selective_scan_bytes"),
            "saved_state_bytes":
            registry.get("pt_selective_scan_saved_state_bytes")}
