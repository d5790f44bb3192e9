"""ssm.scan_ms (layer: State-space mixers). Device time per step in the
chunked scans alone: of `ssm.device_ms`'s rows, those under the mixer op's
inner `scan` scope (forward, the forward traced again, the backward and what
it recomputes), over the window's steps. Its `info` gives the passes and
whether a kernel (`tpu_custom_call`) or XLA runs them. Nothing to read where
`ssm.device_ms` finds nothing."""

from chipbench.readers import load_reader

MIXER = "ssm.device_ms"


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
    return {"by_pass_ms": by_pass, "kernels_ms": kernels,
            "run_by": "kernels" if kernels else "xla"}
