"""loop.accum_ms_per_step (layer: Trainer loop). Host time per step inside
`accumUpdate`: `_PassStats.update`, the step's second jitted dispatch
(`accum_fold`), which `loop.dispatch_per_step` does not count. Read from the
program's own spans: their `profiler.StatSet` totals over the traced window
(`run["timers_s"]`), over the window's steps. Nothing to read where the
program records none of them."""

SPANS = ("accumUpdate",)


def compute(run):
    timers = run.get("timers_s") or {}
    if not any(s in timers for s in SPANS):
        return None
    return 1e3 * sum(timers.get(s, 0.0) for s in SPANS) / run["steps"]
