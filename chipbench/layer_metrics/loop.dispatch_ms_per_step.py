"""loop.dispatch_ms_per_step (layer: Trainer loop). Host time per step inside
`forwardBackward`: the whole of `Executor.run` / `run_window` as the
trainer's loop sees it. The three `step.host_*_ms` metrics split it. Read
from the program's own spans: their `profiler.StatSet` totals over the
traced window (`run["timers_s"]`), over the window's steps. Nothing to read
where the program records none of them."""

SPANS = ("forwardBackward",)


def compute(run):
    timers = run.get("timers_s") or {}
    if not any(s in timers for s in SPANS):
        return None
    return 1e3 * sum(timers.get(s, 0.0) for s in SPANS) / run["steps"]
