"""step.host_commit_ms (layer: Executor step). Host time per step inside
`executor.commit`: after the jitted call to `Executor.run`'s return
(`check_nan_inf`, `scope.set` of every new state buffer, `as_numpy`). Read
from the program's own spans: their `profiler.StatSet` totals over the
traced window (`run["timers_s"]`), over the window's steps. Nothing to read
where the program records none of them."""

SPANS = ("executor.commit",)


def compute(run):
    timers = run.get("timers_s") or {}
    if not any(s in timers for s in SPANS):
        return None
    return 1e3 * sum(timers.get(s, 0.0) for s in SPANS) / run["steps"]
