"""step.host_prepare_ms (layer: Executor step). Host time per step inside
`executor.prepare`: `Executor.run` from its entry to just before the jitted
call (feed normalisation, the persistables scan, cache key and lookup, state
gather, seed, `_place_inputs`). Read from the program's own spans: their
`profiler.StatSet` totals over the traced window (`run["timers_s"]`), over
the window's steps. Nothing to read where the program records none of them."""

SPANS = ("executor.prepare",)


def compute(run):
    timers = run.get("timers_s") or {}
    if not any(s in timers for s in SPANS):
        return None
    return 1e3 * sum(timers.get(s, 0.0) for s in SPANS) / run["steps"]
