"""loop.feed_wait_ms_per_step (layer: Trainer loop). Host time per step the
step loop spends getting its batch, measured inside the program:
`prefetchWait` (the consumer's `q.get()` in `DevicePrefetcher.__iter__`)
plus `prepareBatchData` (the in-loop `DataFeeder` of executors that place
their own input). Read from the program's own spans:
their `profiler.StatSet` totals over the traced window (`run["timers_s"]`),
over the window's steps. Nothing to read where the program records none of
them."""

SPANS = ("prefetchWait", "prepareBatchData")


def compute(run):
    timers = run.get("timers_s") or {}
    if not any(s in timers for s in SPANS):
        return None
    return 1e3 * sum(timers.get(s, 0.0) for s in SPANS) / run["steps"]
