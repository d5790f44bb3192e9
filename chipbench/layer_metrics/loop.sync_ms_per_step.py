"""loop.sync_ms_per_step (layer: Trainer loop). Host time per step blocked in a
read of a device value: `hostSync` (`_host_read_step`, `_PassStats.sync`)
plus `lazyRead` (the first read of an event's `_LazyScalar`, on whichever
thread reads). Where the host waits for the device, when it waits in a read.
Read from the program's own spans: their `profiler.StatSet` totals over the
traced window (`run["timers_s"]`), over the window's steps. Nothing to read
where the program records none of them."""

SPANS = ("hostSync", "lazyRead")


def compute(run):
    timers = run.get("timers_s") or {}
    if not any(s in timers for s in SPANS):
        return None
    return 1e3 * sum(timers.get(s, 0.0) for s in SPANS) / run["steps"]
