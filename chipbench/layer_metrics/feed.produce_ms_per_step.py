"""feed.produce_ms_per_step (layer: Trainer loop). Time per step the
`pt-prefetch` thread spends making a batch: `prefetch.read` (the user's
reader's `next()`) plus `prefetch.batch` (`DataFeeder.feed` + `device_put`).
Off the step loop's thread: it costs the loop only what
`loop.feed_wait_ms_per_step` shows. Read from the program's own spans: their
`profiler.StatSet` totals over the traced window (`run["timers_s"]`), over
the window's steps. Nothing to read where the program records none of them."""

SPANS = ("prefetch.read", "prefetch.batch")


def compute(run):
    timers = run.get("timers_s") or {}
    if not any(s in timers for s in SPANS):
        return None
    return 1e3 * sum(timers.get(s, 0.0) for s in SPANS) / run["steps"]
