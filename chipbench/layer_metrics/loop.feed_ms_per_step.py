"""loop.feed_ms_per_step (layer: Trainer loop). Host time per step the
step loop spends getting its batch: the wait between `EndIteration` and
the next `BeginIteration` (the DevicePrefetcher's queue, i.e. reader +
DataFeeder + h2d when they cannot keep ahead; on the harness's clock)
plus the trainer's own `prepareBatchData` timer (`profiler.StatSet`; the
in-loop DataFeeder of executors that place their own input, such as the
mesh executor). Traced window only, timers on."""


def compute(run):
    wait = run["feed_wait_s"] + run["timers_s"].get("prepareBatchData", 0.0)
    return 1e3 * wait / run["steps"]
