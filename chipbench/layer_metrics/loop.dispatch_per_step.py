"""loop.dispatch_per_step (layer: Trainer loop). Exact counts:
(`Trainer.host_dispatch_count` + `Trainer.host_sync_count` deltas over
the window) / steps. What it counts: the trainer's calls of `Executor.run`
(one a step) plus its fenced reads of a device value (one a sync interval),
so 2.0 where every step is read and 1.2 where every tenth is. It does NOT
count XLA executions: each step also dispatches `accum_fold` (the metric
accumulator, `loop.accum_ms_per_step`) and the seed's
`convert_element_type` from inside `Executor.run`, which no counter of the
trainer sees."""


def compute(run):
    c = run["counters"]
    return (c["dispatches"] + c["syncs"]) / run["steps"]
