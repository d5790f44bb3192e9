"""loop.dispatch_per_step (layer: Trainer loop). Exact counts:
(`Trainer.host_dispatch_count` + `Trainer.host_sync_count` deltas over
the window) / steps."""


def compute(run):
    c = run["counters"]
    return (c["dispatches"] + c["syncs"]) / run["steps"]
