"""repeat.recompute_ms (layer: Looped stack). Device time per step of the turn
that `layers.Repeat` runs AGAIN in the backward pass: the leaf rows under a
`repeat` op whose `op_name` holds `checkpoint/rematted_computation/` (the
backward loop's body recomputes a turn's forward from its saved carry before
it transposes it; `repeat.body_device_ms.py` has the three kinds of path as
read on the chip). What rematerialising by region costs in time: the lever a
later `perf_opt` trades against `repeat.saved_gib` and `peak_hbm_gib`. Its
`info` gives it as a share of the forward loop's time (1 where the recomputed
turn costs what the first run of it did). Nothing to read where the step holds
no such row (no loop, or `remat=False`)."""

from chipbench.readers import load_reader

BODY = "repeat.body_device_ms"


def _rows(run, which):
    body = load_reader(BODY)
    mine = body.body_rows(run)
    return body, [r for r, _, _, q in mine or () if q == which]


def compute(run):
    body, rows = _rows(run, "recomputed")
    return body.ms(rows, run) if rows else None


def info(run):
    body, forward = _rows(run, "forward")
    return {"over_forward": compute(run) / body.ms(forward, run)
            if forward else None}
