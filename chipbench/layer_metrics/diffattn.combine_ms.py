"""diffattn.combine_ms (layer: Differential attention). Device time per step in
the pair arithmetic alone, what lies between the kernels and the
out-projection: of `diffattn.device_ms`'s rows, those of the `diff_combine`
ops (`a_1 - lam a_2`, the norm over a pair's 128 lanes, the scale; forward,
the forward its checkpoint forms again, backward), over the window's steps.
Nothing to read where `diffattn.device_ms` finds nothing."""

from chipbench.readers import load_reader

LAYER = "diffattn.device_ms"


def rows(run):
    return [r for r, part, _ in load_reader(LAYER).rows(run)
            if part == "combine"]


def compute(run):
    mine = rows(run)
    if not mine:
        return None
    return sum(r["ns"] for r in mine) / 1e6 / run["steps"]


def info(run):
    by_pass = {}
    for r in rows(run):
        which = ("transpose" if r["transform"].startswith("transpose")
                 else r["transform"] or "plain")
        by_pass[which] = by_pass.get(which, 0.0) + r["ns"] / 1e6 / run["steps"]
    return {"by_pass_ms": by_pass}
