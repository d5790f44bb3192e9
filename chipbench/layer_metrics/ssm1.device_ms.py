"""ssm1.device_ms (layer: Mamba-1 mixers). Device time per step in the Mamba-1
mixers: the leaf rows of the trace's op table (`run["trace"]["ops"]`) whose
scope is a `mamba1_mixer` op's (found from `run["program_ops"]` by type),
forward (both emissions of it) and backward, over the window's steps: the
in-projection, the conv, the two small projections and dt, the selective scan,
the gate, the out-projection. Its `info` splits the time by the op's inner
`jax.named_scope`s (`in_proj`, `conv`, `dt_bc`, `scan`, `gate`, `out_proj`)
and by pass. Nothing to read where the Program has no such op (a parent of the
PR that added it) or the trace no scopes."""

OP_TYPE = "mamba1_mixer"
INNER = ("in_proj", "conv", "dt_bc", "scan", "gate", "out_proj")


def rows(run):
    ops = (run.get("trace") or {}).get("ops")
    if not ops or not run.get("program_ops"):
        return []
    scopes = {op["scope"] for op in run["program_ops"]
              if op["type"] == OP_TYPE}
    return [r for r in ops if not r["container"] and r["scope"] in scopes]


def inner_scope(row):
    """The first of the op's inner scopes on the row's name stack."""
    return next((p for p in row["op_name"].split("/") if p in INNER), "other")


def which_pass(row):
    return ("transpose" if row["transform"].startswith("transpose")
            else row["transform"] or "plain")


def compute(run):
    mine = rows(run)
    if not mine:
        return None
    return sum(r["ns"] for r in mine) / 1e6 / run["steps"]


def info(run):
    """ms a step by inner scope and by pass (`plain`: the forward as the
    Program lists it; `jvp`: the forward traced again for differentiation;
    `transpose`: backward, what the mixer's checkpoint forms again
    included), and the mixers counted."""
    by_scope, by_pass = {}, {}
    for r in rows(run):
        ms = r["ns"] / 1e6 / run["steps"]
        by_scope[inner_scope(r)] = by_scope.get(inner_scope(r), 0.0) + ms
        by_pass[which_pass(r)] = by_pass.get(which_pass(r), 0.0) + ms
    return {"by_inner_scope_ms": by_scope, "by_pass_ms": by_pass,
            "mixers": sum(op["type"] == OP_TYPE
                          for op in run["program_ops"])}
