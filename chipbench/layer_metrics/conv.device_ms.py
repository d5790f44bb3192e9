"""conv.device_ms (layer: Short-conv operators). Device time per step in the
gated short-convolution operators: the leaf rows of the trace's op table
(`run["trace"]["ops"]`) whose scope is a `short_conv_operator` op's (found from
`run["program_ops"]` by type), forward (both emissions of it) and backward,
over the window's steps: the in-projection, what lies between the two GEMMs
(the gates and the taps), the out-projection. Its `info` splits the time by the
op's inner `jax.named_scope`s (`in_proj`, `mix`, `out_proj`) and by pass.
Nothing to read where the Program has no such op (a parent of the PR that
added it) or the trace no scopes."""

OP_TYPE = "short_conv_operator"
INNER = ("in_proj", "mix", "out_proj")


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
    `transpose`: backward), and the operators counted."""
    by_scope, by_pass = {}, {}
    for r in rows(run):
        ms = r["ns"] / 1e6 / run["steps"]
        by_scope[inner_scope(r)] = by_scope.get(inner_scope(r), 0.0) + ms
        by_pass[which_pass(r)] = by_pass.get(which_pass(r), 0.0) + ms
    return {"by_inner_scope_ms": by_scope, "by_pass_ms": by_pass,
            "operators": sum(op["type"] == OP_TYPE
                             for op in run["program_ops"])}
