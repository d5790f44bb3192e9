"""moe.device_ms (layer: Routed experts). Device time per step in the routed
feed-forward: the leaf rows of the trace's op table (`run["trace"]["ops"]`)
whose scope is a routed-FFN op's, forward (both emissions of it) and
backward, over the window's steps. The ops are found from the Program
(`run["program_ops"]`) by type, not by a name a configuration spells: router,
top-k, sort, gather, the grouped matmuls, silu * up and the weighted combine
all run under the one op's scope. Its `info` splits the time by the op's
inner scopes (`route`, `dispatch`, `experts`, `combine`) and by pass.
Nothing to read where the Program has no such op or the trace no scopes."""

OP_TYPE = "moe_ffn"
INNER = ("route", "dispatch", "experts", "combine")


def rows(run):
    ops = (run.get("trace") or {}).get("ops")
    if not ops or not run.get("program_ops"):
        return []
    scopes = {op["scope"] for op in run["program_ops"]
              if op["type"] == OP_TYPE}
    return [r for r in ops if not r["container"] and r["scope"] in scopes]


def is_kernel(row):
    """A grouped-matmul kernel: a Pallas / Mosaic custom call."""
    return row["target"] == "tpu_custom_call"


def compute(run):
    mine = rows(run)
    if not mine:
        return None
    return sum(r["ns"] for r in mine) / 1e6 / run["steps"]


def info(run):
    """ms a step by inner scope and by pass (`plain`: the forward as the
    Program lists it; `jvp`: the forward traced again for differentiation;
    `transpose`: backward)."""
    by_scope, by_pass = {}, {}
    for r in rows(run):
        parts = r["op_name"].split("/")
        inner = next((p for p in parts if p in INNER), "other")
        ms = r["ns"] / 1e6 / run["steps"]
        by_scope[inner] = by_scope.get(inner, 0.0) + ms
        which = ("transpose" if r["transform"].startswith("transpose")
                 else r["transform"] or "plain")
        by_pass[which] = by_pass.get(which, 0.0) + ms
    return {"by_inner_scope_ms": by_scope, "by_pass_ms": by_pass}
