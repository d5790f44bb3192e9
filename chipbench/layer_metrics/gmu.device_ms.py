"""gmu.device_ms (layer: Mamba-1 mixers). Device time per step in the gated
memory units, the layers that read a mixer's scan output instead of scanning:
the leaf rows of the trace's op table whose scope is one of a unit's three ops
(`layers.gated_memory_unit`: the gate's `fc`, the `silu_gate` that multiplies
the memory, the out-projection's `fc`), forward and backward, over the
window's steps. A unit is found from `run["program_ops"]`: a `silu_gate` op
WITH a `Gate` input names its output `<unit>.gate.tmp_N`, and the unit's ops
are those whose first output starts with `<unit>.`. Its `info` splits the time
by the unit's scopes (`gate_proj`, `gate`, `out_proj`) and by pass. Nothing to
read where the Program has no such op (a parent of the PR that added it) or
the trace no scopes."""

GATE_OP, GATE_SLOT, MARK = "silu_gate", "Gate", ".gate.tmp_"
PARTS = ("gate_proj", "gate", "out_proj")


def units(program_ops):
    """The name prefixes of the Program's gated memory units."""
    out = []
    for op in program_ops:
        if op["type"] == GATE_OP and op["inputs"].get(GATE_SLOT):
            name = next(iter(op["outputs"].values()))[0]
            if MARK in name:
                out.append(name[:name.index(MARK)] + ".")
    return out


def scopes_of(program_ops, prefixes):
    """{scope: the part of its layer} of the ops whose first output starts
    with one of `prefixes`."""
    found = {}
    for op in program_ops:
        first = next((n for names in op["outputs"].values() for n in names), "")
        for prefix in prefixes:
            if first.startswith(prefix):
                found[op["scope"]] = first[len(prefix):].split(".")[0]
    return found


def rows(run):
    """[(row, part)]."""
    ops = (run.get("trace") or {}).get("ops")
    if not ops or not run.get("program_ops"):
        return []
    scopes = scopes_of(run["program_ops"], units(run["program_ops"]))
    return [(r, scopes[r["scope"]]) for r in ops
            if not r["container"] and r["scope"] in scopes]


def compute(run):
    mine = rows(run)
    if not mine:
        return None
    return sum(r["ns"] for r, _ in mine) / 1e6 / run["steps"]


def info(run):
    by_part, by_pass = {}, {}
    for r, part in rows(run):
        ms = r["ns"] / 1e6 / run["steps"]
        which = ("transpose" if r["transform"].startswith("transpose")
                 else r["transform"] or "plain")
        by_part[part] = by_part.get(part, 0.0) + ms
        by_pass[which] = by_pass.get(which, 0.0) + ms
    return {"by_scope_ms": by_part, "by_pass_ms": by_pass,
            "units": len(units(run["program_ops"]))}
