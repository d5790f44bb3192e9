"""step.xla_inserted_ms (layer: Executor step). Device time per step in what
XLA put between the program's ops, put down to the op it belongs to: the leaf
rows of the trace's op table (`run["trace"]["ops"]`) whose `op_name` is empty
(layout copies, `copy-start` / `copy-done`, slices, bitcast custom calls,
fusions rooted in a `tuple`) and that the program's own record names, over
the window's steps. The record is the gauge family
`pt_executor_instruction_scope{program,instruction,scope,via}` in
`run["registry"]`: the Executor's reading of its compiled step program's
optimized HLO (`paddle_tpu/core/provenance.py`), joined to the rows by the
instruction's name; a row with several scopes (a fusion without a root of
the program's) goes to the heaviest. The step program is the `program` whose
instructions cover the most device time. With `step.unnamed_ms` it sums to
what the op table has without a scope. Its `info` says which op's layout or
donation the time is. Nothing to read without a trace or where the program
publishes no such family (a rehearsal on the CPU, a parent of PR 40)."""

import re

from chipbench import xplane

FAMILY = "pt_executor_instruction_scope"
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def leaves(run):
    """The leaf rows of the op table, or None without a trace."""
    ops = (run.get("trace") or {}).get("ops")
    return ops and [r for r in ops if not r["container"]]


def record(run):
    """{instruction: [(scope, via, weight), ...]} of the step program, heaviest
    scope first, or None where there is no trace or no such family."""
    rows, programs = leaves(run), {}
    for series, weight in (run.get("registry") or {}).items():
        if series.startswith(FAMILY + "{"):
            label = dict(_LABEL.findall(series))
            programs.setdefault(label["program"], {}).setdefault(
                label["instruction"], []).append(
                    (label["scope"], label["via"], weight))
    if not rows or not programs:
        return None
    ns = {}
    for r in rows:
        name = r["name"].lstrip("%")
        ns[name] = ns.get(name, 0) + r["ns"]
    step = max(sorted(programs), key=lambda p: sum(
        ns.get(i, 0) for i in programs[p]))
    return {i: sorted(scopes, key=lambda s: (-s[2], s[0]))
            for i, scopes in programs[step].items()}


def split(run):
    """(named, unnamed): the leaf rows with an empty scope that the record
    names, each beside its (scope, via), and those it does not; None where
    there is nothing to read."""
    found = record(run)
    if found is None:
        return None
    named, unnamed = [], []
    for r in leaves(run):
        if r["scope"]:
            continue
        scopes = found.get(r["name"].lstrip("%"))
        if scopes:
            named.append((r, scopes[0][0], scopes[0][1]))
        else:
            unnamed.append(r)
    return named, unnamed


def op_and_pass(scope):
    """`transpose(jvp(mul.fc_3.tmp_4))` -> `mul transpose`: the op's type and
    the pass (`plain`, `jvp` forward, `transpose` backward)."""
    name, transform = xplane.scope_of(scope)
    which = ("transpose" if transform.startswith("transpose")
             else transform or "plain")
    return f"{name.split('.')[0]} {which}"


def compute(run):
    found = split(run)
    if found is None:
        return None
    return sum(r["ns"] for r, _, _ in found[0]) / 1e6 / run["steps"]


def descending(sums):
    return dict(sorted(sums.items(), key=lambda kv: -kv[1]))


def info(run):
    """ms a step by the op type and pass the rows go to, by opcode and by
    `via`, and the ten longest rows."""
    by_op, by_opcode, by_via = {}, {}, {}
    named = split(run)[0]
    for r, scope, via in named:
        ms = r["ns"] / 1e6 / run["steps"]
        for sums, key in ((by_op, op_and_pass(scope)),
                          (by_opcode, r["opcode"]), (by_via, via)):
            sums[key] = sums.get(key, 0.0) + ms
    longest = sorted(named, key=lambda n: -n[0]["ns"])[:10]
    return {"by_op_and_pass_ms": descending(by_op),
            "by_opcode_ms": descending(by_opcode),
            "by_via_ms": descending(by_via),
            "longest": [[r["name"], r["opcode"], r["shape"], scope, via,
                         r["ns"] / 1e6 / run["steps"]]
                        for r, scope, via in longest]}
