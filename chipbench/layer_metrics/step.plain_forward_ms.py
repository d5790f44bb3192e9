"""step.plain_forward_ms (layer: Executor step). Device time per step that
the forward ops of a training Program spend OUTSIDE differentiation: the
leaf rows of the trace's op table (`run["trace"]["ops"]`) whose `transform`
is `""` (neither `jvp`, the forward half of a differentiated op, nor
`transpose(jvp`, its backward half) and whose `scope` is one of the ops
`run["program_ops"]` lists before the first `autodiff` op, over the window's
steps. A guard more than a measure: a step that traces its forward ops
once, under differentiation (PR 33), reads 0.0 here by construction, since
every op traced inside `value_and_grad` carries `jvp(` in its name, the
casts and integer work that no parameter reaches too. A step that ALSO runs
them plainly, as every step did before, reads a whole forward pass (26 ms of
gpt2-small's 117 ms): anything above 0 says the second forward came back.
Its `info` gives the time by op type. Nothing to read without a trace or
without an `autodiff` op (an inference Program)."""


def rows(run):
    ops = (run.get("trace") or {}).get("ops")
    program = run.get("program_ops") or ()
    first = next((i for i, op in enumerate(program)
                  if op["type"] == "autodiff"), None)
    if not ops or first is None:
        return None
    forward = {op["scope"]: op["type"] for op in program[:first]}
    return [(forward[r["scope"]], r["ns"]) for r in ops
            if not r["container"] and r["transform"] == ""
            and r["scope"] in forward]


def compute(run):
    mine = rows(run)
    if mine is None:
        return None
    return sum(ns for _, ns in mine) / 1e6 / run["steps"]


def info(run):
    """ms a step by the forward op's type, longest first."""
    by_type = {}
    for op_type, ns in rows(run):
        by_type[op_type] = by_type.get(op_type, 0.0) + ns / 1e6 / run["steps"]
    return {"by_op_type_ms": dict(sorted(by_type.items(),
                                         key=lambda kv: -kv[1]))}
