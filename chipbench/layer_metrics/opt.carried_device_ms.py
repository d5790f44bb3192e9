"""opt.carried_device_ms (layer: Kernels). Device time per step of the
fusions the optimizer rides in: the leaf rows of the trace's op table
(`run["trace"]["ops"]`) whose own scope is NOT an optimizer op (the ops of
`run["program_ops"]` with a `Param` input, `adam.<parameter>`, as
`opt.device_ms.py` finds them) but among whose fused members the program's
record of its compiled step program lists one
(`pt_executor_instruction_scope` in `run["registry"]`;
`step.xla_inserted_ms.py` has the join). A trace gives a fusion ONE
`op_name`, its root's, so an update XLA fused into the GEMM or the reduction
that makes its gradient is filed under that op and `opt.device_ms` reads only
the updates left standing alone. This is the WHOLE time of the carriers, the
gradient's work with the update's: an upper bound of what `opt.device_ms`
cannot see, no split of it (the record's weights are listing weights). A
carrier counts once however many updates ride in it. Its `info` gives the
time by the carrier's op type and pass with the optimizer members' weight,
and `opt.device_ms` beside it. Nothing to read without a trace, without an
optimizer op, or where the program publishes no such family."""

from chipbench import xplane
from chipbench.readers import load_reader

NAMED, ALONE = "step.xla_inserted_ms", "opt.device_ms"


def carriers(run):
    """[(row, the carrier's scope, its optimizer members' weight)] or None."""
    named = load_reader(NAMED)
    found = named.record(run)
    optimizer = {op["scope"] for op in run.get("program_ops") or ()
                 if op["inputs"].get("Param")}
    if found is None or not optimizer:
        return None
    out = []
    for r in named.leaves(run):
        if r["scope"] in optimizer:
            continue
        scopes = found.get(r["name"].lstrip("%"), ())
        rides = sum(w for s, _, w in scopes
                    if xplane.scope_of(s)[0] in optimizer)
        if rides:
            # a row the trace left without a name goes to its heaviest scope
            out.append((r, r["op_name"] or scopes[0][0], rides))
    return out


def compute(run):
    found = carriers(run)
    if found is None:
        return None
    return sum(r["ns"] for r, _, _ in found) / 1e6 / run["steps"]


def info(run):
    named, by_op, weights = load_reader(NAMED), {}, {}
    for r, scope, rides in carriers(run):
        key = named.op_and_pass(scope)
        by_op[key] = by_op.get(key, 0.0) + r["ns"] / 1e6 / run["steps"]
        weights.setdefault(key, []).append(rides)
    return {"by_carrier_op_and_pass_ms": named.descending(by_op),
            "optimizer_members_weight_mean": {
                k: sum(w) / len(w) for k, w in weights.items()},
            "carriers": sum(len(w) for w in weights.values()),
            ALONE: load_reader(ALONE).compute(run)}
