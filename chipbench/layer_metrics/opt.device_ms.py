"""opt.device_ms (layer: Kernels). Device time per step in the optimizer's
own update ops: the leaf rows of the trace's op table
(`run["trace"]["ops"]`) whose scope is one of the Program's optimizer ops
(`run["program_ops"]`: the ops with a `Param` input: `adam.<parameter>`),
over the window's steps. A fusion carries one scope: where XLA fuses a
parameter's update into the GEMM or the reduction that makes its gradient
(on gpt2-small every matrix's and every bias's), the time is filed under
that op and NOT here, so this reads the updates left standing alone (the
token table's; PERF.md section 5). It rises when an update is un-fused or
an optimizer kernel of its own appears. Nothing to read where no row has
such a scope."""


def compute(run):
    ops = (run.get("trace") or {}).get("ops")
    if not ops or not run.get("program_ops"):
        return None
    scopes = {op["scope"] for op in run["program_ops"]
              if op["inputs"].get("Param")}
    rows = [r for r in ops if not r["container"] and r["scope"] in scopes]
    if not rows:
        return None
    return sum(r["ns"] for r in rows) / 1e6 / run["steps"]
