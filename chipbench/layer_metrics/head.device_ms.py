"""head.device_ms (layer: Kernels). Device time per step in the output head
and its cost: the leaf rows of the trace's op table (`run["trace"]["ops"]`)
whose scope is one of the head's ops, forward and backward, over the
window's steps. The head's ops are read off the Program
(`run["program_ops"]`), not named by a configuration:

- every `softmax_with_cross_entropy` op (its softmax, the cost, their
  gradient and the logits' layout copies XLA files under it) and the ops
  that read its outputs (the `mean`);
- the chain that made its logits, back to and including the first op that
  multiplies by a parameter (the vocabulary-wide GEMM; bias adds between);
- the optimizer ops on those parameters. XLA fuses the head's Adam update
  into the weight gradient's GEMM, which carries the GEMM's scope: it is
  counted either way.

Nothing to read where the Program has no such op or the trace no scopes."""

COST = "softmax_with_cross_entropy"
OPTIMIZER_SLOT = "Param"


def head_scopes(program_ops):
    made_by = {n: op for op in program_ops
               for names in op["outputs"].values() for n in names}
    updates = {op["inputs"][OPTIMIZER_SLOT][0]: op for op in program_ops
               if op["inputs"].get(OPTIMIZER_SLOT)}
    scopes = set()
    for cost in (op for op in program_ops if op["type"] == COST):
        scopes.add(cost["scope"])
        mine = {n for names in cost["outputs"].values() for n in names}
        scopes |= {op["scope"] for op in program_ops
                   if any(n in mine for names in op["inputs"].values()
                          for n in names)}
        name = cost["inputs"]["Logits"][0]
        while name in made_by:          # back to the vocabulary-wide GEMM
            op = made_by[name]
            scopes.add(op["scope"])
            params = [n for names in op["inputs"].values() for n in names
                      if n in updates]
            scopes |= {updates[n]["scope"] for n in params}
            if op["type"] in ("mul", "matmul") or not op["inputs"].get("X"):
                break
            name = op["inputs"]["X"][0]
    return scopes


def compute(run):
    ops = (run.get("trace") or {}).get("ops")
    if not ops or not run.get("program_ops"):
        return None
    scopes = head_scopes(run["program_ops"])
    rows = [r for r in ops if not r["container"] and r["scope"] in scopes]
    if not rows:
        return None
    return sum(r["ns"] for r in rows) / 1e6 / run["steps"]
