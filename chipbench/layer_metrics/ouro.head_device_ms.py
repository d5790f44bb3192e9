"""ouro.head_device_ms (layer: Kernels). Device time per step of the head as a
looped model reads it: K times through one weight, inside the `repeat` op's
body, and the exit cost behind the loop. The rows, found from the Program
(`run["program_ops"]`):

- inside the loop (rows whose innermost Program scope,
  `repeat.body_device_ms.py:body_rows`, is one of these): every
  `softmax_with_cross_entropy` op and the `mul` that makes its logits (the
  vocabulary-wide GEMM, its weight's gradient summed over the turns), forward,
  recomputed and backward;
- outside it, by the row's own scope: the exit op (`exit_expected_cost`) and
  what reads its cost (the `mean`).

`head.device_ms` goes by a row's outer scope and sees nothing inside the loop;
it is not edited. `info` gives the GEMM and the cross-entropy apart by pass,
and the exit cost. Nothing to read where the Program has no such op or the
trace no scopes."""

from chipbench.readers import load_reader

BODY = "repeat.body_device_ms"
COST, EXIT = "softmax_with_cross_entropy", "exit_expected_cost"


def head_scopes(program_ops):
    """({scope: "cross_entropy" | "gemm"} inside the loop, the exit cost's
    scopes outside it)."""
    made_by = {n: op for op in program_ops
               for names in op["outputs"].values() for n in names}
    inside, outside = {}, set()
    for op in program_ops:
        if op["type"] == COST:
            inside[op["scope"]] = "cross_entropy"
            gemm = made_by.get(op["inputs"]["Logits"][0])
            if gemm and gemm["type"] == "mul":
                inside[gemm["scope"]] = "gemm"
        elif op["type"] == EXIT:
            outside.add(op["scope"])
            mine = {n for names in op["outputs"].values() for n in names}
            outside |= {o["scope"] for o in program_ops
                        if any(n in mine for names in o["inputs"].values()
                               for n in names)}
    return inside, outside


def _rows(run):
    body = load_reader(BODY)
    mine = body.body_rows(run)
    if not mine or not run.get("program_ops"):
        return None
    inside, outside = head_scopes(run["program_ops"])
    looped = [(r, inside[inner], which) for r, inner, _, which in mine
              if inner in inside]
    behind = [r for r in run["trace"]["ops"]
              if not r["container"] and r["scope"] in outside]
    return looped, behind


def compute(run):
    found = _rows(run)
    if not found or not found[0]:
        return None
    looped, behind = found
    return load_reader(BODY).ms([r for r, _, _ in looped] + behind, run)


def info(run):
    body = load_reader(BODY)
    looped, behind = _rows(run)
    out = {what: {p: body.ms([r for r, w, q in looped if w == what and q == p],
                             run) for p in body.PASSES}
           for what in ("gemm", "cross_entropy")}
    out["exit_cost_ms"] = body.ms(behind, run)
    return out
