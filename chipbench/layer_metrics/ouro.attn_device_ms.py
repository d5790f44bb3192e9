"""ouro.attn_device_ms (layer: Kernels). Device time per step of the attention
ops INSIDE the `repeat` op's body: the leaf rows whose innermost Program scope
(`repeat.body_device_ms.py:body_rows`) is a `flash_attention` op, over the
forward, recomputed and backward passes: the fused kernels and whatever XLA
runs around them. `attn.device_ms` goes by a row's outer scope, which inside
the loop is the `repeat` op's, and sees nothing here; it is not edited. `info`
gives the kernels (`tpu_custom_call`) apart, the time by pass, and beside
them the ops around the kernels, found from the Program (`run["program_ops"]`):
the rotary ops that feed Q and K, the projections (`mul`) behind Q, K and V
and the one that reads the kernels' output. Nothing to read where no row has
such a scope."""

from chipbench.readers import load_reader

BODY = "repeat.body_device_ms"
KERNEL_OP = "flash_attention"


def around(program_ops):
    """{"rotary": scopes, "projections": scopes} of the ops around the
    attention ops: back from Q, K, V through unary ops to the first `mul`,
    and the `mul`s that read Out."""
    made_by = {n: op for op in program_ops
               for names in op["outputs"].values() for n in names}
    rotary, projections = set(), set()
    for att in (op for op in program_ops if op["type"] == KERNEL_OP):
        for slot in ("Q", "K", "V"):
            name = att["inputs"][slot][0]
            while name in made_by:
                op = made_by[name]
                if op["type"] == "mul":
                    projections.add(op["scope"])
                    break
                rotary.add(op["scope"])
                name = op["inputs"]["X"][0]
        out = set(att["outputs"]["Out"])
        projections |= {op["scope"] for op in program_ops
                        if op["type"] == "mul" and out & set(op["inputs"]["X"])}
    return {"rotary": rotary, "projections": projections}


def rows(run):
    mine = load_reader(BODY).body_rows(run)
    return [(r, which) for r, _, kind, which in mine or () if kind == KERNEL_OP]


def compute(run):
    mine = rows(run)
    if not mine:
        return None
    return load_reader(BODY).ms([r for r, _ in mine], run)


def info(run):
    body = load_reader(BODY)
    mine = rows(run)
    by_pass = {p: body.ms([r for r, q in mine if q == p], run)
               for p in body.PASSES}
    kernels = body.ms([r for r, _ in mine if r["target"] == "tpu_custom_call"], run)
    out = {"kernels_ms": kernels, "not_kernels_ms": compute(run) - kernels,
           "by_pass_ms": by_pass}
    every = body.body_rows(run)
    for what, scopes in around(run["program_ops"]).items():
        out[what + "_ms"] = body.ms(
            [r for r, inner, _, _ in every if inner in scopes], run)
    return out
