"""repeat.body_device_ms (layer: Looped stack). Device time per step of every
leaf row of the trace's op table (`run["trace"]["ops"]`) under a `repeat` op
(`layers.Repeat`: one Program sub-block run K times as a compiled loop):
forward, recomputed and backward, over the window's steps.

`xplane.scope_of` gives a row the FIRST element of its `op_name`, so every row
of the loop's body carries the `repeat` op's scope (`repeat.<first output>`)
and the readers that go by an op's scope see nothing inside the loop. The
body's own ops are further down the path, as read on the chip (PR 44, the
traced run of ouro-2.6b.train-log10):

    jit(raw)/jvp(repeat.looped.turns.out_256)/while/body/closed_call/repeat.turn/flash_attention.looped.h3.attn.tmp_103/jit(_packed_forward)/flash_attention_fwd/pallas_call
    jit(raw)/transpose(jvp(repeat.looped.turns.out_256))/while/body/closed_call/checkpoint/rematted_computation/repeat.turn/mul.fc_243.tmp_244/dot_general
    jit(raw)/transpose(jvp(repeat.looped.turns.out_256))/while/body/closed_call/checkpoint/repeat.turn/flash_attention.looped.h3.attn.tmp_103/jit(_packed_backward)/flash_attention_bwd/pallas_call

the forward loop's body under `jvp(`, the backward loop's under
`transpose(jvp(`, and in the backward loop the turn that is run again before
it is transposed under `checkpoint/rematted_computation/`. `inner_scope` takes
the INNERMOST element of the path that is the scope of a Program op
(`run["program_ops"]` lists every block's ops; the `repeat` ops themselves
left out), and `body_rows` gives each row of a loop that scope, that op's type
and its pass. This file is the helper of every reader of the loop's body:
they load it by path (`chipbench.readers.load_reader`).

`info` gives the three passes apart, the time by the inner op's type and
pass, the rows of the loops that no inner op names (the loops' own
bookkeeping, `.../while/body/closed_call/add_any`: the weights' gradients
summed over the turns; `.../while`: copies and layout fusions XLA files under
the loop itself; `.../remat2`), and the `while` container rows' own time
beside their bodies' sum: what the scan adds. The containers reach the trace
WITHOUT an `op_name` (`%while.10`, `%while.11` on the chip), so every `while`
container that has none is taken for a loop's: in a Program with other
compiled loops beside its `repeat` ops that sum would hold theirs too. Nothing to read where the Program has no
`repeat` op or the trace no scopes."""

from chipbench import xplane

LOOP = "repeat"
RECOMPUTED = "rematted_computation"
PASSES = ("forward", "recomputed", "backward")


def _program(run):
    """({scope: op type} of every Program op but the loops, the loops'
    scopes)."""
    ops = run.get("program_ops") or ()
    return ({op["scope"]: op["type"] for op in ops if op["type"] != LOOP},
            {op["scope"] for op in ops if op["type"] == LOOP})


def inner_scope(op_name, scopes):
    """The innermost element of `op_name`'s path that is one of `scopes`,
    without the transformations JAX wrapped it in; "" where there is none."""
    for part in reversed(op_name.rstrip(":").split("/")):
        scope, _ = xplane.scope_of(part)
        if scope in scopes:
            return scope
    return ""


def pass_of(row):
    if not row["transform"].startswith("transpose"):
        return "forward"
    parts = row["op_name"].split("/")
    return "recomputed" if RECOMPUTED in parts else "backward"


def body_rows(run):
    """[(row, inner scope, inner op type, pass)] of the leaf rows under a
    `repeat` op; None without a trace or a loop."""
    ops = (run.get("trace") or {}).get("ops")
    scopes, loops = _program(run)
    if not ops or not loops:
        return None
    out = []
    for r in ops:
        if r["container"] or r["scope"] not in loops:
            continue
        inner = inner_scope(r["op_name"], scopes)
        out.append((r, inner, scopes.get(inner, ""), pass_of(r)))
    return out


def ms(rows, run):
    return sum(r["ns"] for r in rows) / 1e6 / run["steps"]


def compute(run):
    mine = body_rows(run)
    if not mine:
        return None
    return ms([r for r, *_ in mine], run)


def info(run):
    mine = body_rows(run)
    by_pass = {p: ms([r for r, _, _, q in mine if q == p], run) for p in PASSES}
    by_type = {}
    for r, _, kind, which in mine:
        slot = by_type.setdefault(kind or "(the loop's own)", dict.fromkeys(PASSES, 0.0))
        slot[which] += r["ns"] / 1e6 / run["steps"]
    _, loops = _program(run)
    whiles = [r for r in run["trace"]["ops"]
              if r["container"] and r["opcode"] == "while"
              and (r["scope"] in loops or not r["op_name"])]
    return {"by_pass_ms": by_pass,
            "by_inner_op_type_ms": dict(sorted(
                by_type.items(), key=lambda kv: -sum(kv[1].values()))),
            "no_inner_op_ms": ms([r for r, inner, _, _ in mine if not inner], run),
            "while_containers_ms": ms(whiles, run),
            "while_containers": [[r["name"], r["count"] / run["steps"],
                                  r["ns"] / 1e6 / run["steps"]] for r in whiles],
            "containers_over_their_bodies_ms": ms(whiles, run) - compute(run)}
