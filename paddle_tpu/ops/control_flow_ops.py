"""while_loop / cond op kernels + compare ops.

Reference: paddle/operators/while_op.cc (Executor re-runs the sub-block
while the cond var holds), conditional_block_op.cc, and the compare ops
(less_than/greater_than/equal — operators/compare_op.cc). Sub-blocks are
traced into jax.lax.while_loop / jax.lax.cond — compiled control flow
with no host round-trip per iteration.

`while_loop` is forward-only. `repeat` is the trainable loop of a fixed
count: a `jax.lax.scan` over the sub-block whose body is rematerialised a
turn at a time (`layers.Repeat`); a recurrence over a sequence axis is
`recurrent_group` (ops/recurrent_ops.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.lod import LoDArray
from ..core.registry import register_op


@register_op("while_loop")
def while_loop_kernel(ctx):
    """NOTE on training: jax.lax.while_loop is forward-only — reverse-mode

    differentiation through a While raises. This matches TPU reality
    (unbounded loops can't be rematerialized); a trainable loop of a fixed
    count is `repeat` (below), and for trainable recurrences over a
    sequence use recurrent_group (bounded lax.scan), the same way the
    reference's trainable dynamic RNNs layer on top of while_op via the RNN
    memory machinery rather than raw while backward."""
    from .recurrent_ops import _group_rng

    carried0 = ctx.inputs("Carried")
    carried_names = list(ctx.attr("carried"))
    update_names = list(ctx.attr("updates"))
    block = ctx.executor.program.blocks[ctx.attr("sub_block")]
    outer_env = dict(ctx.env)
    base_key = _group_rng(ctx, outer_env)
    cond_name = ctx.op.inputs["Cond"][0]
    cond_pos = carried_names.index(cond_name)

    def cond_fun(carry):
        it, vals = carry
        return jnp.reshape(vals[cond_pos], ()).astype(bool)

    def body_fun(carry):
        it, vals = carry
        env = dict(outer_env)
        # fresh randomness per iteration (dropout etc.)
        env["@RNG@"] = jax.random.fold_in(base_key, it)
        env["@RNG_COUNTER@"] = 0
        for name, v in zip(carried_names, vals):
            env[name] = v
        ctx.executor.run_ops(block.ops, env, dict(env), block)
        return it + 1, tuple(env[u] for u in update_names)

    # entry condition False -> zero iterations, finals = entry values
    _, final = jax.lax.while_loop(
        cond_fun, body_fun, (jnp.asarray(0, jnp.int32), tuple(carried0))
    )
    for i, v in enumerate(final):
        ctx.set_output("Out", v, i)


_REPEAT_COUNTER = "pt_repeat_dispatch_total"
_REPEAT_HELP = "repeat ops traced, by whether a turn is rematerialised"


def _avals(tree):
    return [(tuple(a.shape), np.dtype(a.dtype).name)
            for a in jax.tree_util.tree_leaves(tree)]


def _nbytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


@register_op("repeat")
def repeat_kernel(ctx):
    """`layers.Repeat`: the sub-block as the body of a `jax.lax.scan` of
    length `times`. Parameters are read from the enclosing environment, so
    under `_run_autodiff` they are the closure's differentiated values and
    their gradient is the sum over the turns. With `remat` the body is under
    `jax.checkpoint`: the loop saves each turn's carries and the stacked
    outputs, and the backward loop recomputes a turn before it transposes
    it. Counted when traced: `pt_repeat_dispatch_total{remat}`; the gauges
    `pt_repeat_turns` and `pt_repeat_saved_bytes` (the carries of every turn
    and the stacked outputs, from the traced shapes) are the last traced
    op's."""
    from ..obs import metrics
    from .recurrent_ops import _group_rng

    carried0 = tuple(ctx.inputs("Carried"))
    carried_names = list(ctx.attr("carried"))
    update_names = list(ctx.attr("updates"))
    out_names = list(ctx.attr("turn_outputs"))
    times, remat = int(ctx.attr("times")), bool(ctx.attr("remat", True))
    block = ctx.executor.program.blocks[ctx.attr("sub_block")]
    outer_env = dict(ctx.env)
    base_key = _group_rng(ctx, outer_env)

    def turn(vals, it):
        env = dict(outer_env)
        # fresh randomness per turn (dropout etc.)
        env["@RNG@"] = jax.random.fold_in(base_key, it)
        env["@RNG_COUNTER@"] = 0
        env.update(zip(carried_names, vals))
        with jax.named_scope("repeat.turn"):
            ctx.executor.run_ops(block.ops, env, dict(env), block)
        new = tuple(env[u] for u in update_names)
        for name, a, b in zip(carried_names, vals, new):
            if _avals(a) != _avals(b):
                raise ValueError(
                    f"repeat: the carry {name} enters a turn as {_avals(a)} "
                    f"and leaves it as {_avals(b)}")
        return new, tuple(env[o] for o in out_names)

    final, stacks = jax.lax.scan(
        jax.checkpoint(turn) if remat else turn, carried0,
        jnp.arange(times, dtype=jnp.int32))
    saved = times * _nbytes(carried0) + _nbytes(stacks)
    reg = metrics.registry()
    reg.counter_inc(_REPEAT_COUNTER, help=_REPEAT_HELP,
                    labels={"remat": str(remat).lower()})
    reg.gauge("pt_repeat_turns", lambda: times,
              help="turns of the last traced repeat op")
    reg.gauge("pt_repeat_saved_bytes", lambda: saved,
              help="bytes the last traced repeat op keeps across its loop for "
                   "the backward pass: every turn's carries and the stacked "
                   "turn outputs")
    for i, v in enumerate(final):
        ctx.set_output("Out", v, i)
    for i, v in enumerate(stacks):
        ctx.set_output("Turns", v, i)


@register_op("cond")
def cond_kernel(ctx):
    from .recurrent_ops import _group_rng

    pred = jnp.reshape(ctx.input("Pred"), ()).astype(bool)
    outer_env = dict(ctx.env)
    base_key = _group_rng(ctx, outer_env)
    prog = ctx.executor.program

    def branch(block_idx, out_names):
        block = prog.blocks[block_idx]

        def run(_):
            env = dict(outer_env)
            env["@RNG@"] = base_key
            env["@RNG_COUNTER@"] = 0
            ctx.executor.run_ops(block.ops, env, dict(env), block)
            return tuple(env[n] for n in out_names)

        return run

    outs = jax.lax.cond(
        pred,
        branch(ctx.attr("true_block"), list(ctx.attr("true_outs"))),
        branch(ctx.attr("false_block"), list(ctx.attr("false_outs"))),
        operand=None,
    )
    for i, v in enumerate(outs):
        ctx.set_output("Out", v, i)


# ------------------------------------------------------------- compares ---
def _data(x):
    return x.data if isinstance(x, LoDArray) else x


def _like(x, data):
    return x.with_data(data) if isinstance(x, LoDArray) else data


def _compare(name, fn):
    @register_op(name)
    def kernel(ctx):  # noqa: F811 — one kernel per registered name
        x_in = ctx.input("X")
        x, y = _data(x_in), _data(ctx.input("Y"))
        ctx.set_output("Out", _like(x_in, fn(x, y)))

    return kernel


_compare("less_than", lambda x, y: x < y)
_compare("less_equal", lambda x, y: x <= y)
_compare("greater_than", lambda x, y: x > y)
_compare("greater_equal", lambda x, y: x >= y)
_compare("equal", lambda x, y: x == y)
_compare("not_equal", lambda x, y: x != y)


@register_op("logical_and")
def logical_and_kernel(ctx):
    x_in = ctx.input("X")
    ctx.set_output(
        "Out",
        _like(x_in, jnp.logical_and(_data(x_in), _data(ctx.input("Y")))),
    )


@register_op("logical_not")
def logical_not_kernel(ctx):
    x_in = ctx.input("X")
    ctx.set_output("Out", _like(x_in, jnp.logical_not(_data(x_in))))
