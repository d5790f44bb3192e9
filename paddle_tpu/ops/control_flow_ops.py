"""while_loop / cond op kernels + compare ops.

Reference: paddle/operators/while_op.cc (Executor re-runs the sub-block
while the cond var holds), conditional_block_op.cc, and the compare ops
(less_than/greater_than/equal — operators/compare_op.cc). Sub-blocks are
traced into jax.lax.while_loop / jax.lax.cond — compiled control flow
with no host round-trip per iteration.

`while_loop` is forward-only. `repeat` is the trainable loop of a fixed
count: a `jax.lax.scan` over the sub-block under a differentiation rule of
its own, which runs every turn but the last again in the backward pass, a
turn at a time, and differentiates the last where it stands
(`layers.Repeat`); a recurrence over a sequence axis is `recurrent_group`
(ops/recurrent_ops.py).
"""

from __future__ import annotations

import functools

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
RERUN_SCOPE = "rematted_computation"   # `jax.checkpoint`'s name for the same


def _avals(tree):
    return [(tuple(a.shape), np.dtype(a.dtype).name)
            for a in jax.tree_util.tree_leaves(tree)]


def _nbytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


def _takes_cotangent(a) -> bool:
    return bool(jnp.issubdtype(a.dtype, jnp.inexact))


def _floats(tree) -> list:
    """The leaves of `tree` that take a cotangent, in order."""
    return [a for a in jax.tree_util.tree_leaves(tree) if _takes_cotangent(a)]


def _with_floats(tree, floats, others=lambda a: a):
    """`tree` with its float leaves replaced, in order, by `floats`, and
    `others` of every other leaf."""
    floats = iter(floats)
    return jax.tree_util.tree_map(
        lambda a: next(floats) if _takes_cotangent(a) else others(a), tree)


def _explicit(fun, *args):
    """`fun(*args)` traced ONCE, as a jitted function of `args` and of every
    traced value it read from its closure: (step, ints, floats) with
    `step(ints, floats, *args) = fun(*args)`. `floats` can take a cotangent
    (the parameters); `ints` cannot (labels, the RNG key). `jax.jit` keeps no
    trace of a function that closes over a traced value, so with these as
    arguments every call of `step` in one program shares its trace, and what
    is derived from it (`jax.vjp`'s two halves, each one's lowering)."""
    closed, shape = jax.make_jaxpr(fun, return_shape=True)(*args)
    out_tree = jax.tree_util.tree_structure(shape)
    consts = closed.consts
    traced = [i for i, c in enumerate(consts) if isinstance(c, jax.core.Tracer)]
    int_at = [i for i in traced if not _takes_cotangent(consts[i])]
    float_at = [i for i in traced if _takes_cotangent(consts[i])]

    @jax.jit
    def step(ints, floats, *args):
        mine = list(consts)
        for i, value in zip(int_at + float_at, (*ints, *floats)):
            mine[i] = value
        return jax.tree_util.tree_unflatten(out_tree, jax.core.eval_jaxpr(
            closed.jaxpr, mine, *jax.tree_util.tree_leaves(args)))

    return step, [consts[i] for i in int_at], [consts[i] for i in float_at]


def _one_mesh_scope(fun):
    """`fun` under the mesh that is in scope, said explicitly. JAX keys its
    traces (`jax.jit`'s, and those of what it derives from one) on the mesh
    context as well, and a backward pass runs under an explicit empty mesh
    where its forward pass ran under none: the same mesh, another key, and
    nothing `_explicit` traced would be found again."""
    @functools.wraps(fun)
    def scoped(*args):
        with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
            return fun(*args)

    return scoped


def _turn_and_pullback(step, ints, floats, vals, it):
    """(`step(ints, floats, vals, it)`, the pullback of its float outputs to
    (`floats`, the float leaves of `vals`)): the forward that keeps its
    residuals. Integer carries and outputs take no part."""
    def of_floats(floats, float_vals):
        out = step(ints, floats, _with_floats(vals, float_vals), it)
        return _floats(out), out

    _, pullback, out = jax.vjp(of_floats, floats, _floats(vals), has_aux=True)
    return out, pullback


def _all_turns_but_the_last_again(step, times):
    """`scan(step, length=times)` under one differentiation rule: turns
    1..K-1 keep their carries and are run again in the backward pass, a turn
    at a time, as `scan(jax.checkpoint(step))` runs all K again; turn K is
    differentiated once, where it stands. Its residuals are what the backward
    pass holds of any turn while it transposes it, and the forward pass held
    them a moment earlier: kept across the ops between the two, they put
    nothing on the peak. The backward loop's gradient sums START from turn
    K's gradients: ((G_K + G_K-1) + ...) + G_1 in the operands' dtypes, the
    transposed scan's sums after its 0 + G_K, and one set of them alive, not
    two. `step(ints, floats, vals, it)` is `_explicit`'s: the forward loop,
    turn K and the backward loop share its trace, and the latter two its
    linearisation, its transpose and their lowerings. The forward that the
    backward loop runs again is under `RERUN_SCOPE`, where a trace's readers
    look for rematerialised work."""
    turns = jnp.arange(times, dtype=jnp.int32)

    @jax.custom_vjp
    def loop(ints, floats, vals):
        return jax.lax.scan(lambda v, it: step(ints, floats, v, it), vals,
                            turns)

    @_one_mesh_scope
    def forward(ints, floats, vals):
        def body(v, it):
            new, outs = step(ints, floats, v, it)
            return new, (v, outs)

        last, (carries, stacks) = jax.lax.scan(body, vals, turns[:-1])
        (final, outs), pullback = _turn_and_pullback(
            step, ints, floats, last, turns[-1])
        stacks = jax.tree_util.tree_map(
            lambda s, o: jnp.concatenate([s, o[None]]), stacks, outs)
        return (final, stacks), (ints, floats, carries, pullback)

    @_one_mesh_scope
    def backward(res, g):
        ints, floats, carries, pullback = res
        g_final, g_stacks = (_floats(part) for part in g)
        sums, d_vals = pullback(g_final + [s[-1] for s in g_stacks])

        def body(carry, xs):
            d_vals, sums = carry
            it, vals, g_outs = xs
            with jax.named_scope(RERUN_SCOPE):
                _, pullback = _turn_and_pullback(step, ints, floats, vals, it)
            grads, d_vals = pullback(d_vals + g_outs)
            return (d_vals, [a + b for a, b in zip(sums, grads)]), None

        (d_vals, sums), _ = jax.lax.scan(
            body, (d_vals, sums),
            (turns[:-1], carries, [s[:-1] for s in g_stacks]), reverse=True)
        # `carries` has the carry's tree and dtypes
        return None, sums, _with_floats(carries, d_vals, others=lambda a: None)

    loop.defvjp(forward, backward)
    return loop


@register_op("repeat")
def repeat_kernel(ctx):
    """`layers.Repeat`: the sub-block as the body of a `jax.lax.scan` of
    length `times`. Parameters are read from the enclosing environment, so
    under `_run_autodiff` they are the closure's differentiated values and
    their gradient is the sum over the turns. With `remat` (and more than one
    turn) the loop saves the carries of turns 1..K-1 and the stacked outputs,
    the backward loop runs each of those turns again before it transposes it,
    and the last turn is differentiated where it stands, run once
    (`_all_turns_but_the_last_again`): the block is traced ONCE, to a jitted
    function of the carries, the turn and whatever traced values it read from
    the environment (`_explicit`). Counted when traced:
    `pt_repeat_dispatch_total{remat}`; the gauges `pt_repeat_turns`,
    `pt_repeat_rerun_turns` and `pt_repeat_saved_bytes` are the last traced
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

    rerun = times - 1 if remat else 0
    if rerun:
        step, ints, floats = _explicit(turn, carried0, jnp.int32(0))
        final, stacks = _all_turns_but_the_last_again(step, times)(
            ints, floats, carried0)
    else:
        final, stacks = jax.lax.scan(turn, carried0,
                                     jnp.arange(times, dtype=jnp.int32))
    # the loop keeps the carries of the turns it runs again; without remat
    # every turn's, among all else
    saved = (rerun or times) * _nbytes(carried0) + _nbytes(stacks)
    reg = metrics.registry()
    reg.counter_inc(_REPEAT_COUNTER, help=_REPEAT_HELP,
                    labels={"remat": str(remat).lower()})
    reg.gauge("pt_repeat_turns", lambda: times,
              help="turns of the last traced repeat op")
    reg.gauge("pt_repeat_rerun_turns", lambda: rerun,
              help="turns the last traced repeat op runs again in its "
                   "backward pass: all but the last with remat, none without")
    reg.gauge("pt_repeat_saved_bytes", lambda: saved,
              help="bytes the last traced repeat op's LOOP keeps for the "
                   "backward pass: the carries of the turns it runs again "
                   "(of every turn without remat) and the stacked turn "
                   "outputs; not the last turn's residuals, which the "
                   "backward pass of any turn holds as well")
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
