"""Optimizer update op kernels.

Reference: paddle/operators/{sgd_op,momentum_op,adagrad_op,adadelta_op,
rmsprop_op,decayed_adagrad_op,adam_op,adamax_op,ftrl_op,proximal_gd_op,
proximal_adagrad_op}.cc — the 10+ Fluid optimizer ops — and the Gen-1
equivalents in paddle/parameter/FirstOrderOptimizer.h:24-346. Update math
follows the reference's kernels exactly; each op updates the parameter (and
its moment persistables) in place in the env, so the new values flow back to
the Scope after the jitted step.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..core.registry import register_op
from ..core.sparse import SelectedRows


def _write(ctx, slot_in, value):
    """Write back through an in/out slot pair (ParamOut etc.)."""
    name = ctx.op.inputs[slot_in][0]
    ctx.env[name] = value
    out_slot = slot_in + "Out"
    if ctx.has_output(out_slot):
        ctx.set_output(out_slot, value)


def _lr(ctx):
    lr = ctx.input("LearningRate")
    return jnp.reshape(lr, ()) if hasattr(lr, "shape") else lr


@register_op("sgd", writes=("Param",))
def sgd_kernel(ctx):
    """Reference: sgd_op.cc — p -= lr * g. SelectedRows grads (embedding
    is_sparse) apply as a row-wise scatter-add, touching only gathered rows
    (sgd_op.cc's SelectedRows branch / SparseRowMatrix sgdUpdate)."""
    p, g = ctx.input("Param"), ctx.input("Grad")
    if isinstance(g, SelectedRows):
        # duplicate rows accumulate — scatter-add is linear, no dedup needed
        _write(ctx, "Param",
               p.at[g.rows].add(-_lr(ctx) * g.values, mode="drop"))
        return
    _write(ctx, "Param", p - _lr(ctx) * g)


@register_op("momentum", writes=("Param", "Velocity"))
def momentum_kernel(ctx):
    """Reference: momentum_op.cc — supports use_nesterov."""
    p, g, v = ctx.input("Param"), ctx.input("Grad"), ctx.input("Velocity")
    mu = ctx.attr("mu", 0.9)
    lr = _lr(ctx)
    if isinstance(g, SelectedRows):
        # lazy momentum: decay + step only on touched rows
        rows, vals = g.dedup()
        v_rows = mu * v[rows] + vals
        if ctx.attr("use_nesterov", False):
            step = -(vals + mu * v_rows) * lr
        else:
            step = -lr * v_rows
        _write(ctx, "Velocity", v.at[rows].set(v_rows, mode="drop"))
        _write(ctx, "Param", p.at[rows].add(step, mode="drop"))
        return
    v_new = mu * v + g
    if ctx.attr("use_nesterov", False):
        p_new = p - (g + mu * v_new) * lr
    else:
        p_new = p - lr * v_new
    _write(ctx, "Velocity", v_new)
    _write(ctx, "Param", p_new)


@register_op("adagrad", writes=("Param", "Moment"))
def adagrad_kernel(ctx):
    """Reference: adagrad_op.cc — moment += g²; p -= lr*g/(√moment+ε).

    SelectedRows grads: lazy row-wise update (adagrad_op.cc SelectedRows
    branch merges duplicate rows first — dedup() here; untouched rows'
    moments stay untouched, matching the reference's sparse semantics)."""
    p, g, m = ctx.input("Param"), ctx.input("Grad"), ctx.input("Moment")
    eps = ctx.attr("epsilon", 1e-6)
    if isinstance(g, SelectedRows):
        rows, vals = g.dedup()
        m_rows = m[rows] + jnp.square(vals)
        upd = -_lr(ctx) * vals / (jnp.sqrt(m_rows) + eps)
        _write(ctx, "Moment", m.at[rows].set(m_rows, mode="drop"))
        _write(ctx, "Param", p.at[rows].add(upd, mode="drop"))
        return
    m_new = m + jnp.square(g)
    p_new = p - _lr(ctx) * g / (jnp.sqrt(m_new) + eps)
    _write(ctx, "Moment", m_new)
    _write(ctx, "Param", p_new)


@register_op("adadelta", writes=("Param", "AvgSquaredGrad", "AvgSquaredUpdate"))
def adadelta_kernel(ctx):
    """Reference: adadelta_op.cc."""
    p, g = ctx.input("Param"), ctx.input("Grad")
    avg_sq_g = ctx.input("AvgSquaredGrad")
    avg_sq_u = ctx.input("AvgSquaredUpdate")
    rho = ctx.attr("rho", 0.95)
    eps = ctx.attr("epsilon", 1e-6)
    g2 = rho * avg_sq_g + (1 - rho) * jnp.square(g)
    update = -jnp.sqrt((avg_sq_u + eps) / (g2 + eps)) * g
    u2 = rho * avg_sq_u + (1 - rho) * jnp.square(update)
    _write(ctx, "AvgSquaredGrad", g2)
    _write(ctx, "AvgSquaredUpdate", u2)
    _write(ctx, "Param", p + update)


@register_op("rmsprop", writes=("Param", "MeanSquare", "Moment"))
def rmsprop_kernel(ctx):
    """Reference: rmsprop_op.cc — with momentum term."""
    p, g = ctx.input("Param"), ctx.input("Grad")
    ms, mom = ctx.input("MeanSquare"), ctx.input("Moment")
    rho = ctx.attr("decay", 0.9)
    mu = ctx.attr("momentum", 0.0)
    eps = ctx.attr("epsilon", 1e-6)
    lr = _lr(ctx)
    ms_new = rho * ms + (1 - rho) * jnp.square(g)
    mom_new = mu * mom + lr * g / jnp.sqrt(ms_new + eps)
    _write(ctx, "MeanSquare", ms_new)
    _write(ctx, "Moment", mom_new)
    _write(ctx, "Param", p - mom_new)


@register_op("decayed_adagrad", writes=("Param", "Moment"))
def decayed_adagrad_kernel(ctx):
    """Reference: decayed_adagrad_op.cc."""
    p, g, m = ctx.input("Param"), ctx.input("Grad"), ctx.input("Moment")
    decay = ctx.attr("decay", 0.95)
    eps = ctx.attr("epsilon", 1e-6)
    m_new = decay * m + (1 - decay) * jnp.square(g)
    _write(ctx, "Moment", m_new)
    _write(ctx, "Param", p - _lr(ctx) * g / (jnp.sqrt(m_new) + eps))


@register_op("adam", writes=("Param", "Moment1", "Moment2", "Beta1Pow", "Beta2Pow"))
def adam_kernel(ctx):
    """Reference: adam_op.cc — bias-corrected via Beta1Pow/Beta2Pow state."""
    p, g = ctx.input("Param"), ctx.input("Grad")
    m1, m2 = ctx.input("Moment1"), ctx.input("Moment2")
    b1p, b2p = ctx.input("Beta1Pow"), ctx.input("Beta2Pow")
    b1 = ctx.attr("beta1", 0.9)
    b2 = ctx.attr("beta2", 0.999)
    eps = ctx.attr("epsilon", 1e-8)
    lr = _lr(ctx)
    if isinstance(g, SelectedRows):
        # lazy adam (adam_op.cc SelectedRows branch): moments and step only
        # on touched rows; Beta*Pow still advance globally per step
        rows, vals = g.dedup()
        m1r = b1 * m1[rows] + (1 - b1) * vals
        m2r = b2 * m2[rows] + (1 - b2) * jnp.square(vals)
        lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
        step = -lr_t * m1r / (jnp.sqrt(m2r) + eps)
        _write(ctx, "Moment1", m1.at[rows].set(m1r, mode="drop"))
        _write(ctx, "Moment2", m2.at[rows].set(m2r, mode="drop"))
        _write(ctx, "Beta1Pow", b1p * b1)
        _write(ctx, "Beta2Pow", b2p * b2)
        _write(ctx, "Param", p.at[rows].add(step, mode="drop"))
        return
    m1n = b1 * m1 + (1 - b1) * g
    m2n = b2 * m2 + (1 - b2) * jnp.square(g)
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    p_new = p - lr_t * m1n / (jnp.sqrt(m2n) + eps)
    _write(ctx, "Moment1", m1n)
    _write(ctx, "Moment2", m2n)
    _write(ctx, "Beta1Pow", b1p * b1)
    _write(ctx, "Beta2Pow", b2p * b2)
    _write(ctx, "Param", p_new)


@register_op("adamax", writes=("Param", "Moment", "InfNorm", "Beta1Pow"))
def adamax_kernel(ctx):
    """Reference: adamax_op.cc."""
    p, g = ctx.input("Param"), ctx.input("Grad")
    m, inf = ctx.input("Moment"), ctx.input("InfNorm")
    b1p = ctx.input("Beta1Pow")
    b1 = ctx.attr("beta1", 0.9)
    b2 = ctx.attr("beta2", 0.999)
    eps = ctx.attr("epsilon", 1e-8)
    lr = _lr(ctx)
    m_new = b1 * m + (1 - b1) * g
    inf_new = jnp.maximum(b2 * inf, jnp.abs(g) + eps)
    p_new = p - (lr / (1 - b1p)) * m_new / inf_new
    _write(ctx, "Moment", m_new)
    _write(ctx, "InfNorm", inf_new)
    _write(ctx, "Beta1Pow", b1p * b1)
    _write(ctx, "Param", p_new)


@register_op("ftrl", writes=("Param", "SquaredAccumulator", "LinearAccumulator"))
def ftrl_kernel(ctx):
    """Reference: ftrl_op.cc."""
    p, g = ctx.input("Param"), ctx.input("Grad")
    sq, lin = ctx.input("SquaredAccumulator"), ctx.input("LinearAccumulator")
    l1 = ctx.attr("l1", 0.0)
    l2 = ctx.attr("l2", 0.0)
    lr_power = ctx.attr("lr_power", -0.5)
    lr = _lr(ctx)
    new_sq = sq + jnp.square(g)
    if lr_power == -0.5:
        sigma = (jnp.sqrt(new_sq) - jnp.sqrt(sq)) / lr
    else:
        sigma = (jnp.power(new_sq, -lr_power) - jnp.power(sq, -lr_power)) / lr
    new_lin = lin + g - sigma * p
    if lr_power == -0.5:
        denom = jnp.sqrt(new_sq) / lr + 2 * l2
    else:
        denom = jnp.power(new_sq, -lr_power) / lr + 2 * l2
    pre_shrink = (l1 * jnp.sign(new_lin) - new_lin) / denom
    p_new = jnp.where(jnp.abs(new_lin) > l1, pre_shrink, 0.0)
    _write(ctx, "SquaredAccumulator", new_sq)
    _write(ctx, "LinearAccumulator", new_lin)
    _write(ctx, "Param", p_new)


@register_op("average_accumulate", writes=("Sum", "Count", "Total"))
def average_accumulate_kernel(ctx):
    """Sliding-window parameter accumulation for ModelAverage.

    Reference: paddle/parameter/AverageOptimizer.h — the accumulator
    restarts once the window (clamp(rate * num_updates, min_window,
    max_window)) is exceeded, so apply() averages only recent values."""
    p = ctx.input("Param")
    s, n, t = ctx.input("Sum"), ctx.input("Count"), ctx.input("Total")
    rate = ctx.attr("average_window", 0.15)
    min_w = ctx.attr("min_average_window", 10000)
    max_w = ctx.attr("max_average_window", 10**9)
    t_new = t + 1.0
    window = jnp.clip(rate * t_new, min_w, max_w)
    restart = (n + 1.0) > window
    s_new = jnp.where(restart, p, s + p)
    n_new = jnp.where(restart, 1.0, n + 1.0)
    ctx.env[ctx.op.inputs["Sum"][0]] = s_new
    ctx.env[ctx.op.inputs["Count"][0]] = n_new
    ctx.env[ctx.op.inputs["Total"][0]] = t_new


@register_op("lr_schedule")
def lr_schedule_kernel(ctx):
    """Computes the scheduled learning rate from the global step.

    Reference: Gen-1 LearningRateScheduler.cpp policies; fluid lr decay.
    The `schedule` attr is an optimizer.LRSchedule instance applied at
    trace time — the schedule math becomes part of the XLA program."""
    step = ctx.input("Step")
    sched = ctx.attr("schedule")
    ctx.set_output("Out", sched(step, ctx.attr("base_lr")))


@register_op("proximal_gd", writes=("Param",))
def proximal_gd_kernel(ctx):
    """Reference: proximal_gd_op.cc — l1/l2-regularized SGD step."""
    p, g = ctx.input("Param"), ctx.input("Grad")
    l1, l2 = ctx.attr("l1", 0.0), ctx.attr("l2", 0.0)
    lr = _lr(ctx)
    prox = p - lr * g
    p_new = (
        jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
        / (1.0 + lr * l2)
    )
    _write(ctx, "Param", p_new)


@register_op("prune_mask_init")
def prune_mask_init_kernel(ctx):
    """Reference: ParameterUpdaterHook.cpp:105 StaticPruningHook::
    generateMask — sort |w|, zero the smallest sparsity_ratio fraction.
    Runs once in the startup program, after the param's initializer."""
    w = ctx.input("Param")
    ratio = float(ctx.attr("sparsity_ratio", 0.8))
    flat = jnp.abs(w).reshape(-1)
    k = int(round(ratio * flat.size))
    if k <= 0:
        ctx.set_output("Out", jnp.ones_like(w))
        return
    # Exactly-k selection by sorted index (the reference partial_sorts
    # indices): a |w| > threshold compare would also prune every value
    # tied at the threshold — a constant-magnitude init would mask to
    # all-zero.
    order = jnp.argsort(flat)
    mask = jnp.ones(flat.shape, w.dtype).at[order[:k]].set(0)
    ctx.set_output("Out", mask.reshape(w.shape))


@register_op("apply_mask", writes=("Param",))
def apply_mask_kernel(ctx):
    """Reference: ParameterUpdaterHook.cpp:86 StaticPruningHook::update —
    re-apply the static mask after every optimizer step."""
    p, m = ctx.input("Param"), ctx.input("Mask")
    _write(ctx, "Param", p * m)
