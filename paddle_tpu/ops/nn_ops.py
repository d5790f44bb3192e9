"""NN op kernels: conv, pool, batch_norm, dropout, losses, metrics.

Reference coverage: paddle/operators/{conv_op,pool_op,batch_norm_op,
dropout_op,cross_entropy_op,softmax_with_cross_entropy_op,accuracy_op,
lrn_op}.cc plus the Gen-1 kernels they generalize (paddle/function/GemmConvOp,
gserver/layers/CudnnConvBaseLayer, CostLayer.cpp). Convs map to
lax.conv_general_dilated (MXU path — XLA lowers conv to systolic-array
matmuls internally); data layout is NCHW to match the reference API, XLA
re-layouts for TPU automatically.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.lod import LoDArray
from .. import amp
from ..core.registry import register_op
from .qk_ops import qk_assemble


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v, v)


# ------------------------------------------------------------------ conv ---
@register_op("conv2d")
def conv2d_kernel(ctx):
    """Reference: paddle/operators/conv_op.cc (REGISTER_OP conv2d);

    groups/dilation semantics per ConvOp::InferShape."""
    x = ctx.input("Input")  # [N, C, H, W] (or NHWC per data_format)
    w = ctx.input("Filter")  # [out_c, in_c/groups, kh, kw] always OIHW
    stride = _pair(ctx.attr("strides", (1, 1)))
    pad = _pair(ctx.attr("paddings", (0, 0)))
    dil = _pair(ctx.attr("dilations", (1, 1)))
    groups = ctx.attr("groups", 1)
    # NHWC: channels-minor is the TPU-preferred layout (channel dim maps
    # to the 128-wide lane dimension without a relayout); the parameter
    # keeps the reference's OIHW shape for checkpoint compatibility and is
    # transposed at trace time (weights are small; XLA folds this)
    fmt = ctx.attr("data_format", "NCHW")
    if fmt == "NHWC":
        w = jnp.transpose(w, (2, 3, 1, 0))  # OIHW -> HWIO
    xc, wc = amp.cast_inputs(ctx, x, w)
    # under amp the conv runs bf16→bf16 and the OUTPUT stays bf16 (the MXU
    # accumulates f32 internally; keeping the activation at 2 B/elem is the
    # HBM-traffic win — see amp.py). A mixed preferred_element_type would
    # break conv's VJP transpose rule, so f32 accumulation is only
    # requested on the pure-f32 path.
    acc = jnp.float32 if xc.dtype == jnp.float32 else None
    out = jax.lax.conv_general_dilated(
        xc,
        wc,
        window_strides=stride,
        padding=[(pad[0], pad[0]), (pad[1], pad[1])],
        rhs_dilation=dil,
        feature_group_count=groups,
        dimension_numbers=(
            (fmt, "OIHW" if fmt == "NCHW" else "HWIO", fmt)
        ),
        preferred_element_type=acc,
    )
    if ctx.has_input("Bias"):
        bshape = (1, -1, 1, 1) if fmt == "NCHW" else (1, 1, 1, -1)
        bias = ctx.input("Bias").reshape(bshape)
        out = out + bias.astype(out.dtype)
    ctx.set_output("Output", out)


@register_op("conv2d_transpose")
def conv2d_transpose_kernel(ctx):
    """Reference: paddle/operators/conv_transpose_op.cc — Filter layout

    [in_c, out_c, kh, kw]. Expressed as the fractionally-strided conv:
    lhs dilated by the stride, spatially-flipped kernel in OIHW, padding
    k-1-p (verified element-wise against torch's conv_transpose2d)."""
    x = ctx.input("Input")
    w = ctx.input("Filter")  # [in_c, out_c, kh, kw]
    stride = _pair(ctx.attr("strides", (1, 1)))
    pad = _pair(ctx.attr("paddings", (0, 0)))
    kh, kw = w.shape[2], w.shape[3]
    wk = jnp.transpose(w, (1, 0, 2, 3))[:, :, ::-1, ::-1]  # OIHW, flipped
    xc, wc = amp.cast_inputs(ctx, x, wk)
    acc = jnp.float32 if xc.dtype == jnp.float32 else None
    out = jax.lax.conv_general_dilated(
        xc,
        wc,
        window_strides=(1, 1),
        padding=[(kh - 1 - pad[0], kh - 1 - pad[0]),
                 (kw - 1 - pad[1], kw - 1 - pad[1])],
        lhs_dilation=stride,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=acc,
    )
    if ctx.has_input("Bias"):
        bias = ctx.input("Bias").reshape((1, -1, 1, 1))
        out = out + bias.astype(out.dtype)
    ctx.set_output("Output", out)


# ------------------------------------------------------------------ pool ---
@register_op("pool2d")
def pool2d_kernel(ctx):
    """Reference: paddle/operators/pool_op.cc — max/avg, ksize/strides/

    paddings, global_pooling."""
    x = ctx.input("X")  # [N, C, H, W] (or NHWC per data_format)
    ptype = ctx.attr("pooling_type", "max")
    ksize = _pair(ctx.attr("ksize", (2, 2)))
    stride = _pair(ctx.attr("strides", (2, 2)))
    pad = _pair(ctx.attr("paddings", (0, 0)))
    fmt = ctx.attr("data_format", "NCHW")
    hw = slice(2, 4) if fmt == "NCHW" else slice(1, 3)
    if ctx.attr("global_pooling", False):
        ksize = x.shape[hw]
        stride = ksize
        pad = (0, 0)
    sp_pad = ((pad[0], pad[0]), (pad[1], pad[1]))
    if fmt == "NCHW":
        window = (1, 1) + tuple(ksize)
        strides = (1, 1) + tuple(stride)
        pads = ((0, 0), (0, 0)) + sp_pad
    else:
        window = (1,) + tuple(ksize) + (1,)
        strides = (1,) + tuple(stride) + (1,)
        pads = ((0, 0),) + sp_pad + ((0, 0),)
    if ptype == "max":
        init = -jnp.inf
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, strides, pads)
    else:
        summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides, pads)
        if ctx.attr("exclusive", True) and pad != (0, 0):
            ones = jnp.ones_like(x)
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides, pads)
            out = summed / counts
        else:
            out = summed / float(ksize[0] * ksize[1])
    ctx.set_output("Out", out)


# ------------------------------------------------------------ batch norm ---
@register_op(
    "batch_norm",
    writes=lambda op: () if op.attrs.get("is_test") else ("Mean", "Variance"),
)
def batch_norm_kernel(ctx):
    """Reference: paddle/operators/batch_norm_op.cc. Train mode computes

    batch stats and updates the running mean/var persistables; eval mode
    consumes them. NCHW: stats over (N, H, W)."""
    x = ctx.input("X")
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    mean_v, var_v = ctx.input("Mean"), ctx.input("Variance")
    momentum = ctx.attr("momentum", 0.9)
    eps = ctx.attr("epsilon", 1e-5)
    is_test = ctx.attr("is_test", False)

    ch = x.ndim - 1 if ctx.attr("data_format", "NCHW") == "NHWC" else 1
    axes = tuple(i for i in range(x.ndim) if i != ch)
    shape = tuple(-1 if i == ch else 1 for i in range(x.ndim))
    # stats in f32 even when activations are bf16 (amp): mean/var of a
    # large batch loses precision in bf16; running stats stay f32 masters
    x32 = x.astype(jnp.float32)
    if is_test:
        mean, var = mean_v, var_v
    else:
        # square in the io dtype, reduce with f32 accumulation,
        # E[x^2]-E[x]^2 var
        mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
        sq = jnp.mean(x * x, axis=axes, dtype=jnp.float32)
        var = jnp.maximum(sq - mean * mean, 0.0)
        new_mean = momentum * mean_v + (1 - momentum) * mean
        new_var = momentum * var_v + (1 - momentum) * var
        # running stats flow back into the Scope as persistables
        ctx.env[ctx.op.inputs["Mean"][0]] = new_mean
        ctx.env[ctx.op.inputs["Variance"][0]] = new_var
    inv = jax.lax.rsqrt(var + eps)
    out = (x32 - mean.reshape(shape)) * inv.reshape(shape) * scale.reshape(
        shape
    ) + bias.reshape(shape)
    ctx.set_output("Y", out.astype(x.dtype))


@register_op("layer_norm")
def layer_norm_kernel(ctx):
    """Reference: paddle/operators/layer_norm_op.cc (added late in v0.11)."""
    x = ctx.input("X")
    eps = ctx.attr("epsilon", 1e-5)
    begin = ctx.attr("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    x32 = x.astype(jnp.float32)  # stats in f32 under amp (see batch_norm)
    mean = jnp.mean(x32, axis=axes, keepdims=True)
    var = jnp.var(x32, axis=axes, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    if ctx.has_input("Scale"):
        out = out * ctx.input("Scale")
    if ctx.has_input("Bias"):
        out = out + ctx.input("Bias")
    ctx.set_output("Y", out.astype(x.dtype))


def rms_norm(x, scale, eps: float):
    """x * rsqrt(mean(x^2, -1) + eps) * scale, in float32 and RETURNED in
    float32 whatever x's dtype (as the losses are under amp, and unlike
    layer_norm): what reads a norm is either an MXU op, which casts its own
    inputs down, or the routed FFN's router, which must see the float32
    value: a router fed a bf16-rounded input turns more near-ties the
    other way than one fed float32 (3.5 % of tokens against 2.0 %). That is
    every norm of a model's stream, the latent norms and a closing norm. The
    two norms an attention layer puts on Q and K in front of its kernel have
    no such reader and go through `qk_ops.qk_assemble`."""
    x32 = x.astype(jnp.float32)
    out = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return out if scale is None else out * scale


# What an attention layer puts between a Q or K projection and its kernel
# (`layers/attention.py:_in_front_of_kernel` sets the attribute, never a
# user): "kernel" on the LAST op in front of the kernel, which emits the dtype
# the kernel would cast it to, "float32" on a norm whose reader is a rotary.
# Neither keeps a float32 array of the projection's shape for its backward.
QK_EMIT_ATTR = "qk_emit"
_QK_COUNTER = "pt_qk_assemble_dispatch_total"
_QK_HELP = ("norm and rotary ops traced between a Q or K projection and an "
            "attention kernel, by what they emit (kernel: the last op in "
            "front of the kernel, in the kernel's input dtype; float32: a "
            "norm in front of a rotary)")


def _qk_emit(ctx, natural):
    """(the attribute's value or None, the dtype the op emits): an op marked
    "kernel" emits what `amp.cast_inputs` at the kernel's door would make of
    its `natural` output dtype, so that cast becomes a no-op; every other op
    its natural one. Counts a marked op."""
    emit = ctx.attr(QK_EMIT_ATTR)
    if emit is None:
        return None, natural
    if emit not in ("kernel", "float32"):
        raise ValueError(f"{QK_EMIT_ATTR} {emit!r}: 'kernel' or 'float32'")
    from ..obs import metrics

    metrics.registry().counter_inc(_QK_COUNTER, help=_QK_HELP,
                                   labels={"op": ctx.op.type, "emit": emit})
    low = ctx.env.get(amp.AMP_KEY)
    if emit == "kernel" and low is not None and natural == jnp.float32:
        return emit, jnp.dtype(low)
    return emit, natural


@register_op("rms_norm")
def rms_norm_kernel(ctx):
    """Root-mean-square norm over the last axis (Zhang & Sennrich 2019; the
    Llama / OLMo family's norm): x * rsqrt(mean(x^2) + eps) * Scale. No
    mean subtraction, no bias. Float32 inside always, and float32 out (see
    `rms_norm`) except for a norm an attention layer marked as the last op
    in front of its kernel (`QK_EMIT_ATTR`), which emits the kernel's input
    dtype; a marked norm runs `qk_assemble`, the same values.
    Attr `group` G (absent: the whole axis): every run of G lanes is normed
    on its own with the one Scale [G] (a per-head norm on a packed
    projection), under the inner scope `per_head`."""
    x, group = ctx.input("X"), ctx.attr("group")
    eps = ctx.attr("epsilon", 1e-5)
    emit, out_dtype = _qk_emit(ctx, jnp.dtype(jnp.float32))
    if emit is not None:
        # [B, T, H, D] rows: a group is a head; the whole axis is all the
        # 128-lane heads it divides into (or one head) normed as one
        D = int(group or (128 if x.shape[-1] % 128 == 0 else x.shape[-1]))
        y = qk_assemble(x.reshape(x.shape[:2] + (-1, D)),
                        ctx.input("Scale").reshape(-1, D), eps, group is None,
                        None, None, out_dtype, emit == "kernel")
    elif group is None:
        y = rms_norm(x, ctx.input("Scale"), eps)
    else:
        with jax.named_scope("per_head"):
            y = rms_norm(x.reshape(x.shape[:-1] + (-1, int(group))),
                         ctx.input("Scale"), eps)
    ctx.set_output("Y", y.reshape(x.shape))


_FED_COUNTER = "pt_rotary_fed_positions_total"


def rotary(x, theta: float, rotary_dim=None, out_dtype=None, tables=None):
    """Rotary position embedding on [B, T, H, D], rotate-half convention
    (the `transformers` one: the head dim's two HALVES pair up, not its
    even/odd lanes): inv_freq_i = theta^(-2i/D), position t; out = x * cos
    + rotate_half(x) * sin. Computed in f32, returned in x's dtype (or
    `out_dtype`). `rotary_dim` R < D: only the LAST R lanes of each head turn
    (their two halves pair up, inv_freq_i = theta^(-2i/R)); the D - R lanes
    in front pass through untouched (latent attention's `[q_nope | q_rope]`
    head). The backward is a rule of its own (`qk_assemble`; reverse mode
    only): the turn by the negated angle of the cotangent, one pass, no
    residual, returned in x's dtype, the passed-through lanes' cotangent
    passed through. `tables`: (cos, sin) of fed positions
    (`qk_ops.fed_tables`) in place of 0..T-1's."""
    R = x.shape[3] if rotary_dim is None else int(rotary_dim)
    return qk_assemble(x, None, 0.0, False, float(theta), R,
                       jnp.dtype(x.dtype if out_dtype is None else out_dtype),
                       True, tables)


def _norm_behind(ctx):
    """The `rms_norm` op whose output this rotary reads, if an attention layer
    marked it as feeding only a rotary (`QK_EMIT_ATTR` "float32"); else
    None."""
    name = ctx.op.inputs["X"][0]
    for op in ctx.block.ops if ctx.block is not None else ():
        if name in op.output_names():
            marked = op.type == "rms_norm" \
                and op.attrs.get(QK_EMIT_ATTR) == "float32"
            return op if marked else None
    return None


@register_op("rotary_embedding")
def rotary_embedding_kernel(ctx):
    """Program-IR face of `rotary`: X is a [B, T, E] packed multi-head
    projection (as flash_attention's Q/K), num_heads splits E. Sits
    between the Q/K projections and the flash_attention op. Attr
    `rotary_dim` (absent: the whole head): the last that many lanes of
    each head turn, the rest pass through. Emits X's dtype, or, marked by an
    attention layer as the last op in front of its kernel (`QK_EMIT_ATTR`),
    the kernel's input dtype. Such a rotary behind a norm the layer marked
    too takes the norm's INPUT and does both (`qk_assemble`: the same
    values): the norm's float32 output stays bound for whoever else reads
    it, and is never computed where nobody does.
    Input `Positions` (optional), int32 [B, A, T] with attr `sections` (A
    counts of frequency pairs, summing to half the turned lanes): positions
    as FED DATA, pair i's from the axis whose section holds it (a three-axis
    rotary: temporal, height, width; A = 1 is a plain fed position). The cos
    and sin tables are made from it here, in float32, under the inner scope
    `tables`, and are operands of `qk_assemble`'s two lowerings; an op
    traced with it counts in `pt_rotary_fed_positions_total`. Without the
    input the op is the one it always was."""
    x = ctx.input("X")
    heads = ctx.attr("num_heads")
    rotary_dim = ctx.attr("rotary_dim")
    B, T, E = x.shape
    if E % heads or (E // heads) % 2:
        raise ValueError(
            f"hidden dim {E} must split into {heads} heads of even size")
    if rotary_dim is not None and not (
            0 < rotary_dim <= E // heads and rotary_dim % 2 == 0):
        raise ValueError(f"rotary_dim {rotary_dim} is not an even part of "
                         f"a head of {E // heads}")
    D, theta = E // heads, float(ctx.attr("theta", 10000.0))
    emit, out_dtype = _qk_emit(ctx, jnp.dtype(x.dtype))
    tables = None
    if ctx.has_input("Positions"):
        from ..obs import metrics
        from .qk_ops import fed_tables

        metrics.registry().counter_inc(
            _FED_COUNTER, help="rotary ops traced whose positions are fed "
            "data (a `Positions` input), by the axes a token has",
            labels={"axes": str(len(ctx.attr("sections")))})
        tables = fed_tables(ctx.input("Positions"), ctx.attr("sections"),
                            theta, D if rotary_dim is None else rotary_dim)
    norm = _norm_behind(ctx) if emit == "kernel" and rotary_dim is None \
        else None
    if norm is not None and norm.attrs.get("group") in (None, D):
        out = qk_assemble(
            ctx.env[norm.inputs["X"][0]].reshape(B, T, heads, D),
            ctx.env[norm.inputs["Scale"][0]].reshape(-1, D),
            norm.attrs.get("epsilon", 1e-5), norm.attrs.get("group") is None,
            theta, D, out_dtype, True, tables)
    else:
        out = rotary(x.reshape(B, T, heads, D), theta, rotary_dim, out_dtype,
                     tables)
    ctx.set_output("Out", out.reshape(B, T, E))


# --------------------------------------------------------------- dropout ---
@register_op("dropout")
def dropout_kernel(ctx):
    """Reference: paddle/operators/dropout_op.cc — upscale-in-train off

    (reference scales at inference? No: reference multiplies by (1-p) at
    test time is NOT done; it masks without rescale in train). v0.11
    semantics: train: out = x * mask, mask ~ Bernoulli(1-p); test:
    out = x * (1-p)."""
    x = ctx.input("X")
    p = ctx.attr("dropout_prob", 0.5)
    if ctx.attr("is_test", False):
        ctx.set_output("Out", x * (1.0 - p) if isinstance(x, jnp.ndarray) else x.with_data(x.data * (1.0 - p)))
        return
    data = x.data if isinstance(x, LoDArray) else x
    mask = jax.random.bernoulli(ctx.rng(), 1.0 - p, data.shape)
    out = data * mask.astype(data.dtype)
    ctx.set_output("Out", x.with_data(out) if isinstance(x, LoDArray) else out)


# ---------------------------------------------------------------- losses ---
@register_op("cross_entropy")
def cross_entropy_kernel(ctx):
    """Reference: paddle/operators/cross_entropy_op.cc — X is a probability

    distribution [N, D]; Label is int [N, 1] (or soft labels [N, D])."""
    x = ctx.input("X")
    label = ctx.input("Label")
    eps = 1e-8
    if ctx.attr("soft_label", False):
        out = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        lbl = label[..., 0] if label.ndim == x.ndim else label
        picked = jnp.take_along_axis(x, lbl[..., None].astype(jnp.int32), axis=-1)
        out = -jnp.log(picked + eps)
    ctx.set_output("Y", out)


@jax.custom_vjp
def _rows_cross_entropy(x, lbl):
    """Hard-label cost of logits [rows, V] (any float dtype) and labels
    [rows] int32: float32 [rows]. Only one float32 scalar a row (the
    log-sum-exp) is kept for the backward; the gradient is recomputed
    from the logits as they arrived, so no float32 [rows, V] array is
    written. Reverse mode only (custom_vjp)."""
    return _rows_cross_entropy_fwd(x, lbl)[0]


def _rows_cross_entropy_fwd(x, lbl):
    xf = x.astype(jnp.float32)
    m = jnp.max(xf, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(xf - m[:, None]), axis=-1))
    picked = jnp.take_along_axis(x, lbl[:, None], axis=-1)[:, 0]
    return lse - picked.astype(jnp.float32), (x, lbl, lse)


def _rows_cross_entropy_bwd(res, g):
    x, lbl, lse = res
    p = jnp.exp(x.astype(jnp.float32) - lse[:, None])
    onehot = lbl[:, None] == jnp.arange(x.shape[-1], dtype=jnp.int32)
    return ((p - onehot.astype(jnp.float32)) * g[:, None]).astype(x.dtype), None


_rows_cross_entropy.defvjp(_rows_cross_entropy_fwd, _rows_cross_entropy_bwd)

_COST_COUNTER = "pt_cost_op_dispatch_total"
_COST_HELP = ("softmax_with_cross_entropy ops traced, by the path the "
              "inputs chose (rows: the custom_vjp over flattened logits)")


@register_op("softmax_with_cross_entropy")
def softmax_with_cross_entropy_kernel(ctx):
    """Reference: paddle/operators/softmax_with_cross_entropy_op.cc —

    numerically-stable fused version. Ragged (LoDArray) logits/labels give
    a per-token LoD loss with padding slots zeroed (the reference computes
    token losses over the flat no-padding layout for free).

    Integer labels take `_rows_cross_entropy` over logits flattened to
    [rows, V] here, inside the op: the flatten is what lets XLA fuse the
    row reductions into the GEMM that made the logits (no layout copy
    across `fc`'s [tokens, V] -> [B, T, V] reshape). Soft labels read the
    whole row of log-probabilities and keep the plain formulation. The
    path is counted in `pt_cost_op_dispatch_total{path}` when traced."""
    from ..obs import metrics

    logits_in = ctx.input("Logits")
    label_in = ctx.input("Label")
    ragged = isinstance(logits_in, LoDArray)
    logits = logits_in.data if ragged else logits_in
    label = label_in.data if isinstance(label_in, LoDArray) else label_in
    soft = bool(ctx.attr("soft_label", False))
    metrics.registry().counter_inc(
        _COST_COUNTER, help=_COST_HELP,
        labels={"path": "soft_label" if soft else "rows"})
    # softmax/log in f32 even under amp (loss numerics)
    if soft:
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        V = logits.shape[-1]
        lbl = label[..., 0] if label.ndim == logits.ndim else label
        lbl = jnp.clip(lbl.astype(jnp.int32), 0, V - 1)
        loss = _rows_cross_entropy(logits.reshape(-1, V), lbl.reshape(-1))
        loss = loss.reshape(lbl.shape + (1,))
    # outside the custom_vjp: dead code to XLA unless the program reads it
    softmax = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if ragged:
        loss = jnp.where(logits_in.token_mask[:, None], loss, 0.0)
        ctx.set_output("Softmax", logits_in.with_data(softmax))
        ctx.set_output("Loss", logits_in.with_data(loss))
    else:
        ctx.set_output("Softmax", softmax)
        ctx.set_output("Loss", loss)


@register_op("square_error_cost")
def square_error_cost_kernel(ctx):
    """Reference: paddle/operators/squared_l2_distance_op.cc /

    gserver CostLayer sum_of_squares."""
    x, y = ctx.input("X"), ctx.input("Y")
    ctx.set_output("Out", jnp.square(x - y))


@register_op("huber_loss")
def huber_loss_kernel(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    d = ctx.attr("delta", 1.0)
    r = y - x
    a = jnp.abs(r)
    loss = jnp.where(a <= d, 0.5 * r * r, d * (a - 0.5 * d))
    ctx.set_output("Out", loss)


# --------------------------------------------------------------- metrics ---
@register_op("accuracy")
def accuracy_kernel(ctx):
    """Reference: paddle/operators/accuracy_op.cc — top-k indices vs label."""
    indices = ctx.input("Indices")  # [N, k] from top_k
    label = ctx.input("Label")  # [N, 1]
    correct = jnp.any(indices == label.astype(indices.dtype), axis=-1)
    ctx.set_output("Accuracy", jnp.mean(correct.astype(jnp.float32)))
    if ctx.has_output("Correct"):
        ctx.set_output("Correct", jnp.sum(correct.astype(jnp.int32)))
    if ctx.has_output("Total"):
        ctx.set_output("Total", jnp.asarray(indices.shape[0], jnp.int32))


# ------------------------------------------------------------------- lrn ---
@register_op("lrn")
def lrn_kernel(ctx):
    """Reference: paddle/operators/lrn_op.cc — local response norm across

    channels (AlexNet/GoogleNet)."""
    x = ctx.input("X")  # [N, C, H, W]
    n = ctx.attr("n", 5)
    k = ctx.attr("k", 2.0)
    alpha = ctx.attr("alpha", 1e-4)
    beta = ctx.attr("beta", 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    windows = sum(pad[:, i : i + x.shape[1]] for i in range(n))
    ctx.set_output("Out", x / jnp.power(k + alpha * windows, beta))
