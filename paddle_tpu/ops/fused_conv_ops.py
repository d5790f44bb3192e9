"""Fused 1x1-conv + BatchNorm Pallas ops (the cuDNN-fused-path analogue).

Reference: the reference never runs its conv hot path as naive composed
ops — conv layers go through cuDNN's fused machinery
(paddle/gserver/layers/CudnnConvBaseLayer.cpp, paddle/cuda/src/
hl_cuda_cudnn.cc). On TPU the XLA formulation of train-mode BN is
irreducibly extra HBM passes over the conv output (stats reduce +
normalize read/write — measured at ~34% of the ResNet-50 step, PERF.md),
so the fused path here rewrites each eligible 1x1 conv as a Pallas
matmul kernel that
  - applies the PREVIOUS BN (normalize+scale+shift+ReLU) in its prologue,
    consuming the raw (pre-BN) activation straight from HBM, and
  - accumulates this conv's OWN output per-channel sum/sumsq in its
    epilogue (VMEM f32 accumulators across row tiles),
so each activation is read once and written once — BN statistics come out
of the conv for free, and the normalize of layer k happens inside layer
k+1's operand read. Op-level protocol (see layers/nn.py fused_conv_bn /
bn_apply / bn_stats and models/image.py _bottleneck):

  raw_k, mean_k, inv_k = fused_conv_bn(raw_{k-1}, stats_{k-1}, W_k)
  ...consumers of the normalized activation call bn_apply (one fused
  XLA elementwise pass) or feed the raw+stats pair to the next fused op.

Training: pallas_call has no automatic VJP, so the fused forward is a
jax.custom_vjp whose backward is the standard conv+BN-prologue chain
composed from XLA matmuls and (fused-by-XLA) elementwise/reduce passes —
recomputing the prologue from the saved raw input instead of saving the
normalized activation (remat: one VPU pass buys an HBM tensor).

Eligibility mirrors the fused-RNN dispatch (pallas_kernels.py): TPU
backend (or the interpret test flag), bf16/f32 io, channels that tile the
128-wide lanes, rows divisible into MXU-sized blocks, and a VMEM model
that keeps the working set under the scoped budget. Ineligible shapes run
an identical-semantics jnp fallback (same raw+stats dataflow).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import amp
from ..core.registry import register_op


def _block_rows(n: int, cin: int, cout: int, itemsize: int) -> int:
    """Row block for the fused kernel. Legality (divides n, tiles the
    8-row sublane, working set — x/y blocks double-buffered by the
    pipeline machinery, full weight panel, f32 accumulators — under the
    VMEM budget) lives in tune/space.py `conv_rows_legal`, shared with
    the autotuner's candidate generator. Consult order: forced/tuned
    override for this (n, cin, cout, dtype, device) -> the analytic
    default (largest legal block <= 1024). Returns 0 when no eligible
    block exists."""
    from ..tune import overrides as tune_overrides
    from ..tune.cache import ITEMSIZE_DTYPE
    from ..tune.space import CONV_ROW_BLOCKS, conv_rows_legal

    ov = tune_overrides.lookup(
        "fused_conv", {"n": n, "cin": cin, "cout": cout},
        ITEMSIZE_DTYPE.get(itemsize, f"itemsize{itemsize}"))
    if ov is not None:
        b = int(ov.config.get("block_rows", 0))
        if b and conv_rows_legal(b, n, cin, cout, itemsize):
            return b
        if ov.source in ("forced", "env"):
            import warnings

            warnings.warn(
                f"forced fused-conv block_rows={b} fails eligibility at "
                f"n={n} cin={cin} cout={cout}; fused conv kernel "
                f"DISABLED for this shape", stacklevel=2)
            return 0
    for b in CONV_ROW_BLOCKS:
        if conv_rows_legal(b, n, cin, cout, itemsize):
            return b
    return 0


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _backend_ok() -> bool:
    from .pallas_kernels import backend_ok

    return backend_ok("fused_conv_interpret")


def fused_conv_eligible(n: int, cin: int, cout: int, dtype) -> bool:
    itemsize = jnp.dtype(dtype).itemsize
    return (
        dtype in (jnp.bfloat16, jnp.float32)
        and cin % 128 == 0
        and cout % 128 == 0
        and _block_rows(n, cin, cout, itemsize) > 0
        and _backend_ok()
    )


# ------------------------------------------------------------- the kernel --
def _fused_kernel(x_ref, w_ref, pm_ref, pi_ref, ps_ref, pb_ref,
                  y_ref, s_ref, sq_ref, acc_s, acc_q,
                  *, prologue: bool, prologue_relu: bool):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_s[:] = jnp.zeros_like(acc_s)
        acc_q[:] = jnp.zeros_like(acc_q)

    x = x_ref[:]
    if prologue:
        xh = (x.astype(jnp.float32) - pm_ref[:]) * (pi_ref[:] * ps_ref[:]) \
            + pb_ref[:]
        if prologue_relu:
            xh = jnp.maximum(xh, 0.0)
        xn = xh.astype(x.dtype)
    else:
        xn = x
    y = jnp.dot(xn, w_ref[:], preferred_element_type=jnp.float32)
    yq = y.astype(y_ref.dtype)
    y_ref[:] = yq
    # stats from the QUANTIZED output (what consumers read back from HBM)
    # so the fused formulation matches batch_norm's stats-of-stored-y
    yf = yq.astype(jnp.float32)
    acc_s[:] = acc_s[:] + jnp.sum(yf, axis=0, keepdims=True)
    acc_q[:] = acc_q[:] + jnp.sum(yf * yf, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        s_ref[:] = acc_s[:]
        sq_ref[:] = acc_q[:]


def _pallas_fwd(x, w, pm, pi, ps, pb, prologue, prologue_relu, interpret):
    n, cin = x.shape
    cout = w.shape[1]
    b = _block_rows(n, cin, cout, x.dtype.itemsize)
    y, s, sq = pl.pallas_call(
        functools.partial(_fused_kernel, prologue=prologue,
                          prologue_relu=prologue_relu),
        grid=(n // b,),
        in_specs=[
            pl.BlockSpec((b, cin), lambda i: (i, 0)),
            pl.BlockSpec((cin, cout), lambda i: (0, 0)),
            pl.BlockSpec((1, cin), lambda i: (0, 0)),
            pl.BlockSpec((1, cin), lambda i: (0, 0)),
            pl.BlockSpec((1, cin), lambda i: (0, 0)),
            pl.BlockSpec((1, cin), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b, cout), lambda i: (i, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, cout), x.dtype),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, cout), jnp.float32),
            pltpu.VMEM((1, cout), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, pm.reshape(1, -1), pi.reshape(1, -1), ps.reshape(1, -1),
      pb.reshape(1, -1))
    return y, s.reshape(-1), sq.reshape(-1)


@functools.lru_cache(maxsize=None)
def _fused_fn(prologue: bool, prologue_relu: bool, interpret: bool):
    """custom_vjp'd fused unit: (x_raw, w[Cin,Cout], prev-BN mean/inv/
    scale/bias) -> (y_raw, sum_y, sqsum_y). Static config via closure."""

    @jax.custom_vjp
    def f(x, w, pm, pi, ps, pb):
        return _pallas_fwd(x, w, pm, pi, ps, pb, prologue, prologue_relu,
                           interpret)

    def fwd(x, w, pm, pi, ps, pb):
        y, s, sq = _pallas_fwd(x, w, pm, pi, ps, pb, prologue,
                               prologue_relu, interpret)
        # y rides along as a residual by reference — no extra HBM copy
        return (y, s, sq), (x, w, pm, pi, ps, pb, y)

    def bwd(res, cts):
        # dtype discipline mirrors amp.py: every [N, C]-sized intermediate
        # stays in the io dtype (an f32 materialization of one stage-2
        # tensor is 400+ MB of HBM traffic); f32 lives only in [C]-sized
        # vectors and matmul-internal accumulation
        x, w, pm, pi, ps, pb, y = res
        dy, ds, dsq = cts
        dt = x.dtype
        # stats outputs fold into an effective dy: d(sum)->+ds,
        # d(sqsum)->+2*y*dsq (one fused elementwise pass over y, dy)
        dy_c = (dy + ds.astype(dt) + (2.0 * dsq).astype(dt) * y).astype(dt)
        if prologue:
            g = pi * ps  # [Cin] f32
            # recompute the prologue in f32, as the forward kernel does,
            # so the ReLU mask `xh > 0` cannot disagree with the forward
            # near zero (a bf16 recompute flips borderline signs and
            # takes dx/dw at slightly different activations — ADVICE r4);
            # XLA fuses this elementwise chain into its consumers, so no
            # f32 [N, Cin] tensor is materialized to HBM
            xh32 = x.astype(jnp.float32) * g + (pb - pm * g)
            xh = xh32.astype(dt)
            if prologue_relu:
                pos = xh32 > 0
                xn_c = jnp.where(pos, xh, jnp.zeros((), dt))
            else:
                xn_c = xh
        else:
            xn_c = x
        dw = jnp.dot(xn_c.T, dy_c).astype(w.dtype)
        dxn = jnp.dot(dy_c, w.T)
        if prologue:
            dxh = jnp.where(pos, dxn, jnp.zeros((), dt)) \
                if prologue_relu else dxn
            dx = (dxh * g.astype(dt)).astype(dt)
            # the two per-channel reductions (XLA fuses both into one
            # pass over dxh, x); every prologue-param grad derives.
            # f32 accumulation: the reduce is over N ~ 1e5 rows
            dxh32 = dxh.astype(jnp.float32)
            r0 = jnp.sum(dxh32, axis=0)                             # [Cin]
            r1 = jnp.sum(dxh32 * x.astype(jnp.float32), axis=0)     # [Cin]
            rc = r1 - pm * r0  # sum(dxh * (x - pm)) without centering x
            dpm = -r0 * g
            dpi = rc * ps
            dps = rc * pi
            dpb = r0
        else:
            dx = dxn.astype(dt)
            dpm = jnp.zeros_like(pm)
            dpi = jnp.zeros_like(pi)
            dps = jnp.zeros_like(ps)
            dpb = jnp.zeros_like(pb)
        return dx, dw, dpm, dpi, dps, dpb

    f.defvjp(fwd, bwd)
    return f


def _prologue(x, pm, pi, ps, pb, prologue, prologue_relu):
    """The previous BN's normalize(+ReLU) in f32, quantized back to the
    io dtype — the one definition shared by the 2-D and 4-D fallbacks
    (the Pallas kernel implements the same math tile-locally). [C]-vector
    params broadcast over any leading rank."""
    if not prologue:
        return x
    xh = (x.astype(jnp.float32) - pm) * (pi * ps) + pb
    if prologue_relu:
        xh = jnp.maximum(xh, 0.0)
    return xh.astype(x.dtype)


def _jnp_fused(x, w, pm, pi, ps, pb, prologue, prologue_relu):
    """Identical-semantics fallback for ineligible shapes/backends.
    bf16 io end-to-end like conv2d_kernel under amp (the MXU accumulates
    f32 internally either way); f32 only in [C]-vectors and the stats
    reduction."""
    xn = _prologue(x, pm, pi, ps, pb, prologue, prologue_relu)
    acc = jnp.float32 if x.dtype == jnp.float32 else None
    y = jnp.dot(xn, w, preferred_element_type=acc).astype(x.dtype)
    return (y,) + _sum_sq(y, axis=0)


def _sum_sq(y, axis):
    """Per-channel sum / sum-of-squares with f32 accumulation; the
    bn_bf16_stats flag squares in the io dtype instead of upcasting
    first (escape-route knob, PERF.md r4) — one definition for every
    stats site."""
    from ..flags import FLAGS

    if FLAGS.bn_bf16_stats:
        return (jnp.sum(y, axis=axis, dtype=jnp.float32),
                jnp.sum(y * y, axis=axis, dtype=jnp.float32))
    yf = y.astype(jnp.float32)
    return jnp.sum(yf, axis=axis), jnp.sum(yf * yf, axis=axis)


def _jnp_fused4(x4, w, pm, pi, ps, pb, prologue, prologue_relu):
    """4-D (NHWC) fallback: same math as _jnp_fused but the matmul runs
    as a 1x1 conv_general_dilated on the un-reshaped activation, keeping
    XLA's conv layout assignment intact between neighboring 3x3 convs
    (a 2-D dot in the middle of a conv tower forces relayouts)."""
    xn = _prologue(x4, pm, pi, ps, pb, prologue, prologue_relu)
    acc = jnp.float32 if x4.dtype == jnp.float32 else None
    y = jax.lax.conv_general_dilated(
        xn, w[None, None], (1, 1), [(0, 0), (0, 0)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=acc,
    ).astype(x4.dtype)
    return (y,) + _sum_sq(y, axis=(0, 1, 2))


def fused_matmul_bn(x, w, pm=None, pi=None, ps=None, pb=None,
                    prologue_relu=True):
    """Public fused unit on 2-D operands; dispatches Pallas vs jnp."""
    prologue = pm is not None
    if not prologue:
        c = x.shape[1]
        pm = jnp.zeros((c,), jnp.float32)
        pi = jnp.ones((c,), jnp.float32)
        ps = jnp.ones((c,), jnp.float32)
        pb = jnp.zeros((c,), jnp.float32)
    n, cin = x.shape
    cout = w.shape[1]
    if fused_conv_eligible(n, cin, cout, x.dtype):
        f = _fused_fn(prologue, bool(prologue_relu), _interpret())
        return f(x, w, pm, pi, ps, pb)
    return _jnp_fused(x, w, pm, pi, ps, pb, prologue, bool(prologue_relu))


# -------------------------------------------------------------------- ops --
def _stats_to_mean_inv(s, sq, n, eps):
    mean = s / n
    var = jnp.maximum(sq / n - mean * mean, 0.0)
    return mean, var, jax.lax.rsqrt(var + eps)


def _update_running(ctx, bmean, bvar):
    momentum = ctx.attr("momentum", 0.9)
    mean_v, var_v = ctx.input("Mean"), ctx.input("Variance")
    ctx.env[ctx.op.inputs["Mean"][0]] = (
        momentum * mean_v + (1 - momentum) * bmean)
    ctx.env[ctx.op.inputs["Variance"][0]] = (
        momentum * var_v + (1 - momentum) * bvar)


@register_op("fused_conv_bn", writes=("Mean", "Variance"))
def fused_conv_bn_kernel(ctx):
    """1x1 conv (NHWC, optional spatial-subsample stride) with fused
    previous-BN prologue and own-BN stats epilogue. Outputs the RAW conv
    result plus its batch mean/inv; consumers apply the normalize
    (bn_apply) or fuse it into their own prologue."""
    x = ctx.input("X")          # [B, H, W, Cin] NHWC
    w = ctx.input("Filter")     # [Cout, Cin, 1, 1] OIHW (checkpoint shape)
    stride = int(ctx.attr("stride", 1))
    eps = ctx.attr("epsilon", 1e-5)
    if stride > 1:
        # a stride-s 1x1 conv only reads every s-th pixel: subsample
        # FIRST so the prologue/matmul touch a quarter of the rows
        x = x[:, ::stride, ::stride, :]
    b, h, wd, cin = x.shape
    cout = w.shape[0]
    w2 = jnp.transpose(w.reshape(cout, cin))  # [Cin, Cout]
    xc, wc = amp.cast_inputs(ctx, x, w2)
    wc = wc.astype(xc.dtype)
    n = b * h * wd
    prologue = ctx.has_input("XMean")
    prologue_relu = ctx.attr("prologue_act", None) == "relu"
    if prologue:
        pm, pi = ctx.input("XMean"), ctx.input("XInv")
        ps, pb = ctx.input("XScale"), ctx.input("XBias")
    else:
        pm = pi = ps = pb = None
    from ..flags import FLAGS

    dot_max_n = FLAGS.fused_conv_dot_max_n
    use_pallas = FLAGS.fused_conv_pallas or FLAGS.fused_conv_interpret
    from .mesh_dispatch import current as _active_mesh

    if _active_mesh() is not None and _active_mesh().dp > 1:
        # mesh policy (ops/mesh_dispatch.py): a bare pallas_call cannot
        # be GSPMD-partitioned. This opt-in kernel (measured slower than
        # XLA's fusion anyway — PERF.md r4) is not shard_map-wrapped;
        # under a mesh it falls back to the identical-semantics jnp
        # formulation, which GSPMD partitions natively
        use_pallas = False
    if n <= dot_max_n and fused_conv_eligible(n, cin, cout, xc.dtype):
        if use_pallas:
            y2, s, sq = fused_matmul_bn(
                xc.reshape(-1, cin), wc, pm, pi, ps, pb,
                prologue_relu=prologue_relu)
        else:
            y2, s, sq = _jnp_fused(xc.reshape(-1, cin), wc, pm, pi, ps, pb,
                                   prologue, prologue_relu)
        y = y2.reshape(b, h, wd, cout)
    else:
        y, s, sq = _jnp_fused4(xc, wc, pm, pi, ps, pb, prologue,
                               prologue_relu)
    bmean, bvar, binv = _stats_to_mean_inv(s, sq, float(n), eps)
    _update_running(ctx, bmean, bvar)
    ctx.set_output("Out", y)
    ctx.set_output("BatchMean", bmean)
    ctx.set_output("BatchInv", binv)


@register_op("bn_stats", writes=("Mean", "Variance"))
def bn_stats_kernel(ctx):
    """Stats-only half of batch_norm (NHWC): one reduce pass emitting
    batch mean/inv + the running-stat update; the normalize is applied
    by the consumer (bn_apply or a fused_conv_bn prologue)."""
    x = ctx.input("X")
    eps = ctx.attr("epsilon", 1e-5)
    s, sq = _sum_sq(x, axis=(0, 1, 2))
    n = float(x.size // x.shape[-1])
    bmean, bvar, binv = _stats_to_mean_inv(s, sq, n, eps)
    _update_running(ctx, bmean, bvar)
    ctx.set_output("BatchMean", bmean)
    ctx.set_output("BatchInv", binv)


@register_op("bn_apply")
def bn_apply_kernel(ctx):
    """Normalize+scale+shift (+act) of a raw activation given its stats —
    one XLA elementwise pass, fusable with adjacent adds/relus."""
    x = ctx.input("X")
    m, iv = ctx.input("Mean"), ctx.input("Inv")
    s, b = ctx.input("Scale"), ctx.input("Bias")
    y = (x.astype(jnp.float32) - m) * (iv * s) + b
    if ctx.attr("act", None) == "relu":
        y = jnp.maximum(y, 0.0)
    ctx.set_output("Out", y.astype(x.dtype))
