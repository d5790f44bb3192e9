"""Fused 1x1-conv + BatchNorm ops (the cuDNN-fused-path analogue).

Reference: the reference never runs its conv hot path as naive composed
ops — conv layers go through cuDNN's fused machinery
(paddle/gserver/layers/CudnnConvBaseLayer.cpp, paddle/cuda/src/
hl_cuda_cudnn.cc). On TPU the composed formulation of train-mode BN is
extra HBM passes over the conv output (stats reduce + normalize
read/write), so each 1x1 conv here
  - applies the PREVIOUS BN (normalize+scale+shift+ReLU) to its operand,
    consuming the raw (pre-BN) activation, and
  - emits its OWN output's per-channel sum/sumsq beside the output,
and XLA fuses both into the conv: the normalize of layer k happens inside
layer k+1's operand read. Op-level protocol (see layers/nn.py
fused_conv_bn / bn_apply / bn_stats and models/image.py _bottleneck):

  raw_k, mean_k, inv_k = fused_conv_bn(raw_{k-1}, stats_{k-1}, W_k)
  ...consumers of the normalized activation call bn_apply (one fused
  XLA elementwise pass) or feed the raw+stats pair to the next fused op.

The matmul runs as a 1x1 conv_general_dilated on the un-reshaped NHWC
activation, keeping XLA's conv layout assignment intact between
neighboring 3x3 convs (a 2-D dot in the middle of a conv tower forces
relayouts). Training differentiates it with JAX's own VJP.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import amp
from ..core.registry import register_op


def _prologue(x, pm, pi, ps, pb, prologue_relu):
    """The previous BN's normalize(+ReLU) in f32, quantized back to the
    io dtype; `pm` None: no previous BN. [C]-vector params broadcast
    over any leading rank."""
    if pm is None:
        return x
    xh = (x.astype(jnp.float32) - pm) * (pi * ps) + pb
    if prologue_relu:
        xh = jnp.maximum(xh, 0.0)
    return xh.astype(x.dtype)


def _sum_sq(y, axis):
    """Per-channel sum / sum-of-squares, squared in the io dtype with
    f32 accumulation — one definition for every stats site."""
    return (jnp.sum(y, axis=axis, dtype=jnp.float32),
            jnp.sum(y * y, axis=axis, dtype=jnp.float32))


def _conv_stats(x4, w, pm, pi, ps, pb, prologue_relu):
    """(x_raw [B,H,W,Cin], w [Cin,Cout], prev-BN mean/inv/scale/bias) ->
    (y_raw, sum_y, sqsum_y). bf16 io end-to-end like conv2d_kernel under
    amp (the MXU accumulates f32 internally either way); f32 only in
    [C]-vectors and the stats reduction. Stats come from the QUANTIZED
    output (what consumers read back), matching batch_norm's
    stats-of-stored-y."""
    xn = _prologue(x4, pm, pi, ps, pb, prologue_relu)
    acc = jnp.float32 if x4.dtype == jnp.float32 else None
    y = jax.lax.conv_general_dilated(
        xn, w[None, None], (1, 1), [(0, 0), (0, 0)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=acc,
    ).astype(x4.dtype)
    return (y,) + _sum_sq(y, axis=(0, 1, 2))


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
    prologue_relu = ctx.attr("prologue_act", None) == "relu"
    if ctx.has_input("XMean"):
        pm, pi = ctx.input("XMean"), ctx.input("XInv")
        ps, pb = ctx.input("XScale"), ctx.input("XBias")
    else:
        pm = pi = ps = pb = None
    y, s, sq = _conv_stats(xc, wc, pm, pi, ps, pb, prologue_relu)
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
