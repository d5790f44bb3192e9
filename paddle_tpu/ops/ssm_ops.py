"""State-space mixers: Mamba-2's selective scan in its chunked (SSD) form,
the short causal depthwise convolution before it and the gated group
RMSNorm after it, and the whole mixer as one op.

Reference lineage: the 2017 reference's recurrences are the LSTM and GRU
of ops/rnn_ops.py (a `lax.scan` over time with a reverse-time backward);
this is their linear, input-gated descendant (Dao & Gu 2024, "Transformers
are SSMs"; `transformers` model_type `nemotron_h` / `mamba2`). Per head h,
with a state S [P, N] that starts at zero:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D_h x_t

x_t [P] is the head's channels, B_t and C_t [N] belong to the head's GROUP
(G groups, head h reads group h // (H / G)), dt_t > 0 and A_h < 0 are
scalars. Because the recurrence is linear it is computed without a step per
token (`ssd_chunked_scan`): inside a chunk of Q tokens every output is a
masked [Q, Q] matmul (the "attention" form, decays as the mask's weights),
each chunk's contribution to the state is one matmul, and only the T / Q
chunk states go through a recurrence. The decays (cumsum(dt A), exp) and
the carried state are float32; the matmuls take the compute dtype (bf16
under amp) and accumulate in float32. The backward pass is JAX's
differentiation of this form under `jax.checkpoint` (in the mixer: conv,
scan and gated norm as one): the backward keeps the inputs and recomputes
the [chunks, H, Q, Q] decay and score blocks instead of holding them (268
MB a layer in float32 at T 8192, H 64). The oracle in the tests is the
recurrence above, token by token (`tests/nemotron_h_reference.py`).

No Pallas kernel here: XLA runs the einsums (PERF.md names what a kernel
would save). `pt_ssm_scan_dispatch_total{path}` counts one per op traced.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import amp
from ..core.registry import register_op

CHUNK = 128


def ssd_chunked_scan(x, dt, A, Bm, Cm, D, chunk: int = CHUNK):
    """x [B, T, H, P] (compute dtype), dt [B, T, H] float32 and positive, A
    [H] negative, Bm / Cm [B, T, G, N] (compute dtype), D [H] -> y [B, T, H,
    P] float32. Any T: a tail shorter than a chunk is padded with dt = 0.
    Differentiated as it stands it keeps its [chunks, H, Q, Q] blocks for
    the backward; `mamba2_mixer` puts it under `jax.checkpoint`."""
    Bsz, T, H, P = x.shape
    G, N = Bm.shape[2:]
    R = H // G                                  # heads a group
    cd = x.dtype
    pad = -T % chunk
    if pad:     # dt 0: no decay and no input, so the tail changes nothing
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                         for a in (x, dt, Bm, Cm))
    c = (T + pad) // chunk
    xg = x.reshape(Bsz, c, chunk, G, R, P)
    dt = dt.astype(jnp.float32).reshape(Bsz, c, chunk, G, R)
    Bm = Bm.reshape(Bsz, c, chunk, G, N)
    Cm = Cm.reshape(Bsz, c, chunk, G, N)
    A = A.astype(jnp.float32).reshape(G, R)
    cum = jnp.cumsum(dt * A, axis=2)            # [B, c, Q, G, R], <= 0
    last = cum[:, :, -1]                        # [B, c, G, R]

    # inside a chunk: y_i = sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j
    scores = jnp.einsum("bcign,bcjgn->bcgij", Cm, Bm,
                        preferred_element_type=jnp.float32)
    ci = jnp.moveaxis(cum, 2, -1)               # [B, c, G, R, Q]
    gap = ci[..., :, None] - ci[..., None, :]   # cum_i - cum_j
    keep = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(keep, gap, -jnp.inf))
    dtj = jnp.moveaxis(dt, 2, -1)[..., None, :]
    m = (scores[:, :, :, None] * decay * dtj).astype(cd)     # [B, c, G, R, Q, Q]
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", m, xg,
                   preferred_element_type=jnp.float32)

    # a chunk's own contribution to the state at its end, and the states
    # carried from chunk to chunk (the one true recurrence, T / Q steps)
    to_end = (jnp.exp(last[:, :, None] - cum) * dt)          # [B, c, Q, G, R]
    xw = (xg.astype(jnp.float32) * to_end[..., None]).astype(cd)
    own = jnp.einsum("bcjgrp,bcjgn->bcgrpn", xw, Bm,
                     preferred_element_type=jnp.float32)

    def carry(S, inp):
        own_c, last_c = inp
        return jnp.exp(last_c)[..., None, None] * S + own_c, S

    _, before = jax.lax.scan(
        carry, jnp.zeros((Bsz, G, R, P, N), jnp.float32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(last, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)         # the state each chunk starts from
    y_in = jnp.einsum("bcign,bcgrpn->bcigrp", Cm, before.astype(cd),
                      preferred_element_type=jnp.float32)
    y = y + y_in * jnp.exp(cum)[..., None]
    y = y + xg.astype(jnp.float32) * D.astype(jnp.float32).reshape(G, R, 1)
    return y.reshape(Bsz, T + pad, H, P)[:, :T]


def _count_dispatch(path: str) -> None:
    from ..obs import metrics

    metrics.registry().counter_inc(
        "pt_ssm_scan_dispatch_total",
        help="state-space scans traced, by the formulation that runs them",
        labels={"path": path})


def causal_depthwise_conv(x, w, b):
    """x [B, T, C], w [K, C], b [C] -> float32 [B, T, C]: out_t = b + sum_k
    w[k] x_{t - (K - 1) + k}, zeros before the sequence's start."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    out = b.astype(jnp.float32)
    for k in range(K):
        out = out + xp[:, k:k + T] * w[k].astype(jnp.float32)
    return out


def gated_group_rms_norm(y, z, w, groups: int, eps: float):
    """rms(y * silu(z)) * w with the mean square taken inside each of
    `groups` equal runs of the last axis; float32 inside and out."""
    v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = v.reshape(*v.shape[:-1], groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(v.shape) * w


def mamba2_mixer(h, in_w, conv_w, conv_b, dt_bias, A_log, D, norm_w, out_w,
                 *, num_heads: int, head_dim: int, n_groups: int,
                 state_size: int, eps: float, chunk: int = CHUNK):
    """h [B, T, d] -> [B, T, d]. in_w [d, 2 d_in + 2 G N + H] gives [z | xBC |
    dt]; conv_w [K, d_in + 2 G N]; dt_bias, A_log, D [H]; norm_w [d_in];
    out_w [d_in, d]. The two projections run in their weights' dtype (the
    amp dtype where the caller cast them), everything between as the module
    docstring says."""
    Bsz, T, _ = h.shape
    H, P, G, N = num_heads, head_dim, n_groups, state_size
    d_in = H * P
    cd = in_w.dtype

    def between(z, xBC, dt, conv_w, conv_b, dt_bias, A_log, D, norm_w):
        with jax.named_scope("conv"):
            xBC = jax.nn.silu(
                causal_depthwise_conv(xBC, conv_w, conv_b)).astype(cd)
        with jax.named_scope("scan"):
            y = ssd_chunked_scan(
                xBC[..., :d_in].reshape(Bsz, T, H, P),
                jax.nn.softplus(dt + dt_bias),
                -jnp.exp(A_log.astype(jnp.float32)),
                xBC[..., d_in:d_in + G * N].reshape(Bsz, T, G, N),
                xBC[..., d_in + G * N:].reshape(Bsz, T, G, N), D, chunk)
        with jax.named_scope("gate_norm"):
            return gated_group_rms_norm(y.reshape(Bsz, T, d_in), z, norm_w,
                                        G, eps).astype(cd)

    with jax.named_scope("in_proj"):
        zxd = jnp.dot(h.astype(cd), in_w, preferred_element_type=jnp.float32)
        z = zxd[..., :d_in].astype(cd)
        xBC = zxd[..., d_in:2 * d_in + 2 * G * N].astype(cd)
        dt = zxd[..., -H:]                                   # float32
    # one checkpoint from the projection's output to the other's input: the
    # backward keeps z, xBC and dt and computes the conv, the scan's
    # [chunks, H, Q, Q] blocks and the float32 y again instead of holding them
    _count_dispatch("xla_chunked")
    y = jax.checkpoint(between)(z, xBC, dt, conv_w, conv_b, dt_bias, A_log,
                                D, norm_w)
    with jax.named_scope("out_proj"):
        return jnp.dot(y, out_w, preferred_element_type=jnp.float32).astype(cd)


@register_op("mamba2_mixer")
def mamba2_mixer_kernel(ctx):
    """Program-IR face: X [B, T, d]; InW, ConvW, ConvB, DtBias, ALog, D,
    NormW, OutW as `mamba2_mixer` takes them. Out shaped like X, in the
    compute dtype: under amp only the two projection matrices are cast down
    (the small tensors of the recurrence stay float32)."""
    in_w, out_w = amp.cast_inputs(ctx, ctx.input("InW"), ctx.input("OutW"))
    ctx.set_output("Out", mamba2_mixer(
        ctx.input("X"), in_w, ctx.input("ConvW"), ctx.input("ConvB"),
        ctx.input("DtBias"), ctx.input("ALog"), ctx.input("D"),
        ctx.input("NormW"), out_w,
        num_heads=int(ctx.attr("num_heads")),
        head_dim=int(ctx.attr("head_dim")),
        n_groups=int(ctx.attr("n_groups")),
        state_size=int(ctx.attr("state_size")),
        eps=float(ctx.attr("epsilon", 1e-5)),
        chunk=int(ctx.attr("chunk", CHUNK))))


@register_op("mamba2_init")
def mamba2_init_kernel(ctx):
    """Startup op: the Mamba family's initial values of a mixer's two
    per-head vectors. `kind` "A_log": log U(1, 16), so that A = -exp(A_log)
    lies in [-16, -1]; "dt_bias": the inverse softplus of a log-uniform draw
    in [dt_min, dt_max], floored at dt_floor, so that softplus(dt_bias) is
    that draw."""
    shape, kind = ctx.attr("shape"), ctx.attr("kind")
    u = jax.random.uniform(ctx.rng(), shape, dtype=jnp.float32)
    if kind == "A_log":
        out = jnp.log(1.0 + 15.0 * u)
    elif kind == "dt_bias":
        lo, hi = math.log(ctx.attr("dt_min", 1e-3)), math.log(ctx.attr("dt_max", 0.1))
        dt = jnp.maximum(jnp.exp(lo + (hi - lo) * u), ctx.attr("dt_floor", 1e-4))
        out = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"mamba2_init: unknown kind {kind!r}")
    ctx.set_output("Out", out)
