"""What stands between a Q or K projection and the attention kernel: the RMS
norm of the projection (per head, or over its whole width) and the rotary
turn, as ONE differentiable function, `qk_assemble`, with a backward of its own.

The ops that call it are `rms_norm` and `rotary_embedding` (ops/nn_ops.py);
an attention layer marks the ones it builds in front of its kernel
(layers/attention.py). Float32 stays inside the arithmetic: x is read in the
dtype the projection wrote (bf16 under AMP), the result is rounded once, to
the kernel's input dtype, and no float32 array of the projection's shape is an
output or a residual.

Two lowerings of the same values:

- the XLA formulation (`_assemble`, `_assemble_bwd`): every backend, every
  shape. XLA:TPU gives a [T, H x D] array that is viewed [T, H, D] a T-minor
  layout (the view is a bitcast there), fuses no producer into a consumer
  that reads it through a `reverse`, a slice or a `concatenate`, and writes
  the float32 of a bf16 array it reads twice: three to five passes a
  direction and a transposing copy at the kernel's door (PERF.md section 6,
  PR 47, compiled for a described v5e).
- two Pallas kernels on the packed [B x T, H x D] layout the projections
  write and the attention kernels read, for a head of one 128-lane tile
  turned whole (or not turned): one pass a direction. A head is
  a lane-aligned slice, the norm's sum of squares a product with ones on the
  MXU, a lane's rotary partner the head rolled by half its lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_ROWS = 64              # rows of a head the kernels hold in registers at once
_BLOCK_ELEMENTS = 256 * 1024    # rows x width of a grid step's block


def _tables(T, theta, R):
    """cos and sin [T, R / 2] of position t's angles t * theta^(-2i / R)."""
    inv_freq = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def fed_tables(positions, sections, theta, R):
    """cos and sin [B, T, R / 2] float32 of FED positions: `positions` int32
    [B, A, T], A axes a token, and `sections` (A counts that sum to R / 2):
    frequency pair i takes its position from the axis whose section holds i
    (contiguous sections, the Qwen2-VL form of a three-axis rotary:
    temporal, height, width), angle = position * theta^(-2i / R). One axis
    and one section is a plain fed position; equal axes give `_tables`' bits
    at position t = the fed one."""
    sections = tuple(int(n) for n in sections)
    if positions.ndim != 3 or positions.shape[1] != len(sections) \
            or sum(sections) != R // 2:
        raise ValueError(
            f"positions {positions.shape} with sections {sections}: [B, "
            f"{len(sections)}, T] and sections that sum to {R // 2} pairs")
    with jax.named_scope("tables"):
        inv_freq = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
        axis_of = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                             total_repeat_length=R // 2)
        # [B, T, R / 2]: pair i's position, from its axis
        at = jnp.take(positions, axis_of, axis=1).transpose(0, 2, 1)
        ang = at.astype(jnp.float32) * inv_freq
        return jnp.cos(ang), jnp.sin(ang)


def _turn(x32, theta, R, backward=False, tables=None):
    """x32 [B, T, H, D] float32 with the last R lanes of each head turned by
    the position's angles (rotate-half pairs), `backward` by their negation:
    the map is linear and that is its transpose. Float32. `tables`: (cos, sin)
    [B, T, R / 2] of fed positions (`fed_tables`) in place of 0..T-1's."""
    B, T, H, D = x32.shape
    if tables is None:
        cos, sin = (t[None, :, None, :] for t in _tables(T, theta, R))
    else:
        cos, sin = (t[:, :, None, :] for t in tables)
    if backward:
        sin = -sin
    if R < D:
        x1, x2 = x32[..., D - R: D - R // 2], x32[..., D - R // 2:]
        return jnp.concatenate(
            [x32[..., : D - R], x1 * cos - x2 * sin, x2 * cos + x1 * sin],
            axis=-1)
    # the two halves as an axis of their own: a lane's partner is its mirror
    # on that axis and the turn x cos + mirror(x) (-+sin), with no slice and
    # no concatenate (pads and adds, once transposed)
    halves = x32.reshape(B, T, H, 2, D // 2)
    out = (halves * cos[..., None, :]
           + halves[..., ::-1, :] * jnp.stack([-sin, sin], axis=-2))
    return out.reshape(B, T, H, D)


def _rsqrt_mean_square(x32, eps, whole):
    rows = x32.reshape(x32.shape[:2] + (1, -1)) if whole else x32
    return jax.lax.rsqrt(jnp.mean(rows * rows, axis=-1, keepdims=True) + eps)


def _assemble(x, scale, eps, whole, theta, R, out_dtype, tables=None):
    x32 = x.astype(jnp.float32)
    if scale is not None:
        x32 = x32 * _rsqrt_mean_square(x32, eps, whole) * scale
    if theta is not None:
        x32 = _turn(x32, theta, R, tables=tables)
    return x32.astype(out_dtype)


def _assemble_bwd(x, scale, g, eps, whole, theta, R, tables=None):
    g = g.astype(jnp.float32)
    if theta is not None:
        g = _turn(g, theta, R, backward=True, tables=tables)
    if scale is None:
        return g, None
    x32 = x.astype(jnp.float32)
    r = _rsqrt_mean_square(x32, eps, whole)
    normed, gs = x32 * r, g * scale
    over = (-2, -1) if whole else (-1,)
    dx = r * (gs - normed * jnp.mean(gs * normed, axis=over, keepdims=True))
    dscale = jnp.sum(g * normed, axis=(0, 1) if scale.shape[0] > 1
                     else (0, 1, 2)).reshape(scale.shape)
    return dx, dscale


# ---- the kernels ---------------------------------------------------------
def _shapes_ok(x, theta, R) -> bool:
    """Backend-independent: heads of one lane tile (every configuration's
    that turns a whole head; wider ones have not met the chip), turned whole
    or not at all, and rows that the kernels' blocks divide."""
    T, D = x.shape[1], x.shape[3]
    return D == _LANES and (theta is None or R == D) and T % _ROWS == 0


def kernels_eligible(x, theta, R) -> bool:
    from . import mesh_dispatch

    # a bare pallas_call cannot be partitioned: under a mesh, XLA's form
    return (jax.default_backend() == "tpu" and _shapes_ok(x, theta, R)
            and mesh_dispatch.current() is None)


_HEADS_A_BLOCK = 4       # heads a grid step holds (all of them under `whole`)


def _block(T, H, D, whole):
    """(rows, heads) of a grid step's block: a few heads wide (the kernel's
    body is traced a head at a time, and set-up pays for every copy), all of
    them where the norm runs over the whole width, and about
    `_BLOCK_ELEMENTS` elements in a whole number of `_ROWS` that divides T."""
    heads = H if whole else next(
        n for n in (_HEADS_A_BLOCK, 2, 1) if H % n == 0)
    rows = max(_ROWS, min(T, _BLOCK_ELEMENTS // (heads * D)) // _ROWS * _ROWS)
    while T % rows:
        rows -= _ROWS
    return rows, heads


def _lane_sum(p):
    """p [rows, D] float32 -> [rows, D], every lane its row's sum: p x ones
    on the MXU (idle here), p in three bf16 pieces whose sum is p exactly,
    accumulated in float32. No cross-lane reduction and no broadcast back:
    the XLU's 128-lane reduction read 0.906 / 1.178 ms a [8192, 4096]
    forward / backward where this reads 0.575 / 0.908 (PERF.md section 6,
    PR 47)."""
    ones = jnp.ones((p.shape[1], p.shape[1]), jnp.bfloat16)
    total = None
    for _ in range(3):
        piece = p.astype(jnp.bfloat16)
        part = jnp.dot(piece, ones, preferred_element_type=jnp.float32)
        total = part if total is None else total + part
        p = p - piece.astype(jnp.float32)
    return total


def _over_row_chunks(ref, body, carry=()):
    """`body(rows, carry) -> carry` over the `_ROWS`-row chunks of a block, as
    a loop in the kernel: its body is traced once."""
    def chunk(c, carry):
        return body(pl.ds(pl.multiple_of(c * _ROWS, _ROWS), _ROWS), carry)
    return jax.lax.fori_loop(0, ref.shape[0] // _ROWS, chunk, carry)


def _fwd_kernel(*refs, D, eps, whole, norm, turn):
    x_ref, o_ref = refs[0], refs[-1]
    scale_ref = refs[1] if norm else None
    cos_ref, sin_ref = refs[-3:-1] if turn else (None, None)
    lanes = [pl.ds(h * D, D) for h in range(x_ref.shape[1] // D)]

    def chunk(rows, carry):
        # a chunk's tables are read once for all its heads
        cos, sin = (cos_ref[rows, :], sin_ref[rows, :]) if turn else (0, 0)
        if norm and whole:      # the heads' squares added lane by lane first
            ss = _lane_sum(sum(jnp.square(x_ref[rows, ln].astype(jnp.float32))
                               for ln in lanes))
            r_whole = jax.lax.rsqrt(ss / (len(lanes) * D) + eps)
        for h, ln in enumerate(lanes):
            v = x_ref[rows, ln].astype(jnp.float32)
            if norm:
                r = r_whole if whole else jax.lax.rsqrt(
                    _lane_sum(v * v) / D + eps)
                s = scale_ref[pl.ds(h if scale_ref.shape[0] > 1 else 0, 1), :]
                v = v * r * s
            if turn:        # a lane's partner is D / 2 lanes away, either way
                v = v * cos + pltpu.roll(v, D // 2, 1) * sin
            o_ref[rows, ln] = v.astype(o_ref.dtype)
        return carry

    _over_row_chunks(x_ref, chunk)


def _bwd_kernel(*refs, D, eps, whole, norm, turn):
    """dx (and, with a norm, this block's share of dScale, eight sublanes of
    partial sums) from the cotangent and x: the cotangent turned back, the
    normed value formed again from x."""
    g_ref = refs[0]
    x_ref, scale_ref = refs[1:3] if norm else (None, None)
    cos_ref, sin_ref = refs[3 if norm else 1:][:2] if turn else (None, None)
    dx_ref = refs[-2] if norm else refs[-1]
    dscale_ref = refs[-1] if norm else None
    per_head_scale = norm and scale_ref.shape[0] > 1
    lanes = [pl.ds(h * D, D) for h in range(g_ref.shape[1] // D)]

    def scale_of(h):
        return scale_ref[pl.ds(h if per_head_scale else 0, 1), :]

    def chunk(rows, dscale):
        cos, sin = (cos_ref[rows, :], sin_ref[rows, :]) if turn else (0, 0)

        def cotangent(ln):
            g = g_ref[rows, ln].astype(jnp.float32)
            return g * cos + pltpu.roll(g, D // 2, 1) * sin if turn else g

        if not norm:
            for ln in lanes:
                dx_ref[rows, ln] = cotangent(ln).astype(dx_ref.dtype)
            return dscale
        if whole:   # a token's two sums over all its heads, before any dx
            ss = gx = 0.0
            for h, ln in enumerate(lanes):
                v = x_ref[rows, ln].astype(jnp.float32)
                ss, gx = ss + v * v, gx + cotangent(ln) * scale_of(h) * v
            r = jax.lax.rsqrt(_lane_sum(ss) / (len(lanes) * D) + eps)
            mean = _lane_sum(gx) * r / (len(lanes) * D)
        dscale = list(dscale)
        for h, ln in enumerate(lanes):
            v = x_ref[rows, ln].astype(jnp.float32)
            g = cotangent(ln)
            gs = g * scale_of(h)
            if not whole:
                r = jax.lax.rsqrt(_lane_sum(v * v) / D + eps)
                mean = _lane_sum(gs * v) * r / D
            normed = v * r
            dx_ref[rows, ln] = (r * (gs - normed * mean)).astype(dx_ref.dtype)
            at = h if per_head_scale else 0
            dscale[at] = dscale[at] + jnp.sum(
                (g * normed).reshape(_ROWS // 8, 8, D), axis=0)
        return tuple(dscale)

    dscale = _over_row_chunks(g_ref, chunk, tuple(
        jnp.zeros((8, D), jnp.float32)
        for _ in range(scale_ref.shape[0] if norm else 0)))
    for at, part in enumerate(dscale):
        dscale_ref[0, :, pl.ds(at * D, D)] = part


def _call(backward, x_like, scale, theta, R, outs, extra, eps, whole,
          interpret, tables=None):
    """One pass over the packed rows of `x_like` [B, T, H, D] (and of the
    arrays in `extra`, its shape): blocks of whole rows of a few heads, the
    tables' blocks by the rows' positions (`tables`, of fed positions: a row
    of the batch has its own, [B x T, D], and a block reads its own rows')."""
    B, T, H, D = x_like.shape
    rows, heads = _block(T, H, D, whole)
    per_seq = T // rows
    block = pl.BlockSpec((rows, heads * D), lambda i, j: (i, j))
    args, specs = [a.reshape(B * T, H * D) for a in (x_like,) + extra], \
        [block] * (1 + len(extra))
    if scale is not None:
        args.append(scale)
        specs.append(pl.BlockSpec(scale.shape, lambda i, j: (0, 0)))
    if theta is not None:
        cos, sin = _tables(T, theta, R) if tables is None else (
            t.reshape(B * T, R // 2) for t in tables)
        if backward:
            sin = -sin
        args += [jnp.concatenate([cos, cos], axis=-1),
                 jnp.concatenate([-sin, sin], axis=-1)]
        specs += [pl.BlockSpec((rows, D), (lambda i, j: (i % per_seq, 0))
                               if tables is None else (lambda i, j: (i, 0)))
                  ] * 2
    out_specs = [block]
    if len(outs) > 1:
        out_specs.append(pl.BlockSpec(
            (1, 8, outs[1].shape[-1]), lambda i, j: (i * (H // heads) + j, 0,
                                                     0)))
    return pl.pallas_call(
        functools.partial(_bwd_kernel if backward else _fwd_kernel, D=D,
                          eps=eps, whole=whole, norm=scale is not None,
                          turn=theta is not None),
        grid=(B * T // rows, H // heads), in_specs=specs,
        out_specs=out_specs, out_shape=outs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="qk_assemble_bwd" if backward else "qk_assemble_fwd",
    )(*args)


# jitted: a launch is traced and lowered once for all the layers (and, in a
# scanned body, all the passes) that call it with one shape
_STATIC = ("eps", "whole", "theta", "R", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC + ("out_dtype",))
def _kernel_fwd(x, scale, eps, whole, theta, R, out_dtype, interpret=False,
                tables=None):
    B, T, H, D = x.shape
    out, = _call(False, x, scale, theta, R,
                 [jax.ShapeDtypeStruct((B * T, H * D), out_dtype)], (), eps,
                 whole, interpret, tables)
    return out.reshape(x.shape)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _kernel_bwd(x, scale, g, eps, whole, theta, R, interpret=False,
                tables=None):
    B, T, H, D = g.shape
    outs = [jax.ShapeDtypeStruct((B * T, H * D), x.dtype)]
    if scale is None:
        dx, = _call(True, g, None, theta, R, outs, (), eps, whole, interpret,
                    tables)
        return dx.reshape(g.shape), None
    # eight sublanes of partial sums a grid step; XLA adds them up
    rows, heads = _block(T, H, D, whole)
    outs.append(jax.ShapeDtypeStruct(
        (B * T // rows * (H // heads), 8, scale.size), jnp.float32))
    dx, dscale = _call(True, g, scale, theta, R, outs, (x,), eps, whole,
                       interpret, tables)
    return dx.reshape(g.shape), jnp.sum(dscale, axis=(0, 1)).reshape(
        scale.shape)


# ---- the function --------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def qk_assemble(x, scale, eps, whole, theta, R, out_dtype, kernels=True,
                tables=None):
    """On the [B, T, H, D] view x of a Q or K projection: the RMS norm with
    `scale` [H | 1, D] (None: no norm) over each head's D lanes, or with
    `whole` over all H x D of a token; then the rotary turn of base `theta`
    (None: no turn) of each head's last R lanes. Float32 arithmetic, the
    values of `nn_ops.rms_norm` and of the rotate-half rotary, rounded ONCE,
    to `out_dtype`. The backward (reverse mode only) keeps x as it came and
    nothing else of its shape: it turns the cotangent by the negated angle,
    forms the normed value again in registers and gives dx in x's dtype,
    dScale in float32. `kernels` False: the XLA formulation whatever the
    backend and the shape (a value that is dead code where the layer's rotary
    takes the norm's input: a launch nobody reads is still traced).
    `tables`: (cos, sin) [B, T, R / 2] float32 of FED positions
    (`fed_tables`), operands of both lowerings in place of the tables of
    0..T-1; they get no gradient. None: every trace is what it was."""
    return _qk_assemble_fwd(x, scale, eps, whole, theta, R, out_dtype,
                            kernels, tables)[0]


def _qk_assemble_fwd(x, scale, eps, whole, theta, R, out_dtype, kernels,
                     tables=None):
    lower = _kernel_fwd if kernels and kernels_eligible(x, theta, R) \
        else _assemble
    out = lower(x, scale, eps, whole, theta, R, out_dtype, tables=tables)
    # without a norm the rule needs x's dtype alone
    return out, (x if scale is not None else jnp.zeros((), x.dtype), scale,
                 tables)


def _qk_assemble_bwd(eps, whole, theta, R, out_dtype, kernels, saved, g):
    x, scale, tables = saved
    lower = _kernel_bwd if kernels and kernels_eligible(g, theta, R) \
        else _assemble_bwd
    dx, dscale = lower(x, scale, g, eps, whole, theta, R, tables=tables)
    return dx.astype(x.dtype), dscale, None


qk_assemble.defvjp(_qk_assemble_fwd, _qk_assemble_bwd)
