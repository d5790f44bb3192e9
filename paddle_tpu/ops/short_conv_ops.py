"""The gated short-convolution operator (LFM2's `conv` layers): the sequence
mixer of a layer that has no attention and no recurrence.

    [B | C | X] = u W_in                  W_in [d, 3 d], in that order
    z = B * X
    c_t = sum_{k < K} w[k] z_{t - (K - 1) + k}      zeros before the start
    out = (C * c) W_out                   W_out [d, d]

a causal depthwise convolution of K taps (3 published) between two gates,
between two GEMMs. No bias, no activation. What lies between the GEMMs,
`gated_short_conv`, is memory-bound work on [T, 3 d]: it reads the projection
as the GEMM wrote it (bf16 under AMP), keeps float32 inside the arithmetic,
rounds its result once, and has a backward of its own that keeps `bcx` and
nothing else of its shape: it reads `bcx` and `dy` once and writes `dbcx`
once and `dw` [K, d] in float32. The op is traced under the scopes `in_proj`,
`mix`, `out_proj` (as `mamba2_mixer`'s), and counted in
`pt_short_conv_dispatch_total{path}` with the bytes of its operands and
results in `pt_short_conv_bytes`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import amp
from ..core.registry import register_op


def _shifted(z, back: int):
    """z [B, T, d] -> z_{t - back}, zeros before the start."""
    if back == 0:
        return z
    T = z.shape[1]
    return jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :T]


def _ahead(g, by: int):
    """g [B, T, d] -> g_{t + by}, zeros past the end."""
    if by == 0:
        return g
    return jnp.pad(g, ((0, 0), (0, by), (0, 0)))[:, by:]


def _taps_and_windows(bcx, w):
    """Float32: the taps, the gates b, c, x and z = b * x as each tap reads
    it (tap k: K - 1 - k positions back)."""
    K, d = w.shape
    b, c, x = (bcx[..., i * d:(i + 1) * d].astype(jnp.float32)
               for i in range(3))
    z = b * x
    return (w.astype(jnp.float32), b, c, x,
            [_shifted(z, K - 1 - k) for k in range(K)])


def _mix(bcx, w):
    wf, _, c, _, back = _taps_and_windows(bcx, w)
    conv = sum(tap * window for tap, window in zip(wf, back))
    return (c * conv).astype(bcx.dtype)


def _mix_bwd(bcx, w, dy):
    wf, b, c, x, back = _taps_and_windows(bcx, w)
    K = len(back)
    g = dy.astype(jnp.float32)
    conv = sum(tap * window for tap, window in zip(wf, back))
    dconv = g * c
    dz = sum(wf[k] * _ahead(dconv, K - 1 - k) for k in range(K))
    dbcx = jnp.concatenate([dz * x, g * conv, dz * b], axis=-1)
    dw = jnp.stack([jnp.sum(dconv * window, axis=(0, 1)) for window in back])
    return dbcx.astype(bcx.dtype), dw.astype(w.dtype)


# ---- the kernels ---------------------------------------------------------
# One pass a direction over [B, T, 3 d] as the in-projection wrote it. A grid
# step holds `_ROWS` whole rows (all 3 d lanes: B, C and X of a lane tile are
# three lane-aligned slices of one block, and the backward writes dB, dC and dX
# into one block of dbcx) and the `_HALO` rows on the side the taps reach to:
# the rows BEFORE the block for z = B * X (forward, and again backward), the
# rows AFTER it for dy * C (backward). Inside, lanes in tiles of `_LANES` (a
# static loop) and rows in chunks of `_CHUNK` (a loop that carries the last
# `_CARRY` rows of z): a shifted window is the carried rows laid in front of
# the chunk and rolled along the sublanes. dw leaves a grid step as partial
# sums over its rows, eight sublanes a tap; XLA adds them up.
_ROWS = 256
_HALO = 16              # a bf16 tile's rows: the smallest block that reaches
_LANES = 512
_CHUNK = 16
_CARRY = 8              # a float32 tile's rows: K - 1 of them are read
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"),
    vmem_limit_bytes=48 * 1024 * 1024)


def _shapes_ok(bcx, w) -> bool:
    """Backend-independent: bf16 rows the blocks divide, lanes the tiles
    divide, taps the carried rows reach."""
    T, d = bcx.shape[1], w.shape[1]
    return (bcx.dtype == jnp.bfloat16 and T % _ROWS == 0 and d % _LANES == 0
            and 1 <= w.shape[0] <= _CARRY + 1)


def kernels_eligible(bcx, w) -> bool:
    from . import mesh_dispatch

    # a bare pallas_call cannot be partitioned: under a mesh, XLA's form
    return (jax.default_backend() == "tpu" and _shapes_ok(bcx, w)
            and mesh_dispatch.current() is None)


def _f32(ref, rows, lanes):
    return ref[0, rows, lanes].astype(jnp.float32)


def _back(ext, by):
    """ext = [carried rows | chunk] -> the chunk's rows read `by` rows back."""
    rolled = pltpu.roll(ext, by, 0) if by else ext
    return rolled[_CARRY:_CARRY + _CHUNK]


def _forth(ext, by):
    """ext = [chunk | following rows] -> the chunk's rows read `by` rows
    ahead."""
    rolled = pltpu.roll(ext, _CHUNK + _CARRY - by, 0) if by else ext
    return rolled[:_CHUNK]


def _lane_tiles(bcx_ref, before_ref, w_ref, d, K):
    """Per lane tile: the lanes of B, C, X in a [., 3 d] block and of the
    tile in a [., d] one, the K taps' rows, and z of the `_CARRY` rows in
    front of the block (zeros in front of the sequence)."""
    first = pl.program_id(1) == 0
    rows = pl.ds(_HALO - _CARRY, _CARRY)
    for tile in range(d // _LANES):
        lb, lc, lx = (pl.ds(part * d + tile * _LANES, _LANES)
                      for part in range(3))
        lanes = pl.ds(tile * _LANES, _LANES)
        carried = _f32(before_ref, rows, lb) * _f32(before_ref, rows, lx)
        yield (lb, lc, lx, lanes, [w_ref[k:k + 1, lanes] for k in range(K)],
               jnp.where(first, 0.0, carried))


def _chunk_rows(r):
    return pl.ds(pl.multiple_of(r * _CHUNK, _CHUNK), _CHUNK)


def _fwd_kernel(bcx_ref, before_ref, w_ref, o_ref, *, d, K):
    for lb, lc, lx, lanes, taps, carried in _lane_tiles(
            bcx_ref, before_ref, w_ref, d, K):

        def chunk(r, carried):
            rows = _chunk_rows(r)
            z = _f32(bcx_ref, rows, lb) * _f32(bcx_ref, rows, lx)
            ext = jnp.concatenate([carried, z], axis=0)
            conv = sum(taps[k] * _back(ext, K - 1 - k) for k in range(K))
            o_ref[0, rows, lanes] = (
                _f32(bcx_ref, rows, lc) * conv).astype(o_ref.dtype)
            return z[_CHUNK - _CARRY:]

        jax.lax.fori_loop(0, _ROWS // _CHUNK, chunk, carried)


def _bwd_kernel(bcx_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
                dbcx_ref, dw_ref, *, d, K):
    last = pl.program_id(1) == pl.num_programs(1) - 1
    chunks = _ROWS // _CHUNK
    ahead = pl.ds(0, _HALO)
    for lb, lc, lx, lanes, taps, carried in _lane_tiles(
            bcx_ref, before_ref, w_ref, d, K):
        # dy * C of the rows behind the block, zeros behind the sequence
        behind = jnp.where(last, 0.0, _f32(dy_after_ref, ahead, lanes)
                           * _f32(after_ref, ahead, lc))[:_CARRY]

        def chunk(r, carry):
            carried, sums = carry
            rows = _chunk_rows(r)
            b, c, x = (_f32(bcx_ref, rows, part) for part in (lb, lc, lx))
            g = _f32(dy_ref, rows, lanes)
            z = b * x
            ext = jnp.concatenate([carried, z], axis=0)
            back = [_back(ext, K - 1 - k) for k in range(K)]
            conv = sum(taps[k] * back[k] for k in range(K))
            dconv = g * c
            # the next chunk's first rows of dy * C: inside the block but for
            # the last chunk, which reads the rows behind it
            nxt = pl.ds(pl.multiple_of(
                jnp.minimum(r + 1, chunks - 1) * _CHUNK, _CHUNK), _HALO)
            inside = (_f32(dy_ref, nxt, lanes)
                      * _f32(bcx_ref, nxt, lc))[:_CARRY]
            ext = jnp.concatenate(
                [dconv, jnp.where(r == chunks - 1, behind, inside)], axis=0)
            dz = sum(taps[k] * _forth(ext, K - 1 - k) for k in range(K))
            dbcx_ref[0, rows, lb] = (dz * x).astype(dbcx_ref.dtype)
            dbcx_ref[0, rows, lc] = (g * conv).astype(dbcx_ref.dtype)
            dbcx_ref[0, rows, lx] = (dz * b).astype(dbcx_ref.dtype)
            sums = tuple(
                acc + sum(jnp.split(dconv * back[k], _CHUNK // 8, axis=0))
                for k, acc in enumerate(sums))
            return z[_CHUNK - _CARRY:], sums

        zeros = tuple(jnp.zeros((8, _LANES), jnp.float32) for _ in range(K))
        _, sums = jax.lax.fori_loop(0, chunks, chunk, (carried, zeros))
        for k in range(K):
            dw_ref[0, k, :, lanes] = sums[k]


def _halo_before(b, i):
    return (b, jnp.maximum(i * (_ROWS // _HALO) - 1, 0), 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kernel_fwd(bcx, w, interpret=False):
    B, T, d3 = bcx.shape
    K, d = w.shape
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, K=K),
        grid=(B, T // _ROWS),
        in_specs=[pl.BlockSpec((1, _ROWS, d3), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, _HALO, d3), _halo_before),
                  pl.BlockSpec((K, d), lambda b, i: (0, 0))],
        out_specs=pl.BlockSpec((1, _ROWS, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, d), bcx.dtype),
        compiler_params=_PARAMS, interpret=interpret,
        name="gated_short_conv_fwd",
    )(bcx, bcx, w.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kernel_bwd(bcx, w, dy, interpret=False):
    B, T, d3 = bcx.shape
    K, d = w.shape
    blocks = T // _ROWS

    def halo_after(b, i):
        return (b, jnp.minimum((i + 1) * (_ROWS // _HALO), T // _HALO - 1), 0)

    dbcx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, K=K),
        grid=(B, blocks),
        in_specs=[pl.BlockSpec((1, _ROWS, d3), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, _HALO, d3), _halo_before),
                  pl.BlockSpec((1, _HALO, d3), halo_after),
                  pl.BlockSpec((1, _ROWS, d), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, _HALO, d), halo_after),
                  pl.BlockSpec((K, d), lambda b, i: (0, 0))],
        out_specs=[pl.BlockSpec((1, _ROWS, d3), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, K, 8, d),
                                lambda b, i: (b * blocks + i, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((B * blocks, K, 8, d), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="gated_short_conv_bwd",
    )(bcx, bcx, bcx, dy, dy, w.astype(jnp.float32))
    return dbcx, jnp.sum(dw, axis=(0, 2)).astype(w.dtype)


# ---- the function --------------------------------------------------------
@jax.custom_vjp
def gated_short_conv(bcx, w):
    """bcx [B, T, 3 d] = [B | C | X] (the in-projection's output, in its
    dtype), w [K, d] -> C * conv_K(B * X) [B, T, d] in bcx's dtype: tap k of
    the causal depthwise convolution reads position t - (K - 1) + k, zeros
    before the start. Float32 arithmetic inside, one rounding out. Reverse
    mode keeps `bcx` and `w`: dbcx in bcx's dtype, dw in w's (float32). On
    the TPU, at shapes the blocks divide, two Pallas kernels; everywhere
    else the XLA formulation of the same values."""
    return _gated_short_conv_fwd(bcx, w)[0]


def _gated_short_conv_fwd(bcx, w):
    lower = _kernel_fwd if kernels_eligible(bcx, w) else _mix
    return lower(bcx, w), (bcx, w)


def _gated_short_conv_bwd(saved, dy):
    bcx, w = saved
    lower = _kernel_bwd if kernels_eligible(bcx, w) else _mix_bwd
    return lower(bcx, w, dy.astype(bcx.dtype))


gated_short_conv.defvjp(_gated_short_conv_fwd, _gated_short_conv_bwd)


def mix_bytes(batch: int, T: int, d: int, K: int, itemsize: int) -> int:
    """The bytes of `gated_short_conv`'s operands and results over one
    forward and one backward: forward reads bcx [T, 3 d] and writes y [T, d];
    backward reads bcx and dy and writes dbcx; the weight is read twice and
    its float32 gradient written once."""
    return batch * T * d * itemsize * (3 + 1 + 4 + 3) + K * d * 4 * 3


_bytes: dict = {}      # the op's name in its Program -> its bytes a step


def _count(path: str, bcx, w, name: str) -> None:
    """One operator traced: its path, and its bytes a step (a gauge: the sum
    over the ops traced so far, an op traced again counted once)."""
    from ..obs import metrics

    reg = metrics.registry()
    reg.counter_inc(
        "pt_short_conv_dispatch_total",
        help="gated short convolutions traced, by the formulation that runs "
             "them",
        labels={"path": path})
    B, T, d3 = bcx.shape
    _bytes[name] = mix_bytes(B, T, d3 // 3, w.shape[0], bcx.dtype.itemsize)
    reg.gauge(
        "pt_short_conv_bytes", lambda: sum(_bytes.values()),
        help="bytes of the gated short convolutions' operands and results "
             "over one forward and one backward, from the static shapes of "
             "the ops traced so far")


def short_conv_operator(h, in_w, conv_w, out_w, name: str = ""):
    """h [B, T, d] -> [B, T, d] in the projections' dtype (the amp dtype
    where the caller cast them): in_w [d, 3 d], conv_w [K, d] float32, out_w
    [d, d]. `name`: what the registry's byte count knows this op by."""
    cd = in_w.dtype
    with jax.named_scope("in_proj"):
        bcx = jnp.dot(h.astype(cd), in_w,
                      preferred_element_type=jnp.float32).astype(cd)
    with jax.named_scope("mix"):
        _count("pallas" if kernels_eligible(bcx, conv_w) else "xla", bcx,
               conv_w, name)
        y = gated_short_conv(bcx, conv_w)
    with jax.named_scope("out_proj"):
        return jnp.dot(y, out_w, preferred_element_type=jnp.float32).astype(cd)


@register_op("short_conv_operator")
def short_conv_operator_kernel(ctx):
    """Program-IR face: X [B, T, d]; InW [d, 3 d], ConvW [K, d], OutW [d, d].
    Out shaped like X, in the compute dtype: under amp the two projection
    matrices are cast down, the taps stay float32."""
    in_w, out_w = amp.cast_inputs(ctx, ctx.input("InW"), ctx.input("OutW"))
    ctx.set_output("Out", short_conv_operator(
        ctx.input("X"), in_w, ctx.input("ConvW"), out_w,
        name=ctx.op.outputs["Out"][0]))
