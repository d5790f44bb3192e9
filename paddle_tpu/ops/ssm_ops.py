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
under amp) and accumulate in float32. The oracle in the tests is the
recurrence above, token by token (`tests/nemotron_h_reference.py`).

Two formulations of that one form, chosen when the op is traced and counted
in `pt_ssm_scan_dispatch_total{path}` (`ssd_scan_packed`,
`ssd_scan_gated_norm`; no flag, no attribute):

- `pallas_chunked`: Pallas kernels (below) whose chunk state lives in VMEM
  across a sequential chunk axis: one forward, one backward behind a
  `jax.custom_vjp`. Nothing of [chunks, .., Q, Q] or [chunks, .., P, N] shape
  goes to HBM in the forward; a scan's forward reads x, B, C and what
  depends on dt and writes y. Taken on the TPU backend, outside a mesh (a
  bare `pallas_call` cannot be partitioned: ops/mesh_dispatch.py), for whole
  chunks of a multiple of 128 tokens, a state size of whole lane tiles and a
  group of R x P channels of whole lane tiles (`_shapes_scan_ok`).
- `pallas_chunked_gated`: the same kernels with the gated group RMSNorm that
  follows the scan in a mixer as the forward's epilogue and the backward's
  prologue: where the kernels take the scan AND a norm group is a grid
  step's [Q, R P] block (`groups` == G, as `mamba2_mixer` has it), the
  forward also reads z's block and the norm weight's lanes and writes
  `rms(y silu(z)) w` ONCE, rounded once, in the compute dtype. The float32
  y never reaches HBM, nothing is relaid for XLA's norm, and the norm has no
  pass of its own (25.6 ms of the hybrid's 283.6 ms step went there:
  PERF.md section 6, PR 43). Inside, float32 with `gated_group_rms_norm`'s
  own expressions; only the order of a block's 512-lane sum may differ.
- `xla_chunked`: four einsums and a `lax.scan` over the chunk states, XLA's
  (`_ssd_einsums`), and XLA's `gated_group_rms_norm` behind them: every
  other case, exactly: the CPU, a mesh, the tests' tiny configuration at
  chunk 16, a ragged tail (padded with dt = 0). Every intermediate between
  the einsums is an HBM array (1.9 GB a mixer forward at T 8192, H 64 where
  the mathematics needs 0.27: PERF.md section 6, PR 41), and its backward is
  JAX's differentiation of that.

All round where the others do (dt, cumsum(dt A), every exp and the state
float32; `m`, `x * to_end` and the state as C reads it in the compute
dtype; the norm float32 with one rounding at its output), so on the chip the
kernels' y is the einsums' to the bit and the gated kernels' output XLA's
norm of it to the bit (one mixer at the hybrid's sizes, PR 43).

The conv in front of the scan, with its bias and its `silu`
(`causal_conv_silu`, counted in `pt_ssm_conv_dispatch_total{path}`), has
the same two homes: `pallas`, one kernel a direction over the bf16 [B, T, C]
array as the in-projection wrote it (`causal_conv_silu_fwd`,
`causal_conv_silu_bwd`: a grid step is a block of rows of one 512-lane
tile, float32 inside, one rounding out; no float32 array of that shape
reaches HBM where XLA's form wrote a padded copy, four shifted windows and
the sum `silu`'s derivative read back: 24.0 ms of the hybrid's 246 ms step,
PERF.md section 6, PR 50), taken on the TPU backend, outside a mesh, for
bf16 rows in whole blocks of 1024 and lanes in whole tiles of 512
(`_shapes_conv_ok`); `xla`, `silu(causal_depthwise_conv)` with that
function's own one-pass backward, every other case, exactly: the CPU, a
mesh, float32, odd shapes. The kernels' values are the XLA form's float32
arithmetic in the same order, rounded once.

What the backward keeps and recomputes: `mamba2_mixer` puts conv, scan and
gated norm under one `jax.checkpoint`, so across the step it keeps z, xBC,
dt and the small per-head vectors and nothing of the conv or the scan.
Inside the checkpoint's backward the conv's forward kernel runs once more
(the scan's backward kernel reads the activated xBC; keeping it instead
would hold 0.39 GiB more over four mixers), and the conv's backward kernel
reads xBC and the cotangent once, forms the pre-activation again in
registers, and writes dx once and the taps' and the bias's gradients as
float32 partial sums a grid step; the cotangent it reads is the scan
backward's dx, dB and dC as three operands, a lane tile the one it lies in,
so their concatenation is never written. The differentiated forward scan
kernel runs once more too and
also writes the state each chunk STARTS from ([T / Q, H P, N] float32, 134
MB a mixer at T 8192, one transient array that lives until the backward
kernel has read it); the backward kernel recomputes a chunk's [Q, Q] blocks
in registers from x, B, C and the dt forms and carries the state's
cotangent in VMEM from the last chunk down. Behind the gate it is handed
the cotangent of the NORMED output (compute dtype), z and the norm weight,
forms the chunk's y again from the blocks it holds already (one more [Q, Q]
x [Q, P] matmul a head on an idle MXU: 0.7 ms a mixer faster on the chip
than reading back a float32 y the forward would have to write, and 134 MB
less), and from it y's cotangent in registers, z's as one more output and
the norm weight's summed over the chunks as D's is.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import amp
from ..core.registry import register_op
from .short_conv_ops import (_CARRY, _CHUNK, _HALO, _back, _chunk_rows, _f32,
                             _forth)

CHUNK = 128
_LANES = 128
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _ssd_einsums(x, dt, A, Bm, Cm, D, chunk: int):
    """The chunked form as four einsums and a `lax.scan` over the chunk
    states, all XLA's: the exact fallback of `ssd_chunked_scan`, any shape,
    any backend. Differentiated as it stands it keeps its [chunks, H, Q, Q]
    blocks for the backward; `mamba2_mixer` puts it under `jax.checkpoint`."""
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


# ------------------------------------------------------------------ kernels
# The same form with the chunk states in VMEM. A grid step is one (batch,
# group, chunk): the group's R heads share B and C, so C B^T is formed once
# a step and the state of the R heads is one [R P, N] float32 scratch that
# the sequential chunk axis carries (the forward from chunk 0 up, the
# backward's cotangent from the last chunk down). x, B and C are column
# ranges of the ONE packed [B, T, H P + 2 G N] array the conv writes (group
# g's x R P lanes at g R P, its B N lanes at H P + g N, its C at H P + G N +
# g N): three BlockSpecs over the same operand, no slice or transpose in HBM.
#
# What depends on dt alone is [T, H]-sized and XLA computes it, with the
# einsum form's own expressions (so its rounding), in the two layouts the
# kernels read (`_small_forms`): per group a COLUMN form [T, 128] (token in
# sublanes; lanes 0..R-1 cumsum(dt A), R..2R-1 its exp, 2R..3R-1 exp(last -
# cum) dt; HBM's tiles pad a narrower minor dimension to 128 lanes anyway)
# and a ROW form [2 R, T] (token in lanes: cum, dt). The [Q, Q] decay block
# of a head is exp(column - row) in registers. Both forms are operands of
# the `custom_vjp`, so their cotangents leave the backward kernel in the
# same layouts and XLA's differentiation of `_small_forms` gives d dt
# (through the input weight and through the decays' cumsum) and dA.
#
# Two heads of P = 64 share a lane tile: a head's [Q, Q] block times the
# tile's x gives its own lanes right and the neighbour's wrong, and a lane
# select keeps the right ones (as ops/flash_ops.py's packed kernels do).


class ScanGeometry(NamedTuple):
    """Static shape of a scan: H heads of P channels in G groups, state
    size N, chunk Q."""
    H: int
    P: int
    G: int
    N: int
    Q: int

    @property
    def R(self) -> int:
        return self.H // self.G


def _shapes_scan_ok(geom: ScanGeometry, T: int, dtype) -> bool:
    """Backend-independent shape rules of the kernels (separately testable):
    whole chunks of a multiple of 128 tokens, a state size of whole lane
    tiles that the packed array's B and C ranges start on, a group's R x P
    channels whole lane tiles with a head either whole tiles or a divisor of
    one, and the three per-head vectors of the column form in one tile."""
    H, P, G, N, Q = geom
    R = H // G
    return (jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and H % G == 0 and T % Q == 0 and Q % _LANES == 0
            and N % _LANES == 0 and (H * P) % N == 0
            and (R * P) % _LANES == 0
            and (P % _LANES == 0 or _LANES % P == 0) and 3 * R <= _LANES)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def scan_kernels_eligible(geom: ScanGeometry, T: int, dtype) -> bool:
    """The kernels take the scan on the TPU backend, outside a mesh (a bare
    `pallas_call` cannot be partitioned: ops/mesh_dispatch.py), at shapes
    `_shapes_scan_ok` admits."""
    from . import mesh_dispatch

    return (_on_tpu() and mesh_dispatch.current() is None
            and _shapes_scan_ok(geom, T, dtype))


def _small_forms(dt, A, geom: ScanGeometry):
    """dt [B, T, H] float32, A [H] -> (col [B, G, T, 128], row [B, G, 2 R,
    T]) float32, as the comment above lays them out. The arithmetic is the
    einsum form's, on [B, chunks, Q, H] arrays (with the groups split off,
    minor dimensions of R x R, XLA's fusion around the cumsum took 1.9 ms a
    mixer on the chip where this takes 0.4: PERF.md section 6, PR 41)."""
    _, _, G, _, Q = geom
    R = geom.R
    Bsz, T, H = dt.shape
    dt = dt.astype(jnp.float32).reshape(Bsz, T // Q, Q, H)
    cum = jnp.cumsum(dt * A.astype(jnp.float32), axis=2)
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dt

    def by_group(*forms):       # [B, T, forms, G, R], a form's heads together
        return jnp.stack(forms, axis=3).reshape(Bsz, T, len(forms), G, R)

    col = by_group(cum, jnp.exp(cum), to_end).transpose(0, 3, 1, 2, 4)
    col = jnp.pad(col.reshape(Bsz, G, T, 3 * R),
                  ((0, 0),) * 3 + ((0, _LANES - 3 * R),))
    row = by_group(cum, dt).transpose(0, 3, 2, 4, 1)
    return col, row.reshape(Bsz, G, 2 * R, T)


def _lane_col(v, k: int, width: int):
    """[rows, width]: column k of v in every lane."""
    return jnp.broadcast_to(v[:, k:k + 1], (v.shape[0], width))


def _at_last(col, k: int, width: int):
    """[1, width]: column k of the column form at the chunk's last token, in
    every lane: a lane broadcast of the last sublane tile and a masked sum
    down it (Mosaic has no broadcast of one element both ways, and folds a
    plain slice of the broadcast into one)."""
    tail = _lane_col(col[-8:], k, width)
    last = jax.lax.broadcasted_iota(jnp.int32, tail.shape, 0) == 7
    return jnp.sum(jnp.where(last, tail, 0.0), axis=0, keepdims=True)


def _heads_a_tile(P: int) -> int:
    return max(1, _LANES // P)


def _tile_of(r: int, P: int):
    """(lane slice, heads a slice, r's place in it): the lane tile head r's
    channels lie in (a head of whole tiles is its own slice)."""
    per, width = _heads_a_tile(P), max(P, _LANES)
    return slice(r // per * width, (r // per + 1) * width), per, r % per


def _head_mask(j: int, per: int, P: int):
    """[1, 128] mask of the j-th head's lanes in a tile of `per` heads (None
    where the tile is one head's)."""
    if per == 1:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    return (lane >= j * P) & (lane < (j + 1) * P)


def _merge_heads(parts, R: int, P: int):
    """[Q, R P] from each head's [Q, tile]: head r's lanes from parts[r]."""
    tiles, per = [], _heads_a_tile(P)
    for r0 in range(0, R, per):
        tile = parts[r0 + per - 1]
        for j in range(per - 2, -1, -1):
            tile = jnp.where(_head_mask(j, per, P), parts[r0 + j], tile)
        tiles.append(tile)
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def _expand(col, at: int, R: int, P: int):
    """[Q, R P]: column at + r of the column form over head r's P lanes."""
    width = max(P, _LANES)
    return _merge_heads([_lane_col(col, at + r, width) for r in range(R)], R, P)


def _head_sums(t, R: int, P: int):
    """R columns [Q, 1]: the sum of t [Q, R P] over each head's lanes."""
    out = []
    for r in range(R):
        lanes, per, j = _tile_of(r, P)
        mask = _head_mask(j, per, P)
        part = t[:, lanes]
        out.append(jnp.sum(part if mask is None else jnp.where(mask, part, 0.0),
                           axis=1, keepdims=True))
    return out


def _causal(Q: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))


def _decay(col, row, r: int, keep):
    """Head r's [Q, Q] block exp(cum_i - cum_j), zero above the diagonal."""
    gap = _lane_col(col, r, keep.shape[1]) - row[r:r + 1, :]
    return jnp.exp(jnp.where(keep, gap, -jnp.inf))


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _head_blocks(scores, col, row, r: int, R: int, keep, cd):
    """Head r's [Q, Q] blocks: (decay, dt_j [1, Q], C B^T x decay, m = that x
    dt_j in the compute dtype)."""
    decay = _decay(col, row, r, keep)
    dtj = row[R + r:R + r + 1, :]
    weighted = scores * decay
    return decay, dtj, weighted, (weighted * dtj).astype(cd)


def _chunk_y(ms, x, xf, read, decayed, d, R: int, P: int):
    """y [Q, R P] float32 of a chunk: inside the chunk a head at a time (its
    m [Q, Q] times its lane tile of x), from the state the chunk starts with
    the whole group at once (`read` = C before^T, `decayed` = exp(cum) over
    the heads' lanes), and the D skip."""
    inside = [_dot(m, x[:, _tile_of(r, P)[0]]) for r, m in enumerate(ms)]
    return _merge_heads(inside, R, P) + read * decayed + xf * d


def _gate_parts(y, z_ref, eps: float):
    """The gated norm's float32 parts over one block, `gated_group_rms_norm`'s
    own expressions: v = y silu(z) [Q, R P], r = rsqrt(mean of v^2 over the
    block's lanes + eps) [Q, 1], and z and sigmoid(z) for the backward. A
    block is one norm group, so the mean never leaves it."""
    z = z_ref[0].astype(jnp.float32)
    s = jax.nn.sigmoid(z)
    v = y * (z * s)
    r = jax.lax.rsqrt(jnp.mean(v * v, axis=1, keepdims=True) + eps)
    return v, r, z, s


def _fwd_kernel(x_ref, b_ref, c_ref, col_ref, row_ref, d_ref, *rest,
                R: int, P: int, eps: float | None):
    """One (batch, group, chunk): y of the chunk's Q tokens for the group's
    R heads, and the carried state moved to the chunk's end. With `eps` two
    more operands (z's block, the norm weight's lanes) and the output is the
    gated norm of y, rounded once to its dtype: y itself stays in registers.
    With a second output (the differentiated forward) the state the chunk
    STARTS from is written too: what the backward kernel reads."""
    gate, (out_ref, *before_ref, s_sc) = ((), rest) if eps is None else (
        rest[:2], rest[2:])

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_sc[...] = jnp.zeros(s_sc.shape, jnp.float32)

    x, Bm, Cm = x_ref[0], b_ref[0], c_ref[0]
    col, row = col_ref[0, 0], row_ref[0, 0]
    Q, cd = x.shape[0], x.dtype
    before = s_sc[...]
    for ref in before_ref:
        ref[0, 0] = before
    xf = x.astype(jnp.float32)
    scores = _dot(Cm, Bm, _NT)
    keep = _causal(Q)
    y = _chunk_y([_head_blocks(scores, col, row, r, R, keep, cd)[3]
                  for r in range(R)], x, xf, _dot(Cm, before.astype(cd), _NT),
                 _expand(col, R, R, P), d_ref[...], R, P)
    if gate:
        z_ref, w_ref = gate
        v, r, _, _ = _gate_parts(y, z_ref, eps)
        y = v * r * w_ref[...]
    out_ref[0] = y.astype(out_ref.dtype)
    # the state at the chunk's end
    own = _dot((xf * _expand(col, 2 * R, R, P)).astype(cd), Bm, _TN)
    for r in range(R):
        rows = slice(r * P, (r + 1) * P)
        s_sc[rows, :] = (_at_last(col, R + r, own.shape[1]) * before[rows]
                         + own[rows])


def _bwd_kernel(x_ref, b_ref, c_ref, col_ref, row_ref, d_ref, dy_ref,
                before_ref, *rest, R: int, P: int, eps: float | None):
    """One (batch, group, chunk), the chunks from the last down: the
    cotangents of the chunk's x, B, C (B and C summed over the group's heads)
    and of the two small forms, D's summed over the chunks in its output
    block, and the state's cotangent carried to the chunk before. With `eps`
    `dy_ref` holds the cotangent of the gated norm's output and two more
    operands (z's block, the norm weight's lanes): the chunk's y is computed
    again from the [Q, Q] blocks the backward forms anyway (one more matmul
    a head), which gives y's cotangent in registers, z's as one more output
    and the norm weight's summed over the chunks as D's is."""
    gate, (dx_ref, db_ref, dc_ref, dcol_ref, drow_ref, dd_ref, *gate_bar,
           ds_sc) = ((), rest) if eps is None else (rest[:2], rest[2:])

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_sc[...] = jnp.zeros(ds_sc.shape, jnp.float32)
        for ref in (dd_ref, *gate_bar[1:]):      # D's, the norm weight's
            ref[...] = jnp.zeros(ref.shape, jnp.float32)

    x, Bm, Cm = x_ref[0], b_ref[0], c_ref[0]
    col, row = col_ref[0, 0], row_ref[0, 0]
    Q, cd = x.shape[0], x.dtype
    N = Bm.shape[1]
    before, after_bar = before_ref[0, 0], ds_sc[...]
    before_cd, after_bar_cd = before.astype(cd), after_bar.astype(cd)
    xf = x.astype(jnp.float32)
    decayed, to_end = _expand(col, R, R, P), _expand(col, 2 * R, R, P)
    read = _dot(Cm, before_cd, _NT)
    scores = _dot(Cm, Bm, _NT)
    keep = _causal(Q)
    blocks = functools.partial(_head_blocks, scores, col, row, R=R, keep=keep,
                               cd=cd)
    g = dy_ref[0]
    if gate:
        # out = v r w: dv = r (g w - v r^2 mean(g w v)), and y's and z's
        # cotangents through v = y z sigmoid(z)
        z_ref, w_ref = gate
        dz_ref, dw_ref = gate_bar
        heads = [blocks(r) for r in range(R)]
        y = _chunk_y([h[3] for h in heads], x, xf, read, decayed, d_ref[...],
                     R, P)
        g = g.astype(jnp.float32)
        v, r, z, s = _gate_parts(y, z_ref, eps)
        dw_ref[0] += jnp.sum(g * (v * r), axis=0, keepdims=True)
        g = g * w_ref[...]
        dv = r * (g - v * (r * r * jnp.mean(g * v, axis=1, keepdims=True)))
        g = dv * (z * s)
        dz_ref[0] = (dv * y * (s * (1.0 + z * (1.0 - s)))).astype(dz_ref.dtype)
    gb = g.astype(cd)

    # y's part from the state the chunk starts with: (C before^T) x exp(cum)
    read_bar = (g * decayed).astype(cd)
    dC = _dot(read_bar, before_cd)
    before_bar = _dot(read_bar, Cm, _TN)
    d_decayed = _head_sums(g * read, R, P)
    # the state at the chunk's end: exp(last) before + (x to_end)^T B
    xw = (xf * to_end).astype(cd)
    xw_bar = _dot(Bm, after_bar_cd, _NT)
    dB = _dot(xw, after_bar_cd)
    d_to_end = _head_sums(xw_bar * xf, R, P)
    dx = g * d_ref[...] + xw_bar * to_end
    dd_ref[0] += jnp.sum(g * xf, axis=0, keepdims=True)

    # inside the chunk, a head at a time
    scores_bar = jnp.zeros((Q, Q), jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    bottom = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    dcol = jnp.zeros((Q, _LANES), jnp.float32)
    inside = []
    for r in range(R):
        lanes, per, j = _tile_of(r, P)
        mask = _head_mask(j, per, P)
        decay, dtj, weighted, m = heads[r] if gate else blocks(r)
        g_r = gb[:, lanes]
        m_bar = _dot(g_r if mask is None else jnp.where(mask, g_r, 0),
                     x[:, lanes], _NT)
        inside.append(_dot(m, g_r, _TN))
        through = m_bar * weighted          # m's cotangent through dt_j
        d_dtj = jnp.sum(through, axis=0, keepdims=True)
        drow_ref[0, 0, r:r + 1, :] = -(d_dtj * dtj)          # cum_j, in rows
        drow_ref[0, 0, R + r:R + r + 1, :] = d_dtj
        d_cum = jnp.sum(through * dtj, axis=1, keepdims=True)    # cum_i
        scores_bar = scores_bar + m_bar * decay * dtj
        # exp(last) = the column form's exp(cum) at the chunk's last token
        rows = slice(r * P, (r + 1) * P)
        d_last = jnp.sum(jnp.sum(after_bar[rows] * before[rows], axis=0,
                                 keepdims=True), axis=1, keepdims=True)
        ds_sc[rows, :] = (before_bar[rows]
                          + _at_last(col, R + r, N) * after_bar[rows])
        for at, v in ((r, d_cum),
                      (R + r, d_decayed[r] + jnp.where(bottom, d_last, 0.0)),
                      (2 * R + r, d_to_end[r])):
            dcol = jnp.where(lane == at, jnp.broadcast_to(v, dcol.shape), dcol)
    scores_bar = scores_bar.astype(cd)
    dc_ref[0] = (dC + _dot(scores_bar, Bm)).astype(dc_ref.dtype)
    db_ref[0] = (dB + _dot(scores_bar, Cm, _TN)).astype(db_ref.dtype)
    dx_ref[0] = (dx + _merge_heads(inside, R, P)).astype(dx_ref.dtype)
    dcol_ref[0, 0] = dcol


def _params():
    # c sequential (the carried state); a [Q, R P] float32 temporary is 256
    # KB at the hybrid's sizes and a step holds a dozen beside its blocks
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)


def _operand_specs(geom: ScanGeometry, chunk_of):
    """BlockSpecs of (x, B, C, col, row, D's lanes) for a grid (batch, group,
    chunk step); `chunk_of` maps the step to the chunk it works on."""
    H, P, G, N, Q = geom
    RP = geom.R * P
    b_at, c_at = H * P // N, H * P // N + G

    def at(lane):
        return lambda b, g, s: (b, chunk_of(s), lane(g))
    return [pl.BlockSpec((1, Q, RP), at(lambda g: g)),
            pl.BlockSpec((1, Q, N), at(lambda g: b_at + g)),
            pl.BlockSpec((1, Q, N), at(lambda g: c_at + g)),
            pl.BlockSpec((1, 1, Q, _LANES),
                         lambda b, g, s: (b, g, chunk_of(s), 0)),
            pl.BlockSpec((1, 1, 2 * geom.R, Q),
                         lambda b, g, s: (b, g, 0, chunk_of(s))),
            pl.BlockSpec((1, RP), lambda b, g, s: (0, g))]


def _gate_specs(geom: ScanGeometry, chunk_of):
    """BlockSpecs of a gate's (z [B, T, H P], the norm weight's lanes [1, H
    P]): z's block lies where x's does, the weight's where D's does."""
    RP = geom.R * geom.P
    return [pl.BlockSpec((1, geom.Q, RP), lambda b, g, s: (b, chunk_of(s), g)),
            pl.BlockSpec((1, RP), lambda b, g, s: (0, g))]


# jitted, as ops/flash_ops.py's launches: a model's mixers share shapes, so
# the kernels are traced and lowered once a program and not once a layer
@functools.partial(jax.jit, static_argnames=("geom", "eps", "states"))
def _scan_forward(xBC, col, row, d_lanes, *gate, geom: ScanGeometry,
                  eps: float | None, states: bool):
    """[y [B, T, H P] float32], or behind a `gate` (z, the norm weight's
    lanes) [its gated norm in z's dtype]; with `states` also the state every
    chunk starts from, [B, T / Q, H P, N] float32."""
    H, P, G, N, Q = geom
    Bsz, T, _ = xBC.shape
    RP = geom.R * P
    out_specs = [pl.BlockSpec((1, Q, RP), lambda b, g, c: (b, c, g))]
    out_shape = [jax.ShapeDtypeStruct(
        (Bsz, T, H * P), gate[0].dtype if gate else jnp.float32)]
    if states:
        out_specs.append(pl.BlockSpec((1, 1, RP, N),
                                      lambda b, g, c: (b, c, g, 0)))
        out_shape.append(jax.ShapeDtypeStruct((Bsz, T // Q, H * P, N),
                                              jnp.float32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, R=geom.R, P=P, eps=eps),
        grid=(Bsz, G, T // Q),
        in_specs=(_operand_specs(geom, lambda c: c)
                  + _gate_specs(geom, lambda c: c)[:len(gate)]),
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((RP, N), jnp.float32)],
        compiler_params=_params(),
        name="ssd_scan_fwd",
    )(xBC, xBC, xBC, col, row, d_lanes, *gate)


@functools.partial(jax.jit, static_argnames=("geom", "eps"))
def _scan_backward(xBC, col, row, d_lanes, before, dy, *gate,
                   geom: ScanGeometry, eps: float | None):
    """Cotangents of (xBC, col, row, d_lanes) given y's; behind a `gate` (z,
    the norm weight's lanes) those and (z's, the lanes') given the gated
    norm's."""
    H, P, G, N, Q = geom
    Bsz, T, _ = xBC.shape
    R, RP, c = geom.R, geom.R * P, T // Q
    back = lambda s: c - 1 - s  # noqa: E731
    lanes = lambda width: pl.BlockSpec(  # noqa: E731
        (1, Q, width), lambda b, g, s: (b, back(s), g))
    summed = pl.BlockSpec((1, 1, RP), lambda b, g, s: (b, 0, g))
    cd = xBC.dtype
    out_specs = [
        lanes(RP), lanes(N), lanes(N),
        pl.BlockSpec((1, 1, Q, _LANES), lambda b, g, s: (b, g, back(s), 0)),
        pl.BlockSpec((1, 1, 2 * R, Q), lambda b, g, s: (b, g, 0, back(s))),
        summed]
    out_shape = [
        jax.ShapeDtypeStruct((Bsz, T, H * P), cd),
        jax.ShapeDtypeStruct((Bsz, T, G * N), cd),
        jax.ShapeDtypeStruct((Bsz, T, G * N), cd),
        jax.ShapeDtypeStruct(col.shape, jnp.float32),
        jax.ShapeDtypeStruct(row.shape, jnp.float32),
        jax.ShapeDtypeStruct((Bsz, 1, H * P), jnp.float32)]
    if gate:
        out_specs += [lanes(RP), summed]
        out_shape += [jax.ShapeDtypeStruct(gate[0].shape, gate[0].dtype),
                      out_shape[-1]]
    dx, dB, dC, dcol, drow, dd, *gate_bar = pl.pallas_call(
        functools.partial(_bwd_kernel, R=R, P=P, eps=eps),
        grid=(Bsz, G, c),
        in_specs=_operand_specs(geom, back) + [
            lanes(RP),
            pl.BlockSpec((1, 1, RP, N), lambda b, g, s: (b, back(s), g, 0)),
            *_gate_specs(geom, back)[:len(gate)]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((RP, N), jnp.float32)],
        compiler_params=_params(),
        name="ssd_scan_bwd",
    )(xBC, xBC, xBC, col, row, d_lanes, dy, before, *gate)
    # XLA folds this concatenation into the fusion that reads it (the conv's
    # backward): it is no pass of its own in the hybrid's step. A kernel
    # that copies the three into one packed output itself was 0.3 ms a
    # mixer slower and saved nothing (PERF.md section 6, PR 41)
    return (jnp.concatenate([dx, dB, dC], axis=-1), dcol, drow,
            jnp.sum(dd, axis=0),
            *([gate_bar[0], jnp.sum(gate_bar[1], axis=0)] if gate else []))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan_kernels(xBC, col, row, d_lanes, gate, geom: ScanGeometry,
                  eps: float | None):
    """The kernels over the packed [x | B | C] and the small forms: no
    dispatch gate. `gate` is () and `eps` None for y alone, float32, or (z,
    the norm weight's lanes) and the norm's eps for the gated norm of y in
    z's dtype. Not differentiated, the forward writes that one array."""
    return _scan_forward(xBC, col, row, d_lanes, *gate, geom=geom, eps=eps,
                         states=False)[0]


def _scan_kernels_fwd(xBC, col, row, d_lanes, gate, geom, eps):
    out, before = _scan_forward(xBC, col, row, d_lanes, *gate, geom=geom,
                                eps=eps, states=True)
    return out, (xBC, col, row, d_lanes, before, gate)


def _scan_kernels_bwd(geom, eps, saved, dout):
    *saved, gate = saved
    dxBC, dcol, drow, dd, *gate_bar = _scan_backward(
        *saved, dout, *gate, geom=geom, eps=eps)
    return dxBC, dcol, drow, dd, tuple(gate_bar)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def _ssd_kernels(xBC, dt, A, D, geom: ScanGeometry, gate=(), eps=None):
    """Through the kernels, from the packed [x | B | C] [B, T, H P + 2 G N]:
    y [B, T, H, P] float32, or with `gate` (z [B, T, H P], the norm's weight
    [H P]) and `eps` the gated norm of y, [B, T, H P] in z's dtype."""
    col, row = _small_forms(dt, A, geom)
    d_lanes = jnp.repeat(D.astype(jnp.float32), geom.P)[None, :]
    if gate:
        z, norm_w = gate
        return _scan_kernels(xBC, col, row, d_lanes,
                             (z, norm_w.astype(jnp.float32)[None, :]), geom,
                             float(eps))
    y = _scan_kernels(xBC, col, row, d_lanes, (), geom, None)
    return y.reshape(*y.shape[:2], geom.H, geom.P)


def _count_dispatch(path: str) -> None:
    from ..obs import metrics

    metrics.registry().counter_inc(
        "pt_ssm_scan_dispatch_total",
        help="state-space scans traced, by the formulation that runs them",
        labels={"path": path})


def ssd_scan_packed(xBC, dt, A, D, geom: ScanGeometry):
    """The scan over the packed [x | B | C] [B, T, H P + 2 G N] (compute
    dtype) the conv writes; dt [B, T, H] float32 and positive, A [H]
    negative, D [H] -> y [B, T, H, P] float32. The path is chosen here, when
    the op is traced, from the backend, the mesh and the shapes, and counted
    in `pt_ssm_scan_dispatch_total{path}`."""
    H, P, G, N, Q = geom
    Bsz, T, _ = xBC.shape
    if scan_kernels_eligible(geom, T, xBC.dtype):
        _count_dispatch("pallas_chunked")
        return _ssd_kernels(xBC, dt, A, D, geom)
    _count_dispatch("xla_chunked")
    d_in = H * P
    return _ssd_einsums(
        xBC[..., :d_in].reshape(Bsz, T, H, P), dt, A,
        xBC[..., d_in:d_in + G * N].reshape(Bsz, T, G, N),
        xBC[..., d_in + G * N:].reshape(Bsz, T, G, N), D, Q)


def ssd_scan_gated_norm(xBC, dt, A, D, z, norm_w, geom: ScanGeometry,
                        groups: int, eps: float):
    """`gated_group_rms_norm` of `ssd_scan_packed`'s y with z [B, T, H P]
    (compute dtype) and norm_w [H P], the mean square inside each of
    `groups` runs of lanes -> [B, T, H P] in z's dtype. Where the kernels
    take the scan and a norm group is a kernel's block (`groups` == G) the
    norm is the kernels' epilogue (`pallas_chunked_gated`): y never reaches
    HBM in the forward. Everywhere else the scan as `ssd_scan_packed`
    chooses it, under the caller's `scan` scope, and XLA's norm under
    `gate_norm`."""
    Bsz, T, _ = xBC.shape
    if groups == geom.G and scan_kernels_eligible(geom, T, xBC.dtype):
        _count_dispatch("pallas_chunked_gated")
        with jax.named_scope("scan"):
            return _ssd_kernels(xBC, dt, A, D, geom, (z, norm_w), eps)
    with jax.named_scope("scan"):
        y = ssd_scan_packed(xBC, dt, A, D, geom)
    with jax.named_scope("gate_norm"):
        return gated_group_rms_norm(y.reshape(Bsz, T, geom.H * geom.P), z,
                                    norm_w, groups, eps).astype(z.dtype)


def ssd_chunked_scan(x, dt, A, Bm, Cm, D, chunk: int = CHUNK):
    """x [B, T, H, P] (compute dtype), dt [B, T, H] float32 and positive, A
    [H] negative, Bm / Cm [B, T, G, N] (compute dtype), D [H] -> y [B, T, H,
    P] float32. Any T: a tail shorter than a chunk is padded with dt = 0
    (the einsum form; the kernels take whole chunks). `ssd_scan_packed` with
    the three sequences side by side: what `mamba2_mixer` holds already."""
    Bsz, T, H, P = x.shape
    G, N = Bm.shape[2:]
    return ssd_scan_packed(
        jnp.concatenate([x.reshape(Bsz, T, H * P), Bm.reshape(Bsz, T, G * N),
                         Cm.reshape(Bsz, T, G * N)], axis=-1),
        dt, A, D, ScanGeometry(H, P, G, N, chunk))


@jax.custom_vjp
def causal_depthwise_conv(x, w, b):
    """x [B, T, C], w [K, C], b [C] -> float32 [B, T, C]: out_t = b + sum_k
    w[k] x_{t - (K - 1) + k}, zeros before the sequence's start."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    out = b.astype(jnp.float32)
    for k in range(K):
        out = out + xp[:, k:k + T] * w[k].astype(jnp.float32)
    return out


def _conv_fwd(x, w, b):
    return causal_depthwise_conv(x, w, b), (x, w, b)


def _conv_bwd(saved, g):
    """The transposed taps on g padded BEHIND the sequence, one fused pass:
    JAX's own transposition pads each tap's product in front and XLA writes
    the K float32 [B, T, C] products to HBM first (0.8 GB a mixer at the
    hybrid's sizes: PERF.md section 6, PR 43)."""
    x, w, b = saved
    K, T = w.shape[0], x.shape[1]
    wf = w.astype(jnp.float32)
    gp = jnp.pad(g, ((0, 0), (0, K - 1), (0, 0)))
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    dx = sum(gp[:, k:k + T] * wf[K - 1 - k] for k in range(K))
    dw = jnp.stack([jnp.sum(g * xp[:, k:k + T], axis=(0, 1))
                    for k in range(K)])
    return (dx.astype(x.dtype), dw.astype(w.dtype),
            jnp.sum(g, axis=(0, 1)).astype(b.dtype))


causal_depthwise_conv.defvjp(_conv_fwd, _conv_bwd)


# ---- the conv, its bias and its silu as kernels --------------------------
# One pass a direction over [B, T, C] as the in-projection wrote it. Nothing
# crosses lanes here, so a grid step is one (batch, block of `_CONV_ROWS`
# rows, tile of `_CONV_LANES` lanes), with the `_HALO` rows on the side the
# taps reach to (ops/short_conv_ops.py's scheme and its row-window helpers):
# the rows BEFORE the block for x (forward, and again backward), the rows
# AFTER it for x and the cotangent (backward). Inside, rows in chunks of
# `_CHUNK`. The forward walks them up and carries the last `_CARRY` rows of
# x; the backward walks them down and carries the first `_CARRY` rows of
# dpre = dy silu'(pre) of the chunk behind, so the pre-activation is formed
# once a row, in registers. dw and db leave a grid step as partial sums over
# its rows, eight sublanes a tap (the bias the K-th); XLA adds them up.
_CONV_ROWS = 1024
_CONV_LANES = 512
_CONV_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"),
    vmem_limit_bytes=48 * 1024 * 1024)
_ALL = slice(None)


def _shapes_conv_ok(x, w) -> bool:
    """Backend-independent: bf16 rows the blocks divide, lanes the tiles
    divide, taps the carried rows reach."""
    T, C = x.shape[1:]
    return (x.dtype == jnp.bfloat16 and T % _CONV_ROWS == 0
            and C % _CONV_LANES == 0 and 1 <= w.shape[0] <= _CARRY + 1)


def conv_kernels_eligible(x, w) -> bool:
    """The kernels take the conv where they take the scan: the TPU backend,
    outside a mesh, at shapes `_shapes_conv_ok` admits."""
    from . import mesh_dispatch

    return (_on_tpu() and mesh_dispatch.current() is None
            and _shapes_conv_ok(x, w))


def _pre_windows(ext, w_ref, b_ref, K: int):
    """ext = [carried rows | chunk] of x, float32 -> (the chunk's
    pre-activation b + sum_k w[k] x_{t - (K - 1) + k}, summed in
    `causal_depthwise_conv`'s order, and x as each tap reads it)."""
    back = [_back(ext, K - 1 - k) for k in range(K)]
    pre = b_ref[...]
    for k in range(K):
        pre = pre + back[k] * w_ref[k:k + 1, :]
    return pre, back


def _conv_fwd_kernel(x_ref, before_ref, w_ref, b_ref, o_ref, *, K: int):
    carried = jnp.where(pl.program_id(1) == 0, 0.0, _f32(
        before_ref, pl.ds(_HALO - _CARRY, _CARRY), _ALL))

    def chunk(r, carried):
        rows = _chunk_rows(r)
        x = _f32(x_ref, rows, _ALL)
        pre, _ = _pre_windows(jnp.concatenate([carried, x], axis=0), w_ref,
                              b_ref, K)
        o_ref[0, rows, :] = (pre * jax.nn.sigmoid(pre)).astype(o_ref.dtype)
        return x[_CHUNK - _CARRY:]

    jax.lax.fori_loop(0, x_ref.shape[1] // _CHUNK, chunk, carried)


def _conv_bwd_kernel(x_ref, before_ref, after_ref, w_ref, b_ref, *rest,
                     K: int, starts: tuple):
    """`starts`: the lane tile each of the cotangent's parts starts at; the
    parts' blocks and their rows behind the block follow the five operands,
    then dx's block and the partial sums'."""
    n = len(starts)
    dy_refs, dy_after_refs, (dx_ref, sums_ref) = (
        rest[:n], rest[n:2 * n], rest[2 * n:])
    tile = pl.program_id(2)
    chunks = x_ref.shape[1] // _CHUNK

    def cotangent(refs, rows):          # of the part this lane tile lies in
        g = _f32(refs[0], rows, _ALL)
        for ref, start in zip(refs[1:], starts[1:]):
            g = jnp.where(tile >= start, _f32(ref, rows, _ALL), g)
        return g

    def dpre_of(ext, g):
        pre, back = _pre_windows(ext, w_ref, b_ref, K)
        s = jax.nn.sigmoid(pre)
        return g * (s * (1.0 + pre * (1.0 - s))), back

    # dpre of the rows behind the block, zeros behind the sequence
    ahead = pl.ds(0, _HALO)
    behind, _ = dpre_of(
        jnp.concatenate([_f32(x_ref, _chunk_rows(chunks - 1), _ALL)[
            _CHUNK - _CARRY:], _f32(after_ref, ahead, _ALL)], axis=0),
        cotangent(dy_after_refs, ahead))
    behind = jnp.where(pl.program_id(1) == pl.num_programs(1) - 1, 0.0,
                       behind[:_CARRY])
    front = jnp.where(pl.program_id(1) == 0, 0.0, _f32(
        before_ref, pl.ds(_HALO - _CARRY, _CARRY), _ALL))

    def chunk(step, carry):
        behind, sums = carry
        r = chunks - 1 - step
        rows = _chunk_rows(r)
        # the rows in front of the chunk: the chunk before it, or the halo
        inside = _f32(x_ref, _chunk_rows(jnp.maximum(r - 1, 0)), _ALL)[
            _CHUNK - _CARRY:]
        ext = jnp.concatenate([jnp.where(r == 0, front, inside),
                               _f32(x_ref, rows, _ALL)], axis=0)
        dpre, back = dpre_of(ext, cotangent(dy_refs, rows))
        ext = jnp.concatenate([dpre, behind], axis=0)
        dx = _forth(ext, K - 1) * w_ref[0:1, :]
        for k in range(1, K):
            dx = dx + _forth(ext, K - 1 - k) * w_ref[k:k + 1, :]
        dx_ref[0, rows, :] = dx.astype(dx_ref.dtype)
        sums = tuple(acc + sum(jnp.split(product, _CHUNK // 8, axis=0))
                     for acc, product in zip(
                         sums, (*[dpre * seen for seen in back], dpre)))
        return dpre[:_CARRY], sums

    zeros = tuple(jnp.zeros((8, x_ref.shape[2]), jnp.float32)
                  for _ in range(K + 1))
    _, sums = jax.lax.fori_loop(0, chunks, chunk, (behind, zeros))
    for k in range(K + 1):
        sums_ref[0, k] = sums[k]


def _conv_specs(T: int):
    """BlockSpec makers over a [B, T, width] array for a grid (batch, row
    block, lane tile): a block, the halo in front of it, the halo behind
    it; `lane` maps the grid's lane tile to the array's."""
    per = _CONV_ROWS // _HALO

    def block(lane=lambda j: j):
        return pl.BlockSpec((1, _CONV_ROWS, _CONV_LANES),
                            lambda b, i, j: (b, i, lane(j)))

    def before(lane=lambda j: j):
        return pl.BlockSpec((1, _HALO, _CONV_LANES), lambda b, i, j: (
            b, jnp.maximum(i * per - 1, 0), lane(j)))

    def after(lane=lambda j: j):
        return pl.BlockSpec((1, _HALO, _CONV_LANES), lambda b, i, j: (
            b, jnp.minimum((i + 1) * per, T // _HALO - 1), lane(j)))

    return block, before, after


def _taps_specs(K: int):
    return [pl.BlockSpec((K, _CONV_LANES), lambda b, i, j: (0, j)),
            pl.BlockSpec((1, _CONV_LANES), lambda b, i, j: (0, j))]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _conv_silu_forward(x, w, b, interpret=False):
    Bsz, T, C = x.shape
    K = w.shape[0]
    block, before, _ = _conv_specs(T)
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, K=K),
        grid=(Bsz, T // _CONV_ROWS, C // _CONV_LANES),
        in_specs=[block(), before(), *_taps_specs(K)],
        out_specs=block(),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_CONV_PARAMS, interpret=interpret,
        name="causal_conv_silu_fwd",
    )(x, x, w.astype(jnp.float32), b.astype(jnp.float32)[None, :])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _conv_silu_backward(x, w, b, dys, interpret=False):
    """(dx in x's dtype, dw [K, C] and db [C] float32) given the cotangent
    of the activated array as the tuple of its parts along the lanes (one
    part: the whole)."""
    Bsz, T, C = x.shape
    K = w.shape[0]
    blocks = T // _CONV_ROWS
    block, before, after = _conv_specs(T)
    tiles = [dy.shape[2] // _CONV_LANES for dy in dys]
    starts = tuple(sum(tiles[:n]) for n in range(len(tiles)))

    def part(spec, start, n):
        # a tile outside the part holds the block of the part's nearest
        # tile, which the pipeline fetches once a row block either way
        return spec(lambda j: jnp.clip(j - start, 0, n - 1))

    parts = list(zip(starts, tiles))
    dx, sums = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, K=K, starts=starts),
        grid=(Bsz, blocks, C // _CONV_LANES),
        in_specs=[block(), before(), after(), *_taps_specs(K),
                  *[part(block, *p) for p in parts],
                  *[part(after, *p) for p in parts]],
        out_specs=[block(),
                   pl.BlockSpec((1, K + 1, 8, _CONV_LANES),
                                lambda b, i, j: (b * blocks + i, 0, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((Bsz * blocks, K + 1, 8, C),
                                        jnp.float32)],
        compiler_params=_CONV_PARAMS, interpret=interpret,
        name="causal_conv_silu_bwd",
    )(x, x, x, w.astype(jnp.float32), b.astype(jnp.float32)[None, :],
      *dys, *dys)
    sums = jnp.sum(sums, axis=(0, 2))
    return dx, sums[:K].astype(w.dtype), sums[K].astype(b.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_silu_kernels(x, w, b, parts: tuple):
    """The kernels, no dispatch gate. `parts`: the widths along the lanes at
    which the backward takes the cotangent apart, (C,) for one operand."""
    return _conv_silu_forward(x, w, b)


def _conv_silu_kernels_fwd(x, w, b, parts):
    return _conv_silu_forward(x, w, b), (x, w, b)


def _conv_silu_kernels_bwd(parts, saved, dy):
    # where the cotangent is a concatenation at these widths (the scan's
    # backward kernel writes dx, dB and dC as three arrays), XLA folds each
    # slice into the array it is a copy of and the concatenation goes
    edges = [sum(parts[:n]) for n in range(len(parts) + 1)]
    return _conv_silu_backward(*saved, tuple(
        dy[..., lo:hi] for lo, hi in zip(edges, edges[1:])))


_conv_silu_kernels.defvjp(_conv_silu_kernels_fwd, _conv_silu_kernels_bwd)


def _count_conv_dispatch(path: str) -> None:
    from ..obs import metrics

    metrics.registry().counter_inc(
        "pt_ssm_conv_dispatch_total",
        help="state-space mixers' short convolutions traced, by the "
             "formulation that runs them",
        labels={"path": path})


def causal_conv_silu(x, w, b, parts: tuple = ()):
    """silu(`causal_depthwise_conv`(x, w, b)) in x's dtype: x [B, T, C], w
    [K, C], b [C]. Float32 inside, one rounding out. The path is chosen
    here, when the op is traced, and counted in
    `pt_ssm_conv_dispatch_total{path}`: `pallas`, one kernel a direction
    (`conv_kernels_eligible`), whose backward keeps x alone and forms the
    pre-activation again in registers; `xla`, today's form exactly, every
    other case. `parts`: widths along C at which the cotangent arrives as a
    concatenation, which the backward kernel then reads as operands of
    their own where each is whole lane tiles."""
    if conv_kernels_eligible(x, w):
        _count_conv_dispatch("pallas")
        if sum(parts) != x.shape[2] or any(
                width % _CONV_LANES for width in parts):
            parts = (x.shape[2],)
        return _conv_silu_kernels(x, w, b, tuple(parts))
    _count_conv_dispatch("xla")
    return jax.nn.silu(causal_depthwise_conv(x, w, b)).astype(x.dtype)


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
    docstring says: on the TPU, outside a mesh, at the shapes their blocks
    divide, the conv with its bias and silu is one Pallas kernel a
    direction under the `conv` scope and the scan with the gated norm one a
    direction under `scan` (each runs its forward twice, the second time
    inside the checkpoint's backward); everywhere else XLA's forms of the
    same values (`causal_depthwise_conv`, `_ssd_einsums`,
    `gated_group_rms_norm`), chosen when the op is traced."""
    H, P, G, N = num_heads, head_dim, n_groups, state_size
    d_in = H * P
    cd = in_w.dtype

    def between(z, xBC, dt, conv_w, conv_b, dt_bias, A_log, D, norm_w):
        with jax.named_scope("conv"):
            xBC = causal_conv_silu(xBC, conv_w, conv_b,
                                   (d_in, G * N, G * N))
        with jax.named_scope("scan"):
            dt = jax.nn.softplus(dt + dt_bias)
            A = -jnp.exp(A_log.astype(jnp.float32))
        return ssd_scan_gated_norm(xBC, dt, A, D, z, norm_w,
                                   ScanGeometry(H, P, G, N, chunk), G, eps)

    with jax.named_scope("in_proj"):
        zxd = jnp.dot(h.astype(cd), in_w, preferred_element_type=jnp.float32)
        z = zxd[..., :d_in].astype(cd)
        xBC = zxd[..., d_in:2 * d_in + 2 * G * N].astype(cd)
        dt = zxd[..., -H:]                                   # float32
    # one checkpoint from the projection's output to the other's input: the
    # backward keeps z, xBC and dt and computes the conv (the activated xBC,
    # in its kernel or as XLA's float32 arrays), the scan (its [Q, Q] blocks
    # and chunk states, in the kernels or as XLA's arrays) and the float32 y
    # (an array only where XLA's norm reads it) again instead of holding them
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


# ---- Mamba-1: the selective scan ------------------------------------------
# Gu & Dao 2023. A decay of its own for every channel c and state n, so the
# recurrence does not turn into [Q, Q] matmuls as Mamba-2's does:
#
#     S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
#     y_t[c]    = sum_n S_t[c, n] C_t[n] + D[c] x_t[c]                S_0 = 0
#
# `selective_scan` is a `jax.custom_vjp` over chunks of `SEL_CHUNK` tokens.
# Its residuals are its operands and the state each chunk STARTS from ([B,
# T / Q, N, C] float32: 10.5 MB a mixer at T 8192, C 5120, N 16); nothing of
# [T, C, N] shape outlives a chunk, forward or backward. Two homes, chosen
# when the op is traced and counted in `pt_selective_scan_dispatch_total
# {path}` (no flag, no attribute):
#
# - `pallas`: the kernels below, on the TPU backend, outside a mesh, at the
#   shapes `_shapes_selective_ok` admits. The state is [N, C] with the
#   states in sublanes and the channels in lanes, resident in VMEM across
#   the sequential chunk axis; a token's step is elementwise work on the
#   vector unit and one sum over the N sublanes. The backward walks the
#   chunks from the last down: it forms a chunk's states again from its
#   saved start into a VMEM scratch, then carries the state's cotangent
#   through the chunk's tokens in reverse.
# - `xla_chunked`: a `lax.scan` over the chunks, each chunk an associative
#   scan over its tokens on [Q, N, C] arrays, its backward `jax.vjp` of the
#   chunk run from the saved start (so a chunk is computed again, as under
#   `jax.checkpoint`): every other case, exactly: the CPU, a mesh, odd shapes.
#
# Float32 arithmetic, state and decays on both; x, B, C and y in the dtype
# they arrive in (the amp dtype), dt float32 (after its softplus).
SEL_CHUNK = 128
# what the state is carried from chunk to chunk in: float32 by design (a
# bf16 carry is the wrong program `tests/phi4flash_controls.py` swaps in)
_CARRY_DTYPE = jnp.float32


def _sel_chunk(S0, x, dt, A_t, Bm, Cm):
    """One chunk by an associative scan. S0 [B, N, C] float32; x, dt [B, Q,
    C]; Bm, Cm [B, Q, N]; A_t [N, C] -> (y [B, Q, C] float32 without the D
    term, the state at the chunk's end)."""
    x, dt, Bm, Cm = (a.astype(jnp.float32) for a in (x, dt, Bm, Cm))
    decay = jnp.exp(dt[:, :, None, :] * A_t)                   # [B, Q, N, C]
    fresh = (dt * x)[:, :, None, :] * Bm[..., None]

    def combine(first, then):
        return then[0] * first[0], then[0] * first[1] + then[1]

    to_here, own = jax.lax.associative_scan(combine, (decay, fresh), axis=1)
    S = to_here * S0[:, None] + own
    return jnp.sum(S * Cm[..., None], axis=2), S[:, -1]


def _sel_chunks(a, Q: int):
    """[B, T, ...] -> [T / Q, B, Q, ...]: the chunks in front, for a scan."""
    B, T = a.shape[:2]
    return jnp.moveaxis(a.reshape(B, T // Q, Q, *a.shape[2:]), 1, 0)


def _sel_unchunk(a):
    a = jnp.moveaxis(a, 0, 1)
    return a.reshape(a.shape[0], -1, *a.shape[3:])


def _sel_pad(arrays, Q: int):
    """A ragged tail padded with zeros: dt 0 is no decay and no input, so the
    state passes through and the tail's outputs are dropped."""
    pad = -arrays[0].shape[1] % Q
    if not pad:
        return arrays
    return tuple(jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in arrays)


def _sel_xla_forward(x, dt, A, Bm, Cm, D):
    """(y in x's dtype, the chunk starts [B, chunks, N, C] float32)."""
    Bsz, T, C = x.shape
    A_t = A.astype(jnp.float32).T
    xs = tuple(_sel_chunks(a, SEL_CHUNK)
               for a in _sel_pad((x, dt, Bm, Cm), SEL_CHUNK))

    def step(S, chunk):
        y, S_end = _sel_chunk(S, *chunk[:2], A_t, *chunk[2:])
        return S_end.astype(_CARRY_DTYPE).astype(jnp.float32), (y, S)

    _, (y, starts) = jax.lax.scan(
        step, jnp.zeros((Bsz, A.shape[1], C), jnp.float32), xs)
    y = _sel_unchunk(y)[:, :T] + D.astype(jnp.float32) * x.astype(jnp.float32)
    return y.astype(x.dtype), jnp.moveaxis(starts, 0, 1)


def _sel_xla_backward(x, dt, A, Bm, Cm, D, starts, dy):
    """The operands' cotangents, in their dtypes: the chunks in reverse, each
    `jax.vjp` of `_sel_chunk` from its saved start, the state's cotangent
    carried down and A's summed on the way."""
    T = x.shape[1]
    A_t = A.astype(jnp.float32).T
    dy32 = dy.astype(jnp.float32)
    x_c, dt_c, B_c, C_c, dy_c = (
        _sel_chunks(a, SEL_CHUNK)
        for a in _sel_pad((x, dt, Bm, Cm, dy32), SEL_CHUNK))

    def step(carry, chunk):
        dS, dA_t = carry
        S0, xc, dtc, Bc, Cc, dyc = chunk
        _, pull = jax.vjp(_sel_chunk, S0, xc, dtc, A_t, Bc, Cc)
        dS0, dxc, ddtc, dA_c, dBc, dCc = pull((dyc, dS))
        return (dS0, dA_t + dA_c), (dxc, ddtc, dBc, dCc)

    zero = jnp.zeros_like(starts[:, 0])
    (_, dA_t), grads = jax.lax.scan(
        step, (zero, jnp.zeros_like(A_t)),
        (jnp.moveaxis(starts, 1, 0), x_c, dt_c, B_c, C_c, dy_c), reverse=True)
    dx, ddt, dB, dC = (_sel_unchunk(g)[:, :T] for g in grads)
    x32 = x.astype(jnp.float32)
    dx = dx.astype(jnp.float32) + D.astype(jnp.float32) * dy32
    return (dx.astype(x.dtype), ddt.astype(dt.dtype), dA_t.T.astype(A.dtype),
            dB.astype(Bm.dtype), dC.astype(Cm.dtype),
            jnp.sum(dy32 * x32, axis=(0, 1)).astype(D.dtype))


# The kernels. A grid step is one (batch, chunk, tile of `lanes` channels),
# the channel tiles innermost: a chunk's B and C blocks stay where they are
# while its tiles go by (they are fetched once a chunk), and the backward's
# dB and dC blocks are summed over the tiles in place. The state of every
# tile lives in one scratch [tiles, N, lanes] across the sequential chunk
# axis. B and C arrive with each of a token's N numbers in all 128 lanes ([B,
# T, N, 128] float32, XLA's broadcast): a token's [N, lanes] operand is that
# vreg column laid side by side, and nothing crosses lanes inside a step.
# dB and dC leave as the same shape, a token's N sums still spread over 128
# lanes' partial sums, and XLA adds the lanes up.
def _sel_lanes(C: int) -> int:
    return next(w for w in (512, 256, 128) if C % w == 0)


def _shapes_selective_ok(x, A) -> bool:
    """Backend-independent: whole chunks, channels in whole lane tiles, the
    states whole sublane tiles."""
    T, C = x.shape[1:]
    return (x.dtype in (jnp.bfloat16, jnp.float32) and T % SEL_CHUNK == 0
            and C % _LANES == 0 and A.shape[1] % 8 == 0)


def selective_kernels_eligible(x, A) -> bool:
    from . import mesh_dispatch

    return (_on_tpu() and mesh_dispatch.current() is None
            and _shapes_selective_ok(x, A))


def _across_tiles(v, lanes: int):
    """[N, 128] -> [N, lanes]: the same vregs side by side."""
    return jnp.concatenate([v] * (lanes // _LANES), axis=1)


def _fold_tiles(v):
    """[N, lanes] -> [N, 128]: the lane tiles added up."""
    return sum(v[:, i:i + _LANES] for i in range(0, v.shape[1], _LANES))


def _row(ref, t, N: int):
    """Row t of a [Q, lanes] float32 ref in all N sublanes."""
    return jnp.broadcast_to(ref[pl.ds(t, 1), :], (N, ref.shape[1]))


def _sel_fwd_kernel(x_ref, dt_ref, bl_ref, cl_ref, at_ref, d_ref, y_ref,
                    start_ref, s_scr, u_scr, y_scr):
    tile, (N, lanes) = pl.program_id(2), at_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[tile] = jnp.zeros((N, lanes), s_scr.dtype)

    start = s_scr[tile].astype(jnp.float32)
    start_ref[0, 0] = start
    x = x_ref[0].astype(jnp.float32)
    u_scr[...] = dt_ref[0] * x
    A_t = at_ref[...]

    def token(t, S):
        S = (jnp.exp(_row(dt_ref.at[0], t, N) * A_t) * S
             + _row(u_scr, t, N) * _across_tiles(bl_ref[0, t], lanes))
        y_scr[pl.ds(t, 1), :] = jnp.sum(
            S * _across_tiles(cl_ref[0, t], lanes), axis=0, keepdims=True)
        return S

    s_scr[tile] = jax.lax.fori_loop(0, x.shape[0], token, start).astype(
        s_scr.dtype)
    y_ref[0] = (y_scr[...] + d_ref[...] * x).astype(y_ref.dtype)


def _sel_bwd_kernel(x_ref, dt_ref, bl_ref, cl_ref, at_ref, d_ref, start_ref,
                    dy_ref, dx_ref, ddt_ref, dbl_ref, dcl_ref, da_ref,
                    h_scr, all_scr, u_scr, dy_scr, du_scr, dd_scr):
    """The chunks arrive last first. `all_scr` [Q, N, lanes]: the state in
    front of each of the chunk's tokens, formed again from the saved start;
    `h_scr` [tiles, N, lanes]: the cotangent of the state the chunk ends in."""
    tile, (N, lanes) = pl.program_id(2), at_ref.shape
    Q = x_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_scr[tile] = jnp.zeros((N, lanes), jnp.float32)

    @pl.when(tile == 0)
    def _():
        dbl_ref[...] = jnp.zeros(dbl_ref.shape, jnp.float32)
        dcl_ref[...] = jnp.zeros(dcl_ref.shape, jnp.float32)

    x = x_ref[0].astype(jnp.float32)
    dt = dt_ref[0]
    u_scr[...] = dt * x
    dy_scr[...] = dy_ref[0].astype(jnp.float32)
    A_t = at_ref[...]

    def decay_input_and_b(t):
        Bl = _across_tiles(bl_ref[0, t], lanes)
        return (jnp.exp(_row(dt_ref.at[0], t, N) * A_t),
                _row(u_scr, t, N) * Bl, Bl)

    def again(t, S):
        all_scr[t] = S
        a, own, _ = decay_input_and_b(t)
        return a * S + own

    jax.lax.fori_loop(0, Q, again, start_ref[0, 0])

    def token(i, carry):
        H, dA_t = carry
        t = Q - 1 - i
        before = all_scr[t]
        a, own, Bl = decay_input_and_b(t)
        dy_b = _row(dy_scr, t, N)
        G = dy_b * _across_tiles(cl_ref[0, t], lanes) + H
        dcl_ref[0, t] += _fold_tiles(dy_b * (a * before + own))
        dbl_ref[0, t] += _fold_tiles(G * _row(u_scr, t, N))
        du_scr[pl.ds(t, 1), :] = jnp.sum(G * Bl, axis=0, keepdims=True)
        H = a * G
        dlog = H * before                    # the cotangent of dt_t A
        dd_scr[pl.ds(t, 1), :] = jnp.sum(dlog * A_t, axis=0, keepdims=True)
        return H, dA_t + dlog * _row(dt_ref.at[0], t, N)

    H, dA_t = jax.lax.fori_loop(
        0, Q, token, (h_scr[tile], jnp.zeros((N, lanes), jnp.float32)))
    h_scr[tile] = H
    da_ref[0, 0] = dA_t
    du = du_scr[...]
    ddt_ref[0] = dd_scr[...] + du * x
    dx_ref[0] = (du * dt + d_ref[...] * dy_scr[...]).astype(dx_ref.dtype)


_SEL_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def _sel_specs(N: int, lanes: int, chunk_of):
    """BlockSpec makers for a grid (batch, chunk step, channel tile):
    [B, T, C] arrays, the lane-spread [B, T, N, 128] ones, the per-chunk
    [B, chunks, N, C] ones, and the two [., C] operands."""
    Q = SEL_CHUNK
    return (pl.BlockSpec((1, Q, lanes), lambda b, s, j: (b, chunk_of(s), j)),
            pl.BlockSpec((1, Q, N, _LANES),
                         lambda b, s, j: (b, chunk_of(s), 0, 0)),
            pl.BlockSpec((1, 1, N, lanes),
                         lambda b, s, j: (b, chunk_of(s), 0, j)),
            lambda rows: pl.BlockSpec((rows, lanes), lambda b, s, j: (0, j)))


def _spread(m):
    """[B, T, N] -> [B, T, N, 128] float32: each number in all 128 lanes."""
    return jnp.broadcast_to(m.astype(jnp.float32)[..., None],
                            (*m.shape, _LANES))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sel_kernel_forward(x, dt, A, Bm, Cm, D, interpret=False):
    Bsz, T, C = x.shape
    N, lanes = A.shape[1], _sel_lanes(C)
    tokens, spread, chunked, small = _sel_specs(N, lanes, lambda s: s)
    return pl.pallas_call(
        _sel_fwd_kernel,
        grid=(Bsz, T // SEL_CHUNK, C // lanes),
        in_specs=[tokens, tokens, spread, spread, small(N), small(1)],
        out_specs=[tokens, chunked],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((Bsz, T // SEL_CHUNK, N, C),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((C // lanes, N, lanes), _CARRY_DTYPE),
                        pltpu.VMEM((SEL_CHUNK, lanes), jnp.float32),
                        pltpu.VMEM((SEL_CHUNK, lanes), jnp.float32)],
        compiler_params=_SEL_PARAMS, interpret=interpret,
        name="selective_scan_fwd",
    )(x, dt, _spread(Bm), _spread(Cm), A.astype(jnp.float32).T,
      D.astype(jnp.float32)[None, :])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sel_kernel_backward(x, dt, A, Bm, Cm, D, starts, dy, interpret=False):
    Bsz, T, C = x.shape
    N, lanes, chunks = A.shape[1], _sel_lanes(C), T // SEL_CHUNK
    tokens, spread, chunked, small = _sel_specs(
        N, lanes, lambda s: chunks - 1 - s)
    rows = pltpu.VMEM((SEL_CHUNK, lanes), jnp.float32)
    dx, ddt, dBl, dCl, dA_t = pl.pallas_call(
        _sel_bwd_kernel,
        grid=(Bsz, chunks, C // lanes),
        in_specs=[tokens, tokens, spread, spread, small(N), small(1), chunked,
                  tokens],
        out_specs=[tokens, tokens, spread, spread, chunked],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct((Bsz, T, N, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((Bsz, T, N, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct(starts.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((C // lanes, N, lanes), jnp.float32),
                        pltpu.VMEM((SEL_CHUNK, N, lanes), jnp.float32),
                        rows, rows, rows, rows],
        compiler_params=_SEL_PARAMS, interpret=interpret,
        name="selective_scan_bwd",
    )(x, dt, _spread(Bm), _spread(Cm), A.astype(jnp.float32).T,
      D.astype(jnp.float32)[None, :], starts, dy)
    dD = jnp.sum(dy.astype(jnp.float32) * x.astype(jnp.float32), axis=(0, 1))
    return (dx, ddt.astype(dt.dtype),
            jnp.sum(dA_t, axis=(0, 1)).T.astype(A.dtype),
            jnp.sum(dBl, axis=-1).astype(Bm.dtype),
            jnp.sum(dCl, axis=-1).astype(Cm.dtype), dD.astype(D.dtype))


@jax.custom_vjp
def _selective_scan(x, dt, A, Bm, Cm, D):
    return _selective_scan_fwd(x, dt, A, Bm, Cm, D)[0]


def _selective_scan_fwd(x, dt, A, Bm, Cm, D):
    forward = (_sel_kernel_forward if selective_kernels_eligible(x, A)
               else _sel_xla_forward)
    y, starts = forward(x, dt, A, Bm, Cm, D)
    return y, (x, dt, A, Bm, Cm, D, starts)


def _selective_scan_bwd(saved, dy):
    backward = (_sel_kernel_backward if selective_kernels_eligible(*saved[:3:2])
                else _sel_xla_backward)
    return backward(*saved, dy.astype(saved[0].dtype))


_selective_scan.defvjp(_selective_scan_fwd, _selective_scan_bwd)

_sel_bytes: dict = {}      # an op's name in its Program -> (bytes, saved)


def selective_scan_bytes(batch: int, T: int, C: int, N: int, itemsize: int):
    """(the bytes of `selective_scan`'s operands and results over one forward
    and one backward, the bytes of the chunk starts kept between them), from
    its static shapes: forward reads x, B, C (`itemsize` an element) and dt
    (float32) and writes y; backward reads those and dy and writes their
    five gradients; A [C, N] and D [C] float32 are read twice and their
    gradients written once. What an implementation reads again or keeps in
    between (the starts, forward and backward) counts nothing here."""
    tokens = batch * T
    forward = tokens * ((2 * C + 2 * N) * itemsize + 4 * C)
    backward = tokens * ((4 * C + 4 * N) * itemsize + 8 * C)
    return (forward + backward + 3 * 4 * (C * N + C),
            batch * -(-T // SEL_CHUNK) * C * N * 4)


def _count_selective(path: str, x, A, name: str) -> None:
    """One scan traced: its path, and its bytes a step (gauges: the sum over
    the ops traced so far, an op traced again counted once)."""
    from ..obs import metrics

    reg = metrics.registry()
    reg.counter_inc(
        "pt_selective_scan_dispatch_total",
        help="Mamba-1 selective scans traced, by the formulation that runs "
             "them",
        labels={"path": path})
    B, T, C = x.shape
    _sel_bytes[name] = selective_scan_bytes(B, T, C, A.shape[1],
                                            x.dtype.itemsize)
    reg.gauge(
        "pt_selective_scan_bytes",
        lambda: sum(b for b, _ in _sel_bytes.values()),
        help="bytes of the selective scans' operands and results over one "
             "forward and one backward, from the static shapes of the ops "
             "traced so far")
    reg.gauge(
        "pt_selective_scan_saved_state_bytes",
        lambda: sum(s for _, s in _sel_bytes.values()),
        help="bytes of the chunk-start states the selective scans keep "
             "between forward and backward ([B, T / Q, N, C] float32 an op)")


def selective_scan(x, dt, A, Bm, Cm, D, name: str = ""):
    """Mamba-1's scan: x [B, T, C], dt [B, T, C] float32 and positive (after
    its softplus), A [C, N] negative, Bm / Cm [B, T, N], D [C] -> y [B, T, C]
    in x's dtype (the recurrence above this section). Differentiable in all
    six; the path is chosen here, when the op is traced, and counted in
    `pt_selective_scan_dispatch_total{path}` with the op's bytes beside it.
    `name`: what the registry's byte counts know this op by."""
    _count_selective(
        "pallas" if selective_kernels_eligible(x, A) else "xla_chunked", x, A,
        name)
    return _selective_scan(x, dt, A, Bm, Cm, D)


@jax.custom_vjp
def silu_gate(value, gate):
    """value * silu(gate) in value's dtype, float32 inside; the backward
    keeps the two operands and nothing of their shape beside them."""
    g = gate.astype(jnp.float32)
    return (value.astype(jnp.float32) * (g * jax.nn.sigmoid(g))).astype(
        value.dtype)


def _silu_gate_fwd(value, gate):
    return silu_gate(value, gate), (value, gate)


def _silu_gate_bwd(saved, dy):
    value, gate = saved
    v, g, dy = (a.astype(jnp.float32) for a in (value, gate, dy))
    s = jax.nn.sigmoid(g)
    return ((dy * g * s).astype(value.dtype),
            (dy * v * (s * (1.0 + g * (1.0 - s)))).astype(gate.dtype))


silu_gate.defvjp(_silu_gate_fwd, _silu_gate_bwd)


@register_op("silu_gate")
def silu_gate_kernel(ctx):
    """X * silu(Gate), shaped like X (`layers.silu_gate`). Without a Gate, X
    is [gate | value] side by side along its last axis (a fused
    up-projection's output) and Out is half as wide."""
    x = ctx.input("X")
    if ctx.has_input("Gate"):
        value, gate = x, ctx.input("Gate")
    else:
        half = x.shape[-1] // 2
        gate, value = x[..., :half], x[..., half:]
    ctx.set_output("Out", silu_gate(value, gate))


def mamba1_mixer(h, in_w, conv_w, conv_b, x_w, dt_w, dt_b, A_log, D, out_w,
                 *, name: str = ""):
    """h [B, T, d] -> (out [B, T, d], the scan's output y [B, T, d_in] in front
    of the gate), both in the projections' dtype (the amp dtype where the
    caller cast them). in_w [d, 2 d_in] gives [x | z]; conv_w [K, d_in],
    conv_b [d_in]; x_w [d_in, R + 2 N] gives [r | B | C]; dt_w [R, d_in],
    dt_b [d_in]; A_log [d_in, N]; D [d_in]; out_w [d_in, d]. One checkpoint
    from the in-projection's output to the out-projection's input, as
    `mamba2_mixer` has it: the backward keeps [x | z] and forms the conv, the
    two small projections, dt and the scan's forward again."""
    cd = in_w.dtype
    d_in, N = A_log.shape
    R = dt_w.shape[0]

    def between(xz, conv_w, conv_b, x_w, dt_w, dt_b, A_log, D):
        with jax.named_scope("conv"):
            x = causal_conv_silu(xz[..., :d_in], conv_w, conv_b)
        with jax.named_scope("dt_bc"):
            rbc = jnp.dot(x, x_w, preferred_element_type=jnp.float32)
            dt = jax.nn.softplus(
                jnp.dot(rbc[..., :R].astype(cd), dt_w,
                        preferred_element_type=jnp.float32) + dt_b)
            Bm, Cm = (rbc[..., R:R + N].astype(cd),
                      rbc[..., R + N:].astype(cd))
        with jax.named_scope("scan"):
            y = selective_scan(x, dt, -jnp.exp(A_log.astype(jnp.float32)),
                               Bm, Cm, D, name=name)
        with jax.named_scope("gate"):
            return silu_gate(y, xz[..., d_in:]), y

    with jax.named_scope("in_proj"):
        xz = jnp.dot(h.astype(cd), in_w,
                     preferred_element_type=jnp.float32).astype(cd)
    gated, y = jax.checkpoint(between)(xz, conv_w, conv_b, x_w, dt_w, dt_b,
                                       A_log, D)
    with jax.named_scope("out_proj"):
        return jnp.dot(gated, out_w,
                       preferred_element_type=jnp.float32).astype(cd), y


@register_op("mamba1_mixer")
def mamba1_mixer_kernel(ctx):
    """Program-IR face: X [B, T, d]; InW, ConvW, ConvB, XW, DtW, DtB, ALog,
    D, OutW as `mamba1_mixer` takes them. Out shaped like X and Memory [B, T,
    d_in] (the scan's output in front of the gate, for the layers that read
    it), in the compute dtype: under amp the four projection matrices are
    cast down, the taps, dt's bias, A_log and D stay float32."""
    in_w, x_w, dt_w, out_w = amp.cast_inputs(
        ctx, ctx.input("InW"), ctx.input("XW"), ctx.input("DtW"),
        ctx.input("OutW"))
    out, memory = mamba1_mixer(
        ctx.input("X"), in_w, ctx.input("ConvW"), ctx.input("ConvB"), x_w,
        dt_w, ctx.input("DtB"), ctx.input("ALog"), ctx.input("D"), out_w,
        name=ctx.op.outputs["Out"][0])
    ctx.set_output("Out", out)
    ctx.set_output("Memory", memory)


@register_op("mamba1_init")
def mamba1_init_kernel(ctx):
    """Startup op: A_log [d_in, N] with A_log[c, n] = log(n + 1) (Mamba-1's
    S4D-real start: A = -(1 .. N) in every channel)."""
    d_in, N = ctx.attr("shape")
    ctx.set_output("Out", jnp.broadcast_to(
        jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (d_in, N)))
