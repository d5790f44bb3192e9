"""Flash attention: fused block-wise attention for long sequences.

Reference lineage: the reference (2017) predates transformer attention —
its fused-kernel philosophy lives in cuda/include/hl_lstm.h:42; this is
the modern long-context analogue (SURVEY.md §5.7's "seam for future
CP/ring-attention"). XLA's unfused attention materializes the [B, H, T, T]
score matrix in HBM (16 GB at T=32k bf16 — impossible); flash attention
streams K/V blocks through VMEM with an online softmax, O(T) memory.

Compute path: on TPU, Pallas kernels of this repo's own (forward, and a
backward behind a `custom_vjp`) that read the packed `[B, T, E]` projections
the `flash_attention` op receives, as they are; anywhere else, and for the
shapes the kernels refuse, the jnp reference formulation. One mask beside
`causal`: a `window` W (position i reads the W keys i - W < j <= i), which
the same kernels take as a second, lower diagonal. `[B, T, H, D]`
callers (the framework's sequence-parallel convention,
parallel/ring_attention.py) reach the same kernels through a free reshape.

Why packed (PERF.md section 6, PR 28): the library kernel this module used
to call wants `[B, H, T, D]`, and XLA did not fold the transposes into the
kernel's operands. On gpt2-small 28.3 ms of the op's 68.6 ms a step were
not kernels at all: transposes and copies of `[12, 12, 1024, 64]` arrays
(64-wide minor dimension, half a lane tile) and broadcasts of the softmax
statistics to 128 and 512 lanes.

`paddle_tpu.parallel.ulysses_attention` routes its per-device full-
sequence attention through here, so the SP path gets the fused kernel
for free.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.registry import register_op

NEG_INF = -1e30  # large-finite mask fill (inf would NaN the softmax grads)


def _repeat_kv(q, k, v):
    """K and V [B, T, KV, D] repeated to Q's H heads (query head j reads K/V
    head j // (H / KV)); as they are where KV == H."""
    group = q.shape[2] // k.shape[2]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def scaled_dot_product_attention(q, k, v, causal: bool = False,
                                 window: int = 0):
    """[B, T, H, D] attention, plain jnp — the numerical oracle for the
    flash kernel AND for ring/Ulysses sequence parallelism (re-exported
    by paddle_tpu.parallel; single implementation lives here). K and V may
    have fewer heads than Q (grouped-query attention): query head j reads
    K/V head j // (H / KV), here by repeating them. `window` W > 0 (with
    `causal`): position i reads the keys i - W < j <= i and no earlier one."""
    d = q.shape[-1]
    k, v = _repeat_kv(q, k, v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        ahead = jnp.arange(Tq)[:, None] - jnp.arange(Tk)[None, :]   # i - j
        mask = ahead >= 0
        if window:
            mask &= ahead < window
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


_reference = scaled_dot_product_attention


def _pair_view(x):
    """[B, T, H, D] -> [B, T, H / 2, 2 D]: heads 2p and 2p + 1 side by side
    as one head of twice the width (a free reshape)."""
    B, T, H, D = x.shape
    return x.reshape(B, T, H // 2, 2 * D)


def paired_attention(q, k, v, causal: bool = False, window: int = 0):
    """Attention whose heads come in PAIRS over one value twice a head wide
    (differential attention's, Ye et al. 2024), plain jnp: the XLA
    formulation and the oracle of the kernels' pair form. q [B, T, H, D], k
    and v [B, T, KV, D]; query pair p = heads (2p, 2p + 1) reads K/V pair
    p // (H / KV). -> (A_1 [v_1 | v_2], A_2 [v_1 | v_2]), each [B, T, H / 2,
    2 D], A_j = softmax(q_j k_j^T / sqrt(D)) under the mask: two dense
    softmaxes a pair."""
    D = q.shape[-1]
    qp, kp, vp = _pair_view(q), _pair_view(k), _pair_view(v)
    return tuple(
        scaled_dot_product_attention(qp[..., j * D:(j + 1) * D],
                                     kp[..., j * D:(j + 1) * D], vp, causal,
                                     window) for j in (0, 1))


def _shapes_flash_ok(q, k, window: int = 0) -> bool:
    """Backend-independent shape rules (separately testable): 128-aligned
    q AND kv sequence lengths (the kernels' blocks divide them), a head dim
    a lane block holds whole (two heads at 64, one at 128, one over two lane
    tiles at 256: latent attention's 192 + 64, 20 heads at T 8192 on the chip
    since PR 37), and heads x D a whole number of lane blocks (an odd head
    count at D 64 leaves half a block: XLA keeps it). Fewer K/V heads than Q
    heads: the index maps share one K/V lane block among a group of query
    heads, so a lane block has to be one head (D 128 or 256; at D 64
    `flash_attention` repeats K and V first, or, in its pair form, hands the
    rules each PAIR of heads as one head of 128 lanes: `_pair_view`). A
    `window` is a whole number
    of 128-row tiles, so the lower diagonal enters a block at a tile's edge
    as the upper one does (any block size divides into them)."""
    Tq, H, D = q.shape[1:]
    Tk, KV = k.shape[1:3]
    return (Tq % 128 == 0 and Tk % 128 == 0 and D in (64, 128, 256)
            and window % 128 == 0 and (H * D) % 128 == 0
            and (KV == H or (D >= _LANES and H % KV == 0)))


# Dispatch policy: from T=1024 up the fused kernels take the job (the
# range every benchmark configuration sits in: PERF.md section 5 has their
# device time beside the rest of the step). Below it, or when the shape
# rules fail, XLA keeps the job; the score-bytes rule stays as the
# memory-capability route for shorter sequences whose [B, H, Tq, Tk] scores
# XLA could not hold (it stops compiling around several GB of scores).
_FLASH_MIN_T = 1024
_SCORE_BYTES_THRESHOLD = 1.5e9


def _prefers_flash(q, k) -> bool:
    import numpy as np

    from . import mesh_dispatch

    B, Tq, H, _ = q.shape
    Tk = k.shape[1]
    if Tq >= _FLASH_MIN_T and Tk >= _FLASH_MIN_T:
        return True  # the kernels' range
    # the shard_map'd kernel runs at the PER-SHARD batch (B/dp under a
    # mesh), so the score-buffer rule must see that batch too — same
    # eligibility discipline as the decoder/RNN kernels. local_batch
    # returns 0 when dp does not divide B; flash_attention falls back
    # to the XLA formulation for that case anyway.
    Bl = mesh_dispatch.local_batch(B)
    if Bl == 0:
        return False
    # scores inherit the input dtype in the reference formulation: f32
    # inputs double the buffer vs bf16
    itemsize = np.dtype(q.dtype).itemsize
    return Bl * H * Tq * Tk * itemsize > _SCORE_BYTES_THRESHOLD


def flash_eligible(q, k=None, window: int = 0) -> bool:
    k = q if k is None else k
    return (
        jax.default_backend() == "tpu"
        and _shapes_flash_ok(q, k, window)
        and _prefers_flash(q, k)
    )


class FlashBlocks(NamedTuple):
    """Rows of Q and rows of K/V a kernel step holds."""
    block_q: int
    block_k: int


def _v5e_block_sizes(Tq: int, Tk: int, dtype=None) -> FlashBlocks:
    """Block choice for the TPU kernels: tune/space.py's rule for the
    flash family (`flash_default`), or what a sweep forced."""
    import numpy as np

    from ..tune import space

    cfg = space.pick(
        "flash_attention", {"Tq": Tq, "Tk": Tk},
        np.dtype(dtype).name if dtype is not None else "bfloat16")
    if cfg is None:
        # _flash_kernel is gate-free (benchmarks call it directly)
        raise ValueError("flash kernel requires 128-aligned sequences, "
                         f"got Tq={Tq} Tk={Tk}")
    return FlashBlocks(int(cfg["block_q"]), int(cfg["block_k"]))


# ------------------------------------------------------------------ kernels
# The kernels work on the packed layout the op receives: Q, K, V and the
# output are [B, T, E], E = heads x D, and a BlockSpec of (1, rows, W) picks
# a lane block of W = max(128, D) lanes: two heads at D 64 (lanes 0-63 and
# 64-127), one head at 128, one head over two lane tiles at 256. Loads and
# stores are whole lane tiles; nothing is transposed in HBM. Two heads in a
# block are separated on the MXU: a contraction over all W lanes with the
# other head's lanes zeroed is that head's contraction (at the price a
# 64-wide contraction has on a 128-wide MXU anyway), and P V over all W value
# lanes followed by a lane select is that head's output.
#
# The PAIR form (`pair`, heads of 64; differential attention's): the two heads
# of a lane block are a pair that shares ONE value of 128 lanes, the block of
# V as it stands (`[v_1 | v_2]`: heads 2p and 2p + 1 are neighbours in the
# packed projection). The scores are the same; what differs is behind the
# softmax: head j's P V over all W lanes IS head j's output, kept whole (an
# accumulator and an output a head, no lane select), and the backward
# contracts dO_j, the block's width, with the unmasked V and sums the two
# heads' P^T dO into dV. A 64-wide value costs the 128-wide MXU what a
# 128-wide one costs, so one launch in this form does what four launches over
# the even and the odd heads did (PERF.md section 6, PR 58: 31.5 -> 16.4 ms
# forward + backward a layer at T 8192). A K/V pair serves its group's query
# pairs through the index map, as a K/V head of 128 lanes serves its group.
#
# Masks: `causal` (column <= row) and, with it, `window` W (column > row - W):
# two diagonals W apart. A (q block, k block) pair wholly outside the band
# between them is neither fetched (the index maps clamp to the band's first
# and last block: `_k_range`, `_q_range`) nor computed (`_on_blocks`); one
# inside runs bare, whole; one a diagonal crosses is computed as two strips
# (`_strips`): the half of its rows that reads the whole width of the block
# (or, two heads a lane block, the half of its columns that every row reads),
# and the triangle alone in the other half; the quarter of the block that
# holds no pair inside the band is not computed. Both strips run under the
# mask, each traced once, at a place the kernel works out from the block's:
# three copies of the step a kernel, whatever the sequence and with or
# without a window (a loop over smaller sub-tiles was measured and lost on
# the chip: PERF.md section 6, PR 46). A row whose window starts right of the
# first strip it visits sees only masked scores there: what it sums meanwhile
# is wiped by the first real one (alpha = exp(-1e30 - m) is 0 exactly), and
# every row has one: its own position.
#
# The softmax statistic the backward needs is one float32 a (row, head): the
# log-sum-exp, kept as [B, Tq, 128 x ceil(heads / 128)] with head h in lane
# h, never broadcast. sum(o * do) is computed in the backward kernel.

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b
_LANES = 128
# one fused backward pass keeps dQ for a whole sequence in VMEM (float32
# accumulator and the output block: 10 bytes a row and lane of a lane block);
# beyond this many (query row, lane) elements dQ gets a pass of its own. 20
# MiB: 8192 rows of a 256-lane block (latent attention's heads), 16 384 rows of
# a 128-lane one (heads of 64 and 128)
_FUSED_BWD_MAX_ELEMENTS = 8192 * 256


def _head_lanes(width: int, D: int):
    """For each head of a lane block, the [1, width] mask of its lanes
    (None where the block is one head)."""
    if width == D:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return [(lane >= j * D) & (lane < (j + 1) * D) for j in range(width // D)]


def _only(x, lanes):
    """x with every other head's lanes zeroed."""
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _merge(parts, masks):
    """One [rows, width] array from each head's [rows, width]: head j's
    lanes from parts[j]."""
    out = parts[-1]
    for part, lanes in zip(parts[-2::-1], masks[-2::-1]):
        out = jnp.where(lanes, part, out)
    return out


def _causal_keep(bq, bk, q0, k0, window=0):
    """[bq, bk] mask of the pairs inside the band, rows from q0 and columns
    from k0: 0 <= row - column (< window). The offsets meet the iotas'
    difference as one scalar, so a mask costs a compare a diagonal."""
    ahead = (jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
             - jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
    off = k0 - q0
    if not window:
        return ahead >= off
    return jnp.logical_and(ahead >= off, ahead < off + window)


def _block_kind(q0, bq, k0, bk, window):
    """(inside, crosses) of the (q block, k block) at rows q0, columns k0:
    some pair is inside the band; one of the two diagonals crosses it (the
    upper one: column == row; with a `window`, the lower one: column == row
    - window + 1). Operators only: Python ints, numpy and traced values."""
    crosses = k0 + bk - 1 > q0          # some column is right of some row
    inside = k0 <= q0 + bq - 1          # some column is at or left of a row
    if window:
        # the last column is within the window of the first row; the first
        # column is left of the window of the last row
        inside = inside & (k0 + bk - 1 > q0 - window)
        crosses = crosses | (k0 < q0 + bq - window)
    return inside, crosses


def _in_strips(bq: int, bk: int, window: int) -> bool:
    """Whether a crossed block is computed as two strips: square blocks (so
    the causal diagonal is a block's own) and a window of whole blocks (so
    the lower diagonal is another block's own, and no block holds both).
    Anything else computes the whole crossed block under the mask."""
    return bq == bk and bq % (2 * _LANES) == 0 and window % bk == 0


def _strips(q0, k0, bq, window, columns=False, where=jnp.where):
    """The two strips of the crossed square block at rows q0, columns k0, h
    = bq / 2: ((rows, columns) of the h x h triangle, where the long strip
    starts). By rows (the long strip h x bq: half the rows, every column):
    under the causal diagonal (k0 == q0) the upper rows read the left
    triangle alone and the lower rows all columns; under a window's lower
    diagonal (k0 == q0 - window) the lower rows read the right triangle alone
    and the upper rows all columns. By `columns` (the long strip bq x h:
    every row, half the columns) the same two regions cut the other way: the
    left columns are read by every row and the right ones by the lower rows'
    triangle alone, and the reverse under a lower diagonal. Either way the h
    x h quarter left out holds no pair inside the band. Without a window the
    places are Python ints."""
    h = bq // 2
    lower = where(k0 < q0, h, 0) if window else 0   # h: the lower diagonal's
    if columns:
        return (h - lower, h - lower), lower
    return (lower, lower), h - lower


def _on_blocks(causal, window, q0, bq, k0, bk, step, columns=False):
    """Run `step(r0, rn, c0, cn, diag)` (rows [r0, r0 + rn) by columns [c0,
    c0 + cn) of the block pair, `diag`: under the mask) for this (q block, k
    block): not at all where the causal rule or the window empties it; bare
    over the whole block where neither diagonal crosses it; where one does,
    as `_strips`' two masked strips (the long one by `columns` or by rows),
    or whole under the mask."""
    whole = lambda diag: step(0, bq, 0, bk, diag)  # noqa: E731
    if not causal:
        whole(False)
        return
    inside, crosses = _block_kind(q0, bq, k0, bk, window)

    def crossed():
        if not _in_strips(bq, bk, window):
            whole(True)
            return
        h = bq // 2
        (r0, c0), long = _strips(q0, k0, bq, window, columns)
        step(r0, h, c0, h, True)
        if columns:
            step(0, bq, long, h, True)
        else:
            step(long, h, 0, bk, True)

    pl.when(jnp.logical_and(inside, crosses))(crossed)
    # crossed by neither diagonal and not above the upper one: inside
    pl.when(jnp.logical_not(crosses))(lambda: whole(False))


def _span(start, size, whole):
    """The rows (or columns) [start, start + size) of a block of `whole`:
    all of it, or a slice at a multiple of its size."""
    if size == whole:
        return slice(None)
    if isinstance(start, int):
        return pl.ds(start, size)
    return pl.ds(pl.multiple_of(start, size), size)


def _stat_lane(stats, head):
    """[rows, 1]: lane `head` (mod 128) of a [rows, 128] statistics block."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    return jnp.sum(jnp.where(lane == head % _LANES, stats, 0.0), axis=1,
                   keepdims=True)


def _across(x, width):
    """A [rows, 128] array whose lanes all hold the row's value, at `width`
    lanes (a multiple of 128: the same vector registers again)."""
    return x if width == _LANES else jnp.tile(x, (1, width // _LANES))


def _scaled(x, scale):
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _exact_in_bf16(scale: float) -> bool:
    """A power of two: scaling a Q or K block by it rounds nothing, so the
    multiply leaves the [block_q, block_k] scores for a [rows, width] block
    (1/sqrt(D) at D 64 and 256; at 128 the scores are scaled, in float32)."""
    return math.frexp(scale)[0] == 0.5


# The KEEP operand (`keep`, a learned sparse attention's: ops/
# sparse_attention_ops.py): beside the two diagonals, which are functions of a
# (row, key)'s indices alone, a kept set is DATA, one bit a (row, key): int32
# [B, Tq, 128 x ceil(Tk / 4096)], lane block w of a row holding its keys
# 4096 w .. 4096 w + 4095, key 128 u + c of them in bit u of lane c, so a
# [rows, 128] block of words opens into thirty-two [rows, 128] key tiles by a
# shift and a compare, with no relayout. A (row, key) pair is inside the mask
# where it is inside the band AND its bit is set; a block the diagonals leave
# bare runs under its bits. Without the operand every trace is what it was.
KEEP_TILES = 32         # key tiles of 128 lanes a lane block of words holds


def _kept_here(words, tile0, cn):
    """[rows, cn] bool: the bits of the cn / 128 key tiles from `tile0` on
    (a traced scalar; the tiles lie inside one lane block of words), from
    `words` [rows, 128] int32."""
    return jnp.concatenate(
        [(words >> ((tile0 + u) % KEEP_TILES)) & 1 for u in range(cn // _LANES)],
        axis=1) != 0


def _operand(keep):
    """The keep operand as the launchers' trailing operands: none, or it."""
    return () if keep is None else (keep,)


def _keep_lane(bk):
    """Index-map helper: the lane block of the keep operand's words that
    holds k block `ki`'s bits."""
    return lambda ki: ki * (bk // _LANES) // KEEP_TILES


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, D, scale, causal, window,
                pair=False, kept=False):
    # grid (B, q blocks, lane blocks, k blocks): the statistics block of a
    # (batch, q block) stays in VMEM while the lane blocks take their turns.
    # The running max and sum of a head are kept across 128 lanes, every lane
    # the row's value: what the VPU broadcasts for nothing.
    # `pair`: the block's value is whole (`[v_1 | v_2]`), so head j's P V over
    # all W lanes is head j's output: an accumulator and an output a head
    # (acc_sc [heads, bq, W]) where two plain heads share one by their lanes
    bq, W = q_ref.shape[1:]
    bk = k_ref.shape[1]
    qi, hb, ki = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    masks = _head_lanes(W, D)
    heads = range(len(masks))
    n = W // D if pair else 1       # outputs; no statistics: no lse_ref
    if kept:
        keep_ref, *rest = rest
    o_refs, (*lse_ref, q_sc, m_sc, l_sc, acc_sc) = rest[:n], rest[n:]
    head0 = hb * len(masks)
    fold = _exact_in_bf16(scale)

    @pl.when(ki == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)
        for j in heads:     # each head's Q, alone in its lanes: once a q block
            qj = _only(q_ref[0], masks[j])
            q_sc[j] = _scaled(qj, scale) if fold else qj

    def step(r0, rn, c0, cn, diag):
        rows = _span(r0, rn, bq)
        cols = _span(c0, cn, bk)
        k, v = k_ref[0, cols, :], v_ref[0, cols, :]
        keep = (_causal_keep(rn, cn, qi * bq + r0, ki * bk + c0, window)
                if diag else None)
        if kept:
            bits = _kept_here(keep_ref[0, rows, :], (ki * bk + c0) // _LANES,
                              cn)
            keep = bits if keep is None else jnp.logical_and(keep, bits)
        alphas, pvs = [], []
        for j in heads:
            s = jax.lax.dot_general(q_sc[j, rows, :], k, _NT,
                                    preferred_element_type=jnp.float32)
            if not fold:
                s = s * scale
            if keep is not None:
                s = jnp.where(keep, s, NEG_INF)
            m_prev = m_sc[j, rows, :]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - _across(m_next, cn))
            alpha = jnp.exp(m_prev - m_next)
            l_sc[j, rows, :] = (alpha * l_sc[j, rows, :]
                                + jnp.sum(p, axis=1, keepdims=True))
            m_sc[j, rows, :] = m_next
            alphas.append(_across(alpha, W))
            pvs.append(jnp.dot(p.astype(v.dtype), v,
                               preferred_element_type=jnp.float32))
            if pair:
                acc_sc[j, rows, :] = alphas[j] * acc_sc[j, rows, :] + pvs[j]
        if not pair:
            acc_sc[rows, :] = (_merge(alphas, masks) * acc_sc[rows, :]
                               + _merge(pvs, masks))

    # two heads a lane block: a crossed block's long strip runs down the rows
    # (0.574 -> 0.562 ms forward, 0.893 -> 0.884 backward at gpt2-small's
    # shape on the chip; one head a block reads the same either way or worse)
    _on_blocks(causal, window, qi * bq, bq, ki * bk, bk, step,
               columns=len(masks) > 1)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _():
        if pair:
            for j, o_ref in enumerate(o_refs):
                o_ref[0] = (acc_sc[j] / _across(l_sc[j], W)).astype(
                    o_ref.dtype)
        else:
            o_ref, = o_refs
            l = _merge([_across(l_sc[j], W) for j in heads], masks)
            o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)
        for ref in lse_ref:
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
            stats = jnp.where(head0 % _LANES == 0, 0.0, ref[0])
            for j in heads:
                stats = jnp.where(lane == (head0 + j) % _LANES,
                                  m_sc[j] + jnp.log(l_sc[j]), stats)
            ref[0] = stats


def _bwd_kernel(q_ref, k_ref, v_ref, *rest, D, scale, causal, window, want,
                pair=False, kept=False):
    """`want` "dkv": grid (B, lane blocks, k blocks, q blocks), dK and dV
    summed over the q blocks; "dq": grid (.., q blocks, k blocks), dQ summed
    over the k blocks; "all": the dkv grid, and dQ summed for the whole
    sequence in a VMEM accumulator beside it, so the scores are recomputed
    once. `pair`: an O and a dO a head, each the block's width, and V read
    whole: dP_j = dO_j V^T and sum(O_j dO_j) over all W lanes, dV the sum of
    the heads' P_j^T dO_j (no V scratch, no lane select); dK and dQ are head
    j's in head j's lanes either way."""
    bq, W = q_ref.shape[1:]
    bk = k_ref.shape[1]
    hb = pl.program_id(1)
    n = W // D if pair else 1
    o_refs, do_refs, lse_ref, rest = (rest[:n], rest[n:2 * n], rest[2 * n],
                                      rest[2 * n + 1:])
    if kept:
        keep_ref, *rest = rest
    if want == "dq":
        dq_ref, dq_sc = rest
        qi, ki = pl.program_id(2), pl.program_id(3)
    else:
        ki, qi = pl.program_id(2), pl.program_id(3)
        dk_ref, dv_ref, *rest = rest
        if want == "all":
            dq_ref, *rest, dq_sc = rest
        k_sc, *v_sc, dk_sc, dv_sc = rest
        v_sc = v_sc[0] if v_sc else None      # a pair reads V whole
    nq = pl.num_programs(2 if want == "dq" else 3)
    nk = pl.num_programs(3 if want == "dq" else 2)
    masks = _head_lanes(W, D)
    heads = range(len(masks))
    head0 = hb * len(masks)
    fold = _exact_in_bf16(scale)
    # the rows of dQ's accumulator this step adds to
    rows = pl.ds(pl.multiple_of(qi * bq, bq), bq) if want == "all" \
        else slice(None)

    def k_alone(j, cols=slice(None)):
        """Head j's K alone in its lanes (scaled where that is exact): the
        contraction with Q then sees that head only."""
        kj = _only(k_ref[0, cols, :], masks[j])
        return _scaled(kj, scale) if fold else kj

    def v_alone(j, cols=slice(None)):
        """Head j's V alone in its lanes, for the contraction with dO (a
        pair's dO_j meets V whole)."""
        return _only(v_ref[0, cols, :], masks[j])

    if want != "dq":
        @pl.when(qi == 0)
        def _():
            dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
            dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)
            for j in heads:      # once a k block: the q blocks are inside
                if pair:
                    k_sc[j] = k_alone(j)
                else:
                    k_sc[j], v_sc[j] = k_alone(j), v_alone(j)
    if want != "dkv":
        @pl.when(ki == 0)
        def _():
            dq_sc[rows, :] = jnp.zeros((bq, W), jnp.float32)

    def step(r0, rn, c0, cn, diag):
        local = _span(r0, rn, bq)
        q, dos = q_ref[0, local, :], [ref[0, local, :] for ref in do_refs]
        stats = lse_ref[0, local, :]
        o_dos = [ref[0, local, :].astype(jnp.float32) * do.astype(jnp.float32)
                 for ref, do in zip(o_refs, dos)]
        # the rows of dQ's accumulator these add to
        into = (pl.ds(pl.multiple_of(qi * bq + r0, rn), rn) if want == "all"
                else local)
        cols = _span(c0, cn, bk)
        keep = (_causal_keep(rn, cn, qi * bq + r0, ki * bk + c0, window)
                if diag else None)
        if kept:
            bits = _kept_here(keep_ref[0, local, :], (ki * bk + c0) // _LANES,
                              cn)
            keep = bits if keep is None else jnp.logical_and(keep, bits)
        v_whole = v_ref[0, cols, :] if pair else None
        dks, dvs, dqs = [], [], []
        for j in heads:
            do = dos[j if pair else 0]
            kj = k_alone(j, cols) if want == "dq" else k_sc[j, cols, :]
            if pair:
                vj = v_whole
            elif want == "dq":
                vj = v_alone(j, cols)
            else:
                vj = v_sc[j, cols, :]
            s = jax.lax.dot_general(q, kj, _NT,
                                    preferred_element_type=jnp.float32)
            if not fold:
                s = s * scale
            if keep is not None:
                s = jnp.where(keep, s, NEG_INF)
            p = jnp.exp(s - _stat_lane(stats, head0 + j))
            dp = jax.lax.dot_general(do, vj, _NT,
                                     preferred_element_type=jnp.float32)
            delta = jnp.sum(o_dos[j] if pair else _only(o_dos[0], masks[j]),
                            axis=1, keepdims=True)
            ds = p * (dp - delta)
            if not fold:
                ds = ds * scale
            ds = ds.astype(q.dtype)
            if want != "dq":
                dvs.append(jax.lax.dot_general(
                    p.astype(do.dtype), do, _TN,
                    preferred_element_type=jnp.float32))
                dks.append(jax.lax.dot_general(
                    ds, q, _TN, preferred_element_type=jnp.float32))
            if want != "dkv":
                dqs.append(jnp.dot(ds, k_ref[0, cols, :],
                                   preferred_element_type=jnp.float32))
        if want != "dq":
            dk_sc[cols, :] += _merge(dks, masks)
            dv_sc[cols, :] += sum(dvs[1:], dvs[0]) if pair \
                else _merge(dvs, masks)
        if want != "dkv":
            dq_sc[into, :] += _merge(dqs, masks)

    _on_blocks(causal, window, qi * bq, bq, ki * bk, bk, step,
               columns=len(masks) > 1)

    # s = scale x q k^T, so dQ and dK carry the scale too: where it is a
    # power of two it is applied once, to the sums, which rounds the same;
    # else to dS before its rounding to the matmul's dtype (after it, dQ and
    # dK read 0.0029 of their rms off the float32 oracle on the chip at D 128
    # where the library's order reads 0.0021: PERF.md section 6, PR 28)
    after = scale if fold else 1.0
    if want != "dq":
        @pl.when(qi == nq - 1)
        def _():
            dk_ref[0] = (dk_sc[...] * after).astype(dk_ref.dtype)
            dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)
    if want != "dkv":
        @pl.when(ki == nk - 1)
        def _():
            dq_ref[0, rows, :] = (dq_sc[rows, :] * after).astype(dq_ref.dtype)


def _geometry(q, k, heads):
    B, Tq, E = q.shape
    D = E // heads
    return B, Tq, k.shape[1], E, D, max(_LANES, D)


def _kv_lane(q, k):
    """Index-map helper: the K/V lane block a query lane block reads. K and
    V packed narrower than Q hold fewer heads (grouped-query attention, one
    head a lane block: `_shapes_flash_ok`; or one PAIR of heads of 64 a lane
    block, in the pair form): a group of E_q / E_kv consecutive query heads
    (or pairs) shares each."""
    group = q.shape[2] // k.shape[2]
    return (lambda hb: hb) if group == 1 else (lambda hb: hb // group)


def _k_range(causal, window, bq, bk):
    """Index map helper: step `ki` of q block `qi` names a k block the q
    block reads: no later than the last one under the causal rule and, with
    a `window`, no earlier than the first one inside it. An empty step names
    its neighbour's block again, so nothing is fetched for it."""
    if not causal:
        return lambda qi, ki: ki

    def kmap(qi, ki):
        ki = jnp.minimum(ki, ((qi + 1) * bq - 1) // bk)
        if window:      # the block of column q0 - window + 1
            ki = jnp.maximum(ki, jnp.maximum(qi * bq - window + 1, 0) // bk)
        return ki

    return kmap


def _q_range(causal, window, bq, bk, Tq):
    """`_k_range`'s twin for the passes whose inner loop is over q blocks:
    from the first q block at or under the diagonal to, with a `window`, the
    last one with a row whose window still reaches this k block."""
    if not causal:
        return lambda ki, qi: qi

    def qmap(ki, qi):
        qi = jnp.maximum(qi, (ki * bk) // bq)
        if window:      # the block of row k0 + bk - 1 + window - 1
            qi = jnp.minimum(qi, jnp.minimum((ki + 1) * bk + window - 2,
                                             Tq - 1) // bq)
        return qi

    return qmap


def _params(*semantics):
    # the default scoped-VMEM limit (16 MiB of the v5e's 128) is under what a
    # fused backward holds at T 4096 and up: dQ's accumulator and output block
    # for the whole sequence beside the [block_q, block_k] float32 temporaries
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=64 * 1024 * 1024)


# jitted, as every kernel launch below: a model's layers share shapes, so the
# kernel is traced and lowered once a program and not once a layer (36 traces
# of gpt2-small's step cost its set-up 17 s: PERF.md section 6, PR 28). What
# is read when the op is traced (the block sizes, a sweep's forced ones among
# them) comes in as a static argument, so a cached trace never hides it.
@functools.partial(jax.jit,
                   static_argnames=("heads", "causal", "blocks", "statistics",
                                    "window", "pair"))
def _packed_forward(q, k, v, keep=None, *, heads: int, causal: bool,
                    blocks: FlashBlocks, statistics: bool, window: int = 0,
                    pair: bool = False):
    """[out [B, Tq, E]] (`pair`: the first heads' and the second heads'
    outputs, each [B, Tq, E]) and, with `statistics`, the log-sum-exp the
    backward reads: [B, Tq, 128 x ceil(heads / 128)] float32, head h in lane
    h. `keep`: the keep operand (above `_kept_here`), or None."""
    B, Tq, Tk, E, D, W = _geometry(q, k, heads)
    bq, bk = blocks
    hpb = W // D
    outs = hpb if pair else 1
    kmap = _k_range(causal, window, bq, bk)
    klane = _kv_lane(q, k)
    kept = _operand(keep)
    wlane = _keep_lane(bk)
    out_specs = [pl.BlockSpec((1, bq, W), lambda b, qi, hb, ki: (b, qi, hb))
                 ] * outs
    out_shape = [jax.ShapeDtypeStruct((B, Tq, E), q.dtype)] * outs
    if statistics:
        out_specs.append(pl.BlockSpec(
            (1, bq, _LANES),
            lambda b, qi, hb, ki: (b, qi, hb * hpb // _LANES)))
        out_shape.append(jax.ShapeDtypeStruct(
            (B, Tq, _LANES * pl.cdiv(heads, _LANES)), jnp.float32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, D=D, scale=1.0 / math.sqrt(D),
                          causal=causal, window=window,
                          pair=pair, kept=bool(kept)),
        grid=(B, Tq // bq, E // W, Tk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, W), lambda b, qi, hb, ki: (b, qi, hb)),
            pl.BlockSpec((1, bk, W),
                         lambda b, qi, hb, ki: (b, kmap(qi, ki), klane(hb))),
            pl.BlockSpec((1, bk, W),
                         lambda b, qi, hb, ki: (b, kmap(qi, ki), klane(hb))),
        ] + [pl.BlockSpec(
            (1, bq, _LANES),
            lambda b, qi, hb, ki: (b, qi, wlane(kmap(qi, ki))))] * len(kept),
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hpb, bq, W), q.dtype),
                        pltpu.VMEM((hpb, bq, _LANES), jnp.float32),
                        pltpu.VMEM((hpb, bq, _LANES), jnp.float32),
                        pltpu.VMEM((hpb, bq, W) if pair else (bq, W),
                                   jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        name="flash_attention_fwd",
    )(q, k, v, *kept)


@functools.partial(jax.jit,
                   static_argnames=("heads", "causal", "blocks", "fused",
                                    "window", "pair"))
def _packed_backward(q, k, v, o, lse, do, keep=None, *, heads: int,
                     causal: bool, blocks: FlashBlocks, fused: bool,
                     window: int = 0, pair: bool = False):
    """(dq, dk, dv). `fused`: one pass with dQ's accumulator for the whole
    sequence in VMEM; else dK / dV and dQ in a pass each. Where a group of
    query lane blocks shares a K/V lane block (a head of 128 lanes or more,
    or a `pair`), the kernels write each query block's dK and dV (float32,
    [B, Tk, E_q]) and one reduction after them sums the group. `pair`: `o`
    and `do` are the two outputs' (first, second)."""
    B, Tq, Tk, E, D, W = _geometry(q, k, heads)
    bq, bk = blocks
    hpb = W // D
    group = E // k.shape[2]
    klane = _kv_lane(q, k)
    kept = _operand(keep)
    wlane = _keep_lane(bk)
    kernel = functools.partial(_bwd_kernel, D=D, scale=1.0 / math.sqrt(D),
                               causal=causal, window=window,
                               pair=pair, kept=bool(kept))
    o, do = (tuple(o), tuple(do)) if pair else ((o,), (do,))
    operands = (q, k, v) + o + do + (lse,) + kept

    def specs(qrow, krow):
        """In specs of (q, k, v, o, do, lse) given the index maps' q and k
        block for a grid point."""
        def at(row, lane=lambda hb: hb):
            return lambda b, hb, i, j: (b, row(i, j), lane(hb))
        qs = pl.BlockSpec((1, bq, W), at(qrow))
        ks = pl.BlockSpec((1, bk, W), at(krow, lane=klane))
        stat = pl.BlockSpec((1, bq, _LANES),
                            at(qrow, lane=lambda hb: hb * hpb // _LANES))
        words = pl.BlockSpec(
            (1, bq, _LANES),
            lambda b, hb, i, j: (b, qrow(i, j), wlane(krow(i, j))))
        return ([qs, ks, ks] + [qs] * (len(o) + len(do)) + [stat]
                + [words] * len(kept), qs, pl.BlockSpec((1, bk, W), at(krow)))

    # dK and dV (and, fused, dQ): k blocks outside, q blocks inside
    qmap = _q_range(causal, window, bq, bk, Tq)
    ins, _, kspec = specs(qmap, lambda ki, qi: ki)
    outs = [kspec, kspec]
    shapes = [jax.ShapeDtypeStruct(k.shape, k.dtype),
              jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if group > 1:
        shapes = [jax.ShapeDtypeStruct((B, Tk, E), jnp.float32)] * 2
    # each head's K alone in its lanes, and V (a pair reads V whole); dK, dV
    scratch = ([pltpu.VMEM((hpb, bk, W), k.dtype)]
               + ([] if pair else [pltpu.VMEM((hpb, bk, W), v.dtype)])
               + [pltpu.VMEM((bk, W), jnp.float32)] * 2)
    if fused:
        outs.append(pl.BlockSpec((1, Tq, W), lambda b, hb, ki, qi: (b, 0, hb)))
        shapes.append(jax.ShapeDtypeStruct(q.shape, q.dtype))
        scratch.append(pltpu.VMEM((Tq, W), jnp.float32))
    got = pl.pallas_call(
        functools.partial(kernel, want="all" if fused else "dkv"),
        grid=(B, E // W, Tk // bk, Tq // bq),
        in_specs=ins, out_specs=outs, out_shape=shapes,
        scratch_shapes=scratch,
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        name="flash_attention_bwd" if fused else "flash_attention_bwd_dkv",
    )(*operands)
    dk, dv = got[:2]
    if group > 1:
        dk, dv = (a.reshape(B, Tk, E // (group * W), group, W).sum(3)
                  .reshape(k.shape).astype(k.dtype) for a in (dk, dv))
    if fused:
        return got[2], dk, dv
    kmap = _k_range(causal, window, bq, bk)
    ins, qspec, _ = specs(lambda qi, ki: qi, kmap)
    dq = pl.pallas_call(
        functools.partial(kernel, want="dq"),
        grid=(B, E // W, Tq // bq, Tk // bk),
        in_specs=ins, out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, W), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        name="flash_attention_bwd_dq",
    )(*operands)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _packed_attention(q, k, v, heads: int, causal: bool, window: int = 0,
                      pair: bool = False, keep=None):
    """The fused kernels over packed Q [B, T, E] and K, V [B, T, E_kv] (E_kv
    < E: fewer K/V heads, shared by groups of query heads): no dispatch gate.
    Not differentiated, the forward writes no statistics: this is what an
    inference program launches. A training step launches `_packed_attention_fwd`
    instead, once (`Executor` traces the forward ops once, under
    differentiation), so no program holds the two side by side any more.
    `pair` (heads of 64): heads 2p and 2p + 1 are a PAIR that shares the value
    of lane block p, `[v_1 | v_2]`, whole; two outputs, (the first heads', the
    second heads'), each [B, T, E]: lane block p of output j is A_j [v_1 |
    v_2]. K/V pair g serves the query pairs of its group. `keep`: the keep
    operand (above `_kept_here`), int32 and so without a gradient; the
    backward respects the same bits."""
    out = _packed_forward(
        q, k, v, keep, heads=heads, causal=causal, statistics=False,
        window=window, pair=pair,
        blocks=_v5e_block_sizes(q.shape[1], k.shape[1], q.dtype))
    return tuple(out) if pair else out[0]


def _packed_attention_fwd(q, k, v, heads, causal, window=0, pair=False,
                          keep=None):
    *o, lse = _packed_forward(
        q, k, v, keep, heads=heads, causal=causal, statistics=True,
        window=window, pair=pair,
        blocks=_v5e_block_sizes(q.shape[1], k.shape[1], q.dtype))
    o = tuple(o) if pair else o[0]
    return o, (q, k, v, o, lse, keep)


def _packed_attention_bwd(heads, causal, window, pair, saved, do):
    q, k, v, o, lse, keep = saved
    return _packed_backward(
        q, k, v, o, lse, do, keep, heads=heads, causal=causal,
        window=window, pair=pair,
        blocks=_v5e_block_sizes(q.shape[1], k.shape[1], q.dtype),
        fused=q.shape[1] * max(_LANES, q.shape[2] // heads)
        <= _FUSED_BWD_MAX_ELEMENTS) + (None,)


_packed_attention.defvjp(_packed_attention_fwd, _packed_attention_bwd)


def _flash_kernel(q, k, v, causal: bool, window: int = 0, pair: bool = False):
    """Direct fused-kernel call over [B, T, H, D], no dispatch gate
    (benchmarks, the sweep tool and the eligible path all come through here):
    merging H and D is a free reshape to the packed layout. `pair`: two
    outputs, each [B, T, H / 2, 2 D] (`_packed_attention`)."""
    B, Tq, H, D = q.shape
    pack = lambda x: x.reshape(x.shape[0], x.shape[1], -1)  # noqa: E731
    out = _packed_attention(pack(q), pack(k), pack(v), H, causal, window,
                            pair)
    if pair:
        return tuple(o.reshape(B, Tq, H // 2, 2 * D) for o in out)
    return out.reshape(B, Tq, H, D)


_DISPATCH_COUNTER = "pt_flash_attention_dispatch_total"
_DISPATCH_HELP = ("attention ops traced, by the path the dispatcher chose "
                  "from the input's shape (packed: the fused kernels; "
                  "packed_window: the same kernels with a window bound)")
_PAIRS_GAUGE = "pt_flash_attention_pairs"
_PAIRS_HELP = ("query-key score pairs of the attention ops traced so far, by "
               "the dispatcher's path: `computed` by the path's forward (the "
               "kernels' whole blocks and strips of crossed ones; all of "
               "them on `xla`), `kept` by the mask")
_pairs: dict = {}      # (path, "computed" | "kept") -> pairs, summed over ops


def pair_counts(Tq, Tk, causal, window=0, blocks=None):
    """(computed, kept) score pairs of one (batch, head) from the static
    shapes: what the mask keeps, and what the forward computes for them:
    every pair without `blocks` (the XLA formulation), else what the kernels'
    `_on_blocks` runs: the whole of a block inside the band, `_strips`' three
    quarters of a block a diagonal crosses (or all of it), nothing of the
    rest."""
    import numpy as np

    if not causal:
        return Tq * Tk, Tq * Tk
    i = np.arange(Tq)
    first = np.maximum(i - window + 1, 0) if window else 0
    kept = int(np.maximum(np.minimum(i, Tk - 1) - first + 1, 0).sum())
    if blocks is None:
        return Tq * Tk, kept
    bq, bk = blocks
    q0 = (np.arange(Tq // bq) * bq)[:, None]
    k0 = (np.arange(Tk // bk) * bk)[None, :]
    inside, crosses = _block_kind(q0, bq, k0, bk, window)
    whole = int((~crosses).sum()) * bq * bk
    crossed = int((inside & crosses).sum()) * bq * bk
    if _in_strips(bq, bk, window):
        crossed = crossed * 3 // 4
    return whole + crossed, kept


def _pairs_family():
    return [(_PAIRS_GAUGE, "gauge", _PAIRS_HELP,
             [({"path": path, "pairs": kind}, float(n))
              for (path, kind), n in sorted(_pairs.items())])]


def _count_dispatch(path: str, q, k, causal, window) -> None:
    """One attention op traced: its path, and its score pairs (a gauge that
    sums over the ops traced: a counter's increase would be over before a
    window of steps opens)."""
    from ..obs import metrics

    reg = metrics.registry()
    reg.counter_inc(_DISPATCH_COUNTER, help=_DISPATCH_HELP,
                    labels={"path": path})
    (B, Tq, H, _), Tk = q.shape, k.shape[1]
    blocks = None if path == "xla" else _v5e_block_sizes(Tq, Tk, q.dtype)
    computed, kept = pair_counts(Tq, Tk, causal, window, blocks)
    for kind, n in (("computed", computed), ("kept", kept)):
        _pairs[path, kind] = _pairs.get((path, kind), 0) + B * H * n
    reg.add_collector(_pairs_family)


def flash_attention(q, k, v, causal: bool = False, window: int = 0,
                    pair: bool = False):
    """[B, T, H, D] attention; K and V may be [B, T, KV, D] with KV dividing
    H (query head j reads K/V head j // (H / KV): at D 128 and up the
    kernels share the K/V block, at D 64 K and V are repeated to H heads
    first). `window` W > 0 (causal only): position i reads the W keys
    i - W < j <= i; a window that covers the sequence is `causal` and runs
    as that; the kernels skip the key blocks wholly left of it and the XLA
    formulation masks them (one meaning on both paths). From T=1024 the
    fused kernels are the path
    (and the O(T)-memory one); below that range XLA keeps the job unless
    the score buffer would exceed the memory threshold. Numerics: the
    inputs' dtype in and out (bf16 under AMP), float32 scores, statistics
    and accumulators inside the kernels. The choice is made when the op is
    traced and counted in `pt_flash_attention_dispatch_total{path}` (`xla`,
    `packed`, or `packed_window` for the kernels with a window bound), with
    the score pairs that path computes and the mask keeps beside it
    (`pt_flash_attention_pairs{path,pairs}`).
    `pair`: the PAIR form (`paired_attention`): heads 2p and 2p + 1 share the
    value `[v_2p | v_2p+1]`, query pair p reads K/V pair p // (H / KV), and
    two arrays come back, (A_first v, A_second v), each [B, T, H / 2, 2 D].
    One launch, each softmax computed once: at D 64 a pair is one lane block
    of Q, of K and of V as the projection packs them, so the gate sees a pair
    as one head of 128 lanes (K/V blocks shared through the index map, none
    repeated); any other D goes to the XLA formulation."""
    if q.ndim != 4:
        raise ValueError(f"expected [B, T, H, D], got {q.shape}")
    window = int(window or 0)
    if window < 0 or (window and not causal):
        raise ValueError(f"window {window}: a positive number of keys, and "
                         f"only with causal=True")
    if window >= k.shape[1]:
        window = 0      # every earlier key is inside it: plain causal
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"{q.shape[2]} query heads do not share "
                         f"{k.shape[2]} K/V heads evenly")
    if pair and (q.shape[2] % 2 or k.shape[2] % 2):
        raise ValueError(f"{q.shape[2]} query and {k.shape[2]} K/V heads do "
                         f"not form pairs")
    from . import mesh_dispatch

    if pair:
        seen = tuple(jax.eval_shape(_pair_view, x) for x in (q, k))
        kernels_take = 2 * q.shape[3] == _LANES
        reference = paired_attention
    else:
        if q.shape[3] < _LANES:  # two heads a block: no K/V block to share
            k, v = _repeat_kv(q, k, v)
        seen, kernels_take, reference = (q, k), True, _reference

    am = mesh_dispatch.current()
    # mesh policy (ops/mesh_dispatch.py): a bare pallas_call cannot be
    # GSPMD-partitioned, so the kernel shard_maps over dp (batch dim 0; no
    # weights -> no cotangent psums). Under an mp axis the wrap replicates
    # heads (a resharding GSPMD inserts); sharding heads over mp inside the
    # wrap is a future multi-chip lever. A batch dp does not divide falls
    # back to the XLA formulation, which GSPMD partitions natively.
    sharded = am is not None and am.dp > 1
    if not (kernels_take and flash_eligible(*seen, window)) or (
            sharded and q.shape[0] % am.dp):
        _count_dispatch("xla", q, k, causal, window)
        return reference(q, k, v, causal, window)
    _count_dispatch("packed_window" if window else "packed", q, k, causal,
                    window)
    call = functools.partial(_flash_kernel, causal=causal, window=window,
                             pair=pair)
    if sharded:
        call = mesh_dispatch.shard_batch(
            call, (0, 0, 0), ((0, 4),) * (2 if pair else 1),
            jax.tree.structure((0, 0)) if pair else None)
    return call(q, k, v)


@register_op("flash_attention")
def flash_attention_kernel(ctx):
    """Program-IR face of the dispatcher: Q/K/V are [B, T, E] packed
    multi-head projections; num_heads splits E (a free reshape: the kernels
    read the packed layout). K and V narrower than Q are [B, T, kv_heads x
    D], the head count read from their width, and each serves a group of
    query heads. Attr `window` (absent or 0: none, the op as it always was):
    with `causal`, position i reads the keys i - window < j <= i. Used by
    layers.multi_head_attention (models/transformer.py; models/afmoe.py:
    window and global layers in one model) and layers.latent_attention.
    Attr `head_pairs` (absent: plain heads), which
    layers.differential_attention sets as it sets `window` (nothing in Q's, K's
    or V's shape tells a pair from two heads): heads 2p and 2p + 1 are a pair
    over one value of 2 D lanes, ONE launch a layer, and the op has two
    outputs, Out = A_first [v_1 | v_2] and Out2 = A_second [v_1 | v_2], each
    [B, T, E] (`flash_attention(pair=True)`)."""
    from .. import amp

    # under amp Q and K may arrive float32 (from rms_norm / rotary, which
    # emit float32); the kernel's io is the amp dtype, like V's
    q, k, v = amp.cast_inputs(ctx, ctx.input("Q"), ctx.input("K"),
                              ctx.input("V"))
    heads = ctx.attr("num_heads")
    causal = ctx.attr("causal", True)
    B, T, E = q.shape
    if E % heads:
        raise ValueError(f"hidden dim {E} not divisible by heads {heads}")
    D = E // heads
    split = lambda x: x.reshape(B, x.shape[1], -1, D)  # noqa: E731
    pair = bool(ctx.attr("head_pairs", False))
    out = flash_attention(split(q), split(k), split(v), causal=causal,
                          window=ctx.attr("window", 0), pair=pair)
    for slot, o in zip(("Out", "Out2"), out if pair else (out,)):
        ctx.set_output(slot, o.reshape(B, T, E))


_LATENT_COUNTER = "pt_latent_attention_dispatch_total"
_LATENT_HELP = ("latent-attention layers traced, by the form their keys and "
                "values take (expanded: K and V materialised per head, the "
                "training form)")


def expand_latent_kv(kv, k_rope, heads: int, nope_dim: int):
    """Latent attention's keys and values in the EXPANDED form, packed for
    the kernels above. kv [B, T, heads x (nope_dim + Dv)]: per head the
    up-projected `[k_nope | v]`; k_rope [B, T, R]: ONE rotary key, already
    turned, that every head shares. Returns K [B, T, heads x (nope_dim + R)]
    with head h = `[k_nope_h | k_rope]`, and V [B, T, heads x Dv]. The
    gradient of `k_rope` is the sum over the heads (autodiff's)."""
    B, T, _ = kv.shape
    per_head = kv.reshape(B, T, heads, -1)
    with jax.named_scope("assemble_k"):
        rope = jnp.broadcast_to(k_rope[:, :, None, :],
                                (B, T, heads, k_rope.shape[-1]))
        k = jnp.concatenate(
            [per_head[..., :nope_dim], rope.astype(kv.dtype)], axis=-1)
    with jax.named_scope("split_v"):
        v = per_head[..., nope_dim:]
    return k.reshape(B, T, -1), v.reshape(B, T, -1)


@register_op("latent_kv_expand")
def latent_kv_expand_kernel(ctx):
    """Program-IR face of `expand_latent_kv`: KV, KRope -> K, V (attrs
    num_heads, nope_dim). Sits between the latent's up-projection and the
    `flash_attention` op of `layers.latent_attention`; one increment of
    `pt_latent_attention_dispatch_total{path="expanded"}` an op traced."""
    from ..obs import metrics

    kv, k_rope = ctx.input("KV"), ctx.input("KRope")
    metrics.registry().counter_inc(_LATENT_COUNTER, help=_LATENT_HELP,
                                   labels={"path": "expanded"})
    k, v = expand_latent_kv(kv, k_rope, int(ctx.attr("num_heads")),
                            int(ctx.attr("nope_dim")))
    ctx.set_output("K", k)
    ctx.set_output("V", v)


# ---- differential attention (Ye et al. 2024) around the kernels -----------
# A pair of heads (2p, 2p + 1) is two softmaxes over one value twice a head
# wide: o_p = (A_1 - lam A_2) [v_1 | v_2]. At heads of 64 that pair IS a lane
# block of the packed projections (`[q_1 | q_2]`, `[k_1 | k_2]`, `[v_1 |
# v_2]`), so `layers.differential_attention` launches the kernels ONCE a
# layer in their pair form (`flash_attention(pair=True)`): each softmax
# computed once, its P V over the block's 128 value lanes kept whole, and
# `diff_combine` takes the two outputs.
def diff_lambda(lq1, lk1, lq2, lk2, lam_init: float):
    """exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init, float32."""
    f = lambda a, b: jnp.exp(jnp.sum(  # noqa: E731
        a.astype(jnp.float32) * b.astype(jnp.float32)))
    return f(lq1, lk1) - f(lq2, lk2) + lam_init


def diff_combine(first, second, lq1, lk1, lq2, lk2, norm_w, *,
                 head_dim: int, lam_init: float, eps: float):
    """The pair arithmetic behind the kernels. first = A_1 [v_1 | v_2], second
    = A_2 [v_1 | v_2], each [B, T, P x 2 D] (pair p in its 2 D lanes: the two
    outputs of the kernels' pair form); the four vectors [D] give lam
    (`diff_lambda`); norm_w [2 D].

        o_p = first_p - lam second_p                              (2 D lanes)
        out_p = o_p rsqrt(mean(o_p^2) + eps) norm_w (1 - lam_init)

    -> [B, T, P x 2 D] in first's dtype, float32 inside. One checkpoint: the
    backward keeps the two operands and forms the float32 arrays again."""
    D = int(head_dim)

    @jax.checkpoint
    def combine(first, second, lq1, lk1, lq2, lk2, norm_w):
        B, T, E = first.shape
        pairs = lambda a: a.astype(jnp.float32).reshape(  # noqa: E731
            B, T, E // (2 * D), 2 * D)
        lam = diff_lambda(lq1, lk1, lq2, lk2, lam_init)
        o = pairs(first) - lam * pairs(second)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        o = o * (norm_w.astype(jnp.float32) * (1.0 - lam_init))
        return o.reshape(B, T, E).astype(first.dtype)

    return combine(first, second, lq1, lk1, lq2, lk2, norm_w)


_launches: dict = {}      # a combine op's name in its Program -> its launches


@register_op("diff_combine")
def diff_combine_kernel(ctx):
    """Program-IR face of `diff_combine`: First, Second, LamQ1, LamK1, LamQ2,
    LamK2, NormW -> Out (attrs head_dim, lam_init, epsilon, and `launches`:
    the `flash_attention` ops the layer appended for this combine, summed
    over the combines traced into the gauge
    `pt_diff_attention_launches_total`: forward launches a step)."""
    from ..obs import metrics

    _launches[ctx.op.outputs["Out"][0]] = int(ctx.attr("launches", 1))
    metrics.registry().gauge(
        "pt_diff_attention_launches_total", lambda: sum(_launches.values()),
        help="flash_attention ops a step behind the differential-attention "
             "layers' pair arithmetic, summed over the combines traced so "
             "far (a combine traced again counted once)")
    ctx.set_output("Out", diff_combine(
        *(ctx.input(s) for s in ("First", "Second", "LamQ1", "LamK1", "LamQ2",
                                 "LamK2", "NormW")),
        head_dim=int(ctx.attr("head_dim")),
        lam_init=float(ctx.attr("lam_init")),
        eps=float(ctx.attr("epsilon", 1e-5))))
