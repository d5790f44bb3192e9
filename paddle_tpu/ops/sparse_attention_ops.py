"""Learned sparse attention (the DeepSeek-Sparse-Attention form): an INDEXER
scores every causal key of a row, the row KEEPS its `topk` best, and attention
runs over the kept keys only, forward and backward.

    I(t, s) = sum_j w[t, j] relu(q^I[t, j] . k^I[s])        s <= t
    S_t = the min(topk, t + 1) keys s <= t of largest I(t, s); among equals
          the LOWER index
    o_j[t] = sum over s in S_t of softmax_s(q_j[t] . k_{j // g}[s] / sqrt(D))
             v_{j // g}[s]

q^I [B, T, Hi, Di], k^I [B, T, Di] (one key head), w [B, T, Hi]; q [B, T, H,
D] over K/V [B, T, KV, D], g = H / KV. The sets are discrete and I enters the
output nowhere else, so nothing here has a gradient towards the indexer.

Two ops (`layers.sparse_attention` appends them behind the projections):

- `sparse_keep`: the scores in tiles of `BLOCK` rows (no [T, T] array is ever
  whole) and the selection, written as the KEEP operand of the attention
  kernels: one BIT a (row, key), int32 [B, T, 128 x ceil(T / 4096)]
  (`flash_ops._kept_here` has the layout): 32 MiB a layer at T 16 384, and
  all the backward keeps of the choice. A tile is scored and counted against
  its CAUSAL PREFIX, not the sequence: the tiles go in groups of consecutive
  tiles (`_groups`: at most `GROUPS` scored, from T, the tile's rows and
  `topk` alone), a group's tiles see the keys before the group's end, and
  the tiles whose rows all lie under `topk` are neither scored nor counted
  (such a row keeps every key it may see). The bits are the whole-sequence
  form's to the bit: a key behind the prefix was never valid. At T 16 384,
  `topk` 2 048: 54.7 % of the [T, T] pairs (`pt_sparse_keep_scored_pairs`),
  where half are causal. Its second output `Chosen` int32
  [B x T, topk] (a row's kept keys by index, ascending, -1 where it has
  fewer) is derived from the bits alone, so a program that does not fetch it
  does not compute it.
- `sparse_attention`: attention under the causal mask AND the bits: on the
  chip the packed flash kernels with the keep operand (ops/flash_ops.py: one
  `custom_vjp`, the kernels every attention layer runs, every causal block
  computed and masked by its bits); anywhere else, under a mesh, and for the
  shapes the kernels refuse, the exact plain form in `jax.numpy`, a block of
  rows at a time under `jax.checkpoint`. Chosen when the op is traced and
  counted (`pt_sparse_attention_dispatch_total{path}`).

One selection on every backend, `select_by_count`: the row's topk-th largest
score found by counting, bit by bit over the float's ordered integer image,
then the ties' lowest indices by counting again: thirty-two passes of
compare-and-sum and never a sort (on the chip a `lax.top_k` of [512, 16 384] a
tile was what the step waited for: 361 ms a layer against 18.5).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op
from . import flash_ops

BLOCK = 512                     # rows a tile of scores holds
GROUPS = 8                      # causal prefixes a sequence is scored against
_LANES = flash_ops._LANES
_TILES = flash_ops.KEEP_TILES
_SCORE_BYTES_A_BLOCK = 256 * 2**20   # the plain attention's float32 scores


def _rows(T: int, cap: int | None = None) -> int:
    """The largest divisor of T that is at most `cap` (`BLOCK` if none)."""
    cap = BLOCK if cap is None else cap
    return max(r for r in range(1, max(1, min(T, cap)) + 1) if T % r == 0)


# ------------------------------------------------------------- the indexer
def index_scores(q_i, k_i, w_i):
    """I [R, T] float32 of R rows against a sequence's T keys: q_i [R, Hi,
    Di], k_i [T, Di], w_i [R, Hi]. The products in the inputs' dtype (bf16
    under AMP) with float32 sums; -0.0 is +0.0 (one float, one place in the
    order)."""
    with jax.named_scope("indexer"):
        s = jnp.einsum("rhd,td->rht", q_i, k_i,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("rh,rht->rt", w_i.astype(jnp.float32),
                          jax.nn.relu(s)) + 0.0


# ----------------------------------------------------------- the selection
def _ordered(z):
    """float32 -> uint32 with the floats' order (NaN aside)."""
    i = jax.lax.bitcast_convert_type(z, jnp.int32)
    i = i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(i, jnp.uint32) ^ jnp.uint32(1 << 31)


def select_by_count(z, valid, k):
    """bool [R, T]: each row's min(k, valid) valid candidates of largest z,
    the lower index among equals, without a sort: the k-th largest valid score
    of a row is built bit by bit, from the top, as the largest value that k
    valid candidates reach (32 counts); what lies above it is kept, and of
    the candidates that equal it the lowest indices up to k, found the same
    way over the index's bits where a tile has such a tie at all."""
    R, T = z.shape
    u = jnp.where(valid, _ordered(z), jnp.uint32(0))
    k = jnp.int32(k)

    def count(mask):
        return jnp.sum(mask, axis=1, dtype=jnp.int32)[:, None]

    def bit(i, kth):
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(count(u >= cand) >= k, cand, kth)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros((R, 1), jnp.uint32))
    above = u > kth
    equal = (u == kth) & valid
    need = k - count(above)             # of the equals, at least one
    index = jnp.arange(T, dtype=jnp.int32)[None, :]

    def lowest(_):
        def bit(i, pos):            # the largest pos with < need equals below
            cand = pos + (jnp.int32(1) << (bits - 1 - i))
            return jnp.where(count(equal & (index < cand)) < need, cand, pos)

        bits = max(1, (T - 1).bit_length())
        pos = jax.lax.fori_loop(0, bits, bit, jnp.zeros((R, 1), jnp.int32))
        return equal & (index <= pos)

    tied = jnp.any(count(equal) > need)
    return (above & valid) | jax.lax.cond(tied, lowest, lambda _: equal, None)


def keep_lanes(T: int) -> int:
    """The lanes of the keep operand's rows for T keys: 128 x ceil(T /
    4096)."""
    return _LANES * -(-T // (_TILES * _LANES))


def pack_bits(keep):
    """bool [R, T] -> int32 [R, `keep_lanes(T)`], the keep operand's rows
    (`flash_ops._kept_here`): key 4096 w + 128 u + c in bit u of lane 128 w
    + c."""
    R, T = keep.shape
    words = keep_lanes(T) // _LANES
    keep = jnp.pad(keep, ((0, 0), (0, words * _TILES * _LANES - T)))
    bits = keep.reshape(R, words, _TILES, _LANES).astype(jnp.uint32) \
        << jnp.arange(_TILES, dtype=jnp.uint32)[:, None]
    return jax.lax.bitcast_convert_type(
        jnp.sum(bits, axis=2, dtype=jnp.uint32), jnp.int32).reshape(R, -1)


def unpack_bits(words, T):
    """`pack_bits`' inverse: int32 [R, 128 x W] -> bool [R, T]."""
    R = words.shape[0]
    w = words.reshape(R, -1, 1, _LANES)
    bits = (w >> jnp.arange(_TILES, dtype=jnp.int32)[:, None]) & 1
    return bits.reshape(R, -1)[:, :T] != 0


def _groups(tiles: int, rows: int, topk: int):
    """[(first tile, end tile)]: a sequence's `tiles` tiles of `rows` rows in
    groups of consecutive tiles; a group's tiles see the keys before its end
    and no others. The tiles that end at or before key `topk` are one group
    (their rows keep every key they may see: nothing to score); the others
    end where a split into `GROUPS` equal parts ends, so at most `GROUPS`
    groups are scored."""
    size = -(-tiles // GROUPS)
    free = min(topk // rows, tiles)
    ends = sorted(({free, tiles} - {0})
                  | {e for e in range(size, tiles, size) if e > free})
    return list(zip([0] + ends[:-1], ends))


def scored_pairs(T: int, topk: int) -> int:
    """The (row, key) pairs the indexer scores for one sequence of T: a
    group's rows times its prefix, over the groups that are scored at all
    (T x T for one group; T (T + 1) / 2 are causal)."""
    rows = _rows(T)
    return sum((end - first) * rows * end * rows
               for first, end in _groups(T // rows, rows, topk)
               if end * rows > topk)


def keep_bits(q_i, k_i, w_i, topk: int):
    """The keep operand int32 [B, T, 128 x ceil(T / 4096)] of q_i [B, T, Hi,
    Di], k_i [B, T, Di], w_i [B, T, Hi]: a tile of at most `BLOCK` rows of
    one sequence at a time, scored and counted against its group's causal
    prefix k_i[b, :P] (`_groups`), its words padded with zero lanes behind
    key P. A group whose prefix is at most `topk` keys long is neither scored
    nor counted: its bits are the causal mask's."""
    T = q_i.shape[1]
    rows = _rows(T)
    return _grouped_bits(q_i, k_i, w_i, topk=topk, rows=rows,
                         groups=tuple(_groups(T // rows, rows, topk)),
                         scores=index_scores, select=select_by_count)


# jitted: a group's loop is traced and lowered once for all the layers that
# call it with one shape; everything that shapes the trace is a static
# argument, the two functions among them (read from the module where
# `keep_bits` is called, so that one replaced there is another trace)
@functools.partial(jax.jit, static_argnames=("topk", "rows", "groups",
                                             "scores", "select"))
def _grouped_bits(q_i, k_i, w_i, *, topk, rows, groups, scores, select):
    B, T = q_i.shape[:2]
    per_seq = T // rows
    lanes = keep_lanes(T)
    out = []
    for first, end in groups:
        n, P = end - first, end * rows
        k_p = k_i[:, :P]

        def tile(i):
            b, t0 = i // per_seq, (i % per_seq) * rows

            def mine(a):
                return jax.lax.dynamic_slice_in_dim(a[b], t0, rows)

            z = scores(mine(q_i), k_p[b], mine(w_i)) if P > topk else None
            with jax.named_scope("select"):
                valid = jnp.arange(P)[None, :] <= (
                    t0 + jnp.arange(rows))[:, None]
                return pack_bits(valid if z is None
                                 else select(z, valid, topk))

        ids = np.add.outer(np.arange(B) * per_seq, np.arange(first, end))
        bits = jax.lax.map(tile, jnp.asarray(ids.ravel(), jnp.int32))
        bits = bits.reshape(B, n * rows, -1)
        out.append(jnp.pad(bits, ((0, 0), (0, 0), (0, lanes - bits.shape[2]))))
    return jnp.concatenate(out, axis=1)


def chosen_from_bits(bits, T: int, topk: int):
    """int32 [B x T, topk]: each row's kept keys by index, ascending, -1
    behind them, from the keep operand alone."""
    B = bits.shape[0]
    rows = _rows(T)
    k = min(topk, T)

    def tile(words):
        value, index = jax.lax.top_k(unpack_bits(words, T).astype(jnp.int32),
                                     k)
        return jnp.where(value > 0, index, -1).astype(jnp.int32)

    with jax.named_scope("chosen"):
        chosen = jax.lax.map(tile, bits.reshape(B * T // rows, rows, -1))
        return jnp.pad(chosen.reshape(B * T, k), ((0, 0), (0, topk - k)),
                       constant_values=-1)


# ----------------------------------------------------------- the attention
def attend_plain(q, k, v, bits):
    """The exact plain form: q [B, T, H, D], k and v [B, T, KV, D], the keep
    operand `bits` -> [B, T, H, D] in q's dtype; float32 scores, softmax
    over a row's kept keys alone. A block of rows of one sequence at a time
    under `jax.checkpoint`: no [T, T] array is whole, forward or backward
    (autodiff's, which sees the same bits)."""
    B, T, H, D = q.shape
    KV = k.shape[2]
    rows = _rows(T, max(8, min(BLOCK, _SCORE_BYTES_A_BLOCK // (4 * H * T))))
    per_seq = T // rows

    def block(i, q, k, v):
        b, t0 = i // per_seq, (i % per_seq) * rows
        q_b = jax.lax.dynamic_slice_in_dim(q[b], t0, rows)
        words = jax.lax.dynamic_slice_in_dim(bits[b], t0, rows)
        mask = unpack_bits(words, T) & (
            jnp.arange(T)[None, :] <= (t0 + jnp.arange(rows))[:, None])
        s = jnp.einsum("rkgd,tkd->kgrt", q_b.reshape(rows, KV, H // KV, D),
                       k[b], preferred_element_type=jnp.float32)
        p = jax.nn.softmax(jnp.where(mask, s / math.sqrt(D), -jnp.inf),
                           axis=-1)
        out = jnp.einsum("kgrt,tkd->rkgd", p.astype(v.dtype), v[b],
                         preferred_element_type=jnp.float32)
        return out.reshape(rows, H, D).astype(q.dtype)

    out = jax.lax.map(
        lambda i: jax.checkpoint(block)(i, q, k, v), jnp.arange(B * per_seq))
    return out.reshape(B, T, H, D)


def kernels_eligible(q, k) -> bool:
    """The packed flash kernels take the keep operand where they take the
    shapes at all (`flash_ops.flash_eligible`: the chip, T a multiple of 128
    from 1024 up, heads a lane block holds), no mesh is active (a bare
    `pallas_call` cannot be partitioned) and a k block's key tiles lie inside
    one lane block of the operand's words."""
    from . import mesh_dispatch

    if mesh_dispatch.current() is not None or not flash_ops.flash_eligible(
            q, k):
        return False
    bk = flash_ops._v5e_block_sizes(q.shape[1], k.shape[1], q.dtype).block_k
    return _TILES % (bk // _LANES) == 0


_DISPATCH_COUNTER = "pt_sparse_attention_dispatch_total"
_DISPATCH_HELP = ("sparse-attention ops traced, by the form their attention "
                  "takes (kernels: the packed flash kernels under the keep "
                  "operand; plain: blocks of rows in jax.numpy)")
# (an op's output name, T, topk, path) -> a step's (kept pairs, causal pairs,
# keep operand's bytes, pairs the forward computes x heads, pairs kept x heads)
_traced: dict = {}
# (a `sparse_keep` op's output name, T, topk) -> the pairs its indexer scores
_scored: dict = {}


def kept_pairs(T: int, topk: int) -> int:
    """The (row, key) pairs one causal sequence of T keeps at `topk` a row:
    every causal pair of the first topk rows, topk of each later one."""
    k = min(topk, T)
    return k * (k + 1) // 2 + (T - k) * k


def _families():
    def of_attention(i):
        return sum(v[i] for v in _traced.values())

    gauges = (
        ("pt_sparse_attention_kept_pairs", "(row, key) pairs the sparse-"
         "attention layers traced so far keep a step, a head (static "
         "arithmetic: min(topk, t + 1) a row)", of_attention(0)),
        ("pt_sparse_attention_causal_pairs", "(row, key) pairs under the "
         "causal mask of the same layers a step, a head", of_attention(1)),
        ("pt_sparse_attention_saved_choice_bytes", "bytes of the keep "
         "operands (a bit a (row, key)) the same layers' backward keeps of "
         "the choice a step", of_attention(2)),
        ("pt_sparse_keep_scored_pairs", "(row, key) pairs the indexer scores "
         "a step in the sparse_keep ops traced so far (static arithmetic: a "
         "group of tiles against its causal prefix; twice the causal pairs "
         "where every tile sees the whole sequence)", sum(_scored.values())))
    return [(name, "gauge", text, [({}, float(value))])
            for name, text, value in gauges]


def _count_scored(ctx, B, T, topk):
    """One `sparse_keep` op traced: the pairs its indexer scores a step,
    recorded once an (op, T, topk) in `_scored`."""
    from ..obs import metrics

    _scored[ctx.op.outputs["Keep"][0], T, topk] = B * scored_pairs(T, topk)
    metrics.registry().add_collector(_families)


def _count(ctx, path, q, topk, bits):
    """One sparse-attention op traced: its path and its static pair counts,
    recorded once an (op, T, topk, path) in `_traced`, the one source of the
    gauges above (a head's pairs, summed over the records) and, beside every
    attention op's, of the pairs its forward computes and its mask keeps
    (`pt_flash_attention_pairs{path="sparse_<path>"}`: the kernels run every
    block the causal rule leaves, the plain form every pair)."""
    from ..obs import metrics

    reg = metrics.registry()
    reg.counter_inc(_DISPATCH_COUNTER, help=_DISPATCH_HELP,
                    labels={"path": path})
    B, T, H, _ = q.shape
    kept = kept_pairs(T, topk)
    blocks = flash_ops._v5e_block_sizes(T, T, q.dtype) \
        if path == "kernels" else None
    computed, _ = flash_ops.pair_counts(T, T, True, 0, blocks)
    _traced[ctx.op.outputs["Out"][0], T, topk, path] = (
        B * kept, B * T * (T + 1) // 2, bits.size * bits.dtype.itemsize,
        B * H * computed, B * H * kept)
    for i, kind in ((3, "computed"), (4, "kept")):
        flash_ops._pairs["sparse_" + path, kind] = sum(
            v[i] for key, v in _traced.items() if key[3] == path)
    reg.add_collector(_families)
    reg.add_collector(flash_ops._pairs_family)


def sparse_attention(q, k, v, bits):
    """(out [B, T, H, D], the path taken): q [B, T, H, D], k and v [B, T,
    KV, D], the keep operand `bits`."""
    B, T, H, D = q.shape
    if kernels_eligible(q, k):
        pack = lambda x: x.reshape(B, T, -1)  # noqa: E731
        out = flash_ops._packed_attention(pack(q), pack(k), pack(v), H, True,
                                          0, False, bits)
        return out.reshape(B, T, H, D), "kernels"
    return attend_plain(q, k, v, bits), "plain"


@register_op("sparse_keep")
def sparse_keep_kernel(ctx):
    """IndexQ [B, T, Hi x Di], IndexK [B, T, Di], IndexW [B, T, Hi] (attrs
    `index_heads`, `topk`) -> Keep int32 [B, T, 128 x ceil(T / 4096)], the
    attention kernels' keep operand, and Chosen int32 [B x T, topk]. Inner
    scopes `indexer` (a tile's scores against its group's causal prefix),
    `select` (the counts over that prefix, the bits packed), `chosen`; one
    loop a group of tiles (`keep_bits`), the scored pairs counted where the
    op is traced (`pt_sparse_keep_scored_pairs`). No gradient."""
    q_i, k_i, w_i = (jax.lax.stop_gradient(ctx.input(s))
                     for s in ("IndexQ", "IndexK", "IndexW"))
    B, T, _ = q_i.shape
    heads, topk = int(ctx.attr("index_heads")), int(ctx.attr("topk"))
    bits = keep_bits(q_i.reshape(B, T, heads, -1), k_i, w_i, topk)
    _count_scored(ctx, B, T, topk)
    ctx.set_output("Keep", bits)
    ctx.set_output("Chosen", chosen_from_bits(bits, T, topk))


@register_op("sparse_attention")
def sparse_attention_kernel(ctx):
    """Q [B, T, H x D], K and V [B, T, KV x D] packed projections, Keep (the
    `sparse_keep` op's; attrs `num_heads`, `topk` for the counts) -> Out [B,
    T, H x D]: causal attention over each row's kept keys only. Under amp Q
    and K may arrive float32; the kernels' io is the amp dtype, like V's."""
    from .. import amp

    q, k, v = amp.cast_inputs(ctx, ctx.input("Q"), ctx.input("K"),
                              ctx.input("V"))
    bits = ctx.input("Keep")
    heads = int(ctx.attr("num_heads"))
    B, T, E = q.shape
    D = E // heads
    split = lambda x: x.reshape(B, T, -1, D)  # noqa: E731
    out, path = sparse_attention(split(q), split(k), split(v), bits)
    _count(ctx, path, split(q), int(ctx.attr("topk")), bits)
    ctx.set_output("Out", out.reshape(B, T, E))
