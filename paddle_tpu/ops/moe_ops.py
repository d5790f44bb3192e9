"""Routed experts: a dropless top-k mixture of experts, and the grouped
matmul under it. By arguments: a softmax router (OLMoE, Mixtral) or a
sigmoid one with a choice bias and a gate scale (DeepSeek-V3's, Nemotron-
H's, GLM-4.7-Flash's); SwiGLU experts (three stacks) or relu^2 ones (two
stacks, no gate); all experts on the chip or `held`, a contiguous share of
them (the others live on other chips: pairs that chose them add nothing
here); and a shared expert of the same kind beside the routed ones.

Reference lineage: the 2017 reference has no routed layer (its nearest
kin is the MixedLayer's per-input projections); this is the sparse-expert
FFN of OLMoE / Mixtral / DeepSeek as MegaBlocks computes it (Gale et al.
2022): no capacity factor and no dropped token. Every (token, slot) pair
is sorted by expert, the experts' matmuls run as ONE grouped matmul over
ragged groups, and the rows go back weighted by their gates.

Compute path of the grouped matmul (`grouped_matmul`): on TPU, JAX's
Pallas megablox kernel (jax.experimental.pallas.ops.tpu.megablox.gmm —
public JAX library code, used the way flash_ops.py uses JAX's flash
kernel) with its custom VJP (two more grouped kernels: gmm against the
transposed weights, tgmm for the weights' gradient); anywhere else, or
when the kernel's shape rules fail, `jax.lax.ragged_dot`. The oracle for
both is `grouped_matmul_reference` (one row at a time against its own
group's matrix).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import amp
from ..core.registry import register_op


def group_ids(group_sizes, m: int):
    """Row -> group for `m` rows laid out group after group."""
    ends = jnp.cumsum(group_sizes)
    return jnp.searchsorted(ends, jnp.arange(m), side="right")


def grouped_matmul_reference(lhs, rhs, group_sizes):
    """out[i] = lhs[i] @ rhs[group of row i], plain jnp: the numerical
    oracle of both formulations below (small sizes only: it gathers one
    matrix per row)."""
    gid = group_ids(group_sizes, lhs.shape[0])
    return jnp.einsum("mk,mkn->mn", lhs, rhs[gid],
                      preferred_element_type=jnp.float32).astype(lhs.dtype)


# Tiling (tm, tk, tn) of the megablox kernels on a v5e, by the chip (PERF.md
# section 6, PR 27; 32768 rows, 64 groups, d 2048, f 1024, bf16): the
# library's all-128 default runs at 9 TFLOP/s (14.6 ms a forward matmul
# where this takes 1.5), as it left flash at 0.6x of XLA in round 2. One
# tuple serves the forward gmm, the backward's gmm against the transposed
# weights and its tgmm, because the library's custom VJP hands the same one
# to all three: (256, 1024, 1024) reads 1.53 / 2.75 ms forward / backward
# on gate and up and 1.40 / 3.29 on down, (512, 1024, 1024) 1.61 / 3.18 and
# 1.54 / 3.53, (256, 2048, 512) wins gate's forward alone (1.33) and loses
# everywhere else; tk 2048 with tn 1024, tm 1024 on down, and tn 2048 do
# not fit VMEM. tm must divide the rows; a tile that straddles a group's
# boundary is visited once per group, so a small tm wastes less where the
# routing is uneven.
_V5E_TILING = (256, 1024, 1024)


def _v5e_tiling(m: int, k: int, n: int):
    def fit(size, want):
        t = min(size, want)
        while size % t:
            t -= 128
        return t

    return fit(m, _V5E_TILING[0]), fit(k, _V5E_TILING[1]), fit(n, _V5E_TILING[2])


def _shapes_gmm_ok(lhs, rhs) -> bool:
    """Backend-independent shape rules of the kernel: 128-aligned rows,
    contraction and output widths (lane and sublane tiling; the kernel
    masks ragged GROUPS, not ragged matrices), bf16 or f32 inputs."""
    m, k = lhs.shape
    n = rhs.shape[2]
    return (m % 128 == 0 and k % 128 == 0 and n % 128 == 0
            and lhs.dtype == rhs.dtype
            and lhs.dtype in (jnp.bfloat16, jnp.float32))


def gmm_eligible(lhs, rhs) -> bool:
    from . import mesh_dispatch

    # a bare pallas_call cannot be GSPMD-partitioned: under a mesh the XLA
    # formulation keeps the job (expert parallelism is ROADMAP Queue 2)
    return (jax.default_backend() == "tpu" and _shapes_gmm_ok(lhs, rhs)
            and mesh_dispatch.current() is None)


def kernel_width_pad(rows: int, d: int, f: int, dtype) -> int:
    """Zero columns to add to an expert width `f` so that the grouped-matmul
    kernel takes the layer (its widths are whole 128-lane tiles; Nemotron's
    1856 is 14.5): 0 where `f` is aligned already or the kernel would not
    take the padded shapes either (another backend, a mesh)."""
    pad = -f % 128
    if pad and gmm_eligible(jax.ShapeDtypeStruct((rows, d), dtype),
                            jax.ShapeDtypeStruct((1, d, f + pad), dtype)):
        return pad
    return 0


# A share computes its live rows in chunks (`moe_ffn`) of this many even
# shares of the rows. The routers learn to prefer the held experts, because
# only their output reaches the cost: by layer the hybrid (8 of 128 held,
# even 0.0625) reads 0.055-0.106 of its rows live after 20 steps, 0.19-0.28
# in its two deepest layers on some seeds, and glm (8 of 64, even 0.125)
# 0.20-0.24 (PERF.md section 6, PRs 37 and 38). A step takes as many chunks
# as its live rows fill, so a small chunk wastes less and a large one loops
# less. By the chip (PERF.md section 6, PR 38; one seed, 12 s windows;
# tokens/s, GiB at the window's close): chunks of 1 / 2 / 4 even shares read
# 25 080 / 25 463 / 24 465 and 12.57 GiB each on the hybrid (the whole rows
# 19 400 and 13.83), 1 / 2 / 3 / 4 read 30 545 / 30 700 / 31 147 / 30 471
# and 11.97 / 12.06 / 12.18 / 12.29 GiB on glm (28 260 and 12.05): from
# three even shares on glm holds more than the whole rows did. A layer may
# ask for another chunk (`chunk_shares`): Trinity's routers, top 8 of 128 with
# 16 held (even 0.125), settle at 0.19-0.28 of their rows by layer and seed,
# on both sides of two even shares, and a layer that takes a second chunk
# costs its step 3.6 % (PERF.md section 6, PR 42): its layers take three.
_ROW_BOUND = 2


def bounds_rows(held, experts: int, shares=None) -> bool:
    """Whether a share `held` = (lo, hi) of `experts` is small enough to
    compute its rows in chunks of `shares` even shares (None: `_ROW_BOUND`):
    the chunk is under all the rows (by default: under half of the experts
    held)."""
    return (shares or _ROW_BOUND) * (held[1] - held[0]) < experts


def row_bound(rows: int, held, experts: int, tile: int, shares=None) -> int:
    """R, the rows of a share's chunk: `shares` (None: `_ROW_BOUND`) times
    the even share of the `rows` = T x k pairs, up to a whole row `tile`,
    and `rows` at most (then there is nothing to bound)."""
    need = -(-(shares or _ROW_BOUND) * rows * (held[1] - held[0]) // experts)
    return min(rows, -(-need // tile) * tile)


def _gmm_kernel(lhs, rhs, group_sizes, interpret: bool = False):
    """Direct kernel call, no dispatch gate (tests compile it for a
    described chip and run it interpreted)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    return gmm(lhs, rhs, group_sizes, lhs.dtype,
               _v5e_tiling(m, k, rhs.shape[2]), None, None, False, interpret)


def grouped_matmul(lhs, rhs, group_sizes):
    """[m, k] x [groups, k, n] -> [m, n]: row i is multiplied by the matrix
    of its group, rows laid out group after group, `group_sizes` int32
    [groups] summing to m (empty groups allowed). f32 accumulation, output
    in lhs's dtype."""
    if gmm_eligible(lhs, rhs):
        return _gmm_kernel(lhs, rhs, group_sizes)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)


# -- dispatch / combine: both directions are row gathers -------------------
# Every token appears in exactly k of the T*k sorted rows, so the gather's
# transpose (a scatter-add, slow on the TPU) is itself a gather through the
# inverse permutation plus a sum over the k slots. `order` sorts the
# (token, slot) pairs by expert; `inverse` undoes it.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inverse, k):
    return x[order // k]


def _dispatch_fwd(x, order, inverse, k):
    return x[order // k], inverse


def _dispatch_bwd(k, inverse, g):
    dx = g[inverse].reshape(-1, k, g.shape[-1])
    return dx.astype(jnp.float32).sum(1).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _undispatch(ys, order, inverse):
    return ys[inverse]


def _undispatch_fwd(ys, order, inverse):
    return ys[inverse], order


def _undispatch_bwd(order, g):
    return g[order], None, None


_undispatch.defvjp(_undispatch_fwd, _undispatch_bwd)


# -- a share's rows in chunks: gather in, sum out ---------------------------
# R rows (`row_bound`) of the sort by expert, the live ones first; `tok` [R]
# is each row's token. The gather's transpose and the combine are sums of
# rows into [T, d] float32. A token's rows are its k slots, so each sum is k
# row GATHERS through the sort's inverse and one fused add: `slot_row` [T, k]
# is where in the chunk a (token, slot) pair lies, `slot_live` [T, k] whether
# it lies there among the live rows. A scatter-add of the R rows is the
# slowest way the chip has of making such a sum: XLA's sorts the R indices,
# copies the rows to float32, gathers the copy into sorted order and adds row
# after row, dead rows (zeros) like live ones. Read on a v5e (PERF.md section
# 6, PR 51; ms a call at lfm2's / trinity's / glm's / the hybrid's shapes: T
# 16 384 / 8 192 / 8 192 / 8 192, k 4 / 8 / 4 / 6, R 24 576 / 24 576 / 8 192
# / 6 144, d 2048 but the hybrid's 2688, bf16 rows): the combine 1.21 / 1.01
# / 0.52 / 1.08 where `acc.at[tok].add(rows)` took 3.49 / 2.79 / 1.32 / 1.37,
# the gather's backward 0.91 / 0.86 / 0.37 / 0.87 for 3.28 / 2.53 / 1.11 /
# 1.05. The same sum as ONE gather of [T, k, d] and a sum over k reads 3.44 /
# 1.36 / 1.49 / 3.50 (a k of 4 or 6 pads the bf16 tile's sublanes), the
# scatter-add over the live rows alone, could it be had, 2.01 / 1.72 / 1.24 /
# 1.23, and in a loop over tiles of the live rows 2.5 / 1.6-3.5 / 0.9-2.1 /
# 0.9-1.7 by the tile (XLA lowers a tile's scatter by its size). PR 38 read
# the hybrid's scatter-add at 2.56 ms (R 12 288) and the whole-rows gather of
# [T x k, d] through the inverse, summed as [T, k, d], at 5.40.
def _sum_of_slots(rows, slot_row, slot_live, weights=None):
    """[T, d] float32: each token's sum over its live slots of rows
    [R, d][slot_row] (x weights [T, k]). The k gathered [T, d] arrays stand
    together in front of the add, so where the pairs are four chunks or more
    (k T >= 4 R) the tokens go in two halves, one after the other: by the
    chip, `peak_hbm_gib` against the scatter-add's (PERF.md section 6, PR
    51): the hybrid (k T = 8 R) + 0.11 GB whole and - 0.00 in halves, glm (4
    R) + 0.08 and + 0.02; lfm2 and trinity (2.7 R) - 0.21 and - 0.11 whole,
    and trinity + 0.11 and 1.3 ms a step slower in halves."""
    T, k = slot_row.shape
    step = T // 2 if T * k >= 4 * rows.shape[0] and T % 2 == 0 else T
    out = []
    for part in (slice(lo, lo + step) for lo in range(0, T, step)):
        total = 0.0
        for s in range(k):
            row = jnp.where(slot_live[part, s, None],
                            rows[slot_row[part, s]],
                            jnp.zeros((), rows.dtype)).astype(jnp.float32)
            total = total + (row if weights is None
                             else row * weights[part, s, None])
        out.append(total)
    return jnp.concatenate(out)


@jax.custom_vjp
def _add_rows(acc, rows, gates, pairs, slot_row, slot_live):
    """acc [T, d] float32 + rows [R, d], each weighted by its pair's gate
    (gates [T, k]; `pairs` [R]: the rows' (token, slot) pairs) and added to
    its token's."""
    return acc + _sum_of_slots(rows, slot_row, slot_live, gates)


def _add_rows_bwd(res, g):
    rows, gates, pairs = res
    of_rows = g[pairs // gates.shape[1]]                       # [R, d]
    row_gates = gates.reshape(-1)[pairs]
    d_gates = jnp.zeros((gates.size,), gates.dtype).at[pairs].add(
        (of_rows * rows.astype(jnp.float32)).sum(-1).astype(gates.dtype))
    return (g, (of_rows * row_gates[:, None]).astype(rows.dtype),
            d_gates.reshape(gates.shape), None, None, None)


_add_rows.defvjp(
    lambda acc, rows, gates, pairs, slot_row, slot_live: (
        _add_rows(acc, rows, gates, pairs, slot_row, slot_live),
        (rows, gates, pairs)), _add_rows_bwd)


@jax.custom_vjp
def _rows_of(x, tok, slot_row, slot_live):
    """x [T, d] -> [R, d]; backward summed in float32 whatever x's dtype."""
    return x[tok]


_rows_of.defvjp(
    lambda x, tok, slot_row, slot_live: (x[tok], (slot_row, slot_live)),
    lambda res, g: (_sum_of_slots(g, *res).astype(g.dtype), None, None, None))


def _summed_chunks(chunk, count, shape):
    """sum over j < count(ints) of `chunk(j, acc, ints, *operands)`, which
    adds chunk j to `acc` (`shape`, float32): a loop whose length the device
    decides, under ONE differentiation rule that keeps only its inputs (what
    `jax.checkpoint` keeps). The backward is the same loop: each chunk runs
    its own forward again and adds its gradients to sums in the operands'
    own dtypes (one chunk's are what they were; a stack's gradient in a
    step of more chunks is rounded to its dtype once a chunk: float32 sums
    would hold 0.3 GB a layer more). So the program holds the chunk's
    kernels once forward and once backward, however many chunks a step
    needs. `ints`: integer arrays, no cotangent.
    A chunk runs under the scope `chunk`, which also takes the `jvp(...)`
    that the backward's differentiation would else wrap around the chunk's
    own first scope."""
    def scoped(*args):
        with jax.named_scope("chunk"):
            return chunk(*args)

    @jax.custom_vjp
    def summed(ints, *operands):
        return jax.lax.fori_loop(
            0, count(ints), lambda j, acc: scoped(j, acc, ints, *operands),
            jnp.zeros(shape, jnp.float32))

    def bwd(res, g):
        ints, operands = res

        def body(j, sums):
            grads = jax.vjp(lambda *operands: scoped(
                j, jnp.zeros_like(g), ints, *operands), *operands)[1](g)
            return tuple(a + b for a, b in zip(sums, grads))

        return (None, *jax.lax.fori_loop(
            0, count(ints), body, tuple(jnp.zeros_like(o) for o in operands)))

    summed.defvjp(lambda ints, *operands: (summed(ints, *operands),
                                           (ints, operands)), bwd)
    return summed


def route(x, router_w, top_k: int, norm_topk_prob: bool,
          scoring: str = "softmax", bias=None, gate_scale: float = 1.0,
          gate_norm_eps: float = 0.0):
    """The router, in float32 whatever the activations' dtype: logits
    [T, E] (matmul at the highest precision: the TPU's default would round
    both inputs to bf16), then the scores and the top-k. `scoring`
    "softmax": the k largest probabilities are the gates. "sigmoid"
    (DeepSeek-V3's): s = sigmoid(logits), the CHOICE is the top k of s +
    `bias` ([E], no gradient), the gates are s of the chosen. Gates are
    divided by their sum (+ `gate_norm_eps`) with `norm_topk_prob` and
    multiplied by `gate_scale`. Returns (logits, gates [T, k], experts [T, k] int32)."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(probs, top_k)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        biased = scores if bias is None else scores + bias.astype(jnp.float32)
        _, experts = jax.lax.top_k(jax.lax.stop_gradient(biased), top_k)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    if norm_topk_prob:
        total = gates.sum(-1, keepdims=True)
        gates = gates / (total + gate_norm_eps if gate_norm_eps else total)
    if gate_scale != 1.0:
        gates = gates * gate_scale
    return logits, gates, experts.astype(jnp.int32)


def relu2(x):
    """relu(x)^2 in float32 (Primer's squared ReLU; Nemotron's `relu2`)."""
    return jnp.square(jax.nn.relu(x.astype(jnp.float32)))


def moe_ffn(x, router_w, gate_w, up_w, down_w, top_k: int,
            norm_topk_prob: bool = False, *, scoring: str = "softmax",
            router_bias=None, gate_scale: float = 1.0, held=None,
            shared=None, chunk_shares=None, gate_norm_eps: float = 0.0):
    """x [T, d] -> (out [T, d], router logits [T, E] f32, tokens per expert
    [E] int32, pairs per held expert [held] int32 or None, the row path [2]
    int32 or None, the chunks' rows [2] int32 or None). y = sum_j gate_j *
    expert_{e_j}(x) over the token's top_k experts e_j of all E the router
    scores; expert e is (silu(x Wg[e]) * (x Wu[e])) Wd[e], or, with `gate_w`
    None, relu(x Wu[e])^2 Wd[e].

    `held` (lo, hi): the stacks hold experts lo..hi-1 only. The (token,
    slot) pairs that chose one of them are sorted to the front of the T x k
    rows, the grouped matmuls get group sizes that sum to fewer than the
    rows, and what lies behind is zero going in and coming out (a kernel
    leaves those rows unwritten); pairs that chose an absent expert add
    nothing. None: all E are here, and the ops are those of a layer that
    knows no shares.

    A share under half of the experts (`bounds_rows`) works through that
    sort in chunks of R rows (`row_bound`: `chunk_shares` even shares of the
    T x k rows, two unless the layer says otherwise), as many as its held
    pairs fill and at least one: a chunk
    gathers R rows of x, runs the matmuls, masks and activation on [R, .]
    and adds the R gate-weighted rows into [T, d]. One chunk in a step whose
    routing keeps to the bound, more in a step whose routing does not, the
    count taken on the device (`_summed_chunks`): every held pair is
    computed either way, and only the order of a token's sum differs from
    the whole rows'. The row path says which it was: [1, 0] one chunk,
    [0, 1] more; None where the rows have no bound. The chunks' rows say
    how full they were: [the live rows among them, the only ones a chunk's
    sums read (`_sum_of_slots`), the step's chunks x R].

    `shared`: a shared expert, every token, of the routed experts' kind:
    (up [d, f_s], down [f_s, d]) beside relu^2 experts: + relu(x up)^2 down;
    (gate, up, down) beside SwiGLU experts: + (silu(x gate) * (x up)) down.

    The expert matmuls run, and `out` is returned, in the expert weights'
    dtype (the amp dtype where the caller cast them); the router reads x as
    it comes (float32 from rms_norm)."""
    T, d = x.shape
    E = router_w.shape[1]
    lo, hi = held or (0, E)
    part = (lo, hi) != (0, E)
    rows = T * top_k
    with jax.named_scope("route"):
        logits, gates, experts = route(x, router_w, top_k, norm_topk_prob,
                                       scoring, router_bias, gate_scale,
                                       gate_norm_eps)
    with jax.named_scope("dispatch"):
        flat = experts.reshape(-1)                       # [T*k]
        # an absent expert's pairs sort behind every held one's
        key = jnp.where((flat >= lo) & (flat < hi), flat - lo, hi - lo) \
            if part else flat
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        counts = jnp.zeros((E,), jnp.int32).at[flat].add(1)
        group_sizes = counts[lo:hi] if part else counts
        cd = up_w.dtype

    # an expert width the kernel's tiling does not take is padded with zero
    # columns of the up (and gate) stack and zero rows of the down stack:
    # the hidden rows stay at the padded width and the padding adds nothing
    f_pad = kernel_width_pad(rows, d, up_w.shape[2], cd)
    # a share under half of the experts computes R of its rows a chunk
    R, bound = rows, part and bounds_rows((lo, hi), E, chunk_shares)
    if bound:
        kernel = gmm_eligible(
            jax.ShapeDtypeStruct((rows, d), cd),
            jax.ShapeDtypeStruct((1, d, up_w.shape[2] + f_pad), cd))
        R = row_bound(rows, (lo, hi), E, _V5E_TILING[0] if kernel else 8,
                      chunk_shares)

    def hidden_rows(xs, down, ups, group_sizes, live):
        """[m, d] rows sorted by expert -> their experts' outputs [m, d];
        `live` [m, 1]: the rows inside the groups, None where all are."""
        if f_pad:
            ups = [jnp.pad(w, ((0, 0), (0, 0), (0, f_pad))) for w in ups]
            down = jnp.pad(down, ((0, 0), (0, f_pad), (0, 0)))

        def alive(rows):
            return rows if live is None else jnp.where(
                live, rows, jnp.zeros_like(rows))

        def matmul(lhs, rhs):
            # zeros in AND out: the backward's rows behind the groups are as
            # unwritten as the forward's, and `where` stops them both ways
            return alive(grouped_matmul(alive(lhs), rhs, group_sizes))

        pre = [matmul(xs, w) for w in ups]
        if len(pre) == 2:
            h = (jax.nn.silu(pre[0].astype(jnp.float32))
                 * pre[1].astype(jnp.float32)).astype(cd)
        else:
            h = relu2(pre[0]).astype(cd)
        return matmul(h, down)

    def whole(ints, x, gates, down, *ups):     # ups: (gate, up) or (up,)
        """Every one of the T x k rows."""
        order, inverse, group_sizes = ints
        live = (jnp.arange(rows) < group_sizes.sum())[:, None] \
            if part else None
        with jax.named_scope("dispatch"):
            xs = _dispatch(x.astype(cd), order, inverse, top_k)   # [T*k, d]
        with jax.named_scope("experts"):
            ys = hidden_rows(xs, down, ups, group_sizes, live)   # [T*k, d]
        with jax.named_scope("combine"):
            yu = _undispatch(ys, order, inverse).reshape(T, top_k, d)
            return (yu.astype(jnp.float32) * gates[..., None]).sum(1)

    def chunk(j, acc, ints, x, gates, down, *ups):
        """Rows j R .. (j + 1) R of the sort, added to `acc` [T, d]: each
        group's part of them; a row behind the live ones is a pair of an
        absent expert (or none: the sort padded to whole chunks), zero going
        in and coming out as in `whole`."""
        order, inverse, group_sizes = ints
        with jax.named_scope("dispatch"):
            first = j * R
            ends = jnp.cumsum(group_sizes)
            cut = jnp.clip(jnp.stack([ends - group_sizes, ends]),
                           first, first + R)
            live = (first + jnp.arange(R) < ends[-1])[:, None]
            pairs = jax.lax.dynamic_slice(order, (first,), (R,))
            # each token's k pairs as rows of this chunk
            slot_row = inverse.reshape(T, top_k) - first
            slot_live = (slot_row >= 0) & (
                slot_row < jnp.minimum(R, ends[-1] - first))
            slot_row = jnp.clip(slot_row, 0, R - 1)
            xs = _rows_of(x.astype(cd), pairs // top_k, slot_row,
                          slot_live)                             # [R, d]
        with jax.named_scope("experts"):
            ys = hidden_rows(xs, down, ups, cut[1] - cut[0], live)
        with jax.named_scope("combine"):
            return _add_rows(acc, ys, gates, pairs, slot_row, slot_live)

    ups = (up_w,) if gate_w is None else (gate_w, up_w)
    ints, path, chunk_rows = (order, inverse, group_sizes), None, None
    if R < rows:
        # as many chunks of R rows as the held pairs fill: one, in a step
        # whose routing keeps to the bound
        def chunks(ints):
            return jnp.maximum(1, -(-ints[2].sum() // R))

        ints = (jnp.pad(order, (0, -rows % R)), inverse, group_sizes)
        out = _summed_chunks(chunk, chunks, (T, d))(
            ints, x, gates, down_w, *ups)
        n = chunks(ints)
        path = jnp.stack([n == 1, n > 1]).astype(jnp.int32)
        chunk_rows = jnp.stack([group_sizes.sum(), n * R])
    elif part:
        # a share keeps nothing of its T x k rows for the backward (held,
        # they would be 1 GB a layer at 8 192 tokens): it gathers them again
        out = jax.checkpoint(whole)(ints, x, gates, down_w, *ups)
        if bound:                          # its bound reaches all the rows
            path = jnp.array([0, 1], jnp.int32)
            chunk_rows = jnp.array([rows, rows], jnp.int32)
    else:
        out = whole(ints, x, gates, down_w, *ups)
    if shared is not None:
        if len(shared) != len(ups) + 1:
            raise ValueError(
                f"the shared expert follows the routed experts' kind: "
                f"{len(ups) + 1} matrices, got {len(shared)}")
        with jax.named_scope("shared"):
            *ups_s, down_s = shared
            pre = [jnp.dot(x.astype(cd), w,
                           preferred_element_type=jnp.float32) for w in ups_s]
            if len(pre) == 2:
                hs = (jax.nn.silu(pre[0]) * pre[1]).astype(cd)
            else:
                hs = relu2(pre[0]).astype(cd)
            out = out + jnp.dot(hs, down_s,
                                preferred_element_type=jnp.float32)
    return (out.astype(cd), logits, counts,
            group_sizes if part else None, path, chunk_rows)


@register_op("moe_ffn")
def moe_ffn_kernel(ctx):
    """Program-IR face: X [B, T, d] (or [T, d]); RouterW [d, E]; GateW
    (absent: relu^2 experts), UpW [held, d, f]; DownW [held, f, d]; optional
    RouterBias [E], SharedUpW [d, f_s], SharedDownW [f_s, d] and, beside
    SwiGLU experts, SharedGateW [d, f_s] (the shared expert is of the routed
    experts' kind: `expert_act`). Attrs: top_k,
    norm_topk_prob, and where they differ from a softmax router over experts
    that are all here: scoring, gate_scale, gate_norm_eps (added to the sum
    the chosen gates are divided by), held_lo / held_hi, and chunk_shares (absent: two even shares of the rows a chunk). Out shaped
    like X, in the compute dtype; RouterLogits [tokens, E] float32 (under
    amp too: the router never drops precision, the expert matmuls do);
    TokensPerExpert [E] int32, summing to tokens x top_k; HeldPairs [held]
    int32 where the op holds a share; where its rows have a bound, RowPath
    [2] and ChunkRows [2] int32 (`moe_ffn`'s row path and chunks' rows)."""
    x = ctx.input("X")
    gate_w, up_w, down_w = amp.cast_inputs(
        ctx, ctx.input("GateW"), ctx.input("UpW"), ctx.input("DownW"))
    shared = None
    if ctx.has_input("SharedUpW"):
        slots = ("SharedUpW", "SharedDownW")
        if ctx.has_input("SharedGateW"):
            slots = ("SharedGateW",) + slots
        shared = amp.cast_inputs(ctx, *(ctx.input(slot) for slot in slots))
    held = None
    if ctx.attr("held_hi") is not None:
        held = (int(ctx.attr("held_lo")), int(ctx.attr("held_hi")))
    out, logits, counts, held_pairs, row_path, chunk_rows = moe_ffn(
        x.reshape(-1, x.shape[-1]), ctx.input("RouterW"), gate_w, up_w,
        down_w, int(ctx.attr("top_k")),
        bool(ctx.attr("norm_topk_prob", False)),
        scoring=ctx.attr("scoring", "softmax"),
        router_bias=ctx.input("RouterBias"),
        gate_scale=float(ctx.attr("gate_scale", 1.0)), held=held,
        shared=shared, chunk_shares=ctx.attr("chunk_shares"),
        gate_norm_eps=float(ctx.attr("gate_norm_eps", 0.0)))
    ctx.set_output("Out", out.reshape(x.shape))
    ctx.set_output("RouterLogits", logits)
    ctx.set_output("TokensPerExpert", counts)
    if held_pairs is not None:
        ctx.set_output("HeldPairs", held_pairs)
    if row_path is not None:
        ctx.set_output("RowPath", row_path)
    if chunk_rows is not None:
        ctx.set_output("ChunkRows", chunk_rows)


@register_op("moe_aux_loss")
def moe_aux_loss_kernel(ctx):
    """The routed layer's two auxiliary costs, float32, as one scalar:
    balance_weight * E * sum_e f_e P_e (f_e: the share of the (token, slot)
    pairs routed to expert e, a count and so without gradient; P_e: the mean
    router probability of e) + z_weight * mean(logsumexp(logits)^2)."""
    logits = ctx.input("RouterLogits").astype(jnp.float32)
    counts = ctx.input("TokensPerExpert").astype(jnp.float32)
    E = logits.shape[-1]
    share = jax.lax.stop_gradient(counts / counts.sum())
    mean_prob = jax.nn.softmax(logits, axis=-1).mean(0)
    balance = E * jnp.sum(share * mean_prob)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    cost = (ctx.attr("balance_weight", 0.01) * balance
            + ctx.attr("z_weight", 0.001) * z)
    ctx.set_output("Out", cost)
