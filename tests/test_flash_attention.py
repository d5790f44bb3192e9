"""Flash attention: the dispatcher, the reference path, and the packed
kernels in Pallas interpret mode (CPU).

On the CPU CI mesh the dispatcher must fall back to the reference
formulation, which the first tests pin against
scaled_dot_product_attention. The fused kernels themselves (ops/flash_ops.py)
run here interpreted, against the same oracle; that they compile for a v5e
at the benchmark's shapes is tests/test_tpu_compile.py's, and what they cost
on the chip PERF.md's.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt  # noqa: F401  (registers ops; forces CPU in CI)
from paddle_tpu import parallel as pp
from paddle_tpu.ops.flash_ops import flash_attention, flash_eligible


def _qkv(B=2, T=16, H=2, D=8, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    return mk(), mk(), mk()


def test_cpu_falls_back_to_reference():
    q, k, v = _qkv()
    assert jax.default_backend() != "tpu"  # conftest forces CPU
    assert not flash_eligible(q)
    out = flash_attention(q, k, v, causal=True)
    ref = pp.scaled_dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_non_causal_matches_oracle():
    q, k, v = _qkv(seed=3)
    out = flash_attention(q, k, v, causal=False)
    ref = pp.scaled_dot_product_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_gradients_flow():
    q, k, v = _qkv(seed=5)
    g = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, causal=True)))(q)
    assert np.all(np.isfinite(np.asarray(g)))
    assert float(jnp.abs(g).max()) > 0


def test_rank_check():
    with pytest.raises(ValueError, match="B, T, H, D"):
        flash_attention(jnp.zeros((4, 8, 2)), jnp.zeros((4, 8, 2)),
                        jnp.zeros((4, 8, 2)))


def test_eligibility_rules():
    """Shape rules are tested backend-independently (_shapes_flash_ok) —
    on the CPU mesh flash_eligible is False for everything via the
    backend check alone, which the fallback test covers."""
    from paddle_tpu.ops.flash_ops import _shapes_flash_ok

    ok = jnp.zeros((1, 256, 2, 128))
    assert _shapes_flash_ok(ok, ok)
    assert not _shapes_flash_ok(jnp.zeros((1, 100, 2, 128)), ok)  # q T
    assert not _shapes_flash_ok(ok, jnp.zeros((1, 100, 2, 128)))  # kv T
    assert not _shapes_flash_ok(jnp.zeros((1, 256, 2, 48)), ok)   # head dim
    assert not flash_eligible(ok)  # CPU backend gate

    # routing: from T=1024 up the kernels take the job; below that window
    # only the memory-capability rule (score bytes past ~1.5 GB) pulls
    # them in
    from paddle_tpu.ops.flash_ops import _prefers_flash

    tiny = jnp.zeros((2, 512, 8, 128))     # below the window, 64 MB → XLA
    medium = jnp.zeros((2, 2048, 8, 128))  # inside the window → kernel
    big = jnp.zeros((1, 32768, 4, 128))    # scores ~8.6 GB → kernel
    assert not _prefers_flash(tiny, tiny)
    assert _prefers_flash(medium, medium)
    assert _prefers_flash(big, big)


def test_ulysses_uses_flash_dispatch_path():
    """Ulysses routes local attention through flash_attention; on the CPU
    mesh that's the reference formulation — results must still match the
    single-device oracle exactly."""
    mesh = pp.make_mesh((8,), (pp.SP,))
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, 32, 8, 4).astype(np.float32))
    out = pp.ulysses_attention(q, q, q, mesh, causal=True)
    ref = pp.scaled_dot_product_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_v5e_blocks_divide_any_eligible_length():
    """The kernel hard-crashes if a block doesn't divide T; every
    128-aligned T the shape rules admit must get divisor blocks."""
    from paddle_tpu.ops.flash_ops import _v5e_block_sizes

    for T in (1024, 1152, 1280, 2048, 4096, 8192, 8320, 16384, 33280):
        bs = _v5e_block_sizes(T, T)
        assert T % bs.block_q == 0 and T % bs.block_k == 0, (T, bs)
        assert bs.block_q % 128 == 0 and bs.block_k % 128 == 0
    # the tuned targets are hit where they divide
    assert _v5e_blocks_q(2048) == 512
    assert _v5e_blocks_q(16384) == 1024
    assert _v5e_blocks_q(1280) == 256


def _v5e_blocks_q(T):
    from paddle_tpu.ops.flash_ops import _v5e_block_sizes

    return _v5e_block_sizes(T, T).block_q


# ---------------------------------------------------------------------------
# The packed kernels (ops/flash_ops.py), in Pallas interpret mode on the CPU
# against the plain formulation. [B, T, E] in and out; at head dim 64 a lane
# block carries two heads.

def _packed_case(B, Tq, Tk, H, D, dtype, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda T: jnp.asarray(rng.randn(B, T, H * D) * 0.5, dtype)  # noqa: E731
    return mk(Tq), mk(Tk), mk(Tk), jnp.asarray(rng.randn(B, Tq, H * D),
                                               jnp.float32)


def _oracle_packed(q, k, v, H, causal):
    """scaled_dot_product_attention over packed float32 copies."""
    split = lambda x: x.astype(jnp.float32).reshape(  # noqa: E731
        x.shape[0], x.shape[1], H, x.shape[2] // H)
    o = pp.scaled_dot_product_attention(split(q), split(k), split(v),
                                        causal=causal)
    return o.reshape(q.shape)


PACKED_CASES = [
    # id, B, Tq, Tk, heads, D, causal, dtype
    ("d64_h2_causal_f32", 1, 256, 256, 2, 64, True, jnp.float32),
    ("d64_h12_causal_bf16", 1, 256, 256, 12, 64, True, jnp.bfloat16),
    ("d64_h2_full_f32", 2, 256, 256, 2, 64, False, jnp.float32),
    ("d64_h4_cross_f32", 1, 256, 384, 4, 64, False, jnp.float32),
    ("d64_h2_cross_causal_f32", 1, 384, 256, 2, 64, True, jnp.float32),
    ("d128_h2_causal_f32", 1, 256, 256, 2, 128, True, jnp.float32),
    ("d128_h3_full_bf16", 1, 256, 256, 3, 128, False, jnp.bfloat16),
    ("d256_h1_causal_f32", 1, 256, 256, 1, 256, True, jnp.float32),
    # latent attention's head (GLM-4.7-Flash: 192 + 64): one head over two
    # lane tiles, several heads, bf16 as under AMP
    ("d256_h2_causal_bf16", 1, 256, 256, 2, 256, True, jnp.bfloat16),
    ("d256_h3_full_f32", 1, 256, 256, 3, 256, False, jnp.float32),
    ("d256_h2_causal_blocks_bf16", 1, 1024, 1024, 2, 256, True, jnp.bfloat16),
    # several q and k blocks: the causal rule skips a block, masks the
    # diagonal ones and leaves one whole
    ("d64_h2_causal_blocks_bf16", 1, 1024, 1024, 2, 64, True, jnp.bfloat16),
    # the same in float32, a head dim each: the diagonal blocks (512 rows) are
    # computed as two strips of 256 rows, three quarters of the block, and
    # the tolerance is the tight one
    ("d64_h2_causal_strips_f32", 1, 1024, 1024, 2, 64, True, jnp.float32),
    ("d128_h2_causal_strips_f32", 1, 1024, 1024, 2, 128, True, jnp.float32),
    ("d256_h1_causal_strips_f32", 1, 1024, 1024, 1, 256, True, jnp.float32),
    ("d64_h2_cross_causal_strips_f32", 1, 1536, 1024, 2, 64, True,
     jnp.float32),
]


@pytest.mark.parametrize("B,Tq,Tk,H,D,causal,dtype",
                         [c[1:] for c in PACKED_CASES],
                         ids=[c[0] for c in PACKED_CASES])
def test_packed_kernel_matches_oracle(B, Tq, Tk, H, D, causal, dtype):
    """Output and the gradients of Q, K and V against jax.grad of the
    oracle."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.flash_ops import _packed_attention

    q, k, v, w = _packed_case(B, Tq, Tk, H, D, dtype)
    loss = lambda att: (lambda q, k, v: jnp.sum(  # noqa: E731
        att(q, k, v).astype(jnp.float32) * w))
    with pltpu.force_tpu_interpret_mode():
        out = _packed_attention(q, k, v, H, causal)
        grads = jax.grad(loss(lambda *a: _packed_attention(*a, H, causal)),
                         (0, 1, 2))(q, k, v)
    assert out.shape == q.shape and out.dtype == q.dtype
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref = _oracle_packed(q, k, v, H, causal)
    ref_grads = jax.grad(loss(lambda *a: _oracle_packed(*a, H, causal)),
                         (0, 1, 2))(*f32)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)
    for g, r in zip(grads, ref_grads):
        assert g.dtype == dtype
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(np.asarray(g, np.float32) / scale,
                                   np.asarray(r) / scale, rtol=0, atol=tol)


def test_packed_backward_split_matches_fused(monkeypatch):
    """Beyond _FUSED_BWD_MAX_ELEMENTS (query rows x a lane block's lanes) dQ
    gets a pass of its own: the same gradients as the one fused pass. The
    rule keeps every shape that ran fused before it (8192 rows of a 256-lane
    block) and takes 16 384 rows of a 128-lane one."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops import flash_ops

    q, k, v, w = _packed_case(1, 256, 384, 2, 64, jnp.float32, seed=4)
    grad = jax.grad(lambda q, k, v: jnp.sum(
        flash_ops._packed_attention(q, k, v, 2, False) * w), (0, 1, 2))
    limit = flash_ops._FUSED_BWD_MAX_ELEMENTS
    with pltpu.force_tpu_interpret_mode():
        fused = grad(q, k, v)
        monkeypatch.setattr(flash_ops, "_FUSED_BWD_MAX_ELEMENTS", 0)
        split = grad(q, k, v)
    for a, b in zip(fused, split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    assert 8192 * 256 <= limit and 16384 * 128 <= limit < 16384 * 256


def test_bthd_entry_and_packed_entry_agree():
    """flash_attention's [B, T, H, D] callers (ring, Ulysses, the dp wrap)
    reach the same kernel through a free reshape."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.flash_ops import _flash_kernel, _packed_attention

    q, k, v, _ = _packed_case(1, 256, 256, 2, 64, jnp.float32, seed=2)
    split = lambda x: x.reshape(1, 256, 2, 64)  # noqa: E731
    with pltpu.force_tpu_interpret_mode():
        packed = _packed_attention(q, k, v, 2, True)
        bthd = _flash_kernel(split(q), split(k), split(v), True)
    np.testing.assert_array_equal(np.asarray(bthd),
                                  np.asarray(split(packed)))


# ---------------------------------------------------------------------------
# The window bound (a second, lower diagonal: position i reads the keys
# i - W < j <= i) in the same kernels, interpreted, against a mask written
# out in numpy. Blocks are handed in, so that a 512-row case has 4 x 4 of them.

def _window_oracle(q, k, v, H, window):
    """Packed float32 attention under `0 <= i - j < window`, GQA by
    repeating K and V: plain numpy-style jnp, no shared code with the op."""
    B, T, E = q.shape
    D = E // H
    KV = k.shape[2] // D
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    qh = q.reshape(B, T, H, D)
    kh = jnp.repeat(k.reshape(B, T, KV, D), H // KV, axis=2)
    vh = jnp.repeat(v.reshape(B, T, KV, D), H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) / np.sqrt(D)
    ahead = np.arange(T)[:, None] - np.arange(T)[None, :]
    keep = (ahead >= 0) & (ahead < window)
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vh).reshape(B, T, E)


def _window_case(T, H, KV, D, dtype, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda heads: jnp.asarray(rng.randn(1, T, heads * D) * 0.5, dtype)  # noqa: E731
    return mk(H), mk(KV), mk(KV), jnp.asarray(rng.randn(1, T, H * D),
                                              jnp.float32)


WINDOW_CASES = [
    # id, T, heads, kv heads, D, window, (block_q, block_k), dtype
    ("one_block", 512, 2, 2, 128, 128, (128, 128), jnp.float32),
    ("several_blocks", 512, 2, 2, 128, 256, (128, 128), jnp.float32),
    ("not_a_multiple_of_the_block", 768, 1, 1, 128, 384, (256, 256),
     jnp.float32),
    ("narrower_than_the_block", 512, 1, 1, 128, 128, (256, 256), jnp.float32),
    ("uneven_blocks", 512, 1, 1, 128, 128, (256, 128), jnp.float32),
    ("uneven_blocks_k_larger", 512, 1, 1, 128, 256, (128, 256), jnp.float32),
    ("d64_two_heads_a_block", 512, 2, 2, 64, 128, (128, 128), jnp.float32),
    ("d256_bf16", 512, 1, 1, 256, 256, (128, 128), jnp.bfloat16),
    ("gqa_group_2", 384, 4, 2, 128, 128, (128, 128), jnp.float32),
    ("gqa_group_8_bf16", 512, 8, 1, 128, 256, (128, 128), jnp.bfloat16),
    # blocks of 512 and of 1024, the sizes the chip runs, a crossed block in
    # two strips of half its rows: causal alone (window 0), windows of one,
    # two and three blocks' width; and what computes the whole crossed block
    # under the mask: a window that is no multiple of the block, uneven
    # blocks; grouped K/V heads; every head dim
    ("b512_causal_alone_d64", 1024, 2, 2, 64, 0, (512, 512), jnp.float32),
    ("b512_w1blk_d128", 1536, 1, 1, 128, 512, (512, 512), jnp.float32),
    ("b512_w1blk_d64_two_heads_a_block", 1536, 2, 2, 64, 512, (512, 512),
     jnp.float32),
    ("b512_w2blk_gqa_group_2", 1536, 4, 2, 128, 1024, (512, 512),
     jnp.float32),
    ("b512_w3blk_d256_bf16", 2048, 1, 1, 256, 1536, (512, 512), jnp.bfloat16),
    ("b512_w_not_a_multiple_d64", 1536, 2, 2, 64, 640, (512, 512),
     jnp.float32),
    ("b1024_causal_alone_d128", 2048, 1, 1, 128, 0, (1024, 1024),
     jnp.float32),
    ("b1024_w1blk_gqa_group_2", 3072, 2, 1, 128, 1024, (1024, 1024),
     jnp.float32),
    ("b1024_w2blk_d128_bf16", 3072, 1, 1, 128, 2048, (1024, 1024),
     jnp.bfloat16),
    ("b1024_w_narrower_than_half_a_block_d256", 2048, 1, 1, 256, 384,
     (1024, 1024), jnp.float32),
    ("b1024_uneven_blocks_d64", 2048, 2, 2, 64, 1280, (512, 1024),
     jnp.float32),
]


@pytest.mark.parametrize("T,H,KV,D,window,blocks,dtype",
                         [c[1:] for c in WINDOW_CASES],
                         ids=[c[0] for c in WINDOW_CASES])
def test_window_kernels_match_the_plain_mask(T, H, KV, D, window, blocks,
                                             dtype):
    """Forward, the fused backward and the split backward (dK / dV and dQ in
    a pass each) under `causal + window` (window 0: causal alone), against
    the mask written out."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops import flash_ops

    q, k, v, w = _window_case(T, H, KV, D, dtype)
    blocks = flash_ops.FlashBlocks(*blocks)
    with pltpu.force_tpu_interpret_mode():
        out, lse = flash_ops._packed_forward(
            q, k, v, heads=H, causal=True, blocks=blocks, statistics=True,
            window=window)
        do = w.astype(dtype)
        back = [flash_ops._packed_backward(
            q, k, v, out, lse, do, heads=H, causal=True, blocks=blocks,
            fused=fused, window=window) for fused in (True, False)]
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref, vjp = jax.vjp(lambda *a: _window_oracle(*a, H, window or T), *f32)
    ref_grads = vjp(do.astype(jnp.float32))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)
    for grads in back:
        for g, r in zip(grads, ref_grads):
            assert g.dtype == dtype and g.shape == r.shape
            scale = float(jnp.max(jnp.abs(r)))
            np.testing.assert_allclose(np.asarray(g, np.float32) / scale,
                                       np.asarray(r) / scale, rtol=0,
                                       atol=tol)


@pytest.mark.parametrize("T,bq,bk,window", [
    (1024, 128, 128, 128), (1024, 128, 128, 384), (1024, 256, 128, 128),
    (1024, 128, 256, 384), (1024, 256, 256, 384), (8192, 1024, 1024, 2048),
    (8192, 512, 1024, 2048),
], ids=["w1blk", "w3blk", "q256_k128", "q128_k256_w384", "w1.5blk",
        "trinity_8k", "trinity_8k_uneven"])
def test_window_index_maps_name_only_blocks_inside_the_band(T, bq, bk, window):
    """The k blocks a q block's steps name (and the q blocks a k block's
    steps name in the backward) are exactly those with a pair inside the band
    0 <= i - j < window: nothing outside it is fetched. At the Trinity cell's
    sizes (T 8192, blocks of 1024, W 2048) a q block names at most 3."""
    from paddle_tpu.ops import flash_ops

    nq, nk = T // bq, T // bk
    kmap = flash_ops._k_range(True, window, bq, bk)
    qmap = flash_ops._q_range(True, window, bq, bk, T)

    def touches(qi, ki):
        # the band's nearest pair: the block's closest (row, column)
        lo = qi * bq - (ki * bk + bk - 1)           # least i - j
        hi = qi * bq + bq - 1 - ki * bk             # greatest i - j
        return hi >= 0 and lo < window

    for qi in range(nq):
        named = {int(kmap(qi, ki)) for ki in range(nk)}
        assert named == {ki for ki in range(nk) if touches(qi, ki)}, qi
    for ki in range(nk):
        named = {int(qmap(ki, qi)) for qi in range(nq)}
        assert named == {qi for qi in range(nq) if touches(qi, ki)}, ki
    if (T, bq, bk, window) == (8192, 1024, 1024, 2048):
        assert max(len({int(kmap(qi, ki)) for ki in range(nk)})
                   for qi in range(nq)) == 3


STRIPS = [
    # id, T, bq, bk, window
    ("gpt2", 1024, 512, 512, 0),
    ("trinity_window", 8192, 1024, 1024, 2048),
    ("glm_global", 8192, 1024, 1024, 0),
    ("window_of_one_block", 2048, 512, 512, 512),
    ("window_of_three_blocks", 4096, 512, 512, 1536),
    ("blocks_of_256", 1024, 256, 256, 256),
    # whole crossed blocks under the mask
    ("window_no_multiple_of_the_block", 2048, 512, 512, 640),
    ("window_inside_a_block", 2048, 512, 512, 128),
    ("uneven_blocks", 2048, 512, 1024, 1024),
    ("uneven_blocks_k_smaller", 2048, 1024, 512, 1024),
    ("blocks_of_one_lane_tile", 1024, 128, 128, 256),
    ("window_of_one_key", 1024, 256, 256, 1),
]


def _band(T, window):
    ahead = np.arange(T)[:, None] - np.arange(T)[None, :]
    return (ahead >= 0) & (ahead < (window or T))


def _computed_of(block, q0, k0, bq, bk, window, columns):
    """The pairs of a crossed block the kernels compute, as a mask over it:
    `_strips`' two pieces, or all of it."""
    from paddle_tpu.ops import flash_ops

    done = np.zeros_like(block)
    if not flash_ops._in_strips(bq, bk, window):
        done[:] = True
        return done
    h = bq // 2
    (r0, c0), long = flash_ops._strips(q0, k0, bq, window, columns,
                                       where=np.where)
    r0, c0, long = int(r0), int(c0), int(long)
    assert r0 == c0 and {r0, long} == {0, h}
    done[r0:r0 + h, c0:c0 + h] = True
    if columns:
        done[:, long:long + h] = True
    else:
        done[long:long + h, :] = True
    return done


@pytest.mark.parametrize("columns", [False, True], ids=["rows", "columns"])
@pytest.mark.parametrize("T,bq,bk,window", [c[1:] for c in STRIPS],
                         ids=[c[0] for c in STRIPS])
def test_strips_hold_every_kept_pair_and_leave_out_the_empty_quarter(
        T, bq, bk, window, columns):
    """`_block_kind` and `_strips`, the pure functions the kernels place their
    steps by, over every block of a sequence against the mask written out: a
    block is computed iff it holds a kept pair, whole and bare iff it holds
    nothing else; of a crossed block every kept pair lies in a computed
    piece, every computed quarter holds a kept pair (a quarter's K/V columns
    are live for other rows, so poison cannot show one computed for nothing;
    the count can), and in strips exactly one quarter is left out. The
    published counts (`pair_counts`) are these."""
    from paddle_tpu.ops import flash_ops

    keep = _band(T, window)
    computed = 0
    for q0 in range(0, T, bq):
        for k0 in range(0, T, bk):
            block = keep[q0:q0 + bq, k0:k0 + bk]
            inside, crosses = flash_ops._block_kind(q0, bq, k0, bk, window)
            assert bool(inside) == block.any(), (q0, k0)
            if not block.any():
                continue
            assert bool(crosses) == (not block.all()), (q0, k0)
            if block.all():
                computed += bq * bk
                continue
            done = _computed_of(block, q0, k0, bq, bk, window, columns)
            assert not (block & ~done).any(), (q0, k0)
            computed += int(done.sum())
            if flash_ops._in_strips(bq, bk, window):
                h = bq // 2
                quarters = block.reshape(2, h, 2, h).any(axis=(1, 3))
                assert (quarters == done.reshape(2, h, 2, h).all(axis=(1, 3))
                        ).all(), (q0, k0)
                assert quarters.sum() == 3
    assert flash_ops.pair_counts(T, T, True, window, (bq, bk)) == (
        computed, int(keep.sum()))
    assert flash_ops.pair_counts(T, T, True, window) == (T * T,
                                                         int(keep.sum()))
    assert flash_ops.pair_counts(T, T, False) == (T * T, T * T)


def test_a_crossed_block_goes_in_strips_where_each_diagonal_has_its_own_block():
    from paddle_tpu.ops.flash_ops import _in_strips

    assert _in_strips(512, 512, 0) and _in_strips(1024, 1024, 2048)
    assert _in_strips(256, 256, 256) and _in_strips(512, 512, 1536)
    assert not _in_strips(128, 128, 0)          # half a block: 64 rows
    assert not _in_strips(384, 384, 0)          # 192 rows: no whole lane tiles
    assert not _in_strips(512, 1024, 0) and not _in_strips(1024, 512, 2048)
    assert not _in_strips(512, 512, 640) and not _in_strips(512, 512, 128)


@pytest.mark.parametrize("T,window,share", [
    (1024, 0, 1 - 524800 / (2.5 * 512 * 512)),          # gpt2-small: 0.1992
    (4096, 0, 1 - 8390656 / (34 * 512 * 512)),          # olmoe, ouro: 0.0586
], ids=["gpt2_small", "olmoe_ouro"])
def test_masked_share_at_the_cells_shapes(T, window, share):
    """The arithmetic PERF.md section 3 quotes: blocks of 512 below T 8192;
    the parent computed 3 and 36 blocks for these pairs (0.3327, 0.1110)."""
    from paddle_tpu.ops.flash_ops import _v5e_block_sizes, pair_counts

    computed, kept = pair_counts(T, T, True, window, _v5e_block_sizes(T, T))
    assert 1 - kept / computed == pytest.approx(share)


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for sub in value if isinstance(value, (list, tuple)) else [value]:
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _kernel_bodies(jaxpr, found):
    """{kernel name: its body's jaxpr} of every pallas_call under `jaxpr`."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = eqn.params["jaxpr"]
            continue
        for sub in _sub_jaxprs(eqn):
            _kernel_bodies(sub, found)
    return found


def _count(jaxpr, primitive):
    return sum((eqn.primitive.name == primitive)
               + sum(_count(sub, primitive) for sub in _sub_jaxprs(eqn))
               for eqn in jaxpr.eqns)


KERNEL_PAIRS = [
    # id, Q's shape, K/V's width, heads, window, the parent's dot_generals in
    # the forward and the fused backward body (c958758: a whole-block masked
    # and a whole-block bare copy of the step, 2 and 5 a head of a lane block)
    ("gpt2_d64", (12, 1024, 768), 768, 12, 0, 8, 20),
    ("trinity_global_d128_gqa", (1, 8192, 4096), 512, 32, 0, 4, 10),
    ("trinity_window_d128_gqa", (1, 8192, 4096), 512, 32, 2048, 4, 10),
    ("glm_d256", (1, 8192, 5120), 5120, 20, 0, 4, 10),
]


@pytest.mark.parametrize("shape,kv_width,heads,window,fwd_dots,bwd_dots",
                         [c[1:] for c in KERNEL_PAIRS],
                         ids=[c[0] for c in KERNEL_PAIRS])
def test_kernel_bodies_hold_no_more_copies_of_the_step_than_the_parents(
        shape, kv_width, heads, window, fwd_dots, bwd_dots):
    """The size guard (PR 45 was refused for set-up time: each of its live
    sub-tiles was a traced copy of the step): the matmuls in the forward and
    the fused backward body of each benchmark kernel pair are at most 1.5
    times the parent's, with or without a window: the bare whole block and
    the two strips of a crossed one, three copies where the parent held
    two."""
    from paddle_tpu.ops import flash_ops

    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct(shape[:2] + (kv_width,), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_ops._packed_attention(
            q, k, v, heads, True, window).astype(jnp.float32).sum(),
        (0, 1, 2)))(q, kv, kv)
    bodies = _kernel_bodies(jaxpr.jaxpr, {})
    assert set(bodies) == {"flash_attention_fwd", "flash_attention_bwd"}
    assert _count(bodies["flash_attention_fwd"], "dot_general") <= 1.5 * fwd_dots
    assert _count(bodies["flash_attention_bwd"], "dot_general") <= 1.5 * bwd_dots


def test_window_kernel_neither_reads_nor_computes_blocks_left_of_the_band():
    """Keys and values wholly left of the last q block's window are NaN: a
    step that fetched and multiplied them, masked or not, would leave NaN
    in that q block's output (0 x NaN). It stays finite and right; an
    earlier q block, whose window holds those keys, does not."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops import flash_ops

    T, H, D, window = 512, 1, 128, 128
    q, k, v, _ = _window_case(T, H, H, D, jnp.float32, seed=3)
    ref = _window_oracle(q, k, v, H, window)
    # the last q block (rows 384..511) reads columns 257..511: blocks 2, 3
    poison = jnp.where(jnp.arange(T)[None, :, None] < 256, jnp.nan, 1.0)
    with pltpu.force_tpu_interpret_mode():
        out, _ = flash_ops._packed_forward(
            q, k * poison, v * poison, heads=H, causal=True, statistics=True,
            blocks=flash_ops.FlashBlocks(128, 128), window=window)
    out = np.asarray(out)
    np.testing.assert_allclose(out[:, 384:], np.asarray(ref)[:, 384:],
                               rtol=2e-5, atol=2e-5)
    assert np.isnan(out[:, :256]).any()


def test_window_wider_than_the_sequence_is_causal(monkeypatch):
    """A window that holds every earlier key runs as plain `causal` (counted
    `packed`, not `packed_window`) and gives its bits; a narrower one is
    counted `packed_window`; both agree with XLA's formulation, which learnt
    the same mask."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.obs import metrics
    from paddle_tpu.ops import flash_ops

    def count(path):
        return metrics.registry().counter_value(
            "pt_flash_attention_dispatch_total", labels={"path": path})

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 1024, 2, 64) * 0.5, jnp.float32)
    xla_wide = flash_attention(x, x, x, causal=True, window=1024)
    xla_narrow = flash_attention(x, x, x, causal=True, window=256)
    monkeypatch.setattr(flash_ops.jax, "default_backend", lambda: "tpu")
    was = {p: count(p) for p in ("packed", "packed_window", "xla")}
    with pltpu.force_tpu_interpret_mode():
        causal = flash_attention(x, x, x, causal=True)
        wide = flash_attention(x, x, x, causal=True, window=4096)
        narrow = flash_attention(x, x, x, causal=True, window=256)
        odd = flash_attention(x, x, x, causal=True, window=200)
    now = {p: count(p) for p in ("packed", "packed_window", "xla")}
    assert now["packed"] - was["packed"] == 2
    assert now["packed_window"] - was["packed_window"] == 1
    assert now["xla"] - was["xla"] == 1     # 200 is not a whole tile
    np.testing.assert_array_equal(np.asarray(wide), np.asarray(causal))
    np.testing.assert_allclose(np.asarray(wide), np.asarray(xla_wide),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(narrow), np.asarray(xla_narrow),
                               rtol=2e-5, atol=2e-5)
    assert float(jnp.max(jnp.abs(narrow - causal))) > 1e-3
    assert float(jnp.max(jnp.abs(odd - narrow))) > 1e-4
    with pytest.raises(ValueError, match="causal=True"):
        flash_attention(x, x, x, causal=False, window=256)


def test_xla_formulation_masks_the_window_to_the_token():
    """Position i reads i - W + 1 and not i - W."""
    T, W = 12, 4
    rng = np.random.RandomState(1)
    q, k = (jnp.asarray(rng.randn(1, T, 1, 8), jnp.float32) for _ in "qk")
    v = jnp.eye(T, dtype=jnp.float32)[None, :, None, :]   # P itself comes out
    p = np.asarray(pp.scaled_dot_product_attention(
        q, k, v, causal=True, window=W))[0, :, 0, :]
    ahead = np.arange(T)[:, None] - np.arange(T)[None, :]
    assert ((p > 0) == ((ahead >= 0) & (ahead < W))).all()
    np.testing.assert_allclose(p.sum(-1), 1.0, rtol=1e-6)


def test_window_absent_is_the_op_as_it_was():
    """No `window`: the kernels trace to what they traced to before the
    window came (the older configurations' step programs stand: the digests
    of tests/test_tpu_compile.py), here as the same jaxpr whether `window`
    is left out or 0, and another with one."""
    from paddle_tpu.ops import flash_ops

    q = jax.ShapeDtypeStruct((1, 1024, 256), jnp.bfloat16)
    blocks = flash_ops.FlashBlocks(512, 512)

    def text(**kw):
        return str(jax.make_jaxpr(lambda q, k, v: flash_ops._packed_forward(
            q, k, v, heads=2, causal=True, blocks=blocks, statistics=True,
            **kw))(q, q, q))

    assert text() == text(window=0)
    assert text() != text(window=256)


@pytest.mark.parametrize("shape,ok", [
    ((1, 1024, 12, 64), True),     # gpt2-small: two heads a lane block
    ((1, 1024, 3, 64), False),     # odd head count at D 64: half a block
    ((1, 1000, 12, 64), False),    # T not a multiple of 128
    ((1, 4096, 16, 128), True),    # olmoe: one head a block
    ((1, 1024, 3, 128), True),
    ((1, 1024, 2, 256), True),
    ((1, 8192, 20, 256), True),    # glm-4.7-flash: one head over two tiles
    ((1, 1024, 4, 32), False),
], ids=["d64_h12", "d64_h3_odd", "t1000", "d128_h16", "d128_h3", "d256",
        "d256_h20", "d32"])
def test_shapes_the_packed_kernel_takes(shape, ok):
    from paddle_tpu.ops.flash_ops import _shapes_flash_ok

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert _shapes_flash_ok(x, x) is ok


@pytest.mark.parametrize("window,ok", [(0, True), (2048, True), (128, True),
                                       (2000, False)])
def test_shapes_rule_wants_a_window_of_whole_tiles(window, ok):
    """Trinity-Mini's layers: 32 query heads over 4 K/V heads at D 128, T
    8192, a window of 2048 (global layers: none)."""
    from paddle_tpu.ops.flash_ops import _shapes_flash_ok

    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16)
    assert _shapes_flash_ok(q, k, window) is ok


def _dispatch_counts():
    from paddle_tpu.obs import metrics

    reg = metrics.registry()
    return {p: reg.counter_value("pt_flash_attention_dispatch_total",
                                 labels={"path": p})
            for p in ("packed", "xla")}


def test_dispatch_counter_counts_each_traced_op(monkeypatch):
    """One increment an attention op traced, labelled by the path the
    shapes chose; a cached trace counts nothing more."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops import flash_ops

    before = _dispatch_counts()
    q, k, v = _qkv(seed=7)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    fn(q, k, v)
    fn(q, k, v)   # the compiled program: nothing is traced
    mid = _dispatch_counts()
    assert mid["xla"] - before["xla"] == 1
    assert mid["packed"] == before["packed"]
    # a TPU backend, shapes inside the window: the packed kernels; an odd
    # head count at D 64 and an unaligned T stay with XLA
    monkeypatch.setattr(flash_ops.jax, "default_backend", lambda: "tpu")
    rng = np.random.RandomState(0)
    mk = lambda *s: jnp.asarray(rng.randn(*s) * 0.5, jnp.float32)  # noqa: E731
    for shape, path in (((1, 1024, 2, 64), "packed"),
                        ((1, 1024, 3, 64), "xla"),
                        ((1, 1100, 2, 64), "xla")):
        x = mk(*shape)
        was = _dispatch_counts()
        with pltpu.force_tpu_interpret_mode():
            out = flash_attention(x, x, x, causal=True)
        now = _dispatch_counts()
        assert now[path] - was[path] == 1, (shape, path)
        assert sum(now.values()) - sum(was.values()) == 1
        ref = pp.scaled_dot_product_attention(x, x, x, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_op_counts_its_dispatch_in_the_built_program():
    """The Program-IR op over packed [B, T, E] inputs goes through the same
    dispatcher: a transformer block's attention op is counted when the
    Executor traces it."""
    import paddle_tpu as pt

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", shape=[16, 32], dtype="float32")
        out = pt.layers.multi_head_attention(x, num_heads=4, causal=True)
        loss = pt.layers.mean(out)
    exe = pt.Executor()
    exe.run(startup)
    before = _dispatch_counts()
    exe.run(main, feed={"x": np.random.RandomState(0).randn(
        2, 16, 32).astype(np.float32)}, fetch_list=[loss])
    after = _dispatch_counts()
    assert after["xla"] - before["xla"] >= 1    # the CPU: XLA's formulation
    assert after["packed"] == before["packed"]


# ---------------------------------------------------------------------------
# The PAIR form: heads 2p and 2p + 1 are one lane block over one value of 128
# lanes (differential attention's), two outputs a launch. Interpreted, against
# two dense softmaxes a pair written out here.

def _pair_oracle(q, k, v, H, KV, window):
    """(A_1 [v_1 | v_2], A_2 [v_1 | v_2]), each [B, T, H x 64], float32."""
    B, T, _ = q.shape
    D, group = 64, H // KV
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    qh = f32(q).reshape(B, T, H // 2, 2, D)
    kh = jnp.repeat(f32(k).reshape(B, T, KV // 2, 2, D), group, axis=2)
    vv = jnp.repeat(f32(v).reshape(B, T, KV // 2, 2 * D), group, axis=2)
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    keep = (ahead >= 0) & ((ahead < window) if window else True)
    outs = []
    for j in (0, 1):
        s = jnp.einsum("bqpd,bkpd->bpqk", qh[:, :, :, j], kh[:, :, :, j]) / 8.0
        a = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bpqk,bkpe->bqpe", a, vv).reshape(B, T, H * D))
    return tuple(outs)


def _pair_case(T, H, KV, dtype, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda n, t=dtype: jnp.asarray(  # noqa: E731
        rng.randn(1, T, n * 64) * (0.5 if t == dtype else 1.0), t)
    return (mk(H), mk(KV), mk(KV)), (mk(H, jnp.float32), mk(H, jnp.float32))


def _pair_loss(att, weights):
    def loss(q, k, v):
        return sum(jnp.sum(o.astype(jnp.float32) * w)
                   for o, w in zip(att(q, k, v), weights))
    return loss


PAIR_CASES = [
    # id, T, heads, K/V heads, window, dtype. T 1024: four (q block, k block)
    # pairs of 512, two crossed by the diagonal (in strips), one skipped;
    # under the window 512 the lower diagonal has a block of its own too
    ("f32_whole_group2", 1024, 4, 2, 0, jnp.float32),
    ("f32_window512_group1", 1024, 4, 4, 512, jnp.float32),
    ("bf16_whole_group2", 1024, 8, 4, 0, jnp.bfloat16),
    ("bf16_window512_group2", 1024, 4, 2, 512, jnp.bfloat16),
    ("bf16_whole_group1", 512, 2, 2, 0, jnp.bfloat16),
    # a window narrower than a block: a crossed block whole under the mask
    ("f32_window128_group2", 512, 4, 2, 128, jnp.float32),
]


@pytest.mark.parametrize("T,H,KV,window,dtype", [c[1:] for c in PAIR_CASES],
                         ids=[c[0] for c in PAIR_CASES])
def test_pair_kernels_match_two_dense_softmaxes(T, H, KV, window, dtype):
    """Both outputs, and dQ, dK and dV through both outputs (the five
    operands' gradients: each half of dQ and of dK is one head's, dV is the
    pair's), with K/V pairs read by one and by two query pairs."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.flash_ops import _packed_attention

    (q, k, v), weights = _pair_case(T, H, KV, dtype)
    att = lambda *a: _packed_attention(*a, H, True, window, True)  # noqa: E731
    with pltpu.force_tpu_interpret_mode():
        outs = att(q, k, v)
        grads = jax.grad(_pair_loss(att, weights), (0, 1, 2))(q, k, v)
    oracle = lambda *a: _pair_oracle(*a, H, KV, window)  # noqa: E731
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want = oracle(*f32)
    want_grads = jax.grad(_pair_loss(oracle, weights), (0, 1, 2))(*f32)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert len(outs) == 2
    for o, w in zip(outs, want):
        assert o.shape == q.shape and o.dtype == dtype
        np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(w),
                                   rtol=tol, atol=tol)
    for g, x, w in zip(grads, (q, k, v), want_grads):
        assert g.shape == x.shape and g.dtype == dtype
        scale = float(jnp.max(jnp.abs(w)))
        for half in (slice(0, 64), slice(64, 128)):     # a head's lanes
            lanes = lambda a: np.asarray(a, np.float32).reshape(  # noqa: E731
                1, T, -1, 128)[..., half] / scale
            np.testing.assert_allclose(lanes(g), lanes(w), rtol=0, atol=tol)


def test_pair_backward_split_matches_fused(monkeypatch):
    """dQ in a pass of its own (beyond `_FUSED_BWD_MAX_ELEMENTS`) reads the two
    O and the two dO as the fused pass does."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops import flash_ops

    (q, k, v), weights = _pair_case(512, 4, 2, jnp.float32, seed=3)
    grad = jax.grad(_pair_loss(lambda *a: flash_ops._packed_attention(
        *a, 4, True, 256, True), weights), (0, 1, 2))
    with pltpu.force_tpu_interpret_mode():
        fused = grad(q, k, v)
        monkeypatch.setattr(flash_ops, "_FUSED_BWD_MAX_ELEMENTS", 0)
        split = grad(q, k, v)
    for a, b in zip(fused, split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_a_trace_without_pairs_is_the_trace_it_was():
    """`pair` is a static argument of the launches: left out, or False, the
    kernels' jaxprs are the same text; True, another."""
    from paddle_tpu.ops import flash_ops

    q = jax.ShapeDtypeStruct((1, 1024, 256), jnp.bfloat16)
    blocks = flash_ops.FlashBlocks(512, 512)

    def text(**kw):
        return str(jax.make_jaxpr(lambda q, k, v: flash_ops._packed_forward(
            q, k, v, heads=4, causal=True, blocks=blocks, statistics=True,
            **kw))(q, q, q))

    assert text() == text(pair=False)
    assert text() != text(pair=True)


@pytest.mark.parametrize("H,KV,D,path", [
    (4, 2, 64, "packed"),      # a pair is a lane block: the kernels
    (4, 4, 64, "packed"),
    (4, 2, 128, "xla"),        # a pair of 256 lanes: XLA's two softmaxes
    (8, 4, 32, "xla"),         # two pairs a lane block
], ids=["d64_group2", "d64_group1", "d128", "d32"])
def test_pair_dispatch_takes_heads_of_64_and_leaves_the_rest_to_xla(
        monkeypatch, H, KV, D, path):
    """The shape rule of the pair form (D 64 only, pairs x 128 lanes, the
    sequence rules as ever), and what it refuses computed by the XLA pair
    form: the same numbers either way."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops import flash_ops

    monkeypatch.setattr(flash_ops.jax, "default_backend", lambda: "tpu")
    rng = np.random.RandomState(5)
    mk = lambda n: jnp.asarray(rng.randn(1, 1024, n, D) * 0.5,  # noqa: E731
                               jnp.float32)
    q, k, v = mk(H), mk(KV), mk(KV)
    was = _dispatch_counts()
    with pltpu.force_tpu_interpret_mode():
        outs = flash_attention(q, k, v, causal=True, pair=True)
    now = _dispatch_counts()
    assert now[path] - was[path] == 1
    assert sum(now.values()) - sum(was.values()) == 1
    want = flash_ops.paired_attention(q, k, v, True)
    for o, w in zip(outs, want):
        assert o.shape == (1, 1024, H // 2, 2 * D)
        np.testing.assert_allclose(np.asarray(o), np.asarray(w), rtol=2e-5,
                                   atol=2e-5)
    if D == 64:     # the XLA pair form against the oracle written out above
        pack = lambda x: x.reshape(1, 1024, -1)  # noqa: E731
        for o, w in zip(want, _pair_oracle(pack(q), pack(k), pack(v), H, KV,
                                           0)):
            np.testing.assert_allclose(np.asarray(pack(o)), np.asarray(w),
                                       rtol=2e-5, atol=2e-5)


def test_pair_form_wants_whole_pairs():
    q = jnp.zeros((1, 256, 6, 64))
    with pytest.raises(ValueError, match="pairs"):
        flash_attention(q, jnp.zeros((1, 256, 3, 64)),
                        jnp.zeros((1, 256, 3, 64)), causal=True, pair=True)
    with pytest.raises(ValueError, match="pairs"):
        flash_attention(q[:, :, :3], q[:, :, :3], q[:, :, :3], causal=True,
                        pair=True)
    # unaligned T, an odd number of PAIRS (three lane blocks): the rules see
    # a pair as one head of 128 lanes
    from paddle_tpu.ops.flash_ops import _pair_view, _shapes_flash_ok

    ok = jax.ShapeDtypeStruct((1, 8192, 40, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 20, 64), jnp.bfloat16)
    view = lambda x: jax.eval_shape(_pair_view, x)  # noqa: E731
    assert view(ok).shape == (1, 8192, 20, 128)
    assert _shapes_flash_ok(view(ok), view(kv), 512)
    assert not _shapes_flash_ok(ok, kv)     # plain heads of 64 share no block
    assert _shapes_flash_ok(view(q), view(q))
    assert not _shapes_flash_ok(
        view(jax.ShapeDtypeStruct((1, 1000, 4, 64), jnp.float32)),
        view(jax.ShapeDtypeStruct((1, 1000, 4, 64), jnp.float32)))
