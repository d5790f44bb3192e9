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
    """Beyond _FUSED_BWD_MAX_TQ query rows dQ gets a pass of its own: the
    same gradients as the one fused pass."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops import flash_ops

    q, k, v, w = _packed_case(1, 256, 384, 2, 64, jnp.float32, seed=4)
    grad = jax.grad(lambda q, k, v: jnp.sum(
        flash_ops._packed_attention(q, k, v, 2, False) * w), (0, 1, 2))
    with pltpu.force_tpu_interpret_mode():
        fused = grad(q, k, v)
        monkeypatch.setattr(flash_ops, "_FUSED_BWD_MAX_TQ", 0)
        split = grad(q, k, v)
    for a, b in zip(fused, split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


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
