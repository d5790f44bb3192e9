"""The Nemotron-H-shaped hybrid decoder: the chunked state-space scan against
the token-by-token recurrence, the packed attention kernels with shared K/V
blocks (interpreted) against plain attention with K/V repeated, the routed
layer's sigmoid router, relu^2 experts, shared expert and `held_experts`
against plain `jax.numpy` (the shares of a layer add up to the layer), and
the whole model through `Executor` against `tests/nemotron_h_reference.py`
on seeded weights. CPU: the grouped matmul takes `jax.lax.ragged_dot`,
attention the jnp formulation unless a test runs the kernels interpreted;
`tests/test_tpu_compile.py` compiles both for a described v5e.
"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.ops import flash_ops, moe_ops, ssm_ops

sys.path.insert(0, os.path.dirname(__file__))
import nemotron_h_reference as ref  # noqa: E402

SMALL = dict(vocab_size=256, hidden_size=48, hybrid_override_pattern="ME*ME",
             num_hidden_layers=5, mamba_num_heads=4, mamba_head_dim=8,
             n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=16,
             num_attention_heads=4, num_key_value_heads=2, head_dim=8,
             n_routed_experts=8, num_experts_per_tok=3,
             moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
             routed_scaling_factor=2.5, norm_topk_prob=True,
             layer_norm_epsilon=1e-5)
B, T = 2, 40      # two and a half chunks of 16


def _rng(seed=0):
    return np.random.RandomState(seed)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-12))


# ------------------------------------------------------------ the scan ---
def _scan_inputs(T, H, G, P=8, N=16, Bsz=2, seed=0):
    r = _rng(seed)
    f = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)  # noqa: E731
    dt = jax.nn.softplus(f(Bsz, T, H) - 1.0)
    A = -jnp.exp(jnp.asarray(r.rand(H) * 2.5, jnp.float32))
    return f(Bsz, T, H, P), dt, A, f(Bsz, T, G, N), f(Bsz, T, G, N), f(H)


def _recurrence(x, dt, A, Bm, Cm, D):
    """The definition, one token at a time, in float32: the reference's
    own recurrence (heads read their group's B and C) plus the D skip."""
    R = x.shape[2] // Bm.shape[2]
    y = ref._recurrence(x, dt, A, jnp.repeat(Bm, R, axis=2),
                        jnp.repeat(Cm, R, axis=2), 16)
    return y + D[:, None] * x


def _dispatched(path):
    from paddle_tpu.obs import metrics

    return metrics.registry().counter_value(
        "pt_ssm_scan_dispatch_total", labels={"path": path})


# T, H, G, P, N, chunk, batch; the kernels' cases (interpreted) are the
# smallest the shape rules admit: a group's R x P = 128 lanes, Q = N = 128,
# two groups, three chunks (the carried state and its cotangent cross two
# chunk edges). A `gated_` case runs the scan WITH the gated group norm
# behind it (`ssd_scan_gated_norm`, what the mixer calls): at the kernels'
# shapes the norm is the kernels' epilogue, at the tiny ones XLA's
SCAN_CASES = {
    "4chunks": (64, 4, 4, 8, 16, 16, 2),
    "groups<heads": (64, 4, 2, 8, 16, 16, 2),
    "ragged_tail": (40, 4, 1, 8, 16, 16, 2),
    "under_a_chunk": (7, 2, 2, 8, 16, 16, 2),
    "kernels_groups<heads": (384, 4, 2, 64, 128, 128, 1),
    "kernels_groups=heads": (384, 2, 2, 128, 128, 128, 1),
    "gated_groups<heads": (64, 4, 2, 8, 16, 16, 2),
    "gated_kernels_groups<heads": (384, 4, 2, 64, 128, 128, 1),
    "gated_kernels_groups=heads": (384, 2, 2, 128, 128, 128, 1),
    # the norm weight's cotangent summed over two chunks AND two sequences
    "gated_kernels_batch2": (256, 4, 2, 64, 128, 128, 2),
}
NAMES = ("x", "dt", "A", "B", "C", "D", "z", "norm_w")
EPS = 1e-5


def _gate_inputs(x, seed=4):
    """z [B, T, H P] and a norm weight [H P] around one."""
    r = _rng(seed)
    Bsz, T, H, P = x.shape
    return (jnp.asarray(r.randn(Bsz, T, H * P), jnp.float32),
            jnp.asarray(1.0 + 0.3 * r.randn(H * P), jnp.float32))


def _gated(scan, G):
    """`scan`'s y [B, T, H, P] through XLA's gated group norm: the oracle's
    second half."""
    def fn(x, dt, A, Bm, Cm, D, z, norm_w):
        y = scan(x, dt, A, Bm, Cm, D)
        return ssm_ops.gated_group_rms_norm(
            y.reshape(*y.shape[:2], -1), z, norm_w, G, EPS)
    return fn


def _scan_gated_norm(chunk):
    """`ssd_scan_gated_norm` on the eight tensors, packed as the mixer's
    conv hands them over."""
    def fn(x, dt, A, Bm, Cm, D, z, norm_w):
        Bsz, T, H, P = x.shape
        G, N = Bm.shape[2:]
        xBC = jnp.concatenate([x.reshape(Bsz, T, -1), Bm.reshape(Bsz, T, -1),
                               Cm.reshape(Bsz, T, -1)], axis=-1)
        return ssm_ops.ssd_scan_gated_norm(
            xBC, dt, A, D, z.astype(x.dtype), norm_w,
            ssm_ops.ScanGeometry(H, P, G, N, chunk), G, EPS)
    return fn


def _grads(fn, args, w):
    return jax.grad(lambda *a: (fn(*a) * w).sum(),
                    argnums=tuple(range(len(args))))(*args)


@pytest.mark.parametrize("case", list(SCAN_CASES))
def test_chunked_scan_matches_the_recurrence(interpreted, case):
    """Values and every gradient, float32 at the highest precision: the two
    differ only in the order of float32 sums. The einsum form at the tiny
    shapes, the kernels at the shapes they take; behind a gate z's and the
    norm weight's gradients too, against XLA's norm of the recurrence."""
    T, H, G, P, N, chunk, Bsz = SCAN_CASES[case]
    kernels, gated = "kernels" in case, case.startswith("gated")
    args = _scan_inputs(T, H, G, P=P, N=N, Bsz=Bsz)
    scan = lambda *a: ssm_ops.ssd_chunked_scan(*a, chunk=chunk)  # noqa: E731
    want_fn = _recurrence
    if gated:
        args += _gate_inputs(args[0])
        scan, want_fn = _scan_gated_norm(chunk), _gated(_recurrence, G)
    path = ("pallas_chunked_gated" if gated else "pallas_chunked") \
        if kernels else "xla_chunked"
    before = {p: _dispatched(p) for p in
              ("pallas_chunked", "pallas_chunked_gated", "xla_chunked")}
    with jax.default_matmul_precision("highest"):
        got = scan(*args)
        want = want_fn(*args)
        assert got.dtype == jnp.float32 and got.shape == want.shape
        assert _rel(got, want) < 1e-5
        w = jnp.asarray(_rng(9).randn(*want.shape), jnp.float32)
        for name, g, r in zip(NAMES, _grads(scan, args, w),
                              _grads(want_fn, args, w)):
            assert g.shape == r.shape and g.dtype == r.dtype
            assert _rel(g, r) < 1e-4, (name, _rel(g, r))
    assert {p for p, n in before.items() if _dispatched(p) > n} == {path}


@pytest.mark.parametrize("case", ["kernels_groups<heads",
                                  "kernels_groups=heads",
                                  "gated_kernels_groups<heads",
                                  "gated_kernels_batch2"])
def test_scan_kernels_in_bf16_round_where_the_einsums_do(interpreted, case):
    """Under amp x, B and C arrive bf16. The kernels' y is the einsum
    form's but for the order of float32 sums (1e-3 and up is a dropped rounding point: a
    bf16 decay, a bf16 state, an unrounded `m`), every gradient is within
    bf16's rounding of the float32 recurrence's, and so is the einsum
    form's: the two backward passes round alike. Behind a gate z arrives
    bf16 too and the output is bf16, rounded once in the kernel as after
    XLA's norm of the einsums' y: the two outputs are the same bf16 numbers
    but where a float32 sum's order moved one across a rounding edge."""
    T, H, G, P, N, chunk, Bsz = SCAN_CASES[case]
    gated = case.startswith("gated")
    x, dt, A, Bm, Cm, D = full = _scan_inputs(T, H, G, P=P, N=N, Bsz=Bsz)
    lo = lambda a: a.astype(jnp.bfloat16)  # noqa: E731
    args = (lo(x), dt, A, lo(Bm), lo(Cm), D)
    kernels = lambda *a: ssm_ops.ssd_chunked_scan(*a, chunk=chunk)  # noqa: E731
    einsums = lambda *a: ssm_ops._ssd_einsums(*a, chunk)  # noqa: E731
    want_fn = _recurrence
    if gated:
        z, norm_w = _gate_inputs(x)
        full, args = full + (z, norm_w), args + (lo(z), norm_w)
        kernels = _scan_gated_norm(chunk)
        einsums = lambda *a: _gated(  # noqa: E731
            lambda *s: ssm_ops._ssd_einsums(*s, chunk), G)(*a).astype(
                jnp.bfloat16)
        want_fn = _gated(_recurrence, G)
    got, same = kernels(*args), einsums(*args)
    assert got.dtype == same.dtype == (jnp.bfloat16 if gated else jnp.float32)
    if gated:       # an ulp of bf16 is 4e-3: one number in 25 000 moved
        assert np.mean(np.asarray(got != same)) < 1e-3
    assert _rel(got, same) < 2e-5
    assert _rel(got, want_fn(*full)) < 0.02
    w = jnp.asarray(_rng(9).randn(*got.shape), jnp.float32)
    loss = lambda fn: lambda *a: fn(*a).astype(jnp.float32)  # noqa: E731
    want = _grads(want_fn, full, w)
    # a bf16 z and a bf16 output add their rounding to B's and C's gradients
    # (0.0225 on both forms at three chunks)
    limit = 0.03 if gated else 0.02
    for name, k, e, r in zip(NAMES, _grads(loss(kernels), args, w),
                             _grads(loss(einsums), args, w), want):
        assert k.dtype == e.dtype and k.shape == e.shape
        assert _rel(k, r) < limit and _rel(e, r) < limit, (
            name, _rel(k, r), _rel(e, r))


def test_scan_shape_rules():
    """What the kernels take and what stays the einsums': whole chunks of
    128, a state of whole lane tiles, a group of whole lane tiles."""
    ok = lambda T, dtype=jnp.bfloat16, **kw: ssm_ops._shapes_scan_ok(  # noqa: E731
        ssm_ops.ScanGeometry(**{**dict(H=64, P=64, G=8, N=128, Q=128), **kw}),
        T, dtype)
    assert ok(8192) and ok(8192, jnp.float32) and ok(128, G=64, P=128)
    assert ok(256, H=8, G=8, P=128, Q=256)
    assert not ok(8192 + 64)              # a ragged tail
    assert not ok(8192, Q=16) and not ok(8192, N=16) and not ok(8192, N=64)
    assert ok(8192, P=32)                 # a group of 8 x 32: two tiles
    assert not ok(8192, H=24, P=32)       # ... of 3 x 32: three quarters
    assert not ok(8192, H=8, G=8)         # one head of 64: half a tile
    assert not ok(8192, jnp.float16) and not ok(8192, H=60, G=8)
    # and nothing is taken off the TPU, whatever the shape
    assert not ssm_ops.scan_kernels_eligible(
        ssm_ops.ScanGeometry(64, 64, 8, 128, 128), 8192, jnp.bfloat16)


def test_scan_in_bf16_keeps_decays_and_state_in_float32():
    """Under amp x, B and C arrive bf16: the result is float32 and within
    bf16's rounding of the float32 recurrence; the traced scan holds no
    bf16 exponential and carries a float32 state."""
    x, dt, A, Bm, Cm, D = _scan_inputs(64, 4, 2)
    lo = lambda a: a.astype(jnp.bfloat16)  # noqa: E731
    got = ssm_ops.ssd_chunked_scan(lo(x), dt, A, lo(Bm), lo(Cm), D, chunk=16)
    assert got.dtype == jnp.float32
    assert _rel(got, _recurrence(x, dt, A, Bm, Cm, D)) < 0.02
    jaxpr = jax.make_jaxpr(lambda *a: ssm_ops.ssd_chunked_scan(*a, chunk=16))(
        lo(x), dt, A, lo(Bm), lo(Cm), D)
    exps = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "exp"]
    assert exps and all(e.outvars[0].aval.dtype == jnp.float32 for e in exps)
    scan = next(e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan")
    assert scan.outvars[0].aval.dtype == jnp.float32


def test_conv_and_gated_norm_against_plain_numpy():
    r = _rng(3)
    x, w, b = r.randn(2, 9, 6), r.randn(4, 6), r.randn(6)
    want = np.zeros_like(x)
    for t in range(9):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += w[k] * x[:, t - 3 + k]
    np.testing.assert_allclose(
        ssm_ops.causal_depthwise_conv(jnp.asarray(x, jnp.float32), w, b),
        want + b, rtol=1e-5, atol=1e-5)
    y, z, nw = r.randn(3, 12), r.randn(3, 12), r.rand(12) + 0.5
    v = (y * z / (1 + np.exp(-z))).reshape(3, 4, 3)
    v = v / np.sqrt((v ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(
        ssm_ops.gated_group_rms_norm(jnp.asarray(y, jnp.float32), z, nw, 4,
                                     1e-5),
        v.reshape(3, 12) * nw, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16_in"])
def test_conv_backward_is_the_transposed_taps(dtype):
    """The conv's own backward (one pass over the cotangent padded behind
    the sequence) against JAX's differentiation of the same taps written
    plainly: x's, the taps' and the bias's gradients, K - 1 tokens past both
    ends included; x's comes back in x's dtype."""
    r = _rng(11)
    x = jnp.asarray(r.randn(2, 9, 6), dtype)
    w, b = (jnp.asarray(r.randn(*s), jnp.float32) for s in ((4, 6), (6,)))
    cot = jnp.asarray(r.randn(2, 9, 6), jnp.float32)

    def plain(x, w, b):
        xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (3, 0), (0, 0)))
        return b + sum(xp[:, k:k + 9] * w[k] for k in range(4))

    grad = lambda fn: jax.grad(  # noqa: E731
        lambda *a: (fn(*a) * cot).sum(), argnums=(0, 1, 2))(x, w, b)
    for got, want in zip(grad(ssm_ops.causal_depthwise_conv), grad(plain)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=1e-5, atol=1e-5)


def _silu_of_the_conv(x, w, b):
    """Today's form, the kernels' oracle and the op's exact fallback."""
    return jax.nn.silu(ssm_ops.causal_depthwise_conv(x, w, b)).astype(x.dtype)


@pytest.mark.parametrize("B_,T_,C,K,parts", [
    (1, 1024, 512, 4, ()),
    (2, 2048, 512, 2, ()),
    (1, 3072, 1024, 4, (512, 512)),
    (1, 1024, 6144, 4, (4096, 1024, 1024))],
    ids=["one_block_one_tile", "K2_two_sequences_two_blocks",
         "three_blocks_two_parts", "nemotrons_twelve_tiles_x_B_C"])
def test_conv_kernels_interpreted_against_the_plain_form(B_, T_, C, K, parts):
    """The two Pallas kernels of the conv, its bias and its silu, interpreted,
    give `silu(causal_depthwise_conv)`'s values and its gradients to a
    rounding of bf16 (a block's halo rows in front and behind, the carried
    rows between chunks, the zeros before the start and behind the end of
    EACH sequence of the batch), dw and db to float32's; the backward reads
    the cotangent whole or as its parts along the lanes, a lane tile the part
    it lies in."""
    r = _rng(T_ + C + K)
    x = jnp.asarray(r.randn(B_, T_, C), jnp.bfloat16)
    w = jnp.asarray(r.randn(K, C) * 0.5, jnp.float32)
    b = jnp.asarray(r.randn(C) * 0.3, jnp.float32)
    dy = jnp.asarray(r.randn(B_, T_, C), jnp.bfloat16)
    assert ssm_ops._shapes_conv_ok(x, w)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    want, vjp = jax.vjp(_silu_of_the_conv, x, w, b)
    y = ssm_ops._conv_silu_forward(x, w, b, interpret=True)
    assert y.dtype == want.dtype and y.shape == want.shape
    np.testing.assert_allclose(f32(y), f32(want), rtol=2 ** -7, atol=1e-6)
    edges = np.cumsum((0,) + (parts or (C,)))
    dx, dw, db = ssm_ops._conv_silu_backward(
        x, w, b, tuple(dy[..., lo:hi] for lo, hi in zip(edges, edges[1:])),
        interpret=True)
    dx0, dw0, db0 = vjp(dy)
    assert (dx.dtype, dw.dtype, db.dtype) == (jnp.bfloat16, jnp.float32,
                                              jnp.float32)
    assert (dx.shape, dw.shape, db.shape) == (x.shape, w.shape, b.shape)
    np.testing.assert_allclose(f32(dx), f32(dx0), rtol=2 ** -7, atol=1e-5)
    # sums of B T products of order one: float32's rounding of their size
    np.testing.assert_allclose(dw, dw0, rtol=1e-4, atol=2e-2)
    np.testing.assert_allclose(db, db0, rtol=1e-4, atol=2e-2)


def test_conv_kernels_take_only_shapes_their_blocks_divide():
    ok = lambda T_, C, K, dt=jnp.bfloat16: ssm_ops._shapes_conv_ok(  # noqa: E731
        jax.ShapeDtypeStruct((1, T_, C), dt),
        jax.ShapeDtypeStruct((K, C), jnp.float32))
    assert ok(8192, 6144, 4) and ok(1024, 512, 9) and ok(2048, 1024, 1)
    assert not ok(8192, 6144, 4, jnp.float32)       # float32 rows: XLA's form
    assert not ok(8192 + 256, 6144, 4) and not ok(40, 512, 4)
    assert not ok(1024, 768, 4) and not ok(1024, 512, 10)
    # the CPU backend never dispatches to them
    assert not ssm_ops.conv_kernels_eligible(
        jnp.zeros((1, 1024, 512), jnp.bfloat16), jnp.zeros((4, 512)))


@pytest.mark.parametrize("where,shape,dtype", [
    ("cpu", (1, 1024, 512), jnp.bfloat16),
    ("tpu_mesh", (1, 1024, 512), jnp.bfloat16),
    ("tpu", (1, 1024, 512), jnp.float32),
    ("tpu", (2, 40, 512), jnp.bfloat16),
    ("tpu", (1, 1024, 96), jnp.bfloat16)],
    ids=["cpu", "under_a_mesh", "float32", "rows_no_block_divides",
         "lanes_no_tile_divides"])
def test_conv_silu_elsewhere_is_todays_form_to_the_bit(monkeypatch, where,
                                                       shape, dtype):
    """Where the kernels do not take it (the CPU; the TPU backend, steered,
    under a mesh, in float32, at shapes the blocks do not divide) the op is
    `silu(causal_depthwise_conv)` cast to x's dtype: the same program (no
    `pallas_call` is traced), so the same values and gradients to the bit."""
    import contextlib

    from jax.sharding import Mesh

    from paddle_tpu.ops import mesh_dispatch

    if where != "cpu":
        monkeypatch.setattr(ssm_ops, "_on_tpu", lambda: True)
    r = _rng(shape[1])
    x = jnp.asarray(r.randn(*shape), dtype)
    w = jnp.asarray(r.randn(4, shape[2]), jnp.float32)
    b = jnp.asarray(r.randn(shape[2]), jnp.float32)
    cot = jnp.asarray(r.randn(*shape), jnp.float32)
    both = lambda fn: jax.value_and_grad(  # noqa: E731
        lambda *a: (fn(*a).astype(jnp.float32) * cot).sum(),
        argnums=(0, 1, 2))
    mesh = mesh_dispatch.active_mesh(
        Mesh(np.array(jax.devices()[:1]), ("dp",)), "dp") \
        if where == "tpu_mesh" else contextlib.nullcontext()
    with mesh:
        op = lambda *a: ssm_ops.causal_conv_silu(  # noqa: E731
            *a, parts=(shape[2],))
        assert "pallas_call" not in str(jax.make_jaxpr(both(op))(x, w, b))
        got = both(op)(x, w, b)
    want = both(_silu_of_the_conv)(x, w, b)
    for a, e in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == e.dtype and a.shape == e.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(e, np.float32))


@pytest.mark.parametrize("parts,operands", [
    ((512, 512), 2), ((768, 256), 1), ((), 1)],
    ids=["two_parts", "parts_no_tile_divides", "no_parts"])
def test_conv_silu_takes_its_kernels_on_the_tpu(monkeypatch, parts, operands):
    """... and where the backend is the TPU (steered; only traced), for bf16
    rows the blocks divide, both directions are one `pallas_call` each, the
    backward's cotangent in the parts the caller named where each is whole
    lane tiles, else whole."""
    monkeypatch.setattr(ssm_ops, "_on_tpu", lambda: True)
    x = jax.ShapeDtypeStruct((1, 2048, 1024), jnp.bfloat16)
    w, b = (jax.ShapeDtypeStruct(s, jnp.float32) for s in ((4, 1024), (1024,)))
    grad = jax.grad(lambda *a: ssm_ops.causal_conv_silu(
        *a, parts=parts).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    jaxpr = jax.make_jaxpr(grad)(x, w, b)
    text = str(jaxpr)
    assert text.count("pallas_call") == 2
    assert "causal_conv_silu_fwd" in text and "causal_conv_silu_bwd" in text

    def launches(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from launches(sub)

    bwd = next(e for e in launches(jaxpr.jaxpr)
               if "bwd" in str(e.params["name"]))
    # x, its two halos, the taps, the bias; then the parts and their halos
    assert len(bwd.invars) == 5 + 2 * operands
    out = jax.eval_shape(grad, x, w, b)
    assert [(o.shape, o.dtype) for o in out] == [
        (x.shape, jnp.bfloat16), (w.shape, jnp.float32),
        (b.shape, jnp.float32)]


def test_mamba2_init_draws_the_family_ranges():
    pt.reset()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = pt.layers.data("x", shape=[8, 16], dtype=np.float32)
        pt.layers.mamba2_mixer(x, 64, 4, 8, 4, name="m")
    startup.random_seed = 5
    pt.Executor().run(startup)
    scope = pt.global_scope()
    A = np.exp(np.asarray(scope.get("m.A_log")))
    assert A.min() >= 1.0 and A.max() <= 16.0 and A.std() > 1.0
    dt = np.log1p(np.exp(np.asarray(scope.get("m.dt_bias"))))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001
    assert np.all(np.asarray(scope.get("m.D")) == 1.0)
    assert [p.name for p in prog.parameters()] == [
        f"m.{n}" for n in ("in_w", "conv_w", "conv_b", "dt_bias", "A_log",
                           "D", "norm_w", "out_w")]
    assert scope.get("m.in_w").shape == (16, 2 * 256 + 2 * 8 * 4 + 64)


# ------------------------------------- attention with shared K/V blocks ---
@pytest.fixture
def interpreted(monkeypatch):
    """The Pallas kernels (attention's packed ones, the scan's), interpreted
    on the CPU."""
    from jax.experimental import pallas as pl

    real = pl.pallas_call

    def call(*a, **kw):
        kw.pop("compiler_params", None)
        return real(*a, interpret=True, **kw)

    monkeypatch.setattr(flash_ops.pl, "pallas_call", call)
    # the scan's kernels too (the same `pl`), wherever their shape rules hold
    monkeypatch.setattr(ssm_ops, "_on_tpu", lambda: True)
    jitted = (flash_ops._packed_forward, flash_ops._packed_backward,
              ssm_ops._scan_forward, ssm_ops._scan_backward)
    for fn in jitted:
        fn.clear_cache()
    yield
    for fn in jitted:
        fn.clear_cache()


@pytest.mark.parametrize("H,KV,fused", [(4, 2, True), (4, 1, False),
                                        (2, 2, True)],
                         ids=["2_share_a_block", "4_share_unfused",
                              "no_sharing"])
def test_packed_kernels_with_shared_kv_blocks(interpreted, H, KV, fused):
    """Forward, dQ, dK, dV of the kernels at D 128 against
    `scaled_dot_product_attention` with K and V repeated to H heads."""
    Bq, Tq, D = 1, 256, 128
    r = _rng(1)
    f = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)  # noqa: E731
    q, k, v, do = f(Bq, Tq, H * D), f(Bq, Tq, KV * D), f(Bq, Tq, KV * D), \
        f(Bq, Tq, H * D)
    blocks = flash_ops.FlashBlocks(128, 128)
    heads = lambda a: a.reshape(Bq, Tq, -1, D)  # noqa: E731

    def plain(q, k, v):
        rep = lambda a: jnp.repeat(heads(a), H // KV, axis=2)  # noqa: E731
        return flash_ops.scaled_dot_product_attention(
            heads(q), rep(k), rep(v), causal=True).reshape(Bq, Tq, H * D)

    with jax.default_matmul_precision("highest"):
        o, lse = flash_ops._packed_forward(
            q, k, v, heads=H, causal=True, blocks=blocks, statistics=True)
        assert _rel(o, plain(q, k, v)) < 1e-5
        got = flash_ops._packed_backward(
            q, k, v, o, lse, do, heads=H, causal=True, blocks=blocks,
            fused=fused)
        want = jax.grad(lambda *a: (plain(*a) * do).sum(), (0, 1, 2))(q, k, v)
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        assert g.shape == w.shape, name
        assert _rel(g, w) < 1e-5, (name, _rel(g, w))


def test_flash_attention_dispatcher_takes_fewer_kv_heads():
    """The op's face: K and V narrower than Q, the XLA formulation on the
    CPU; the shape rule admits sharing only where a lane block is a head."""
    r = _rng(2)
    q = jnp.asarray(r.randn(1, 32, 4, 8), jnp.float32)
    k, v = (jnp.asarray(r.randn(1, 32, 2, 8), jnp.float32) for _ in "kv")
    got = flash_ops.flash_attention(q, k, v, causal=True)
    rep = lambda a: jnp.repeat(a, 2, axis=2)  # noqa: E731
    np.testing.assert_allclose(
        got, flash_ops.scaled_dot_product_attention(q, rep(k), rep(v), True),
        rtol=1e-6, atol=1e-6)
    z = lambda h, d: jnp.zeros((1, 1024, h, d))  # noqa: E731
    assert flash_ops._shapes_flash_ok(z(32, 128), z(2, 128))
    assert flash_ops._shapes_flash_ok(z(16, 128), z(16, 128))
    assert not flash_ops._shapes_flash_ok(z(8, 64), z(2, 64))
    assert not flash_ops._shapes_flash_ok(z(6, 128), z(4, 128))
    with pytest.raises(ValueError, match="evenly"):
        flash_ops.flash_attention(z(6, 128), z(4, 128), z(4, 128))


def test_multi_head_attention_kv_heads_and_head_dim():
    pt.reset()
    prog = pt.Program()
    with pt.program_guard(prog, pt.Program()):
        x = pt.layers.data("x", shape=[16, 48], dtype=np.float32)
        out = pt.layers.multi_head_attention(
            x, num_heads=4, num_kv_heads=2, head_dim=8, bias_attr=False,
            name="attn")
    assert tuple(out.shape)[-1] == 48
    shapes = {p.name: tuple(p.shape) for p in prog.parameters()}
    assert shapes == {"attn.wq": (48, 32), "attn.wk": (48, 16),
                      "attn.wv": (48, 16), "attn.wo": (32, 48)}
    op = next(o for o in prog.global_block().ops
              if o.type == "flash_attention")
    # the kernel reads the K/V heads from the shapes: no attribute for them
    assert op.attrs == {"num_heads": 4, "causal": True}
    with pytest.raises(ValueError, match="evenly"):
        with pt.program_guard(pt.Program(), pt.Program()):
            pt.layers.multi_head_attention(x, num_heads=4, num_kv_heads=3)


# ------------------------------------------------------ the routed layer ---
def _layer_inputs(tokens=64, d=16, f=24, fs=20, E=16, seed=0):
    r = _rng(seed)
    mk = lambda *s: jnp.asarray(r.randn(*s) * 0.3, jnp.float32)  # noqa: E731
    return dict(x=mk(tokens, d), wr=mk(d, E) * 3, up=mk(E, d, f),
                down=mk(E, f, d), b=jnp.zeros((E,), jnp.float32),
                up_s=mk(d, fs), down_s=mk(fs, d))


def _layer_config(E, lo, hi, k=3):
    return dict(n_routed_experts=hi - lo, router_experts=E,
                held_experts=(lo, hi), num_experts_per_tok=k,
                norm_topk_prob=True, routed_scaling_factor=2.5)


def _share(p, lo, hi, k=3, shared=True):
    """One chip's share of the layer through the op's function."""
    return moe_ops.moe_ffn(
        p["x"], p["wr"], None, p["up"][lo:hi], p["down"][lo:hi], k, True,
        scoring="sigmoid", router_bias=p["b"], gate_scale=2.5,
        held=(lo, hi), shared=(p["up_s"], p["down_s"]) if shared else None)


def test_sigmoid_router_against_the_reference():
    p = _layer_inputs()
    _, gates, experts = moe_ops.route(p["x"], p["wr"], 3, True, "sigmoid",
                                      p["b"], 2.5)
    _, want = ref.router_scores(_layer_config(16, 0, 16), p["x"], p["wr"],
                                p["b"])
    dense = jnp.zeros_like(want).at[
        jnp.arange(64)[:, None], experts].set(gates)
    np.testing.assert_allclose(dense, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 2.5, rtol=1e-5)
    # the bias moves the CHOICE and not the gate
    bias = jnp.zeros((16,)).at[5].set(10.0)
    _, g2, e2 = moe_ops.route(p["x"], p["wr"], 3, True, "sigmoid", bias, 2.5)
    assert bool(jnp.all(jnp.any(e2 == 5, axis=-1)))
    assert float(g2.max()) <= 2.5


@pytest.mark.parametrize("lo,hi", [(0, 16), (0, 4), (8, 12)],
                         ids=["all_held", "first_share", "third_share"])
def test_held_experts_against_the_reference(lo, hi):
    """Values and every gradient of a share, float32."""
    p = _layer_inputs()
    cfg = _layer_config(16, lo, hi)
    w = jnp.asarray(_rng(4).randn(64, 16), jnp.float32)

    def got(p):
        return _share(p, lo, hi)[0]

    def want(p):
        return ref._experts(cfg, p["x"], p["wr"], p["up"][lo:hi],
                            p["down"][lo:hi], p["b"], p["up_s"],
                            p["down_s"])[0]

    with jax.default_matmul_precision("highest"):
        out, logits, counts, held, path, _ = _share(p, lo, hi)
        # a quarter of the experts: chunks of 96 of the 192 rows
        assert (path is None) == ((lo, hi) == (0, 16))
        np.testing.assert_allclose(out, want(p), rtol=1e-4, atol=1e-5)
        assert int(counts.sum()) == 64 * 3 and counts.shape == (16,)
        if (lo, hi) == (0, 16):
            assert held is None
        else:
            np.testing.assert_array_equal(held, counts[lo:hi])
        g = jax.grad(lambda p: (got(p) * w).sum())(p)
        r = jax.grad(lambda p: (want(p) * w).sum())(p)
    for name in ("x", "wr", "up", "down", "up_s", "down_s"):
        assert _rel(g[name], r[name]) < 1e-4, (name, _rel(g[name], r[name]))
    assert not np.any(np.asarray(g["b"]))


def _steered(p, lo, hi, tokens):
    """The layer's inputs with the choice of the held experts in hand: the
    first `tokens` tokens score every held expert at sigmoid(6) and choose
    them all (hi - lo <= k), every other token scores them at sigmoid(-6)
    and chooses none: the live pairs are tokens x (hi - lo)."""
    sign = jnp.where(jnp.arange(p["x"].shape[0]) < tokens, 1.0, -1.0)
    wr = p["wr"].at[:, lo:hi].set(0.0).at[0, lo:hi].set(6.0)
    return dict(p, x=p["x"].at[:, 0].set(sign), wr=wr)


@pytest.mark.parametrize("shared", [True, False],
                         ids=["shared_expert", "no_shared_expert"])
@pytest.mark.parametrize("held,routing,live", [
    ((4, 6), "fits", None), ((4, 6), "exactly_R", 24),
    ((4, 6), "spills", 26), ((4, 6), "three_chunks", 50),
    ((4, 7), "ragged_last_chunk", 162)],
    ids=["fits", "exactly_R", "spills", "three_chunks", "ragged_last_chunk"])
def test_a_share_on_a_bound_of_its_rows_against_the_reference(
        held, routing, live, shared):
    """relu^2 experts, two stacks, 2 of 32 held: R = 24 of the 192 rows.
    Values, every input's gradient (the gates' path is the router's) and the
    path output, in one chunk of R rows (routing as it falls: 10 live pairs;
    12 tokens steered to both held experts: 24, the bound itself) and in
    more (13 tokens: 26 live pairs, two chunks; 25: 50, three). 3 of 32
    held: R = 40, the sort is padded to five chunks (200), and 54 tokens'
    162 pairs reach into the last."""
    lo, hi = held
    p = _layer_inputs(E=32)
    R = moe_ops.row_bound(64 * 3, held, 32, 8)
    assert R == {2: 24, 3: 40}[hi - lo]
    if live:
        p = _steered(p, lo, hi, live // (hi - lo))
    if not shared:      # the reference always has one: a zero one adds 0
        p = dict(p, up_s=jnp.zeros_like(p["up_s"]))
    cfg = _layer_config(32, lo, hi)
    w = jnp.asarray(_rng(4).randn(64, 16), jnp.float32)

    def got(p):
        return _share(p, lo, hi, shared=shared)

    def want(p):
        return ref._experts(cfg, p["x"], p["wr"], p["up"][lo:hi],
                            p["down"][lo:hi], p["b"], p["up_s"],
                            p["down_s"])[0]

    with jax.default_matmul_precision("highest"):
        out, _, counts, pairs, path, _ = got(p)
        np.testing.assert_allclose(out, want(p), rtol=1e-4, atol=1e-5)
        g = jax.grad(lambda p: (got(p)[0] * w).sum())(p)
        r = jax.grad(lambda p: (want(p) * w).sum())(p)
    np.testing.assert_array_equal(pairs, counts[lo:hi])
    live = live or int(pairs.sum())
    assert int(pairs.sum()) == live and (live <= R) == (
        routing in ("fits", "exactly_R"))
    np.testing.assert_array_equal(path, [1, 0] if live <= R else [0, 1])
    for name in ("x", "wr", "up", "down") + (("up_s", "down_s") * shared):
        assert _rel(g[name], r[name]) < 1e-4, (name, _rel(g[name], r[name]))
    assert float(jnp.abs(g["wr"][:, lo:hi]).max()) > 0     # the gates' path


def test_the_shares_add_up():
    """E 32 as 8 shares of 4, each in one chunk of 48 of the 192 rows: every
    share's routed part, plus the shared expert counted once, is the uncut
    layer, and the held pairs are all the pairs."""
    p = _layer_inputs(E=32)
    with jax.default_matmul_precision("highest"):
        whole = ref._experts(_layer_config(32, 0, 32), p["x"], p["wr"],
                             p["up"], p["down"], p["b"], p["up_s"],
                             p["down_s"])[0]
        total, pairs = 0.0, 0
        for lo in range(0, 32, 4):
            out, _, counts, held, path, _ = _share(p, lo, lo + 4,
                                                shared=(lo == 0))
            total, pairs = total + out, pairs + int(held.sum())
            np.testing.assert_array_equal(path, [1, 0])
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    assert pairs == 64 * 3 == int(counts.sum())


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("E,held,chunked", [
    (16, None, False), (16, (4, 12), False), (16, (0, 4), True),
    (32, (0, 4), True)], ids=["all_held", "a_half", "a_quarter", "an_eighth"])
def test_only_a_share_under_a_half_traces_a_loop_over_chunks(E, held,
                                                             chunked):
    """All experts held, or half of them or more: the bound is all the
    rows, a static branch, and the traced op has no `while` and no `cond`
    (forward or backward) and gives no path output. A smaller share runs
    its chunks in a `while`, forward and backward, and no `cond`."""
    p = _layer_inputs(E=E)
    lo, hi = held or (0, E)

    def share(p):
        return moe_ops.moe_ffn(
            p["x"], p["wr"], None, p["up"][lo:hi], p["down"][lo:hi], 3, True,
            scoring="sigmoid", router_bias=p["b"], gate_scale=2.5, held=held)

    assert (share(p)[4] is not None) == chunked
    traced = jax.make_jaxpr(jax.grad(lambda p: share(p)[0].sum()))(p)
    found = list(_primitives(traced.jaxpr))
    assert found.count("while") == (2 if chunked else 0)
    assert "cond" not in found


@pytest.mark.parametrize("E,hi", [(16, 12), (32, 6)],
                         ids=["all_rows", "bounded_rows"])
def test_rows_behind_the_groups_are_zero_both_ways(monkeypatch, E, hi):
    """A kernel leaves the rows behind the held groups unwritten: with the
    grouped matmul made to write NaN there, forward AND backward, the layer's
    values and gradients are what they were; on all T x k rows (half of
    the experts) and on the R rows of a smaller share's chunk."""
    p = _layer_inputs(E=E)
    assert (_share(p, 4, hi)[4] is not None) == (E == 32)
    clean = jax.value_and_grad(lambda p: (_share(p, 4, hi)[0] ** 2).sum())(p)

    @jax.custom_vjp
    def dirty(lhs, rhs, sizes):
        return _poison(jax.lax.ragged_dot(lhs, rhs, sizes), sizes)

    def _poison(rows, sizes):
        dead = jnp.arange(rows.shape[0])[:, None] >= sizes.sum()
        return jnp.where(dead, jnp.nan, rows)

    def fwd(lhs, rhs, sizes):
        return dirty(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes), lhs,
                         rhs)
        assert_zero = jnp.where(
            jnp.arange(g.shape[0])[:, None] >= sizes.sum(), g, 0.0)
        dl, dr = vjp(g + 0.0 * assert_zero)
        return _poison(dl, sizes), dr, None

    dirty.defvjp(fwd, bwd)
    monkeypatch.setattr(moe_ops, "grouped_matmul", dirty)
    value, grads = jax.value_and_grad(
        lambda p: (_share(p, 4, hi)[0] ** 2).sum())(p)
    np.testing.assert_allclose(value, clean[0], rtol=1e-6)
    for name in ("x", "wr", "up", "down"):
        assert np.all(np.isfinite(grads[name])), name
        np.testing.assert_allclose(grads[name], clean[1][name], rtol=1e-5,
                                   atol=1e-6)


def test_olmoe_arguments_append_the_op_they_did():
    """Softmax, SwiGLU, all experts held: the op's slots and attrs are the
    ones of a layer that knows no shares (the olmoe step program is held to
    its bytes in tests/test_tpu_compile.py)."""
    pt.reset()
    prog = pt.Program()
    with pt.program_guard(prog, pt.Program()):
        x = pt.layers.data("x", shape=[8, 16], dtype=np.float32)
        pt.layers.moe_ffn(x, 4, 2, 8, name="moe")
        pt.layers.moe_ffn(x, 8, 3, 8, name="nemo", scoring="sigmoid",
                          router_bias=True, gate_scale=2.5,
                          norm_topk_prob=True, expert_act="relu2",
                          held_experts=(2, 4), shared_expert_dim=12)
    old, new = [o for o in prog.global_block().ops if o.type == "moe_ffn"]
    assert sorted(old.inputs) == ["DownW", "GateW", "RouterW", "UpW", "X"]
    assert sorted(old.outputs) == ["Out", "RouterLogits", "TokensPerExpert"]
    assert old.attrs == {"top_k": 2, "norm_topk_prob": False}
    assert sorted(new.inputs) == ["DownW", "RouterBias", "RouterW",
                                  "SharedDownW", "SharedUpW", "UpW", "X"]
    assert new.attrs == {"top_k": 3, "norm_topk_prob": True,
                         "scoring": "sigmoid", "gate_scale": 2.5,
                         "held_lo": 2, "held_hi": 4}
    shapes = {p.name: tuple(p.shape) for p in prog.parameters()}
    assert shapes["nemo.up"] == (2, 16, 8) and shapes["nemo.router"] == (16, 8)
    assert not prog.global_block().var("nemo.router_bias").trainable
    # 2 of 8 held: under half of the experts, so its rows run in chunks
    assert sorted(new.outputs) == ["ChunkRows", "HeldPairs", "Out",
                                   "RouterLogits", "RowPath",
                                   "TokensPerExpert"]
    assert [s["counter"] for s in prog.step_statistics] == [
        "pt_moe_expert_tokens_total", "pt_moe_expert_tokens_total",
        "pt_moe_held_pairs_total", "pt_moe_row_path_total",
        "pt_moe_chunk_rows_total"]


# ------------------------------ the whole model against the plain reference ---
def _build(amp, cfg=SMALL, held=None):
    pt.reset()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        toks = pt.layers.data("toks", shape=[T], dtype=np.int32)
        labels = pt.layers.data("labels", shape=[T, 1], dtype=np.int32)
        logits = models.nemotron_h_lm(
            toks, vocab_size=cfg["vocab_size"],
            pattern=cfg["hybrid_override_pattern"], dim=cfg["hidden_size"],
            mamba_heads=cfg["mamba_num_heads"],
            mamba_head_dim=cfg["mamba_head_dim"], n_groups=cfg["n_groups"],
            state_size=cfg["ssm_state_size"], conv_kernel=cfg["conv_kernel"],
            chunk=cfg["chunk_size"], num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            num_experts=cfg.get("router_experts", cfg["n_routed_experts"]),
            experts_per_token=cfg["num_experts_per_tok"],
            expert_dim=cfg["moe_intermediate_size"],
            shared_expert_dim=cfg["moe_shared_expert_intermediate_size"],
            gate_scale=cfg["routed_scaling_factor"],
            norm_topk_prob=cfg["norm_topk_prob"], held_experts=held,
            rms_eps=cfg["layer_norm_epsilon"])
        cost = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, labels))
        pt.optimizer.Adam(learning_rate=3e-4).minimize(cost)
    prog.random_seed = startup.random_seed = 11
    if amp:
        prog.set_amp("bfloat16")
    return prog, startup, logits, cost


def _batch(seed=5):
    toks = _rng(seed).randint(0, SMALL["vocab_size"], (B, T + 1))
    return {"toks": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:, None].astype(np.int32)}


def _first_step(amp, cfg=SMALL, held=None):
    """One step through Executor on seeded weights: the system's logits,
    cost and every trained parameter's gradient (read as the harness reads
    it: Adam's first moment over 1 - beta1), and the reference's."""
    prog, startup, logits, cost = _build(amp, cfg, held)
    exe = pt.Executor()
    exe.run(startup)
    scope = pt.global_scope()
    names = [p.name for p in prog.parameters()]
    params = [np.array(scope.get(n)) for n in names]
    feed = _batch()
    got_logits, got_cost = exe.run(prog, feed=feed, fetch_list=[logits, cost])
    moments = {op.inputs["Param"][0]: op.inputs["Moment1"][0]
               for op in prog.global_block().ops if op.type == "adam"}
    want_cost, want_grads = ref.loss_and_grads(cfg, params, feed)
    errs = {n: _rel(np.asarray(scope.get(moments[n]), np.float32) / (1 - 0.9),
                    w) for n, w in zip(names, want_grads) if n in moments}
    untrained = [n for n in names if n not in moments]
    return dict(names=names, errs=errs, untrained=untrained,
                logits=np.asarray(got_logits, np.float32),
                want_logits=np.asarray(ref.logits(cfg, params, feed["toks"])),
                cost=float(got_cost), want_cost=float(want_cost))


def test_program_parameter_order_is_the_reference_order():
    prog, *_ = _build(False)
    kinds = {"M": ["mamba.in_w", "mamba.conv_w", "mamba.conv_b",
                   "mamba.dt_bias", "mamba.A_log", "mamba.D", "mamba.norm_w",
                   "mamba.out_w"],
             "*": ["attn.wq", "attn.wk", "attn.wv", "attn.wo"],
             "E": ["moe.router", "moe.up", "moe.down", "moe.router_bias",
                   "moe.shared_up", "moe.shared_down"]}
    want = ["nemotron_h.tok_emb"]
    for i, kind in enumerate(SMALL["hybrid_override_pattern"]):
        assert len(kinds[kind]) + 1 == ref.PER_KIND[kind]
        want += [f"nemotron_h.h{i}.{n}" for n in ["ln.w"] + kinds[kind]]
    assert [p.name for p in prog.parameters()] == want + [
        "nemotron_h.ln_f.w", "nemotron_h.out_w"]
    with pytest.raises(ValueError, match="blocks are of"):
        models.nemotron_h_lm(None, 8, pattern="MXE")


def test_matrices_that_write_to_the_stream_start_at_out_scale_of_glorot():
    """The model hands its scaled initialiser to single weights of a layer
    through a {suffix: attr} mapping (`ParamAttr.derive`): those matrices'
    largest value is `out_scale` (1 / sqrt(5 blocks) here) of their Glorot
    limit, per expert for the stacks; every other matrix keeps the limit."""
    prog, startup, *_ = _build(False)
    pt.Executor().run(startup)
    scope = pt.global_scope()
    scale = len(SMALL["hybrid_override_pattern"]) ** -0.5
    scaled = ("mamba.out_w", "attn.wo", "moe.down", "moe.shared_down")
    seen = set()
    for p in prog.parameters():
        kind = p.name.split(".", 2)[-1]
        if len(p.shape) < 2 or kind in ("mamba.conv_w", "tok_emb") \
                or "nemotron_h.h" not in p.name:
            continue
        w = np.asarray(scope.get(p.name))
        limit = np.sqrt(6.0 / (p.shape[-2] + p.shape[-1]))
        want = limit * (scale if kind in scaled else 1.0)
        assert 0.9 * want < np.abs(w).max() <= want * (1 + 1e-6), p.name
        seen.add(kind)
    assert set(scaled) <= seen and "mamba.in_w" in seen and "moe.up" in seen
    # the mapping names single weights; a suffix it leaves out is the default
    from paddle_tpu.param_attr import ParamAttr
    mapping = {"wo": ParamAttr(learning_rate=0.5)}
    assert ParamAttr.derive(mapping, "attn", "wo") == ParamAttr(
        name="attn.wo", learning_rate=0.5)
    assert ParamAttr.derive(mapping, "attn", "wq") == ParamAttr(name="attn.wq")


@pytest.mark.parametrize("held", [None, (2, 6)], ids=["all_held", "a_share"])
def test_float32_model_matches_the_reference(held):
    """float32 on the CPU at the highest matmul precision, both sides: the
    differences are the order of float32 sums (chunked scan against the
    recurrence, sorted rows against a scan over experts). A gradient that
    is missing, doubled or handed to the wrong parameter reads ~1."""
    cfg = SMALL if held is None else dict(
        SMALL, router_experts=8, n_routed_experts=4, held_experts=held)
    with jax.default_matmul_precision("highest"):
        r = _first_step(False, cfg, held)
    assert _rel(r["logits"], r["want_logits"]) < 1e-4
    assert abs(r["cost"] - r["want_cost"]) < 1e-5 * abs(r["want_cost"])
    assert r["untrained"] == [f"nemotron_h.h{i}.moe.router_bias"
                              for i in (1, 4)]
    assert len(r["errs"]) == len(r["names"]) - 2
    for name, err in r["errs"].items():
        assert err < 1e-3, (name, err)


def test_bf16_amp_model_stays_near_the_reference():
    """bf16 AMP against float32, measured where no top-k choice can turn
    (every token to EVERY expert; sort, dispatch, grouped matmuls and
    combine all still run). Read on the CPU (PR 32): logits 0.9 %, every
    gradient 0.7-3.3 % but a deep mixer's dt_bias and A_log at 7 %, with the
    scan itself in float32 too: four per-head sums of 80 tokens' signed
    terms. With top-3 of 8 the turned choices of 80 tokens put 7-30 % on
    every tensor: what the chip reads at 8 192 tokens is PERF.md's."""
    r = _first_step(True, dict(SMALL, num_experts_per_tok=8))
    assert _rel(r["logits"], r["want_logits"]) < 0.02
    assert abs(r["cost"] - r["want_cost"]) < 5e-4 * abs(r["want_cost"])
    per_head = (".dt_bias", ".A_log", ".D")
    for name, err in r["errs"].items():
        assert err < (0.15 if name.endswith(per_head) else 0.04), (name, err)


def test_small_scan_tensors_are_float32_under_amp():
    """Under amp only the mixer's two projection matrices are cast down; the
    per-head vectors, the conv and the norm reach the op as float32."""
    prog, startup, _, _ = _build(amp=True)
    from paddle_tpu import amp

    assert amp.precision_policy("mamba2_mixer") == "low"
    seen = {}
    real = ssm_ops.mamba2_mixer

    def spy(h, in_w, conv_w, conv_b, dt_bias, A_log, D, norm_w, out_w, **kw):
        seen.update(in_w=in_w.dtype, out_w=out_w.dtype, small={
            a.dtype for a in (conv_w, conv_b, dt_bias, A_log, D, norm_w)})
        return real(h, in_w, conv_w, conv_b, dt_bias, A_log, D, norm_w, out_w,
                    **kw)

    ssm_ops.mamba2_mixer = spy
    try:
        exe = pt.Executor()
        exe.run(startup)
        exe.run(prog, feed=_batch(), fetch_list=[])
    finally:
        ssm_ops.mamba2_mixer = real
    assert seen["in_w"] == seen["out_w"] == jnp.bfloat16
    assert seen["small"] == {jnp.dtype("float32")}


def _load_config():
    path = os.path.join(os.path.dirname(__file__), "..", "configs",
                        "nemotron_h.py")
    spec = importlib.util.spec_from_file_location("nemotron_h_config", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("amp", [None, "bfloat16"], ids=["float32", "amp"])
def test_configs_nemotron_h_trains_at_tiny_sizes(amp):
    from paddle_tpu.obs import metrics
    from paddle_tpu.trainer import EndIteration, Trainer

    pt.reset()
    metrics.registry().reset_metrics()
    m = _load_config().get_model(
        pattern="ME*E", dim=48, mamba_heads=4, mamba_head_dim=8, n_groups=2,
        state_size=16, heads=4, kv_heads=2, head_dim=8, experts=16,
        held_experts=(0, 4), experts_per_token=3, expert_dim=24,
        shared_expert_dim=40, seqlen=160, vocab=64, batch=2, steps=30, seed=3,
        amp=amp)
    costs = []

    def handler(e):
        if isinstance(e, EndIteration):
            costs.append(e.cost)

    Trainer(cost=m["cost"]).train(m["reader"], num_passes=1,
                                  event_handler=handler, log_interval=10)
    first, last = float(costs[0]), float(costs[-1])
    assert np.isfinite(last) and last < first - 0.1, (first, last)
    reg = metrics.registry()
    for layer in ("nemotron_h.h1.moe", "nemotron_h.h3.moe"):
        every = [reg.counter_value("pt_moe_expert_tokens_total", labels={
            "layer": layer, "expert": e}) for e in range(16)]
        held = [reg.counter_value("pt_moe_held_pairs_total", labels={
            "layer": layer, "expert": e}) for e in range(4)]
        assert sum(every) == 30 * 2 * 160 * 3, every
        assert held == every[:4] and 0 < sum(held) < sum(every)
        # a quarter of the experts: each step ran one chunk of its rows
        # (480 of 960) or both
        one, more = (reg.counter_value("pt_moe_row_path_total", labels={
            "layer": layer, "path": path}) for path in (0, 1))
        assert one + more == 30 and one > 0
    assert reg.counter_value("pt_ssm_scan_dispatch_total",
                             labels={"path": "xla_chunked"}) >= 1
