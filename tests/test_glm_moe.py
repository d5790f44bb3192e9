"""The GLM-4.7-Flash-shaped decoder: the rotary op over a sub-range of a
head's lanes against plain numpy, the latent attention layer's ops, the packed
attention kernels at head size 256 (interpreted) against plain attention, the
routed layer's SwiGLU shared expert and `held_experts` against plain
`jax.numpy` (the shares of a layer add up to the layer), and the whole model
through `Executor` against `tests/glm_moe_reference.py` on seeded weights.
CPU: the grouped matmul takes `jax.lax.ragged_dot`, attention the jnp
formulation unless a test runs the kernels interpreted;
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
from paddle_tpu.ops import flash_ops, moe_ops, nn_ops

sys.path.insert(0, os.path.dirname(__file__))
import glm_moe_reference as ref  # noqa: E402

SMALL = dict(vocab_size=256, hidden_size=48, num_hidden_layers=3,
             first_k_dense_replace=1, num_attention_heads=3, q_lora_rank=24,
             kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
             v_head_dim=16, rope_theta=1e6, rms_norm_eps=1e-5,
             intermediate_size=80, n_routed_experts=8, num_experts_per_tok=3,
             moe_intermediate_size=24, n_shared_experts=1,
             routed_scaling_factor=1.8, norm_topk_prob=True)
B, T = 2, 40


def _rng(seed=0):
    return np.random.RandomState(seed)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-12))


# ------------------------------------------------- rotary over a sub-range ---
def _numpy_rotary(x, theta, R):
    """x [B, T, H, D], float64 numpy: the last R lanes of each head turn,
    lane i of them with lane i + R/2."""
    out = np.array(x, np.float64)
    D = x.shape[-1]
    for t in range(x.shape[1]):
        for i in range(R // 2):
            ang = t * theta ** (-2.0 * i / R)
            a, b = x[:, t, :, D - R + i], x[:, t, :, D - R // 2 + i]
            out[:, t, :, D - R + i] = a * np.cos(ang) - b * np.sin(ang)
            out[:, t, :, D - R // 2 + i] = b * np.cos(ang) + a * np.sin(ang)
    return out


@pytest.mark.parametrize("H,D,R", [(3, 16, 4), (1, 8, 8), (2, 32, 16)],
                         ids=["last_4_of_16", "one_whole_head", "half_a_head"])
def test_rotary_sub_range_against_plain_numpy(H, D, R):
    x = _rng(2).randn(2, 9, H, D)
    got = nn_ops.rotary(jnp.asarray(x, jnp.float32), 1e4,
                        None if R == D else R)
    np.testing.assert_allclose(got, _numpy_rotary(x, 1e4, R), rtol=2e-5,
                               atol=2e-5)
    # the lanes in front of the rotary ones pass through to the bit
    np.testing.assert_array_equal(got[..., : D - R],
                                  jnp.asarray(x, jnp.float32)[..., : D - R])


def test_rotary_layer_attribute_absent_is_the_old_op():
    """Without `rotary_dim` the op appended carries the two attributes it
    always did (olmoe's step program is held to its bytes in
    tests/test_tpu_compile.py); with it, one more, and the op refuses a
    range that is no even part of a head."""
    pt.reset()
    prog = pt.Program()
    with pt.program_guard(prog, pt.Program()):
        x = pt.layers.data("x", shape=[8, 32], dtype=np.float32)
        pt.layers.rotary_embedding(x, 2, 1e4)
        pt.layers.rotary_embedding(x, 2, 1e6, rotary_dim=4)
    old, new = [o for o in prog.global_block().ops
                if o.type == "rotary_embedding"]
    assert old.attrs == {"num_heads": 2, "theta": 1e4}
    assert new.attrs == {"num_heads": 2, "theta": 1e6, "rotary_dim": 4}
    exe = pt.Executor()
    feed = {"x": _rng(0).randn(2, 8, 32).astype(np.float32)}
    whole, part = exe.run(prog, feed=feed, fetch_list=[
        old.outputs["Out"][0], new.outputs["Out"][0]])
    np.testing.assert_allclose(
        whole.reshape(2, 8, 2, 16),
        _numpy_rotary(feed["x"].reshape(2, 8, 2, 16), 1e4, 16), atol=2e-5)
    np.testing.assert_allclose(
        part.reshape(2, 8, 2, 16),
        _numpy_rotary(feed["x"].reshape(2, 8, 2, 16), 1e6, 4), atol=2e-5)
    for bad in (5, 20):     # lanes pair up; a head has 16
        prog = pt.Program()
        with pt.program_guard(prog, pt.Program()):
            x = pt.layers.data("x", shape=[8, 32], dtype=np.float32)
            out = pt.layers.rotary_embedding(x, 2, 1e6, rotary_dim=bad)
        with pytest.raises(Exception, match="rotary_dim"):
            exe.run(prog, feed=feed, fetch_list=[out])


# ----------------------------------------------- the latent attention layer ---
def test_latent_attention_appends_its_parts_as_ops_of_their_own():
    """q_down, q_up, kv_down, kv_up and out are `mul` ops, the two latent
    norms `rms_norm`, the rotary passes `rotary_embedding` (Q over the last
    rope_dim lanes of 3 heads, the key over ONE whole head), the assembling
    of K `latent_kv_expand`, the kernels `flash_attention`: a reader finds
    each from the Program's structure. The op counts one dispatch."""
    from paddle_tpu.obs import metrics

    pt.reset()
    metrics.registry().reset_metrics()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = pt.layers.data("x", shape=[T, 48], dtype=np.float32)
        out = pt.layers.latent_attention(
            x, num_heads=3, q_rank=24, kv_rank=16, nope_dim=12, rope_dim=4,
            v_dim=16, rotary_theta=1e6, name="attn")
        with pytest.raises(ValueError, match="one head size"):
            pt.layers.latent_attention(x, 3, 24, 16, 12, 4, 12, name="bad")
    ops = prog.global_block().ops
    assert [o.type for o in ops] == [
        "mul", "rms_norm", "mul", "rotary_embedding", "mul", "split",
        "rms_norm", "mul", "rotary_embedding", "latent_kv_expand",
        "flash_attention", "mul"]
    rot_q, rot_k = [o for o in ops if o.type == "rotary_embedding"]
    assert rot_q.attrs == {"num_heads": 3, "theta": 1e6, "rotary_dim": 4,
                           "qk_emit": "kernel"}
    assert rot_k.attrs == {"num_heads": 1, "theta": 1e6, "qk_emit": "kernel"}
    # the latent norms feed projections, not a kernel: no mark
    assert all("qk_emit" not in o.attrs for o in ops if o.type == "rms_norm")
    expand, = [o for o in ops if o.type == "latent_kv_expand"]
    flash, = [o for o in ops if o.type == "flash_attention"]
    assert flash.inputs["K"] == expand.outputs["K"]
    assert flash.inputs["V"] == expand.outputs["V"]
    assert flash.inputs["Q"] == rot_q.outputs["Out"]
    assert expand.inputs["KRope"] == rot_k.outputs["Out"]
    assert flash.attrs == {"num_heads": 3, "causal": True}
    shapes = {p.name: tuple(p.shape) for p in prog.parameters()}
    assert shapes == {"attn.wq_a": (48, 24), "attn.q_norm": (24,),
                      "attn.wq_b": (24, 48), "attn.wkv_a": (48, 20),
                      "attn.kv_norm": (16,), "attn.wkv_b": (16, 84),
                      "attn.wo": (48, 48)}
    assert list(shapes) == ["attn.wq_a", "attn.q_norm", "attn.wq_b",
                            "attn.wkv_a", "attn.kv_norm", "attn.wkv_b",
                            "attn.wo"]
    exe = pt.Executor()
    exe.run(startup)
    scope = pt.global_scope()
    feed = {"x": _rng(1).randn(B, T, 48).astype(np.float32)}
    with jax.default_matmul_precision("highest"):
        got, = exe.run(prog, feed=feed, fetch_list=[out])
        want = ref._latent_attention(
            SMALL, jnp.asarray(feed["x"]),
            *[jnp.asarray(scope.get(n)) for n in shapes])
    assert _rel(got, want) < 1e-5
    assert metrics.registry().counter_value(
        "pt_latent_attention_dispatch_total",
        labels={"path": "expanded"}) >= 1


def test_expand_latent_kv_lays_one_rotary_key_beside_every_head():
    r = _rng(3)
    kv = jnp.asarray(r.randn(2, 5, 3 * (12 + 16)), jnp.float32)
    k_r = jnp.asarray(r.randn(2, 5, 4), jnp.float32)
    k, v = flash_ops.expand_latent_kv(kv, k_r, 3, 12)
    k, v = k.reshape(2, 5, 3, 16), v.reshape(2, 5, 3, 16)
    per_head = kv.reshape(2, 5, 3, 28)
    np.testing.assert_array_equal(k[..., :12], per_head[..., :12])
    np.testing.assert_array_equal(v, per_head[..., 12:])
    for h in range(3):
        np.testing.assert_array_equal(k[:, :, h, 12:], k_r)
    # the shared key's gradient is the sum over the heads
    g = jax.grad(lambda a: flash_ops.expand_latent_kv(kv, a, 3, 12)[0].sum())(
        k_r)
    np.testing.assert_allclose(g, 3.0)


# ------------------------------------------- the packed kernels at D 256 ---
@pytest.fixture
def interpreted(monkeypatch):
    """The packed kernels, interpreted on the CPU."""
    from jax.experimental import pallas as pl

    real = pl.pallas_call

    def call(*a, **kw):
        kw.pop("compiler_params", None)
        return real(*a, interpret=True, **kw)

    monkeypatch.setattr(flash_ops.pl, "pallas_call", call)
    for fn in (flash_ops._packed_forward, flash_ops._packed_backward):
        fn.clear_cache()
    yield
    for fn in (flash_ops._packed_forward, flash_ops._packed_backward):
        fn.clear_cache()


@pytest.mark.parametrize("H,fused", [(2, True), (3, True), (2, False),
                                     (3, False)],
                         ids=["2_heads_fused", "3_heads_fused",
                              "2_heads_split", "3_heads_split"])
def test_packed_kernels_at_head_size_256_in_bf16(interpreted, H, fused):
    """Forward, dQ, dK, dV of the kernels at D 256 (one head over two lane
    tiles), bf16 in and out, three q blocks by three k blocks, against
    `scaled_dot_product_attention` in float32 on the same bf16 values: the
    difference is the rounding of P and dS to bf16 inside the kernels and of
    the outputs."""
    Bq, Tq, D = 1, 384, 256
    r = _rng(7 + H)
    f = lambda *s: jnp.asarray(r.randn(*s), jnp.bfloat16)  # noqa: E731
    q, k, v, do = (f(Bq, Tq, H * D) for _ in range(4))
    blocks = flash_ops.FlashBlocks(128, 128)
    heads = lambda a: a.astype(jnp.float32).reshape(Bq, Tq, H, D)  # noqa: E731

    def plain(q, k, v):
        return flash_ops.scaled_dot_product_attention(
            heads(q), heads(k), heads(v), causal=True).reshape(Bq, Tq, H * D)

    o, lse = flash_ops._packed_forward(
        q, k, v, heads=H, causal=True, blocks=blocks, statistics=True)
    assert o.dtype == jnp.bfloat16 and lse.shape == (Bq, Tq, 128)
    with jax.default_matmul_precision("highest"):
        want_o = plain(q, k, v)
        want = jax.grad(lambda *a: (plain(*a) * do.astype(jnp.float32)).sum(),
                        (0, 1, 2))(q, k, v)
    assert _rel(o, want_o) < 0.01
    got = flash_ops._packed_backward(
        q, k, v, o, lse, do, heads=H, causal=True, blocks=blocks, fused=fused)
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        assert g.shape == w.shape and g.dtype == jnp.bfloat16, name
        assert _rel(g, w) < 0.015, (name, _rel(g, w))


# ------------------------------ the routed layer with a SwiGLU shared expert ---
def _layer_inputs(tokens=64, d=16, f=24, E=16, seed=0):
    r = _rng(seed)
    mk = lambda *s: jnp.asarray(r.randn(*s) * 0.3, jnp.float32)  # noqa: E731
    return dict(x=mk(tokens, d), wr=mk(d, E) * 3, gate=mk(E, d, f),
                up=mk(E, d, f), down=mk(E, f, d),
                b=jnp.zeros((E,), jnp.float32), gate_s=mk(d, f),
                up_s=mk(d, f), down_s=mk(f, d))


def _layer_config(E, lo, hi, k=3):
    return dict(n_routed_experts=hi - lo, router_experts=E,
                held_experts=(lo, hi), num_experts_per_tok=k,
                norm_topk_prob=True, routed_scaling_factor=1.8)


def _share(p, lo, hi, k=3, shared=True):
    """One chip's share of the layer through the op's function."""
    return moe_ops.moe_ffn(
        p["x"], p["wr"], p["gate"][lo:hi], p["up"][lo:hi], p["down"][lo:hi],
        k, True, scoring="sigmoid", router_bias=p["b"], gate_scale=1.8,
        held=(lo, hi),
        shared=(p["gate_s"], p["up_s"], p["down_s"]) if shared else None)


def _whole(p, cfg, lo=0, hi=16):
    return ref._experts(cfg, p["x"], p["wr"], p["gate"][lo:hi],
                        p["up"][lo:hi], p["down"][lo:hi], p["b"],
                        p["gate_s"], p["up_s"], p["down_s"])[0]


@pytest.mark.parametrize("lo,hi", [(0, 16), (0, 2), (8, 10)],
                         ids=["all_held", "first_share", "fifth_share"])
def test_swiglu_share_with_its_shared_expert_against_the_reference(lo, hi):
    """Values and every gradient of a share, float32."""
    p = _layer_inputs()
    cfg = _layer_config(16, lo, hi)
    w = jnp.asarray(_rng(4).randn(64, 16), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, _, counts, held, path, _ = _share(p, lo, hi)
        # an eighth of the experts: 48 of the 192 rows, and the pairs fit
        assert path is None if (lo, hi) == (0, 16) else list(path) == [1, 0]
        np.testing.assert_allclose(out, _whole(p, cfg, lo, hi), rtol=1e-4,
                                   atol=1e-5)
        assert int(counts.sum()) == 64 * 3 and counts.shape == (16,)
        if (lo, hi) != (0, 16):
            np.testing.assert_array_equal(held, counts[lo:hi])
        g = jax.grad(lambda p: (_share(p, lo, hi)[0] * w).sum())(p)
        r = jax.grad(lambda p: (_whole(p, cfg, lo, hi) * w).sum())(p)
    for name in ("x", "wr", "gate", "up", "down", "gate_s", "up_s", "down_s"):
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
@pytest.mark.parametrize("routing", ["fits", "exactly_R", "spills"])
def test_a_swiglu_share_on_a_bound_of_its_rows_against_the_reference(
        routing, shared):
    """SwiGLU experts, three stacks, 2 of 16 held: R = 48 of the 192 rows.
    Values, every input's gradient (the gates' path is the router's) and the
    path output, in one chunk of R rows (routing as it falls; 24 tokens
    steered to both held experts: 48 live pairs, the bound itself) and in
    two (25 tokens: 50)."""
    lo, hi = 8, 10
    p = _layer_inputs()
    assert moe_ops.row_bound(64 * 3, (lo, hi), 16, 8) == 48
    if routing != "fits":
        p = _steered(p, lo, hi, 24 if routing == "exactly_R" else 25)
    if not shared:      # the reference always has one: a zero one adds 0
        p = dict(p, up_s=jnp.zeros_like(p["up_s"]))
    cfg = _layer_config(16, lo, hi)
    w = jnp.asarray(_rng(4).randn(64, 16), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, _, counts, held, path, _ = _share(p, lo, hi, shared=shared)
        np.testing.assert_allclose(out, _whole(p, cfg, lo, hi), rtol=1e-4,
                                   atol=1e-5)
        g = jax.grad(
            lambda p: (_share(p, lo, hi, shared=shared)[0] * w).sum())(p)
        r = jax.grad(lambda p: (_whole(p, cfg, lo, hi) * w).sum())(p)
    np.testing.assert_array_equal(held, counts[lo:hi])
    live = {"fits": int(held.sum()), "exactly_R": 48, "spills": 50}[routing]
    assert int(held.sum()) == live and (live <= 48) == (routing != "spills")
    np.testing.assert_array_equal(path, [1, 0] if live <= 48 else [0, 1])
    names = ("x", "wr", "gate", "up", "down") + (
        ("gate_s", "up_s", "down_s") * shared)
    for name in names:
        assert _rel(g[name], r[name]) < 1e-4, (name, _rel(g[name], r[name]))
    assert float(jnp.abs(g["wr"][:, lo:hi]).max()) > 0     # the gates' path


@pytest.mark.parametrize("n", [0, 1, 15, 16, 17, 47, 48])
def test_a_chunks_gather_and_sum_against_the_plain_gather_and_add(n):
    """`_rows_of` and `_add_rows` over a chunk of R 48 rows of which `n` are
    live (none, one, ..., all but one, all), against `x[tok]` and
    `.at[tok].add` of the gate-weighted rows, forward and VJP, float32: the
    sums go through each token's k slots (the sort's inverse), not through
    the rows' tokens. The pairs behind the live rows are RANDOM and drawn
    twice: with the rows behind the live ones masked as a chunk masks them
    (`alive`), neither draw shows."""
    T, k, d, R = 40, 3, 16, 48
    r = _rng(n)
    x = jnp.asarray(r.randn(T, d), jnp.float32)
    acc = jnp.asarray(r.randn(T, d), jnp.float32)
    rows = jnp.asarray(r.randn(R, d), jnp.float32)
    gates = jnp.asarray(r.rand(T, k), jnp.float32)
    g_rows = jnp.asarray(r.randn(R, d), jnp.float32)
    g_acc = jnp.asarray(r.randn(T, d), jnp.float32)
    alive = lambda a: jnp.where(  # noqa: E731
        (jnp.arange(R) < n)[:, None], a, 0.0)
    results = []
    for draw in range(2):
        # a sort of the T x k pairs whose first n rows are the same pairs in
        # both draws; this chunk is its first R rows
        order = np.concatenate([_rng(7).permutation(T * k)[:n], np.setdiff1d(
            _rng(10 + draw).permutation(T * k), _rng(7).permutation(T * k)[:n],
            assume_unique=True)])
        pairs = jnp.asarray(order[:R], jnp.int32)
        tok = pairs // k
        slot_row = np.argsort(order).reshape(T, k)
        slot_live = jnp.asarray(slot_row < n)
        slot_row = jnp.asarray(np.clip(slot_row, 0, R - 1), jnp.int32)
        got, vjp = jax.vjp(
            lambda x: moe_ops._rows_of(x, tok, slot_row, slot_live), x)
        want, vjp_plain = jax.vjp(lambda x: x[tok], x)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_allclose(vjp(alive(g_rows))[0],
                                   vjp_plain(alive(g_rows))[0],
                                   rtol=1e-5, atol=1e-6)
        summed, vjp = jax.vjp(
            lambda acc, rows, gates: moe_ops._add_rows(
                acc, alive(rows), gates, pairs, slot_row, slot_live),
            acc, rows, gates)
        plain, vjp_plain = jax.vjp(
            lambda acc, rows, gates: acc.at[tok].add(
                alive(rows) * gates.reshape(-1)[pairs][:, None]),
            acc, rows, gates)
        np.testing.assert_allclose(summed, plain, rtol=1e-5, atol=1e-6)
        for a, b in zip(vjp(g_acc), vjp_plain(g_acc)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        results.append((alive(got), summed) + vjp(g_acc))
    for a, b in zip(*results):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("tokens,chunks", [(13, 1), (24, 1), (25, 2)],
                         ids=["inside_a_chunk", "on_its_edge", "past_R"])
def test_a_share_sums_its_chunks_through_the_slots_against_the_reference(
        tokens, chunks):
    """2 of 16 held, R = 48 rows: `tokens` steered to both held experts are
    26 live pairs (the live rows end inside the chunk), 48 (on its edge) and
    50 (a second chunk of two live rows). Values, every input's gradient,
    and the chunks' rows: [the live ones, chunks x R]."""
    lo, hi = 8, 10
    p = _steered(_layer_inputs(), lo, hi, tokens)
    cfg = _layer_config(16, lo, hi)
    w = jnp.asarray(_rng(4).randn(64, 16), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, _, _, held, _, rows = _share(p, lo, hi)
        np.testing.assert_allclose(out, _whole(p, cfg, lo, hi), rtol=1e-4,
                                   atol=1e-5)
        g = jax.grad(lambda p: (_share(p, lo, hi)[0] * w).sum())(p)
        r = jax.grad(lambda p: (_whole(p, cfg, lo, hi) * w).sum())(p)
    assert int(held.sum()) == 2 * tokens
    np.testing.assert_array_equal(rows, [2 * tokens, chunks * 48])
    for name in ("x", "wr", "gate", "up", "down", "gate_s", "up_s", "down_s"):
        assert _rel(g[name], r[name]) < 1e-4, (name, _rel(g[name], r[name]))


def test_the_eight_shares_add_up():
    """E 16 as 8 shares of 2, each in one chunk of 48 of the 192 rows: every
    share's routed part, plus the SwiGLU shared expert counted once, is the
    uncut layer of the reference, and the held pairs are all the pairs."""
    p = _layer_inputs()
    with jax.default_matmul_precision("highest"):
        whole = _whole(p, _layer_config(16, 0, 16))
        total, pairs = 0.0, 0
        for lo in range(0, 16, 2):
            out, _, counts, held, path, _ = _share(p, lo, lo + 2,
                                                shared=(lo == 0))
            total, pairs = total + out, pairs + int(held.sum())
            np.testing.assert_array_equal(path, [1, 0])
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    assert pairs == 64 * 3 == int(counts.sum())


def test_the_shared_expert_is_of_the_routed_experts_kind():
    """`moe_ffn(expert_act="swiglu", shared_expert_dim>0)` builds a SwiGLU
    shared expert (a `shared_gate` in front of `shared_up`); relu^2 experts
    keep the two-matrix one, slot for slot the op PR 32 wrote; the op's
    function refuses a shared expert of the other kind."""
    pt.reset()
    prog = pt.Program()
    with pt.program_guard(prog, pt.Program()):
        x = pt.layers.data("x", shape=[8, 16], dtype=np.float32)
        pt.layers.moe_ffn(x, 8, 3, 8, name="glm", scoring="sigmoid",
                          router_bias=True, gate_scale=1.8,
                          norm_topk_prob=True, held_experts=(0, 2),
                          shared_expert_dim=12)
        pt.layers.moe_ffn(x, 8, 3, 8, name="nemo", scoring="sigmoid",
                          router_bias=True, gate_scale=2.5,
                          norm_topk_prob=True, expert_act="relu2",
                          held_experts=(2, 4), shared_expert_dim=12)
    glm, nemo = [o for o in prog.global_block().ops if o.type == "moe_ffn"]
    assert sorted(glm.inputs) == ["DownW", "GateW", "RouterBias", "RouterW",
                                  "SharedDownW", "SharedGateW", "SharedUpW",
                                  "UpW", "X"]
    assert sorted(nemo.inputs) == ["DownW", "RouterBias", "RouterW",
                                   "SharedDownW", "SharedUpW", "UpW", "X"]
    assert glm.attrs == {"top_k": 3, "norm_topk_prob": True,
                         "scoring": "sigmoid", "gate_scale": 1.8,
                         "held_lo": 0, "held_hi": 2}
    names = [p.name for p in prog.parameters() if p.name.startswith("glm.")]
    assert names == ["glm.router", "glm.gate", "glm.up", "glm.down",
                     "glm.router_bias", "glm.shared_gate", "glm.shared_up",
                     "glm.shared_down"]
    p = _layer_inputs()
    with pytest.raises(ValueError, match="routed experts' kind"):
        moe_ops.moe_ffn(p["x"], p["wr"], p["gate"], p["up"], p["down"], 3,
                        shared=(p["up_s"], p["down_s"]))
    with pytest.raises(ValueError, match="routed experts' kind"):
        moe_ops.moe_ffn(p["x"], p["wr"], None, p["up"], p["down"], 3,
                        shared=(p["gate_s"], p["up_s"], p["down_s"]))


# ------------------------------ the whole model against the plain reference ---
def _build(amp, cfg=SMALL, held=None):
    pt.reset()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        toks = pt.layers.data("toks", shape=[T], dtype=np.int32)
        labels = pt.layers.data("labels", shape=[T, 1], dtype=np.int32)
        logits, routers = models.glm_moe_lm(
            toks, vocab_size=cfg["vocab_size"],
            num_layers=cfg["num_hidden_layers"],
            first_k_dense=cfg["first_k_dense_replace"],
            dim=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
            q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
            nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
            v_dim=cfg["v_head_dim"], dense_dim=cfg["intermediate_size"],
            num_experts=cfg.get("router_experts", cfg["n_routed_experts"]),
            experts_per_token=cfg["num_experts_per_tok"],
            expert_dim=cfg["moe_intermediate_size"],
            shared_expert_dim=cfg["n_shared_experts"]
            * cfg["moe_intermediate_size"],
            gate_scale=cfg["routed_scaling_factor"],
            norm_topk_prob=cfg["norm_topk_prob"], held_experts=held,
            rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"])
        cost = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, labels))
        pt.optimizer.Adam(learning_rate=3e-4).minimize(cost)
    prog.random_seed = startup.random_seed = 11
    if amp:
        prog.set_amp("bfloat16")
    return prog, startup, logits, cost, routers


def _batch(seed=5):
    toks = _rng(seed).randint(0, SMALL["vocab_size"], (B, T + 1))
    return {"toks": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:, None].astype(np.int32)}


def _first_step(amp, cfg=SMALL, held=None, hand_choice=False):
    """One step through Executor on seeded weights: the system's logits,
    cost and every trained parameter's gradient (read as the harness reads
    it: Adam's first moment over 1 - beta1), and the reference's; with
    `hand_choice` the reference is handed the program's own choice of
    experts, derived from its fetched `RouterLogits` as the driver does."""
    prog, startup, logits, cost, routers = _build(amp, cfg, held)
    exe = pt.Executor()
    exe.run(startup)
    scope = pt.global_scope()
    names = [p.name for p in prog.parameters()]
    params = [np.array(scope.get(n)) for n in names]
    feed = _batch()
    got_logits, got_cost, *got_routers = exe.run(
        prog, feed=feed,
        fetch_list=[logits, cost] + [z for z, _ in routers])
    moments = {op.inputs["Param"][0]: op.inputs["Moment1"][0]
               for op in prog.global_block().ops if op.type == "adam"}
    choice = ref.chosen(cfg, params, got_routers) if hand_choice else None
    want_cost, want_grads, want_routers = ref.loss_grads_and_routers(
        cfg, params, feed, choice)
    errs = {n: _rel(np.asarray(scope.get(moments[n]), np.float32) / (1 - 0.9),
                    w) for n, w in zip(names, want_grads) if n in moments}
    untrained = [n for n in names if n not in moments]
    return dict(names=names, errs=errs, untrained=untrained,
                logits=np.asarray(got_logits, np.float32),
                want_logits=np.asarray(
                    ref.logits(cfg, params, feed["toks"], choice)),
                cost=float(got_cost), want_cost=float(want_cost),
                routers=got_routers, want_routers=want_routers)


def test_program_parameter_order_is_the_reference_order():
    prog, *_, routers = _build(False)
    attn = ["ln_in.w", "attn.wq_a", "attn.q_norm", "attn.wq_b", "attn.wkv_a",
            "attn.kv_norm", "attn.wkv_b", "attn.wo", "ln_post.w"]
    kinds = {"dense": ["mlp.gate", "mlp.up", "mlp.down"],
             "routed": ["moe.router", "moe.gate", "moe.up", "moe.down",
                        "moe.router_bias", "moe.shared_gate", "moe.shared_up",
                        "moe.shared_down"]}
    want = ["glm_moe.tok_emb"]
    for i, kind in enumerate(ref._kinds(SMALL)):
        assert len(attn) + len(kinds[kind]) == ref.PER_KIND[kind]
        want += [f"glm_moe.h{i}.{n}" for n in attn + kinds[kind]]
    assert [p.name for p in prog.parameters()] == want + [
        "glm_moe.ln_f.w", "glm_moe.out_w"]
    # every routed layer hands out its RouterLogits and TokensPerExpert
    assert len(routers) == 2
    ops = [o for o in prog.global_block().ops if o.type == "moe_ffn"]
    assert [(o.outputs["RouterLogits"][0], o.outputs["TokensPerExpert"][0])
            for o in ops] == [(z.name, c.name) for z, c in routers]
    with pytest.raises(ValueError, match="first_k_dense"):
        models.glm_moe_lm(None, 8, num_layers=2, first_k_dense=3)


def test_matrices_that_write_to_the_stream_start_at_out_scale_of_glorot():
    prog, startup, *_ = _build(False)
    pt.Executor().run(startup)
    scope = pt.global_scope()
    scale = SMALL["num_hidden_layers"] ** -0.5
    scaled = ("attn.wo", "mlp.down", "moe.down", "moe.shared_down")
    seen = set()
    for p in prog.parameters():
        kind = p.name.split(".", 2)[-1]
        if len(p.shape) < 2 or "glm_moe.h" not in p.name:
            continue
        w = np.asarray(scope.get(p.name))
        limit = np.sqrt(6.0 / (p.shape[-2] + p.shape[-1]))
        want = limit * (scale if kind in scaled else 1.0)
        assert 0.9 * want < np.abs(w).max() <= want * (1 + 1e-6), p.name
        seen.add(kind)
    assert set(scaled) <= seen and {"attn.wq_b", "moe.shared_gate",
                                    "mlp.gate"} <= seen


@pytest.mark.parametrize("held", [None, (2, 6)], ids=["all_held", "a_share"])
def test_float32_model_matches_the_reference(held):
    """float32 on the CPU at the highest matmul precision, both sides: the
    cost and every gradient within 2e-4 of its rms. A gradient that is
    missing, doubled or handed to the wrong parameter reads ~1."""
    cfg = SMALL if held is None else dict(
        SMALL, router_experts=8, n_routed_experts=4, held_experts=held)
    with jax.default_matmul_precision("highest"):
        r = _first_step(False, cfg, held)
    assert _rel(r["logits"], r["want_logits"]) < 1e-4
    assert abs(r["cost"] - r["want_cost"]) < 2e-4 * abs(r["want_cost"])
    assert r["untrained"] == [f"glm_moe.h{i}.moe.router_bias" for i in (1, 2)]
    assert len(r["errs"]) == len(r["names"]) - 2
    for name, err in r["errs"].items():
        assert err < 2e-4, (name, err)
    for got, (_, _, want) in zip(r["routers"], r["want_routers"]):
        assert _rel(got, want) < 1e-4


def test_bf16_amp_model_stays_near_the_reference():
    """bf16 AMP against float32 with the reference handed the program's own
    choice of experts (the benchmark's rule since PR 36): what is left is
    rounding. Read on the CPU (this PR): logits under 1 %, every gradient
    under 4 %."""
    r = _first_step(True, hand_choice=True)
    assert _rel(r["logits"], r["want_logits"]) < 0.02
    assert abs(r["cost"] - r["want_cost"]) < 5e-4 * abs(r["want_cost"])
    for name, err in r["errs"].items():
        assert err < 0.05, (name, err)


def test_the_residual_stream_is_float32_under_amp():
    """One `cast` to float32 in front of every residual add (two a layer):
    under amp the stream is not rounded to bf16 at each add."""
    prog, *_ = _build(True)
    ops = prog.global_block().ops
    casts = [o for o in ops if o.type == "cast"]
    assert len(casts) == 2 * SMALL["num_hidden_layers"]
    adds = [o for o in ops if o.type == "elementwise_add"]
    cast_outs = {o.outputs["Out"][0] for o in casts}
    assert sum(o.inputs["Y"][0] in cast_outs for o in adds) == len(casts)


def _load_config():
    path = os.path.join(os.path.dirname(__file__), "..", "configs",
                        "glm_moe.py")
    spec = importlib.util.spec_from_file_location("glm_moe_config", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("amp", [None, "bfloat16"], ids=["float32", "amp"])
def test_configs_glm_moe_trains_at_tiny_sizes(amp):
    from paddle_tpu.obs import metrics
    from paddle_tpu.trainer import EndIteration, Trainer

    pt.reset()
    metrics.registry().reset_metrics()
    m = _load_config().get_model(
        layers=3, first_k_dense=1, dim=48, heads=3, q_rank=24, kv_rank=16,
        nope_dim=12, rope_dim=4, v_dim=16, dense_dim=80, experts=16,
        held_experts=(0, 2), experts_per_token=3, expert_dim=24,
        shared_expert_dim=24, seqlen=160, vocab=64, model_layers=3, batch=2,
        steps=30, seed=3, amp=amp)
    costs = []

    def handler(e):
        if isinstance(e, EndIteration):
            costs.append(e.cost)

    Trainer(cost=m["cost"]).train(m["reader"], num_passes=1,
                                  event_handler=handler, log_interval=10)
    first, last = float(costs[0]), float(costs[-1])
    assert np.isfinite(last) and last < first - 0.1, (first, last)
    reg = metrics.registry()
    for layer in ("glm_moe.h1.moe", "glm_moe.h2.moe"):
        every = [reg.counter_value("pt_moe_expert_tokens_total", labels={
            "layer": layer, "expert": e}) for e in range(16)]
        held = [reg.counter_value("pt_moe_held_pairs_total", labels={
            "layer": layer, "expert": e}) for e in range(2)]
        assert sum(every) == 30 * 2 * 160 * 3, every
        assert held == every[:2] and 0 < sum(held) < sum(every)
        # an eighth of the experts, as the configuration's 8 of 64: each
        # step ran one chunk of its rows (240 of 960) or more
        bounded, whole = (reg.counter_value("pt_moe_row_path_total", labels={
            "layer": layer, "path": path}) for path in (0, 1))
        assert bounded + whole == 30 and bounded > 0
    assert reg.counter_value("pt_latent_attention_dispatch_total",
                             labels={"path": "expanded"}) >= 3
