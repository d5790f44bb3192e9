"""The LFM2-shaped decoder (`models.lfm2_moe_lm`): the gated short convolution
(`ops/short_conv_ops.py`) and its backward against `jax.grad` of three shifted
multiplies, the operator layer against plain numpy, the routed layer's eight
shares at LFM2's router (sigmoid, top 4 of 64 of score + choice bias, gates
over their sum + 1e-6, no shared expert) adding up to the uncut layer of the
reference, the whole model through `Executor` against
`tests/lfm2_moe_reference.py` on seeded weights, and four wrong programs
(`tests/lfm2_controls.py`) that the comparison has to catch. CPU: attention
takes the jnp formulation; `tests/test_tpu_compile.py` compiles the step for
a described v5e.
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
from paddle_tpu.ops import moe_ops, short_conv_ops

sys.path.insert(0, os.path.dirname(__file__))
import lfm2_controls  # noqa: E402
import lfm2_moe_reference as ref  # noqa: E402

C_, A_ = "conv", "full_attention"
SMALL = dict(vocab_size=256, hidden_size=64, num_hidden_layers=5,
             layer_types=[C_, A_, C_, C_, C_], num_dense_layers=1,
             num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
             rope_parameters={"rope_theta": 1e6}, norm_eps=1e-5,
             intermediate_size=96, num_experts=8, num_experts_per_tok=2,
             moe_intermediate_size=24, norm_topk_prob=True,
             routed_scaling_factor=1.0, use_expert_bias=True)
B, T = 2, 40


def _rng(seed=0):
    return np.random.RandomState(seed)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-12))


# ------------------------------------------------- the gated short convolution ---
def _three_shifted_multiplies(bcx, w):
    """C * conv_K(B * X) as K shifted multiplies, float32, no custom rule."""
    K, d = w.shape
    T_ = bcx.shape[1]
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    z = b * x
    conv = sum(w[k] * jnp.pad(z, ((0, 0), (K - 1 - k, 0), (0, 0)))[:, :T_]
               for k in range(K))
    return c * conv


@pytest.mark.parametrize("T_,K", [(40, 3), (37, 3), (2, 3), (1, 3), (24, 4),
                                  (16, 1)],
                         ids=["T40_K3", "T37_odd", "T2_under_K", "T1", "K4",
                              "K1"])
def test_op_and_its_backward_against_shifted_multiplies(T_, K):
    r = _rng(T_ + K)
    bcx = jnp.asarray(r.randn(2, T_, 3 * 16), jnp.float32)
    w = jnp.asarray(r.randn(K, 16), jnp.float32)
    g = jnp.asarray(r.randn(2, T_, 16), jnp.float32)
    np.testing.assert_allclose(short_conv_ops.gated_short_conv(bcx, w),
                               _three_shifted_multiplies(bcx, w), rtol=1e-5,
                               atol=1e-6)
    got = jax.grad(lambda a, b: (short_conv_ops.gated_short_conv(a, b)
                                 * g).sum(), argnums=(0, 1))(bcx, w)
    want = jax.grad(lambda a, b: (_three_shifted_multiplies(a, b) * g).sum(),
                    argnums=(0, 1))(bcx, w)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_op_reads_and_writes_bf16_with_float32_inside():
    """bf16 in, bf16 out, dbcx bf16 and dw float32: one rounding of the
    float32 value a float32 run gives."""
    r = _rng(3)
    bcx32 = jnp.asarray(r.randn(1, 48, 3 * 128), jnp.float32)
    bcx = bcx32.astype(jnp.bfloat16)
    w = jnp.asarray(r.randn(3, 128), jnp.float32)
    y, vjp = jax.vjp(short_conv_ops.gated_short_conv, bcx, w)
    assert y.dtype == jnp.bfloat16
    want = _three_shifted_multiplies(bcx.astype(jnp.float32), w)
    np.testing.assert_array_equal(y, want.astype(jnp.bfloat16))
    dbcx, dw = vjp(jnp.ones_like(y))
    assert dbcx.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    assert dbcx.shape == bcx.shape and dw.shape == w.shape


@pytest.mark.parametrize("B_,T_,d,K", [(2, 512, 512, 3), (1, 256, 1024, 4),
                                       (1, 768, 512, 1), (1, 256, 512, 9)],
                         ids=["two_sequences_two_blocks", "K4_two_lane_tiles",
                              "K1_three_blocks", "K9_all_carried_rows"])
def test_kernels_interpreted_against_the_plain_form(B_, T_, d, K):
    """The two Pallas kernels, interpreted, give the XLA formulation's values
    to a rounding of bf16 (the block's halo rows, the carried rows between
    chunks, the zeros before the start and behind the end), and dw to
    float32's."""
    r = _rng(T_ + d + K)
    bcx = jnp.asarray(r.randn(B_, T_, 3 * d), jnp.bfloat16)
    w = jnp.asarray(r.randn(K, d), jnp.float32)
    dy = jnp.asarray(r.randn(B_, T_, d), jnp.bfloat16)
    assert short_conv_ops._shapes_ok(bcx, w)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    y, want = short_conv_ops._kernel_fwd(bcx, w, interpret=True), \
        short_conv_ops._mix(bcx, w)
    assert y.dtype == want.dtype and y.shape == want.shape
    np.testing.assert_allclose(f32(y), f32(want), rtol=2 ** -7, atol=1e-6)
    (dbcx, dw), (dbcx0, dw0) = (
        short_conv_ops._kernel_bwd(bcx, w, dy, interpret=True),
        short_conv_ops._mix_bwd(bcx, w, dy))
    assert dbcx.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    np.testing.assert_allclose(f32(dbcx), f32(dbcx0), rtol=2 ** -7, atol=1e-6)
    np.testing.assert_allclose(dw, dw0, rtol=1e-5, atol=1e-3)


def test_kernels_take_only_shapes_their_blocks_divide():
    ok = lambda T_, d, K, dt=jnp.bfloat16: short_conv_ops._shapes_ok(  # noqa: E731
        jax.ShapeDtypeStruct((1, T_, 3 * d), dt),
        jax.ShapeDtypeStruct((K, d), jnp.float32))
    assert ok(16384, 2048, 3) and ok(256, 512, 9)
    assert not ok(16384, 2048, 3, jnp.float32)      # float32 rows: XLA's form
    assert not ok(200, 2048, 3) and not ok(256, 64, 3) and not ok(256, 512, 10)
    # the CPU backend never dispatches to them
    assert not short_conv_ops.kernels_eligible(
        jnp.zeros((1, 256, 1536), jnp.bfloat16), jnp.zeros((3, 512)))


def test_operator_layer_against_plain_numpy():
    """`layers.short_conv_operator` through Executor: three parameters, the
    op's inner scopes, and the values of a numpy transcription."""
    pt.reset()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = pt.layers.data("x", shape=[T, 32], dtype=np.float32)
        out = pt.layers.short_conv_operator(x, kernel=3, name="op")
    assert [p.name for p in prog.parameters()] == [
        "op.in_w", "op.conv_w", "op.out_w"]
    assert [tuple(p.shape) for p in prog.parameters()] == [
        (32, 96), (3, 32), (32, 32)]
    exe = pt.Executor()
    exe.run(startup)
    scope = pt.global_scope()
    w_in, w, w_out = (np.array(scope.get(p.name), np.float64)
                      for p in prog.parameters())
    assert np.abs(w).max() <= 1 / np.sqrt(3) and np.abs(w).max() > 0.3
    u = _rng(2).randn(B, T, 32).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got, = exe.run(prog, feed={"x": u}, fetch_list=[out])
    bcx = u.astype(np.float64) @ w_in
    z = bcx[..., :32] * bcx[..., 64:]
    zp = np.concatenate([np.zeros((B, 2, 32)), z], axis=1)
    conv = sum(w[k] * zp[:, k:k + T] for k in range(3))
    np.testing.assert_allclose(got, (bcx[..., 32:64] * conv) @ w_out,
                               rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="at least one tap"):
        pt.layers.short_conv_operator(x, kernel=0)


def test_the_byte_count_is_the_ops_operands_and_results():
    # forward: read 3 T d, write T d; backward: read 4 T d, write 3 T d; the
    # [K, d] weight twice and its gradient once, float32
    assert short_conv_ops.mix_bytes(1, 16384, 2048, 3, 2) == (
        11 * 16384 * 2048 * 2 + 3 * 3 * 2048 * 4)


# ------------------------------------------------------- the routed layer ---
def _layer_inputs(tokens=64, d=16, f=24, E=64, seed=0):
    r = _rng(seed)
    mk = lambda *s: jnp.asarray(r.randn(*s) * 0.3, jnp.float32)  # noqa: E731
    return dict(x=mk(tokens, d), wr=mk(d, E) * 3, w1=mk(E, d, f),
                w3=mk(E, d, f), w2=mk(E, f, d),
                b=jnp.zeros((E,), jnp.float32))


def _layer_config(E, lo, hi, k=4):
    return dict(num_experts=hi - lo, router_experts=E, held_experts=(lo, hi),
                num_experts_per_tok=k, norm_topk_prob=True,
                routed_scaling_factor=1.0, use_expert_bias=True)


def _share(p, lo, hi, k=4):
    """One chip's share of the layer through the op's function."""
    return moe_ops.moe_ffn(
        p["x"], p["wr"], p["w1"][lo:hi], p["w3"][lo:hi], p["w2"][lo:hi], k,
        True, scoring="sigmoid", router_bias=p["b"], held=(lo, hi),
        gate_norm_eps=models.lfm2_moe.GATE_NORM_EPS)


def _whole(p, cfg, lo=0, hi=64):
    return ref.experts(cfg, p["x"], p["wr"], p["w1"][lo:hi], p["w3"][lo:hi],
                       p["w2"][lo:hi], p["b"])[0]


def test_the_eight_shares_add_up():
    """The share test: 64 experts as the 8 shares [0, 8) ... [56, 64) under
    one router: the routed parts, summed, are the uncut layer of the
    reference, and the held pairs are all the pairs."""
    p = _layer_inputs()
    with jax.default_matmul_precision("highest"):
        whole = _whole(p, _layer_config(64, 0, 64))
        total, pairs = 0.0, 0
        for lo in range(0, 64, 8):
            out, _, counts, held, _, _ = _share(p, lo, lo + 8)
            total, pairs = total + out, pairs + int(held.sum())
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    assert pairs == 64 * 4 == int(counts.sum())


@pytest.mark.parametrize("lo,hi", [(0, 64), (0, 8), (56, 64)],
                         ids=["all_held", "first_share", "last_share"])
def test_a_share_against_the_reference(lo, hi):
    """Values and every gradient of a share at LFM2's router, float32."""
    p = _layer_inputs(seed=1)
    cfg = _layer_config(64, lo, hi)
    w = jnp.asarray(_rng(4).randn(64, 16), jnp.float32)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(_share(p, lo, hi)[0],
                                   _whole(p, cfg, lo, hi), rtol=1e-4,
                                   atol=1e-5)
        g = jax.grad(lambda p: (_share(p, lo, hi)[0] * w).sum())(p)
        r = jax.grad(lambda p: (_whole(p, cfg, lo, hi) * w).sum())(p)
    for name in ("x", "wr", "w1", "w3", "w2"):
        assert _rel(g[name], r[name]) < 1e-4, (name, _rel(g[name], r[name]))
    assert not np.any(np.asarray(g["b"]))


def test_the_gates_sum_carries_the_published_epsilon():
    """`gate_norm_eps` is in the sum the chosen gates are divided by, an
    attribute only of a layer that asks for it."""
    x = jnp.asarray(_rng(0).randn(8, 16), jnp.float32)
    wr = jnp.asarray(_rng(1).randn(16, 8), jnp.float32)
    _, plain, experts = moe_ops.route(x, wr, 2, True, "sigmoid")
    _, eps, same = moe_ops.route(x, wr, 2, True, "sigmoid", gate_norm_eps=0.5)
    np.testing.assert_array_equal(experts, same)
    np.testing.assert_allclose(plain.sum(-1), 1.0, rtol=1e-6)
    s = jnp.take_along_axis(jax.nn.sigmoid(x @ wr), experts, axis=-1)
    np.testing.assert_allclose(eps, s / (s.sum(-1, keepdims=True) + 0.5),
                               rtol=1e-5)
    pt.reset()
    prog = pt.Program()
    with pt.program_guard(prog, pt.Program()):
        v = pt.layers.data("x", shape=[8, 16], dtype=np.float32)
        pt.layers.moe_ffn(v, 8, 2, 8, name="without", norm_topk_prob=True)
        pt.layers.moe_ffn(v, 8, 2, 8, name="with", norm_topk_prob=True,
                          gate_norm_eps=1e-6)
    without, with_ = [o for o in prog.global_block().ops
                      if o.type == "moe_ffn"]
    assert "gate_norm_eps" not in without.attrs
    assert with_.attrs["gate_norm_eps"] == 1e-6


# ------------------------------ the whole model against the plain reference ---
def _build(amp, cfg=SMALL, held=None):
    pt.reset()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        toks = pt.layers.data("toks", shape=[T], dtype=np.int32)
        labels = pt.layers.data("labels", shape=[T, 1], dtype=np.int32)
        logits, routers = models.lfm2_moe_lm(
            toks, vocab_size=cfg["vocab_size"],
            layer_types=cfg["layer_types"],
            num_dense_layers=cfg["num_dense_layers"], dim=cfg["hidden_size"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            conv_kernel=cfg["conv_L_cache"],
            dense_dim=cfg["intermediate_size"],
            num_experts=cfg.get("router_experts", cfg["num_experts"]),
            experts_per_token=cfg["num_experts_per_tok"],
            expert_dim=cfg["moe_intermediate_size"],
            gate_scale=cfg["routed_scaling_factor"],
            norm_topk_prob=cfg["norm_topk_prob"],
            use_expert_bias=cfg["use_expert_bias"], held_experts=held,
            rope_theta=cfg["rope_parameters"]["rope_theta"],
            rms_eps=cfg["norm_eps"])
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
    operator = {C_: ["conv.in_w", "conv.conv_w", "conv.out_w"],
                A_: ["attn.wq", "attn.wk", "attn.wv", "attn.q_norm",
                     "attn.k_norm", "attn.wo"]}
    ffn = {"dense": ["mlp.w1", "mlp.w3", "mlp.w2"],
           "routed": ["moe.router", "moe.gate", "moe.up", "moe.down",
                      "moe.router_bias"]}
    want = ["lfm2.tok_emb"]
    for i, (op, kind) in enumerate(zip(SMALL["layer_types"],
                                       ref._kinds(SMALL))):
        assert len(operator[op]) == ref.OPERATOR[op]
        assert len(ffn[kind]) == ref.FFN[kind]
        want += [f"lfm2.h{i}.{n}" for n in
                 ["operator_norm.w"] + operator[op] + ["ffn_norm.w"]
                 + ffn[kind]]
    assert [p.name for p in prog.parameters()] == want + [
        "lfm2.embedding_norm.w", "lfm2.out_w"]
    # every routed layer hands out its RouterLogits and TokensPerExpert
    assert len(routers) == 4
    ops = [o for o in prog.global_block().ops if o.type == "moe_ffn"]
    assert [(o.outputs["RouterLogits"][0], o.outputs["TokensPerExpert"][0])
            for o in ops] == [(z.name, c.name) for z, c in routers]
    assert all(o.attrs["gate_norm_eps"] == 1e-6 and "gate_scale" not in o.attrs
               for o in ops)
    with pytest.raises(ValueError, match="num_dense_layers"):
        models.lfm2_moe_lm(None, 8, layer_types=(C_, A_), num_dense_layers=3)
    with pytest.raises(ValueError, match="layer_types"):
        models.lfm2_moe_lm(None, 8, layer_types=(C_, "sliding_attention"))


def test_layer_kinds_decide_operator_and_attention():
    """A `conv` layer is one `short_conv_operator` op and no attention op;
    a `full_attention` layer norms Q and K per head and THEN turns them."""
    prog, *_ = _build(False)
    ops = [o.type for o in prog.global_block().ops]
    assert ops.count("short_conv_operator") == 4
    assert ops.count("flash_attention") == 1
    assert ops.count("rotary_embedding") == 2
    at = ops.index("flash_attention")
    assert ops[at - 4:at] == ["rms_norm", "rms_norm", "rotary_embedding",
                              "rotary_embedding"]
    flash = prog.global_block().ops[at]
    assert flash.attrs["causal"] and "window" not in flash.attrs
    kinds = models.lfm2_moe.LFM2_24B_LAYER_TYPES
    assert len(kinds) == 40 and kinds.count(A_) == 10
    assert kinds[:6] == (C_, C_, A_, C_, C_, C_)


@pytest.mark.parametrize("held", [None, (2, 6)], ids=["all_held", "a_share"])
def test_float32_model_matches_the_reference(held):
    """float32 on the CPU at the highest matmul precision, both sides: the
    cost and EVERY gradient within 2e-4 of its rms. A gradient that is
    missing, doubled or handed to the wrong parameter reads ~1."""
    cfg = SMALL if held is None else dict(
        SMALL, router_experts=8, num_experts=4, held_experts=held)
    with jax.default_matmul_precision("highest"):
        r = _first_step(False, cfg, held)
    assert _rel(r["logits"], r["want_logits"]) < 1e-4
    assert abs(r["cost"] - r["want_cost"]) < 2e-4 * abs(r["want_cost"])
    assert r["untrained"] == [f"lfm2.h{i}.moe.router_bias"
                              for i in (1, 2, 3, 4)]
    assert len(r["errs"]) == len(r["names"]) - 4
    for name, err in r["errs"].items():
        assert err < 2e-4, (name, err)
    for got, (_, _, want) in zip(r["routers"], r["want_routers"]):
        assert _rel(got, want) < 1e-4


@pytest.mark.parametrize("control", lfm2_controls.CONTROLS)
def test_the_reference_tells_a_wrong_program(control):
    """Each of the four wrong programs of `tests/lfm2_controls.py` reads far
    outside what the right one is held to (logits 1e-4, every gradient
    2e-4): a tap that reads ahead, a missing gate, keys that do not turn, a
    router in bf16."""
    with jax.default_matmul_precision("highest"), lfm2_controls.applied(
            control, kv_heads=SMALL["num_key_value_heads"]):
        r = _first_step(False)
    routers = max(_rel(got, want) for got, (_, _, want)
                  in zip(r["routers"], r["want_routers"]))
    assert routers > 1e-3, routers
    if control != "bf16_router":
        assert _rel(r["logits"], r["want_logits"]) > 1e-2
        assert max(r["errs"].values()) > 0.1


def test_bf16_amp_model_stays_near_the_reference():
    """bf16 AMP against float32 with the reference handed the program's own
    choice of experts (the benchmark's rule since PR 36): what is left is
    rounding."""
    r = _first_step(True, hand_choice=True)
    assert _rel(r["logits"], r["want_logits"]) < 0.02
    assert abs(r["cost"] - r["want_cost"]) < 5e-4 * abs(r["want_cost"])
    for name, err in r["errs"].items():
        assert err < 0.05, (name, err)


def test_the_residual_stream_is_float32_under_amp():
    """Pre-norm residuals: a layer's bf16 branch is cast up before it is
    added, so the stream is not rounded at every add."""
    prog, *_ = _build(True)
    ops = prog.global_block().ops
    made_by = {name: o.type for o in ops for outs in o.outputs.values()
               for name in outs}
    adds = [o for o in ops if o.type == "elementwise_add"]
    assert len(adds) == 2 * SMALL["num_hidden_layers"]
    assert {made_by[o.inputs["Y"][0]] for o in adds} == {"cast"}
    assert made_by[adds[0].inputs["X"][0]] == "lookup_table"


def _load_config():
    path = os.path.join(os.path.dirname(__file__), "..", "configs",
                        "lfm2_moe.py")
    spec = importlib.util.spec_from_file_location("lfm2_moe_config", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("amp", [None, "bfloat16"], ids=["float32", "amp"])
def test_configs_lfm2_moe_trains_at_tiny_sizes(amp):
    from paddle_tpu.obs import metrics
    from paddle_tpu.trainer import EndIteration, Trainer

    pt.reset()
    metrics.registry().reset_metrics()
    short_conv_ops._bytes.clear()
    m = _load_config().get_model(
        layer_types=(C_, A_, C_), dense_layers=1, dim=64, heads=4, kv_heads=2,
        dense_dim=96, experts=16, held_experts=(0, 2), experts_per_token=2,
        expert_dim=24, model_layers=3, seqlen=160, vocab=64, batch=2,
        steps=30, learning_rate=3e-4, seed=3, amp=amp)
    costs = []

    def handler(e):
        if isinstance(e, EndIteration):
            costs.append(e.cost)

    Trainer(cost=m["cost"]).train(m["reader"], num_passes=1,
                                  event_handler=handler, log_interval=10)
    first, last = float(costs[0]), float(costs[-1])
    assert np.isfinite(last) and last < first - 0.1, (first, last)
    reg = metrics.registry()
    for layer in ("lfm2.h1.moe", "lfm2.h2.moe"):
        every = [reg.counter_value("pt_moe_expert_tokens_total", labels={
            "layer": layer, "expert": e}) for e in range(16)]
        held = [reg.counter_value("pt_moe_held_pairs_total", labels={
            "layer": layer, "expert": e}) for e in range(2)]
        assert sum(every) == 30 * 2 * 160 * 2, every
        assert held == every[:2] and 0 < sum(held) < sum(every)
    # two operators traced, their bytes a step from the static shapes
    assert reg.counter_value("pt_short_conv_dispatch_total",
                             labels={"path": "xla"}) >= 2
    itemsize = 2 if amp else 4
    rendered = reg.render()
    assert "pt_short_conv_bytes " in rendered
    assert sum(short_conv_ops._bytes.values()) == 2 * (
        short_conv_ops.mix_bytes(2, 160, 64, 3, itemsize))
