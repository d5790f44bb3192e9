"""The OLMoE-shaped routed-expert decoder: its new ops against plain
`jax.numpy`, the grouped-matmul dispatcher's formulations against their
oracle, and the whole model through `Executor` against the plain float32
reference (`tests/olmoe_reference.py`) on seeded weights, in float32 and
under bf16 AMP. CPU: the grouped matmul takes its `jax.lax.ragged_dot`
formulation, attention the jnp one; the megablox kernel is run
interpreted here and compiled for a described v5e in test_tpu_compile.py.
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
from paddle_tpu.ops import moe_ops, nn_ops

sys.path.insert(0, os.path.dirname(__file__))
import olmoe_reference as ref  # noqa: E402

SMALL = dict(vocab_size=256, hidden_size=64, num_attention_heads=4,
             num_hidden_layers=2, num_experts=8, num_experts_per_tok=2,
             intermediate_size=32, rope_theta=10000.0, rms_norm_eps=1e-5,
             norm_topk_prob=False, aux_balance_weight=0.01,
             aux_z_weight=0.001)
B, T = 2, 64


def _rng(seed=0):
    return np.random.RandomState(seed)


# ----------------------------------------------------------- the new ops ---
def test_rms_norm_against_plain_jnp():
    x = jnp.asarray(_rng().randn(3, 5, 16), jnp.float32)
    w = jnp.asarray(_rng(1).rand(16) + 0.5, jnp.float32)

    def plain(x, w):
        return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * w

    np.testing.assert_allclose(nn_ops.rms_norm(x, w, 1e-5), plain(x, w),
                               rtol=1e-6, atol=1e-6)
    got = jax.grad(lambda x, w: (nn_ops.rms_norm(x, w, 1e-5) ** 3).sum(),
                   (0, 1))(x, w)
    want = jax.grad(lambda x, w: (plain(x, w) ** 3).sum(), (0, 1))(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)


def test_rms_norm_of_bf16_is_float32_inside_and_out():
    x = jnp.asarray(_rng().randn(4, 256) * 30, jnp.bfloat16)
    out = nn_ops.rms_norm(x, jnp.ones((256,), jnp.float32), 1e-5)
    assert out.dtype == jnp.float32      # the router reads it unrounded
    want = ref._rms(x.astype(jnp.float32), 1.0, 1e-5)
    np.testing.assert_allclose(out, want, rtol=1e-6)


def test_rotary_against_the_complex_form():
    """Rotate-half pairs lane i with lane i + D/2: the pair is one complex
    number turned by t * theta^(-2i/D)."""
    Bq, Tq, H, D = 2, 12, 3, 8
    x = jnp.asarray(_rng().randn(Bq, Tq, H, D), jnp.float32)
    z = np.asarray(x[..., : D // 2]) + 1j * np.asarray(x[..., D // 2:])
    ang = (np.arange(Tq)[:, None]
           * 10000.0 ** (-np.arange(0, D, 2) / D)[None, :])
    z = z * np.exp(1j * ang)[None, :, None, :]
    want = np.concatenate([z.real, z.imag], axis=-1)
    np.testing.assert_allclose(nn_ops.rotary(x, 10000.0), want,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ref._rope(x, 10000.0), want,
                               rtol=1e-5, atol=1e-5)
    # a rotation: norms are kept, and so is the gradient's
    g = jax.grad(lambda x: (nn_ops.rotary(x, 10000.0) ** 2).sum())(x)
    np.testing.assert_allclose(g, 2 * x, rtol=1e-4, atol=1e-5)


def _every_expert_masked(x, wr, wg, wu, wd, k):
    """Every expert on every token, masked: the routed FFN with no sort."""
    p = jax.nn.softmax(x @ wr, axis=-1)
    kth = jnp.sort(p, axis=-1)[:, -k][:, None]
    gates = jnp.where(p >= kth, p, 0.0)
    per = jnp.einsum("tef,efd->ted",
                     jax.nn.silu(jnp.einsum("td,edf->tef", x, wg))
                     * jnp.einsum("td,edf->tef", x, wu), wd)
    return (per * gates[..., None]).sum(1)


def _moe_inputs(tokens=48, d=16, f=24, E=8, seed=0):
    r = _rng(seed)
    mk = lambda *s: jnp.asarray(r.randn(*s) * 0.3, jnp.float32)  # noqa: E731
    return mk(tokens, d), mk(d, E) * 3, mk(E, d, f), mk(E, d, f), mk(E, f, d)


def test_moe_ffn_against_every_expert_on_every_token():
    args = _moe_inputs()
    out, logits, counts, *_ = moe_ops.moe_ffn(*args, top_k=2)
    # ties absent by construction: continuous random logits
    assert np.unique(np.asarray(logits), axis=-1).shape == logits.shape
    np.testing.assert_allclose(out, _every_expert_masked(*args, 2),
                               rtol=1e-5, atol=1e-5)
    assert logits.dtype == jnp.float32 and counts.dtype == jnp.int32
    assert int(counts.sum()) == 48 * 2

    def f(fn):
        return jax.grad(lambda *a: (fn(*a) ** 2).sum(), (0, 1, 2, 3, 4))(*args)

    got = f(lambda *a: moe_ops.moe_ffn(*a, top_k=2)[0])
    want = f(lambda *a: _every_expert_masked(*a, 2))
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)


def test_moe_ffn_renormalised_gates_sum_to_one():
    x, wr, wg, wu, wd = _moe_inputs()
    ones = lambda w: jnp.ones_like(w) / w.shape[1]  # noqa: E731
    # experts that all compute the same thing: the output is that thing
    # times the sum of the gates
    plain, *_ = moe_ops.moe_ffn(x, wr, ones(wg), ones(wu), ones(wd), 3)
    renorm, *_ = moe_ops.moe_ffn(x, wr, ones(wg), ones(wu), ones(wd), 3,
                                   norm_topk_prob=True)
    p = jax.nn.softmax(x @ wr, -1)
    share = jax.lax.top_k(p, 3)[0].sum(-1, keepdims=True)
    np.testing.assert_allclose(plain, renorm * share, rtol=1e-5, atol=1e-6)


def test_no_token_is_dropped_when_one_expert_takes_everything():
    x, wr, wg, wu, wd = _moe_inputs(tokens=128)
    wr = jnp.zeros_like(wr).at[:, 5].set(0.0)
    x = jnp.abs(x)
    wr = wr.at[:, 5].set(10.0).at[:, 2].set(5.0)   # everyone: 5 then 2
    out, _, counts, *_ = moe_ops.moe_ffn(x, wr, wg, wu, wd, top_k=2)
    want = np.zeros(8, np.int32)
    want[5] = want[2] = 128
    np.testing.assert_array_equal(counts, want)
    assert int(counts.sum()) == 128 * 2
    np.testing.assert_allclose(out, _every_expert_masked(x, wr, wg, wu, wd, 2),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------- the grouped-matmul dispatcher ---
GROUPS = {
    "ragged": [40, 0, 88, 1, 0, 63, 64, 0],
    "empty_groups_first_and_last": [0, 0, 128, 0, 128, 0, 0, 0],
    "one_group_holds_everything": [0, 0, 0, 256, 0, 0, 0, 0],
    "even": [32] * 8,
}


@pytest.mark.parametrize("sizes", list(GROUPS.values()), ids=list(GROUPS))
def test_grouped_matmul_formulations_agree(sizes):
    """The dispatcher's CPU formulation (`jax.lax.ragged_dot`) and the
    megablox kernel (interpreted) against the one oracle, forward and
    both gradients."""
    r = _rng(3)
    gs = jnp.asarray(sizes, jnp.int32)
    lhs = jnp.asarray(r.randn(256, 128), jnp.float32)
    rhs = jnp.asarray(r.randn(8, 128, 128) * 0.1, jnp.float32)
    assert not moe_ops.gmm_eligible(lhs, rhs)      # the CPU: ragged_dot
    forms = {
        "oracle": moe_ops.grouped_matmul_reference,
        "dispatcher": moe_ops.grouped_matmul,
        "kernel": lambda a, b, g: moe_ops._gmm_kernel(a, b, g, interpret=True),
    }

    def all_of(fn):
        out = fn(lhs, rhs, gs)
        grads = jax.grad(lambda a, b: (fn(a, b, gs) ** 2).sum(), (0, 1))(
            lhs, rhs)
        return (out, *grads)

    want = all_of(forms["oracle"])
    for name in ("dispatcher", "kernel"):
        for got, w in zip(all_of(forms[name]), want):
            np.testing.assert_allclose(got, w, rtol=2e-4, atol=2e-4,
                                       err_msg=name)


def test_grouped_matmul_shape_rules():
    ok = lambda m, k, n, dt=jnp.bfloat16: moe_ops._shapes_gmm_ok(  # noqa: E731
        jnp.zeros((m, k), dt), jnp.zeros((4, k, n), dt))
    assert ok(32768, 2048, 1024) and ok(32768, 1024, 2048)
    assert ok(256, 128, 128, jnp.float32)
    assert not ok(100, 128, 128) and not ok(256, 64, 128)
    assert not ok(256, 128, 96) and not ok(256, 128, 128, jnp.float16)
    # the tiling divides what it tiles, at OLMoE's sizes and at odd ones
    assert moe_ops._v5e_tiling(32768, 2048, 1024) == (256, 1024, 1024)
    assert moe_ops._v5e_tiling(32768, 1024, 2048) == (256, 1024, 1024)
    tm, tk, tn = moe_ops._v5e_tiling(384, 640, 128)
    assert 384 % tm == 0 and 640 % tk == 0 and tn == 128


# ------------------------------------------------ attention's new options ---
# what `multi_head_attention(h, num_heads=4)` appended before this PR
MHA_DEFAULT_OPS = [
    ("mul", {"x_num_col_dims": 2, "y_num_col_dims": 1}),
    ("elementwise_add", {"axis": -1}),
    ("mul", {"x_num_col_dims": 2, "y_num_col_dims": 1}),
    ("elementwise_add", {"axis": -1}),
    ("mul", {"x_num_col_dims": 2, "y_num_col_dims": 1}),
    ("elementwise_add", {"axis": -1}),
    ("flash_attention", {"num_heads": 4, "causal": True}),
    ("mul", {"x_num_col_dims": 2, "y_num_col_dims": 1}),
    ("elementwise_add", {"axis": -1}),
]


def _mha_ops(**kw):
    pt.reset()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = pt.layers.data("x", shape=[8, 32])
        pt.layers.multi_head_attention(x, num_heads=4, name="attn", **kw)
    return prog, [(op.type, dict(op.attrs)) for op in prog.global_block().ops]


def test_multi_head_attention_defaults_append_what_they_did():
    prog, ops = _mha_ops()
    assert ops == MHA_DEFAULT_OPS
    assert [p.name for p in prog.parameters()] == [
        "attn.wq", "attn.wq_b", "attn.wk", "attn.wk_b", "attn.wv",
        "attn.wv_b", "attn.wo", "attn.wo_b"]


def test_multi_head_attention_qk_norm_and_rotary_sit_before_flash():
    prog, ops = _mha_ops(qk_norm=True, rotary_theta=10000.0, bias_attr=False)
    assert [t for t, _ in ops] == [
        "mul", "mul", "mul", "rms_norm", "rms_norm", "rotary_embedding",
        "rotary_embedding", "flash_attention", "mul"]
    assert [p.name for p in prog.parameters()] == [
        "attn.wq", "attn.wk", "attn.wv", "attn.q_norm", "attn.k_norm",
        "attn.wo"]


# ------------------------------ the whole model against the plain reference ---
def _build(amp, cfg=SMALL):
    pt.reset()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        toks = pt.layers.data("toks", shape=[T], dtype=np.int32)
        labels = pt.layers.data("labels", shape=[T, 1], dtype=np.int32)
        logits, aux = models.olmoe_lm(
            toks, vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
            num_heads=cfg["num_attention_heads"],
            num_layers=cfg["num_hidden_layers"],
            num_experts=cfg["num_experts"],
            experts_per_token=cfg["num_experts_per_tok"],
            expert_dim=cfg["intermediate_size"],
            rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"])
        ce = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, labels))
        cost = pt.layers.elementwise_add(ce, aux)
        pt.optimizer.Adam(learning_rate=3e-4).minimize(cost)
    prog.random_seed = startup.random_seed = 11
    if amp:
        prog.set_amp("bfloat16")
    return prog, startup, logits, cost


def _batch(seed=5, b=B):
    r = _rng(seed)
    toks = r.randint(0, SMALL["vocab_size"], (b, T + 1))
    return {"toks": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:, None].astype(np.int32)}


def _first_step(amp, cfg=SMALL, b=B):
    """One step through Executor on seeded weights: the system's logits,
    cost and every parameter's gradient (read as the harness reads it:
    Adam's first moment over 1 - beta1), and the reference's."""
    prog, startup, logits, cost = _build(amp, cfg)
    exe = pt.Executor()
    exe.run(startup)
    scope = pt.global_scope()
    names = [p.name for p in prog.parameters()]
    params = [np.array(scope.get(n)) for n in names]
    feed = _batch(b=b)
    got_logits, got_cost = exe.run(prog, feed=feed, fetch_list=[logits, cost])
    moments = {op.inputs["Param"][0]: op.inputs["Moment1"][0]
               for op in prog.global_block().ops if op.type == "adam"}
    grads = [np.asarray(scope.get(moments[n]), np.float32) / (1 - 0.9)
             for n in names]
    want_cost, want_grads = ref.loss_and_grads(cfg, params, feed)
    want_logits = ref.logits(cfg, params, feed["toks"])
    return dict(names=names, logits=np.asarray(got_logits, np.float32),
                cost=float(got_cost), grads=grads,
                want_logits=np.asarray(want_logits), want_cost=float(want_cost),
                want_grads=[np.asarray(g) for g in want_grads])


def _rel(got, want):
    """rms(got - want) / rms(want): the harness's measure."""
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-12))


def test_program_parameter_order_is_the_reference_order():
    prog, *_ = _build(False)
    per_layer = ["ln_in.w", "attn.wq", "attn.wk", "attn.wv", "attn.q_norm",
                 "attn.k_norm", "attn.wo", "ln_post.w", "moe.router",
                 "moe.gate", "moe.up", "moe.down"]
    assert len(per_layer) == ref.PER_LAYER
    assert [p.name for p in prog.parameters()] == (
        ["olmoe.tok_emb"]
        + [f"olmoe.h{i}.{n}" for i in range(2) for n in per_layer]
        + ["olmoe.ln_f.w", "olmoe.out_w"])


def test_float32_model_matches_the_reference():
    """float32 on the CPU at the highest matmul precision, both sides: the
    only differences are the order of float32 sums (sorted rows against a
    scan over experts, fused attention against mapped heads). 1e-4
    relative on the logits is ~100 float32 roundings; a gradient that is
    missing, doubled or handed to the wrong parameter reads ~1."""
    r = _first_step(amp=False)
    assert _rel(r["logits"], r["want_logits"]) < 1e-4
    np.testing.assert_allclose(r["logits"], r["want_logits"],
                               rtol=1e-4, atol=1e-4 * np.abs(
                                   r["want_logits"]).max())
    assert abs(r["cost"] - r["want_cost"]) < 1e-5 * abs(r["want_cost"])
    for name, g, w in zip(r["names"], r["grads"], r["want_grads"]):
        assert g.shape == w.shape, name
        assert _rel(g, w) < 1e-3, (name, _rel(g, w))


# bf16 AMP against the float32 reference. Two things separate them: bf16's
# 8 bits of mantissa, and the top-k choice, which a rounding turns the other
# way for a token on a near-tie and so moves its whole contribution to
# another expert. Precision is measured where nothing can flip: every token
# to EVERY expert (top-8 of 8; sort, dispatch, grouped matmuls and combine
# all still run). Read there (CPU, PR 27): bf16 0.6 % on the logits and
# 0.4-1.6 % on every gradient; with the expert matmuls' inputs rounded to
# fp8 e4m3 (3 bits of mantissa) 2.9 % on the logits, 6.2-7.2 % on the expert
# stacks and 2-6 % on everything upstream of them. The limits are about
# twice bf16's reading.
ALL_EXPERTS = dict(SMALL, num_experts_per_tok=SMALL["num_experts"])
AMP_LOGITS_TOL, AMP_GRAD_TOL = 0.015, 0.03
ROUTED = (".moe.", ".ln_post.")


def _amp_errors(r):
    errs = {n: _rel(g, w) for n, g, w in
            zip(r["names"], r["grads"], r["want_grads"])}
    return errs, _rel(r["logits"], r["want_logits"])


def test_bf16_amp_model_matches_the_reference_at_bf16_tolerance():
    r = _first_step(True, ALL_EXPERTS)
    errs, logit_err = _amp_errors(r)
    assert logit_err < AMP_LOGITS_TOL, logit_err
    assert abs(r["cost"] - r["want_cost"]) < 1e-4 * abs(r["want_cost"])
    assert max(errs.values()) < AMP_GRAD_TOL, errs


def test_an_8_bit_expert_path_fails_the_bf16_tolerance(monkeypatch):
    """The control: the same step with the expert matmuls' inputs rounded
    to 8 bits (fp8 e4m3, straight-through so that the backward pass sees
    the rounded values too) must NOT pass the limits above, or they would
    let a lower-precision expert path through."""
    plain = moe_ops.grouped_matmul

    def fp8(a):
        rounded = a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        return a + jax.lax.stop_gradient(rounded - a)

    monkeypatch.setattr(
        moe_ops, "grouped_matmul",
        lambda lhs, rhs, sizes: plain(fp8(lhs), fp8(rhs), sizes))
    errs, logit_err = _amp_errors(_first_step(True, ALL_EXPERTS))
    assert logit_err > AMP_LOGITS_TOL, logit_err
    stacks = [e for n, e in errs.items()
              if n.endswith((".gate", ".up", ".down"))]
    assert min(stacks) > AMP_GRAD_TOL, errs


def test_bf16_amp_top_k_model_stays_near_the_reference():
    """Top-2 of 8 under AMP: near-ties flip, and the tensors behind a
    flipped choice (the stacks, router and norm of a routed layer: up to 4 %
    at 128 tokens) get the room the harness's routed rule gives them; every
    other tensor stays near bf16's limit. A gradient that is missing,
    doubled or handed to the wrong parameter reads ~1."""
    r = _first_step(True, SMALL)
    errs, logit_err = _amp_errors(r)
    assert logit_err < 2 * AMP_LOGITS_TOL, logit_err
    assert abs(r["cost"] - r["want_cost"]) < 1e-4 * abs(r["want_cost"])
    for name, err in errs.items():
        limit = 0.2 if any(s in name for s in ROUTED) else 0.04
        assert err < limit, (name, err)


def test_router_is_float32_under_amp():
    """Read off the traced program: under bf16 AMP the router's matmul
    takes float32 operands and gives float32 logits, while the grouped
    expert matmuls take bf16."""
    x = jnp.zeros((64, 32), jnp.bfloat16)
    wr = jnp.zeros((32, 8), jnp.float32)
    w = jnp.zeros((8, 32, 16), jnp.bfloat16)
    wd = jnp.zeros((8, 16, 32), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda *a: moe_ops.moe_ffn(*a, top_k=2))(
        x, wr, w, w, wd)
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert [v.aval.dtype for v in dots[0].invars] == [jnp.float32] * 2
    assert dots[0].outvars[0].aval.dtype == jnp.float32
    assert dots[0].params["precision"] is not None
    ragged = [e for e in jaxpr.jaxpr.eqns
              if e.primitive.name.startswith("ragged_dot")]
    assert len(ragged) == 3
    assert all(v.aval.dtype == jnp.bfloat16
               for e in ragged for v in e.invars[:2])
    # and through the Program: the op's RouterLogits is float32 under AMP
    prog, startup, _, _ = _build(amp=True)
    exe = pt.Executor()
    exe.run(startup)
    op = next(o for o in prog.global_block().ops if o.type == "moe_ffn")
    (z, out) = exe.run(prog, feed=_batch(), as_numpy=False, fetch_list=[
        op.outputs["RouterLogits"][0], op.outputs["Out"][0]])
    assert z.dtype == jnp.float32 and out.dtype == jnp.bfloat16


def test_amp_policy_table():
    from paddle_tpu import amp

    assert amp.precision_policy("rms_norm") == "high"
    assert amp.precision_policy("moe_aux_loss") == "high"
    assert amp.precision_policy("moe_ffn") == "low"
    assert amp.precision_policy("rotary_embedding") == "follow"
    assert "moe_ffn" not in amp.QUANTIZABLE_OPS


# ------------------------------------------------ training and statistics ---
def _load_config():
    path = os.path.join(os.path.dirname(__file__), "..", "configs", "olmoe.py")
    spec = importlib.util.spec_from_file_location("configs_olmoe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("amp", [None, "bfloat16"], ids=["float32", "amp"])
def test_configs_olmoe_trains_at_tiny_sizes(amp):
    from paddle_tpu.obs import metrics
    from paddle_tpu.trainer import EndIteration, Trainer

    pt.reset()
    metrics.registry().reset_metrics()
    m = _load_config().get_model(
        dim=64, heads=4, layers=1, experts=8, experts_per_token=2,
        expert_dim=32, seqlen=32, vocab=64, batch=4, steps=30, seed=3, amp=amp)
    costs = []

    def handler(e):
        if isinstance(e, EndIteration):
            costs.append(e.cost)

    trainer = Trainer(cost=m["cost"])
    trainer.train(m["reader"], num_passes=1, event_handler=handler,
                  log_interval=10)
    first, last = float(costs[0]), float(costs[-1])
    assert np.isfinite(last) and last < first - 0.1, (first, last)
    # the statistics route: tokens per expert reached the registry, summed
    # over all 30 steps, with nothing dropped, at no extra dispatch
    reg = metrics.registry()
    per_expert = [reg.counter_value(
        "pt_moe_expert_tokens_total",
        labels={"layer": "olmoe.h0.moe", "expert": e}) for e in range(8)]
    assert sum(per_expert) == 30 * 4 * 32 * 2, per_expert
    assert trainer.host_dispatch_count == 30


def test_step_statistics_reach_the_registry_at_every_cadence():
    """Per-step reads and reads every few steps publish the same counts;
    a program without statistics fetches what it fetched before."""
    from paddle_tpu.obs import metrics
    from paddle_tpu.trainer import Trainer

    totals = []
    for log_interval in (1, 4):
        pt.reset()
        metrics.registry().reset_metrics()
        m = _load_config().get_model(
            dim=32, heads=2, layers=2, experts=4, experts_per_token=2,
            expert_dim=16, seqlen=16, vocab=32, batch=2, steps=6, seed=3,
            amp=None)
        assert [s["counter"] for s in
                pt.default_main_program().step_statistics] == [
            "pt_moe_expert_tokens_total"] * 2
        Trainer(cost=m["cost"]).train(m["reader"], num_passes=1,
                                      log_interval=log_interval)
        reg = metrics.registry()
        totals.append([[reg.counter_value(
            "pt_moe_expert_tokens_total",
            labels={"layer": f"olmoe.h{i}.moe", "expert": e})
            for e in range(4)] for i in range(2)])
    assert totals[0] == totals[1]
    assert all(sum(layer) == 6 * 2 * 16 * 2 for layer in totals[0])
    assert pt.Program().step_statistics == []
