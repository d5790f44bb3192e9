"""The Trinity-shaped decoder (`models.afmoe_lm`): the per-head QK-norm and
the sigmoid output gate of `layers.multi_head_attention` against plain numpy,
the window's edge held to the token, a global layer that carries no position
signal, the routed layer's shares at Trinity's router (sigmoid, top k over
all, gates renormalised x 2.826, a SwiGLU shared expert) adding up to the
uncut layer of the reference, and the whole model through `Executor` against
`tests/afmoe_reference.py` on seeded weights. CPU: attention takes the jnp
formulation (the window kernels run interpreted in
tests/test_flash_attention.py); `tests/test_tpu_compile.py` compiles the
step for a described v5e.
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
from paddle_tpu.ops import moe_ops

sys.path.insert(0, os.path.dirname(__file__))
import afmoe_reference as ref  # noqa: E402

W_, G_ = "sliding_attention", "full_attention"
SMALL = dict(vocab_size=256, hidden_size=48, num_hidden_layers=4,
             layer_types=[W_, W_, G_, W_], num_dense_layers=1,
             num_attention_heads=4, num_key_value_heads=2, head_dim=8,
             sliding_window=8, rope_theta=1e4, rms_norm_eps=1e-5,
             intermediate_size=80, num_experts=8, num_experts_per_tok=3,
             moe_intermediate_size=24, num_shared_experts=1,
             route_scale=2.826, route_norm=True, mup_enabled=True)
B, T = 2, 40


def _rng(seed=0):
    return np.random.RandomState(seed)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-12))


# -------------------------------------------- the attention layer's new parts ---
def _attention_program(T=24, E=32, **kw):
    pt.reset()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = pt.layers.data("x", shape=[T, E], dtype=np.float32)
        out = pt.layers.multi_head_attention(x, name="att", bias_attr=False,
                                             **kw)
    prog.random_seed = startup.random_seed = 5
    return prog, startup, out


def _run_attention(prog, startup, out, x, extra=()):
    exe = pt.Executor()
    exe.run(startup)
    got = exe.run(prog, feed={"x": x}, fetch_list=[out, *extra])
    weights = {p.name.split(".")[-1]: np.asarray(pt.global_scope().get(p.name),
                                                 np.float64)
               for p in prog.parameters()}
    return got, weights


def _numpy_attention(x, w, H, KV, D, window=None, theta=None, eps=1e-5,
                     per_head_norm=True, gate=True):
    """float64 numpy, loops over heads: section 1's Attn_l to the letter."""
    x = np.asarray(x, np.float64)
    Bsz, T, _ = x.shape

    def rms(v, s):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * s

    def rope(v):                        # [B, T, D]
        ang = np.arange(T)[:, None] * theta ** (-np.arange(0, D, 2) / D)
        a, b = v[..., : D // 2], v[..., D // 2:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang)], -1)

    q, k, v = x @ w["wq"], x @ w["wk"], x @ w["wv"]
    out = np.zeros((Bsz, T, H * D))
    ahead = np.arange(T)[:, None] - np.arange(T)[None, :]
    seen = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    for h in range(H):
        g = h // (H // KV)
        qh, kh = q[..., h * D:(h + 1) * D], k[..., g * D:(g + 1) * D]
        if per_head_norm:
            qh, kh = rms(qh, w["q_norm"]), rms(kh, w["k_norm"])
        if theta:
            qh, kh = rope(qh), rope(kh)
        s = np.einsum("bqd,bkd->bqk", qh, kh) / np.sqrt(D)
        s = np.where(seen, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[..., h * D:(h + 1) * D] = p @ v[..., g * D:(g + 1) * D]
    if gate:
        out = out / (1.0 + np.exp(-(x @ w["wg"])))
    return out @ w["wo"]


@pytest.mark.parametrize("kind", ["window_rotary", "global_no_position"])
def test_attention_layer_against_plain_numpy(kind):
    """32-wide input, 4 query heads over 2 K/V heads of 8: the per-head norm
    (scales moved off one so that they show), the rotary on a window layer
    only, the window's mask and the gate, against loops in float64."""
    local = kind == "window_rotary"
    prog, startup, out = _attention_program(
        num_heads=4, num_kv_heads=2, head_dim=8, qk_norm="head",
        out_gate=True, window=8 if local else None,
        rotary_theta=1e4 if local else None)
    names = [p.name for p in prog.parameters()]
    assert names == ["att.wq", "att.wk", "att.wv", "att.q_norm", "att.k_norm",
                     "att.wg", "att.wo"]
    shapes = {p.name: tuple(p.shape) for p in prog.parameters()}
    assert shapes["att.q_norm"] == shapes["att.k_norm"] == (8,)
    assert shapes["att.wg"] == (32, 32) and shapes["att.wk"] == (32, 16)
    pt.Executor().run(startup)
    scope = pt.global_scope()
    for n in ("att.q_norm", "att.k_norm"):
        scope.set(n, (1.0 + 0.3 * _rng(1).randn(8)).astype(np.float32))
    x = _rng(0).randn(2, 24, 32).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got, = pt.Executor().run(prog, feed={"x": x}, fetch_list=[out])
    w = {n.split(".")[-1]: np.asarray(scope.get(n), np.float64) for n in names}
    want = _numpy_attention(x, w, 4, 2, 8, window=8 if local else None,
                            theta=1e4 if local else None)
    assert _rel(got, want) < 2e-5
    # the same layer with the norm over all lanes, or without the gate, is
    # another function
    assert _rel(_numpy_attention(x, w, 4, 2, 8, window=8 if local else None,
                                 theta=1e4 if local else None, gate=False),
                want) > 0.1


def test_per_head_norm_op_against_plain_numpy():
    """`rms_norm(group=D)`: every run of D lanes on its own, one scale [D];
    without `group` the op carries the one attribute it always did."""
    pt.reset()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = pt.layers.data("x", shape=[5, 24], dtype=np.float32)
        whole = pt.layers.rms_norm(x, epsilon=1e-5, name="whole")
        heads = pt.layers.rms_norm(x, epsilon=1e-5, name="heads", group=8)
    old, new = [o for o in prog.global_block().ops if o.type == "rms_norm"]
    assert old.attrs == {"epsilon": 1e-5}
    assert new.attrs == {"epsilon": 1e-5, "group": 8}
    assert [tuple(p.shape) for p in prog.parameters()] == [(24,), (8,)]
    exe = pt.Executor()
    exe.run(startup)
    scale = (1.0 + 0.5 * _rng(2).randn(8)).astype(np.float32)
    pt.global_scope().set(prog.parameters()[1].name, scale)
    xv = _rng(3).randn(2, 5, 24).astype(np.float32)
    got_whole, got = exe.run(prog, feed={"x": xv}, fetch_list=[whole, heads])
    v = xv.astype(np.float64).reshape(2, 5, 3, 8)
    want = v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5) * scale
    np.testing.assert_allclose(got, want.reshape(2, 5, 24), rtol=1e-5,
                               atol=1e-6)
    v = xv.astype(np.float64)
    np.testing.assert_allclose(
        got_whole, v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5),
        rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="do not divide"):
        with pt.program_guard(pt.Program(), pt.Program()):
            pt.layers.rms_norm(pt.layers.data("y", shape=[5, 24],
                                              dtype=np.float32), group=7)


def test_every_existing_caller_appends_the_ops_it_did():
    """No window, gate or per-head norm asked for: the ops, their attributes
    and the parameters of a layer built before they existed (OLMoE's whole-
    width QK-norm with rotary; nemotron's 4-over-2 heads without position
    signal). An unknown `qk_norm` raises."""
    prog, _, _ = _attention_program(num_heads=4, qk_norm=True,
                                    rotary_theta=1e4)
    ops = prog.global_block().ops
    assert [o.type for o in ops] == [
        "mul", "mul", "mul", "rms_norm", "rms_norm", "rotary_embedding",
        "rotary_embedding", "flash_attention", "mul"]
    assert ops[7].attrs == {"num_heads": 4, "causal": True}
    # the layer's own mark on what it builds in front of its kernel (PR 47):
    # the norms feed a rotary, the rotaries are the last ops in front of it
    assert ops[3].attrs == {"epsilon": 1e-5, "qk_emit": "float32"}
    assert [o.attrs.get("qk_emit") for o in ops] == [
        None, None, None, "float32", "float32", "kernel", "kernel", None,
        None]
    assert [tuple(p.shape) for p in prog.parameters()][3:5] == [(32,), (32,)]
    prog, _, _ = _attention_program(num_heads=4, num_kv_heads=2, head_dim=16)
    ops = prog.global_block().ops
    assert [o.type for o in ops] == ["mul"] * 3 + ["flash_attention", "mul"]
    assert ops[3].attrs == {"num_heads": 4, "causal": True}
    # Trinity's window layer: the attribute, and the gate behind the kernels
    prog, _, _ = _attention_program(num_heads=4, qk_norm="head",
                                    rotary_theta=1e4, window=8, out_gate=True)
    ops = prog.global_block().ops
    assert [o.type for o in ops] == [
        "mul", "mul", "mul", "rms_norm", "rms_norm", "rotary_embedding",
        "rotary_embedding", "flash_attention", "mul", "sigmoid",
        "elementwise_mul", "mul"]
    assert ops[7].attrs == {"num_heads": 4, "causal": True, "window": 8}
    # Trinity's global layer: no rotary, so the norms are the last ops
    prog, _, _ = _attention_program(num_heads=4, qk_norm="head", out_gate=True)
    assert [(o.type, o.attrs.get("qk_emit"))
            for o in prog.global_block().ops][3:6] == [
        ("rms_norm", "kernel"), ("rms_norm", "kernel"),
        ("flash_attention", None)]
    for bad in ("per_head", 2, "heads"):
        with pytest.raises(ValueError, match="qk_norm"):
            _attention_program(num_heads=4, qk_norm=bad)
    with pytest.raises(ValueError, match="window"):
        _attention_program(num_heads=4, window=8, causal=False)


def test_window_edge_is_held_to_the_token():
    """A window layer (W 8) at position i: moving the token at i - 8 or
    earlier moves nothing, moving the one at i - 7 does."""
    W, i = 8, 20
    prog, startup, out = _attention_program(
        num_heads=4, num_kv_heads=2, head_dim=8, qk_norm="head",
        out_gate=True, window=W, rotary_theta=1e4)
    exe = pt.Executor()
    exe.run(startup)
    x = _rng(0).randn(1, 24, 32).astype(np.float32)
    run = lambda v: exe.run(prog, feed={"x": v}, fetch_list=[out])[0][0, i]  # noqa: E731
    base = run(x)
    for j in (i - W, i - W - 1, 0):
        moved = x.copy()
        moved[0, j] += 1.0
        np.testing.assert_array_equal(run(moved), base)
    for j in (i - W + 1, i - 1, i):
        moved = x.copy()
        moved[0, j] += 1.0
        assert np.abs(run(moved) - base).max() > 1e-4, j


def test_global_layer_carries_no_position_signal():
    """A global layer without rotary at position i: the earlier tokens in
    another order give the same output (a set, not a sequence); a window
    layer's rotary tells the orders apart."""
    i = 12
    x = _rng(1).randn(1, 24, 32).astype(np.float32)
    mixed = x.copy()
    mixed[0, :i] = x[0, :i][_rng(2).permutation(i)]
    for theta, window, same in ((None, None, True), (1e4, 16, False)):
        prog, startup, out = _attention_program(
            num_heads=4, num_kv_heads=2, head_dim=8, qk_norm="head",
            out_gate=True, window=window, rotary_theta=theta)
        exe = pt.Executor()
        exe.run(startup)
        a, b = (exe.run(prog, feed={"x": v}, fetch_list=[out])[0][0, i]
                for v in (x, mixed))
        if same:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        else:
            assert np.abs(a - b).max() > 1e-3


# ------------------------------------------------- the shares of a layer ---
def _layer_inputs(tokens=64, d=16, f=24, E=16, seed=0):
    r = _rng(seed)
    mk = lambda *s: jnp.asarray(r.randn(*s) * 0.3, jnp.float32)  # noqa: E731
    return dict(x=mk(tokens, d), wr=mk(d, E) * 3, gate=mk(E, d, f),
                up=mk(E, d, f), down=mk(E, f, d),
                b=jnp.zeros((E,), jnp.float32), gate_s=mk(d, f),
                up_s=mk(d, f), down_s=mk(f, d))


def _layer_config(E, lo, hi, k=3):
    return dict(num_experts=hi - lo, router_experts=E, held_experts=(lo, hi),
                num_experts_per_tok=k, route_norm=True, route_scale=2.826)


def _share(p, lo, hi, k=3, shared=True):
    """One chip's share of the layer through the op's function."""
    return moe_ops.moe_ffn(
        p["x"], p["wr"], p["gate"][lo:hi], p["up"][lo:hi], p["down"][lo:hi],
        k, True, scoring="sigmoid", router_bias=p["b"], gate_scale=2.826,
        held=(lo, hi),
        shared=(p["gate_s"], p["up_s"], p["down_s"]) if shared else None)


def _whole(p, cfg, lo=0, hi=16):
    return ref._experts(cfg, p["x"], p["wr"], p["gate"][lo:hi],
                        p["up"][lo:hi], p["down"][lo:hi], p["b"],
                        p["gate_s"], p["up_s"], p["down_s"])[0]


def test_the_eight_shares_add_up():
    """E 16 as 8 shares of 2 (Trinity's 128 as 8 of 16): every share's routed
    part, plus the SwiGLU shared expert counted once, is the uncut layer of
    the reference, and the held pairs are all the pairs."""
    p = _layer_inputs()
    with jax.default_matmul_precision("highest"):
        whole = _whole(p, _layer_config(16, 0, 16))
        total, pairs = 0.0, 0
        for lo in range(0, 16, 2):
            out, _, counts, held, *_ = _share(p, lo, lo + 2,
                                              shared=(lo == 0))
            total, pairs = total + out, pairs + int(held.sum())
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    assert pairs == 64 * 3 == int(counts.sum())


@pytest.mark.parametrize("lo,hi", [(0, 16), (0, 2), (14, 16)],
                         ids=["all_held", "first_share", "last_share"])
def test_a_share_against_the_reference(lo, hi):
    """Values and every gradient of a share at Trinity's router, float32."""
    p = _layer_inputs(seed=1)
    cfg = _layer_config(16, lo, hi)
    w = jnp.asarray(_rng(4).randn(64, 16), jnp.float32)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(_share(p, lo, hi)[0],
                                   _whole(p, cfg, lo, hi), rtol=1e-4,
                                   atol=1e-5)
        g = jax.grad(lambda p: (_share(p, lo, hi)[0] * w).sum())(p)
        r = jax.grad(lambda p: (_whole(p, cfg, lo, hi) * w).sum())(p)
    for name in ("x", "wr", "gate", "up", "down", "gate_s", "up_s", "down_s"):
        assert _rel(g[name], r[name]) < 1e-4, (name, _rel(g[name], r[name]))
    assert not np.any(np.asarray(g["b"]))


# ------------------------------ the whole model against the plain reference ---
def _build(amp, cfg=SMALL, held=None):
    pt.reset()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        toks = pt.layers.data("toks", shape=[T], dtype=np.int32)
        labels = pt.layers.data("labels", shape=[T, 1], dtype=np.int32)
        logits, routers = models.afmoe_lm(
            toks, vocab_size=cfg["vocab_size"],
            layer_types=cfg["layer_types"],
            num_dense_layers=cfg["num_dense_layers"], dim=cfg["hidden_size"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            window=cfg["sliding_window"], dense_dim=cfg["intermediate_size"],
            num_experts=cfg.get("router_experts", cfg["num_experts"]),
            experts_per_token=cfg["num_experts_per_tok"],
            expert_dim=cfg["moe_intermediate_size"],
            shared_expert_dim=cfg["num_shared_experts"]
            * cfg["moe_intermediate_size"],
            gate_scale=cfg["route_scale"], norm_topk_prob=cfg["route_norm"],
            held_experts=held, rope_theta=cfg["rope_theta"],
            rms_eps=cfg["rms_norm_eps"])
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
    attn = ["n1.w", "attn.wq", "attn.wk", "attn.wv", "attn.q_norm",
            "attn.k_norm", "attn.wg", "attn.wo", "n2.w", "n3.w"]
    kinds = {"dense": ["mlp.gate", "mlp.up", "mlp.down"],
             "routed": ["moe.router", "moe.gate", "moe.up", "moe.down",
                        "moe.router_bias", "moe.shared_gate", "moe.shared_up",
                        "moe.shared_down"]}
    want = ["afmoe.tok_emb"]
    for i, kind in enumerate(ref._kinds(SMALL)):
        assert len(attn) + len(kinds[kind]) + 1 == ref.PER_KIND[kind]
        want += [f"afmoe.h{i}.{n}" for n in attn + kinds[kind] + ["n4.w"]]
    assert [p.name for p in prog.parameters()] == want + [
        "afmoe.ln_f.w", "afmoe.out_w"]
    # every routed layer hands out its RouterLogits and TokensPerExpert
    assert len(routers) == 3
    ops = [o for o in prog.global_block().ops if o.type == "moe_ffn"]
    assert [(o.outputs["RouterLogits"][0], o.outputs["TokensPerExpert"][0])
            for o in ops] == [(z.name, c.name) for z, c in routers]
    with pytest.raises(ValueError, match="num_dense_layers"):
        models.afmoe_lm(None, 8, layer_types=(W_, G_), num_dense_layers=3)
    with pytest.raises(ValueError, match="layer_types"):
        models.afmoe_lm(None, 8, layer_types=(W_, "chunked_attention"))


def test_the_stream_starts_as_the_tokens_own_rows():
    """The token table starts at N(0, 1) (not the layer DSL's Glorot over
    [vocab, dim], and not N(0, 1 / dim)), every norm's scale at one: behind
    the sqrt(dim) multiplier the stream's rms is sqrt(dim) and a layer adds
    two unit-rms branches to it."""
    prog, startup, *_ = _build(False)
    exe = pt.Executor()
    exe.run(startup)
    scope = pt.global_scope()
    seen = set()
    for p in prog.parameters():
        if len(p.shape) == 1 and "router_bias" not in p.name:
            np.testing.assert_array_equal(np.asarray(scope.get(p.name)), 1.0)
            *_, before, last = p.name.split(".")
            seen.add(before if last == "w" else last)
    assert seen == {"n1", "n2", "n3", "n4", "q_norm", "k_norm", "ln_f"}
    assert abs(np.asarray(scope.get("afmoe.tok_emb")).std() - 1.0) < 0.02
    ops = prog.global_block().ops
    scale, add = (next(o for o in ops if o.type == t)
                  for t in ("scale", "elementwise_add"))
    assert scale.attrs["scale"] == pytest.approx(48 ** 0.5)
    x0, branch = exe.run(prog, feed=_batch(), fetch_list=[
        scale.outputs["Out"][0], add.inputs["Y"][0]])
    assert abs(np.sqrt(np.mean(x0 ** 2)) / 48 ** 0.5 - 1.0) < 0.05
    assert abs(np.sqrt(np.mean(np.square(branch))) - 1.0) < 0.05


def test_layer_kinds_decide_window_and_rotary():
    """`layer_types` alone says which layers get the `window` attribute and
    the rotary ops: a window layer's kernel reads a rotary op's output, a
    global layer's a norm's (what the benchmark's readers tell them by)."""
    prog, *_ = _build(False)
    ops = prog.global_block().ops
    made_by = {name: o.type for o in ops for outs in o.outputs.values()
               for name in outs}
    flash = [o for o in ops if o.type == "flash_attention"]
    assert [o.attrs.get("window") for o in flash] == [8, 8, None, 8]
    assert [made_by[o.inputs["Q"][0]] for o in flash] == [
        "rotary_embedding", "rotary_embedding", "rms_norm",
        "rotary_embedding"]
    assert sum(o.type == "rotary_embedding" for o in ops) == 2 * 3
    assert models.afmoe.TRINITY_MINI_LAYER_TYPES.count(G_) == 8
    assert models.afmoe.TRINITY_MINI_LAYER_TYPES[:4] == (W_, W_, W_, G_)


@pytest.mark.parametrize("held", [None, (2, 6)], ids=["all_held", "a_share"])
def test_float32_model_matches_the_reference(held):
    """float32 on the CPU at the highest matmul precision, both sides: the
    cost and every gradient within 2e-4 of its rms. A gradient that is
    missing, doubled or handed to the wrong parameter reads ~1."""
    cfg = SMALL if held is None else dict(
        SMALL, router_experts=8, num_experts=4, held_experts=held)
    with jax.default_matmul_precision("highest"):
        r = _first_step(False, cfg, held)
    assert _rel(r["logits"], r["want_logits"]) < 1e-4
    assert abs(r["cost"] - r["want_cost"]) < 2e-4 * abs(r["want_cost"])
    assert r["untrained"] == [f"afmoe.h{i}.moe.router_bias"
                              for i in (1, 2, 3)]
    assert len(r["errs"]) == len(r["names"]) - 3
    for name, err in r["errs"].items():
        assert err < 2e-4, (name, err)
    for got, (_, _, want) in zip(r["routers"], r["want_routers"]):
        assert _rel(got, want) < 1e-4


@pytest.mark.parametrize("what", ["window_on_every_layer", "no_window",
                                  "rotary_on_the_global_layer"])
def test_the_reference_tells_a_wrong_layer_kind(what):
    """The model built with another `layer_types` than the reference is
    given reads far outside 2e-4: the comparison sees the window and the
    rotary, layer by layer."""
    wrong = {"window_on_every_layer": [W_] * 4,
             "no_window": [W_, W_, G_, W_],
             "rotary_on_the_global_layer": [W_, W_, W_, W_]}[what]
    cfg = dict(SMALL, layer_types=wrong)
    if what == "no_window":
        cfg["sliding_window"] = T      # every key inside: plain causal
    prog, startup, logits, *_ = _build(False, cfg)
    exe = pt.Executor()
    exe.run(startup)
    params = [np.array(pt.global_scope().get(p.name))
              for p in prog.parameters()]
    feed = _batch()
    with jax.default_matmul_precision("highest"):
        got, = exe.run(prog, feed=feed, fetch_list=[logits])
        assert _rel(got, ref.logits(cfg, params, feed["toks"])) < 1e-4
        assert _rel(got, ref.logits(SMALL, params, feed["toks"])) > 1e-2


def test_bf16_amp_model_stays_near_the_reference():
    """bf16 AMP against float32 with the reference handed the program's own
    choice of experts (the benchmark's rule since PR 36): what is left is
    rounding."""
    r = _first_step(True, hand_choice=True)
    assert _rel(r["logits"], r["want_logits"]) < 0.02
    assert abs(r["cost"] - r["want_cost"]) < 5e-4 * abs(r["want_cost"])
    for name, err in r["errs"].items():
        assert err < 0.05, (name, err)


def test_the_residual_stream_is_float32_under_amp_without_a_cast():
    """Sandwich norms: both summands of every residual add are float32 under
    amp (the scaled table row or the stream, and a norm's output), so the
    stream is float32 with no `cast` op in the program."""
    prog, *_ = _build(True)
    ops = prog.global_block().ops
    assert not [o for o in ops if o.type == "cast"]
    made_by = {name: o.type for o in ops for outs in o.outputs.values()
               for name in outs}
    adds = [o for o in ops if o.type == "elementwise_add"]
    assert len(adds) == 2 * SMALL["num_hidden_layers"]
    assert {made_by[o.inputs["Y"][0]] for o in adds} == {"rms_norm"}
    assert made_by[adds[0].inputs["X"][0]] == "scale"


def _load_config():
    path = os.path.join(os.path.dirname(__file__), "..", "configs",
                        "afmoe.py")
    spec = importlib.util.spec_from_file_location("afmoe_config", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("amp", [None, "bfloat16"], ids=["float32", "amp"])
def test_configs_afmoe_trains_at_tiny_sizes(amp):
    from paddle_tpu.obs import metrics
    from paddle_tpu.trainer import EndIteration, Trainer

    pt.reset()
    metrics.registry().reset_metrics()
    m = _load_config().get_model(
        layer_types=(W_, G_, W_), dense_layers=1, dim=48, heads=4, kv_heads=2,
        head_dim=8, window=32, dense_dim=80, experts=16, held_experts=(0, 2),
        experts_per_token=3, expert_dim=24, shared_expert_dim=24, seqlen=160,
        vocab=64, batch=2, steps=30, seed=3, amp=amp)
    costs = []

    def handler(e):
        if isinstance(e, EndIteration):
            costs.append(e.cost)

    Trainer(cost=m["cost"]).train(m["reader"], num_passes=1,
                                  event_handler=handler, log_interval=10)
    first, last = float(costs[0]), float(costs[-1])
    assert np.isfinite(last) and last < first - 0.1, (first, last)
    reg = metrics.registry()
    for layer in ("afmoe.h1.moe", "afmoe.h2.moe"):
        every = [reg.counter_value("pt_moe_expert_tokens_total", labels={
            "layer": layer, "expert": e}) for e in range(16)]
        held = [reg.counter_value("pt_moe_held_pairs_total", labels={
            "layer": layer, "expert": e}) for e in range(2)]
        assert sum(every) == 30 * 2 * 160 * 3, every
        assert held == every[:2] and 0 < sum(held) < sum(every)
        bounded, whole = (reg.counter_value("pt_moe_row_path_total", labels={
            "layer": layer, "path": path}) for path in (0, 1))
        assert bounded + whole == 30 and bounded > 0
    assert reg.counter_value("pt_flash_attention_dispatch_total",
                             labels={"path": "xla"}) >= 3


def test_a_bounded_share_publishes_how_full_its_chunks_were():
    """`pt_moe_chunk_rows_total{layer,kind}`, step by step (a sync a step):
    kind 0 is the chunks' live rows (the step's held pairs: the only rows a
    chunk's sums read), kind 1 the step's chunks x R, live <= bound; a layer
    whose rows have no bound (half of its experts held) publishes neither."""
    from paddle_tpu.obs import metrics
    from paddle_tpu.trainer import EndIteration, Trainer

    pt.reset()
    metrics.registry().reset_metrics()
    m = _load_config().get_model(
        layer_types=(W_, G_, W_), dense_layers=1, dim=48, heads=4, kv_heads=2,
        head_dim=8, window=32, dense_dim=80, experts=16, held_experts=(0, 2),
        experts_per_token=3, expert_dim=24, shared_expert_dim=24, seqlen=160,
        vocab=64, batch=2, steps=6, seed=3, amp=None)
    # 2 x 160 tokens x 3 = 960 rows, 2 of 16 held, three even shares a chunk
    R = moe_ops.row_bound(960, (0, 2), 16, 8, 3)
    assert R == 360
    reg = metrics.registry()
    layers = ("afmoe.h1.moe", "afmoe.h2.moe")

    def read(counter, label, n):
        return [[reg.counter_value(counter, labels={"layer": layer, label: i})
                 for i in range(n)] for layer in layers]

    seen = []

    def handler(e):
        if isinstance(e, EndIteration):
            seen.append((read("pt_moe_held_pairs_total", "expert", 2),
                         read("pt_moe_chunk_rows_total", "kind", 2)))

    Trainer(cost=m["cost"]).train(m["reader"], num_passes=1,
                                  event_handler=handler, log_interval=1)
    assert len(seen) == 6
    steps = np.diff(np.asarray([(np.zeros((2, 2)),) * 2] + seen), axis=0)
    for held, (live, bound) in zip(steps[:, 0].sum(-1).ravel(),
                                   steps[:, 1].reshape(-1, 2)):
        assert 0 < held == live <= bound == max(1, -(-held // R)) * R
    prog = pt.Program()
    with pt.program_guard(prog, pt.Program()):
        x = pt.layers.data("x", shape=[8, 16], dtype=np.float32)
        pt.layers.moe_ffn(x, 4, 2, 8, name="a_half", held_experts=(0, 2))
        pt.layers.moe_ffn(x, 16, 2, 8, name="an_eighth", held_experts=(0, 2))
    assert [s["labels"]["layer"] for s in prog.step_statistics
            if s["counter"] == "pt_moe_chunk_rows_total"] == ["an_eighth"]


def test_a_layer_may_ask_for_chunks_of_three_even_shares():
    """`moe_ffn(chunk_shares=)`: absent, the op and its bound are what they
    were (two even shares of the T x k rows); Trinity's layers ask for
    three, and a step whose live pairs fit three takes one chunk where two
    would have taken two, with the same sum."""
    assert moe_ops.row_bound(65536, (0, 16), 128, 256) == 16384
    assert moe_ops.row_bound(65536, (0, 16), 128, 256, 3) == 24576
    assert moe_ops.bounds_rows((0, 16), 128, 3)
    assert not moe_ops.bounds_rows((0, 16), 32, 3)      # half and over: none
    pt.reset()
    prog = pt.Program()
    with pt.program_guard(prog, pt.Program()):
        x = pt.layers.data("x", shape=[8, 16], dtype=np.float32)
        for name, shares in (("two", None), ("three", 3)):
            pt.layers.moe_ffn(x, 16, 3, 8, name=name, scoring="sigmoid",
                              router_bias=True, gate_scale=2.826,
                              norm_topk_prob=True, held_experts=(0, 2),
                              shared_expert_dim=8, chunk_shares=shares)
    two, three = [o for o in prog.global_block().ops if o.type == "moe_ffn"]
    assert "chunk_shares" not in two.attrs
    assert three.attrs["chunk_shares"] == 3 and "RowPath" in three.outputs
    # 64 tokens x 3 = 192 rows, 2 of 16 held: chunks of 48 or 72 rows; 25
    # tokens steered to both held experts are 50 live pairs
    p = _layer_inputs()
    sign = jnp.where(jnp.arange(64) < 25, 1.0, -1.0)
    p = dict(p, x=p["x"].at[:, 0].set(sign),
             wr=p["wr"].at[:, 8:10].set(0.0).at[0, 8:10].set(6.0))

    def share(shares):
        return moe_ops.moe_ffn(
            p["x"], p["wr"], p["gate"][8:10], p["up"][8:10], p["down"][8:10],
            3, True, scoring="sigmoid", router_bias=p["b"], gate_scale=2.826,
            held=(8, 10), shared=(p["gate_s"], p["up_s"], p["down_s"]),
            chunk_shares=shares)

    with jax.default_matmul_precision("highest"):
        (out2, *_, held2, path2, _), (out3, *_, held3, path3, _) = (
            share(None), share(3))
    assert int(held2.sum()) == int(held3.sum()) == 50
    np.testing.assert_array_equal(path2, [0, 1])        # 50 > 48: two chunks
    np.testing.assert_array_equal(path3, [1, 0])        # 50 <= 72: one
    np.testing.assert_allclose(out2, out3, rtol=1e-5, atol=1e-6)
