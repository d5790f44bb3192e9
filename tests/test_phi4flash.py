"""The Phi-4-mini-flash-shaped decoder (`models.phi4flash_lm`): the selective
scan (`ops/ssm_ops.py:selective_scan`) and its `custom_vjp` against `jax.grad`
of a token-by-token loop, its Pallas kernels interpreted against the plain
form, differential attention against two dense softmaxes (own and shared keys
and values, with and without a window), the whole model through `Executor`
against `tests/phi4flash_reference.py` on seeded weights, gradient by
gradient, the 32-layer map and the published parameter count, and four wrong
programs (`tests/phi4flash_controls.py`) that the comparison has to catch.
CPU: attention takes the jnp formulation and the scan its plain form;
`tests/test_tpu_compile.py` compiles the step for a described v5e.
"""

import contextlib
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.core.backward import append_backward
from paddle_tpu.ops import flash_ops, ssm_ops

sys.path.insert(0, os.path.dirname(__file__))
import phi4flash_controls  # noqa: E402
import phi4flash_reference as ref  # noqa: E402

SMALL = dict(vocab_size=96, hidden_size=64, num_attention_heads=8,
             num_key_value_heads=4, intermediate_size=96, sliding_window=8,
             mb_per_layer=2, layer_norm_eps=1e-5, num_hidden_layers=6,
             layer_ids=[0, 1, 4, 5, 6, 7], published={"num_hidden_layers": 8},
             mamba_d_state=4, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4)
B, T = 2, 32
# the program's parameters at SMALL, in creation order (a test a tensor)
MIXER = ("in_w", "conv_w", "conv_b", "x_w", "dt_w", "dt_b", "A_log", "D",
         "out_w")
ATTN = ("wqkv", "wqkv_b", "lq1", "lk1", "lq2", "lk2", "subln", "wo", "wo_b")
LAYERS = {0: ("mamba", MIXER), 1: ("attn", ATTN), 4: ("mamba", MIXER),
          5: ("attn", ATTN), 6: ("gmu", ("gate_w", "out_w")),
          7: ("attn", ("wq", "wq_b") + ATTN[2:])}
TENSORS = ["tok_emb"] + [
    f"h{i}.{part}" for i, (kind, names) in LAYERS.items()
    for part in (["mix_norm.w", "mix_norm.b"]
                 + [f"{kind}.{n}" for n in names]
                 + ["mlp_norm.w", "mlp_norm.b", "mlp.w1", "mlp.w2"])
] + ["final_norm.w", "final_norm.b"]


def _rng(seed=0):
    return np.random.RandomState(seed)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-12))


# ------------------------------------------------------- the selective scan ---
def _token_loop(x, dt, A, Bm, Cm, D):
    """The recurrence, a token at a time, float32, no custom rule."""
    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        S = (jnp.exp(dt_t[:, :, None] * A) * S
             + (dt_t * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.einsum("bcn,bn->bc", S, C_t) + D * x_t

    _, y = jax.lax.scan(step, jnp.zeros(x.shape[:1] + A.shape),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


def _scan_operands(T_, C=24, N=4, seed=0, batch=2):
    r = _rng(seed + T_)
    f = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)  # noqa: E731
    return (f(batch, T_, C), jnp.abs(f(batch, T_, C)) * 0.3 + 0.01,
            -jnp.abs(f(C, N)) - 0.2, f(batch, T_, N), f(batch, T_, N), f(C),
            f(batch, T_, C))


@pytest.mark.parametrize("T_", [128, 256, 300, 40, 1],
                         ids=["one_chunk", "two_chunks", "T300_ragged_tail",
                              "T40_under_a_chunk", "T1"])
def test_scan_and_its_backward_against_a_token_loop(T_):
    *operands, g = _scan_operands(T_)
    np.testing.assert_allclose(ssm_ops.selective_scan(*operands),
                               _token_loop(*operands), rtol=2e-5, atol=2e-5)
    grads = lambda f: jax.grad(  # noqa: E731
        lambda *a: (f(*a) * g).sum(), argnums=tuple(range(6)))(*operands)
    for name, got, want in zip("x dt A B C D".split(),
                               grads(ssm_ops.selective_scan),
                               grads(_token_loop)):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert _rel(got, want) < 2e-5, (name, _rel(got, want))


def test_scan_keeps_the_chunk_starts_and_nothing_of_T_C_N():
    """The residuals of the `custom_vjp`: the six operands and [B, T / Q, N,
    C] float32; the amp dtype in is the amp dtype out."""
    x, dt, A, Bm, Cm, D, _ = _scan_operands(256)
    x, Bm, Cm = (a.astype(jnp.bfloat16) for a in (x, Bm, Cm))
    y, saved = ssm_ops._selective_scan_fwd(x, dt, A, Bm, Cm, D)
    assert y.dtype == jnp.bfloat16 and y.shape == x.shape
    assert [a.shape for a in saved[:6]] == [
        a.shape for a in (x, dt, A, Bm, Cm, D)]
    assert saved[6].shape == (2, 2, 4, 24) and saved[6].dtype == jnp.float32
    grads = ssm_ops._selective_scan_bwd(saved, y)
    assert [g.dtype for g in grads] == [
        a.dtype for a in (x, dt, A, Bm, Cm, D)]


@pytest.mark.parametrize("C,N,dtype", [(256, 8, jnp.float32),
                                       (128, 16, jnp.bfloat16),
                                       (512, 8, jnp.bfloat16)],
                         ids=["two_tiles_f32", "one_tile_bf16_N16",
                              "a_512_lane_tile"])
def test_interpreted_kernels_against_the_plain_form(C, N, dtype):
    """The Pallas kernels, interpreted on the CPU, over two chunks: the
    forward's y and chunk starts, and every cotangent of the backward."""
    x, dt, A, Bm, Cm, D, g = _scan_operands(256, C=C, N=N, batch=1)
    x, Bm, Cm, g = (a.astype(dtype) for a in (x, Bm, Cm, g))
    assert ssm_ops._shapes_selective_ok(x, A)
    y, starts = ssm_ops._sel_kernel_forward(x, dt, A, Bm, Cm, D,
                                            interpret=True)
    want_y, want_starts = ssm_ops._sel_xla_forward(x, dt, A, Bm, Cm, D)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    assert y.dtype == dtype and _rel(y, want_y) < tol
    assert _rel(starts, want_starts) < 1e-5 and float(
        jnp.abs(starts[:, 1]).max()) > 0
    got = ssm_ops._sel_kernel_backward(x, dt, A, Bm, Cm, D, starts, g,
                                       interpret=True)
    want = ssm_ops._sel_xla_backward(x, dt, A, Bm, Cm, D, want_starts, g)
    for name, a, b in zip("x dt A B C D".split(), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < tol, (name, _rel(a, b))


@pytest.mark.parametrize("shape,n,ok", [
    ((1, 8192, 5120), 16, True), ((1, 8192, 5000), 16, False),
    ((1, 8100, 5120), 16, False), ((2, 256, 128), 8, True),
    ((1, 256, 128), 4, False)],
    ids=["the_cell", "lanes_not_whole", "ragged_T", "small", "N4"])
def test_kernel_shape_rules(shape, n, ok):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert ssm_ops._shapes_selective_ok(
        x, jax.ShapeDtypeStruct((shape[2], n), jnp.float32)) is ok


def test_scan_counts_its_path_and_its_bytes():
    from paddle_tpu.obs import metrics

    reg = metrics.registry()
    before = reg.counter_value("pt_selective_scan_dispatch_total",
                               labels={"path": "xla_chunked"})
    x, dt, A, Bm, Cm, D, _ = _scan_operands(40)
    ssm_ops._sel_bytes.clear()
    ssm_ops.selective_scan(x, dt, A, Bm, Cm, D, name="a_scan")
    ssm_ops.selective_scan(x, dt, A, Bm, Cm, D, name="a_scan")   # once
    assert reg.counter_value("pt_selective_scan_dispatch_total",
                             labels={"path": "xla_chunked"}) == before + 2
    text = reg.render()
    bytes_, saved = ssm_ops.selective_scan_bytes(2, 40, 24, 4, 4)
    assert f"pt_selective_scan_bytes {bytes_}\n" in text
    assert f"pt_selective_scan_saved_state_bytes {saved}\n" in text
    assert saved == 2 * 1 * 24 * 4 * 4


# ------------------------------------------------ differential attention ---
def _dense_pairs(q, k, v, lam, w_n, lam_init, H, KV, window):
    """Two dense softmaxes a pair, numpy float64."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    Bsz, T_, E = q.shape
    D = E // H
    q, k, v = (a.reshape(Bsz, T_, -1, D) for a in (q, k, v))
    i, j = np.arange(T_)[:, None], np.arange(T_)[None, :]
    seen = (j <= i) & ((j > i - window) if window else True)
    out = np.zeros((Bsz, T_, H // 2, 2 * D))

    def soft(s):
        s = np.where(seen, s, -np.inf)
        e = np.exp(s - s.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)

    for b in range(Bsz):
        for p in range(H // 2):
            m = p // (H // KV)
            a1 = soft(q[b, :, 2 * p] @ k[b, :, 2 * m].T / math.sqrt(D))
            a2 = soft(q[b, :, 2 * p + 1] @ k[b, :, 2 * m + 1].T / math.sqrt(D))
            vv = np.concatenate([v[b, :, 2 * m], v[b, :, 2 * m + 1]], -1)
            o = (a1 - lam * a2) @ vv
            o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-5)
            out[b, :, p] = o * np.asarray(w_n, np.float64) * (1 - lam_init)
    return out.reshape(Bsz, T_, E)


@pytest.mark.parametrize("shared", [False, True], ids=["own_kv", "shared_kv"])
@pytest.mark.parametrize("window", [None, 8], ids=["whole", "window8"])
def test_differential_attention_against_two_dense_softmaxes(shared, window):
    H, KV, E, depth = 8, 4, 64, 5
    r = _rng(3)
    pt.reset()
    x = pt.layers.data("x", shape=[T, E], dtype=np.float32)
    kv = None
    if shared:
        kv = (pt.layers.data("k", shape=[T, E // 2], dtype=np.float32),
              pt.layers.data("v", shape=[T, E // 2], dtype=np.float32))
    out = pt.layers.differential_attention(
        x, H, KV, depth=depth, window=window, shared_kv=kv, return_kv=True,
        name="da")
    out, (k_var, v_var) = out
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    scope = pt.global_scope()
    for p in pt.default_main_program().parameters():
        scope.set(p.name, jnp.asarray(
            np.asarray(scope.get(p.name))
            + 0.3 * r.randn(*p.shape).astype(np.float32)))
    feed = {"x": r.randn(B, T, E).astype(np.float32)}
    if shared:
        feed.update(k=r.randn(B, T, E // 2).astype(np.float32),
                    v=r.randn(B, T, E // 2).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        got, k_got, v_got = exe.run(feed=feed,
                                    fetch_list=[out, k_var, v_var])
    P = {p.name.split(".")[-1]: np.asarray(scope.get(p.name), np.float64)
         for p in pt.default_main_program().parameters()}
    if shared:
        q = feed["x"] @ P["wq"] + P["wq_b"]
        k, v = feed["k"], feed["v"]
    else:
        qkv = feed["x"] @ P["wqkv"] + P["wqkv_b"]
        q, k, v = qkv[..., :E], qkv[..., E:E + E // 2], qkv[..., E + E // 2:]
    np.testing.assert_allclose(k_got, k, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(v_got, v, rtol=1e-4, atol=1e-5)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = (np.exp(P["lq1"] @ P["lk1"]) - np.exp(P["lq2"] @ P["lk2"])
           + lam_init)
    want = _dense_pairs(q, k, v, lam, P["subln"], lam_init, H, KV,
                        window or 0) @ P["wo"] + P["wo_b"]
    assert _rel(got, want) < 1e-4


def test_differential_attention_refuses_what_it_cannot_mask():
    pt.reset()
    x = pt.layers.data("x", shape=[T, 64], dtype=np.float32)
    short = pt.layers.data("k", shape=[T // 2, 32], dtype=np.float32)
    with pytest.raises(ValueError, match="SAME sequence"):
        pt.layers.differential_attention(x, 8, 4, depth=1,
                                         shared_kv=(short, short))
    with pytest.raises(ValueError, match="pairs"):
        pt.layers.differential_attention(x, 8, 3, depth=1)
    with pytest.raises(ValueError, match="window"):
        pt.layers.differential_attention(x, 8, 4, depth=1, window=0)


def test_the_launch_gauge_reads_one_launch_a_layer():
    """A differential-attention layer is ONE `flash_attention` op with two
    outputs: the gauge reads 1 a layer (3 at SMALL, where the four launches a
    layer read 12), and the pairs gauge counts each of the 8 heads' softmaxes
    once (the four launches counted 4 heads four times)."""
    from paddle_tpu.obs import metrics

    flash_ops._launches.clear()
    flash_ops._pairs.clear()
    main, *_ = _build()
    ops = main.global_block().ops
    launches = [op for op in ops if op.type == "flash_attention"]
    assert len(launches) == 3 == sum(op.type == "diff_combine" for op in ops)
    for op in launches:
        assert op.attrs["head_pairs"] and op.attrs["num_heads"] == 8
        assert sorted(op.outputs) == ["Out", "Out2"]
        # what `diffattn.device_ms` and the flash rooflines find the launch by
        assert re.fullmatch(r"phi4\.h\d\.attn\.kernels\.tmp_\d+",
                            next(n for names in op.outputs.values()
                                 for n in names))
    assert not any(op.type == "split_head_pairs" for op in ops)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    exe.run(feed=_feed(), fetch_list=[])
    text = metrics.registry().render()
    assert "pt_diff_attention_launches_total 3\n" in text
    computed = 3 * B * SMALL["num_attention_heads"] * T * T
    assert flash_ops._pairs["xla", "computed"] == computed
    assert ('pt_flash_attention_pairs{pairs="computed",path="xla"} '
            + str(computed)) in text


def _steer_to_the_kernels(monkeypatch):
    """The dispatcher as the chip would decide (the shape rules alone), the
    kernels interpreted."""
    monkeypatch.setattr(
        flash_ops, "flash_eligible", lambda q, k=None, window=0:
        flash_ops._shapes_flash_ok(q, q if k is None else k, window))


@pytest.mark.parametrize("window", [None, 128], ids=["whole", "window128"])
def test_the_layer_through_the_interpreted_pair_kernels(monkeypatch, window):
    """The layer's one launch on the kernels' path (heads of 64, T 256: a block
    the diagonal crosses; two query pairs a K/V pair), forward and every
    parameter's gradient against the same Program on the XLA pair form."""
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.obs import metrics

    H, KV, E, T_ = 4, 2, 256, 256
    r = _rng(17)
    feed = {"x": r.randn(1, T_, E).astype(np.float32)}

    def run(steer):
        pt.reset()
        main, startup = pt.default_main_program(), pt.default_startup_program()
        main.random_seed = startup.random_seed = 3
        x = pt.layers.data("x", shape=[T_, E], dtype=np.float32)
        out = pt.layers.differential_attention(x, H, KV, depth=2,
                                               window=window, name="da")
        loss = pt.layers.mean(pt.layers.elementwise_mul(out, out))
        pairs = append_backward(loss)
        exe = pt.Executor()
        exe.run(startup)
        before = metrics.registry().render()
        if steer:
            _steer_to_the_kernels(monkeypatch)
        with jax.default_matmul_precision("highest"), (
                pltpu.force_tpu_interpret_mode() if steer
                else contextlib.nullcontext()):
            got = exe.run(main, feed=feed,
                          fetch_list=[out] + [g for _, g in pairs])
        return [p.name for p, _ in pairs], got, before

    names, want, _ = run(False)
    _, got, before = run(True)
    path = "packed_window" if window else "packed"
    counted = re.search(
        r'pt_flash_attention_dispatch_total\{path="%s"\} (\d+)' % path,
        metrics.registry().render())
    was = re.search(
        r'pt_flash_attention_dispatch_total\{path="%s"\} (\d+)' % path,
        before)
    assert int(counted.group(1)) == (int(was.group(1)) if was else 0) + 1
    assert _rel(got[0], want[0]) < 1e-5
    for name, g, w in zip(names, got[1:], want[1:]):
        assert _rel(g, w) < 2e-4, (name, _rel(g, w))


# -------------------------------------------------------- the two layers ---
def test_mamba1_mixer_layer_hands_on_its_scan_output():
    pt.reset()
    x = pt.layers.data("x", shape=[T, 16], dtype=np.float32)
    out, memory = pt.layers.mamba1_mixer(x, state_size=4, emit_memory=True,
                                         name="m")
    alone = pt.layers.mamba1_mixer(x, state_size=4, name="m2")
    assert tuple(out.shape[1:]) == (T, 16) == tuple(alone.shape[1:])
    assert tuple(memory.shape[1:]) == (T, 32)
    shapes = {p.name: tuple(p.shape)
              for p in pt.default_main_program().parameters()
              if p.name.startswith("m.")}
    assert shapes == {"m.in_w": (16, 64), "m.conv_w": (4, 32),
                      "m.conv_b": (32,), "m.x_w": (32, 1 + 8),
                      "m.dt_w": (1, 32), "m.dt_b": (32,),
                      "m.A_log": (32, 4), "m.D": (32,), "m.out_w": (32, 16)}
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    scope = pt.global_scope()
    np.testing.assert_allclose(
        scope.get("m.A_log"), np.log(np.arange(1, 5))[None].repeat(32, 0),
        rtol=1e-6)
    dt = np.log1p(np.exp(np.asarray(scope.get("m.dt_b"))))
    assert 0.001 * 0.99 <= dt.min() and dt.max() <= 0.1 * 1.01
    assert np.abs(np.asarray(scope.get("m.dt_w"))).max() <= 1.0
    np.testing.assert_array_equal(scope.get("m.D"), np.ones(32))
    feed = {"x": _rng(1).randn(B, T, 16).astype(np.float32)}
    got_out, got_m = exe.run(feed=feed, fetch_list=[out, memory])
    P = [np.asarray(scope.get(f"m.{n}")) for n in MIXER]
    cfg = dict(mamba_d_conv=4, mamba_d_state=4, mamba_dt_rank=1,
               mamba_expand=2, hidden_size=16)
    with jax.default_matmul_precision("highest"):
        want_out, want_m = ref.mamba(cfg, jnp.asarray(feed["x"]), *P)
    assert _rel(got_out, want_out) < 1e-4 and _rel(got_m, want_m) < 1e-4


def test_gated_memory_unit_layer():
    pt.reset()
    x = pt.layers.data("x", shape=[T, 16], dtype=np.float32)
    m = pt.layers.data("m", shape=[T, 24], dtype=np.float32)
    out = pt.layers.gated_memory_unit(x, m, name="g")
    ops = [(o.type, next(iter(o.outputs.values()))[0].rsplit(".tmp", 1)[0])
           for o in pt.default_main_program().global_block().ops]
    assert ops == [("mul", "g.gate_proj"), ("silu_gate", "g.gate"),
                   ("mul", "g.out_proj")]
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    r = _rng(2)
    feed = {"x": r.randn(B, T, 16).astype(np.float32),
            "m": r.randn(B, T, 24).astype(np.float32)}
    got, = exe.run(feed=feed, fetch_list=[out])
    scope = pt.global_scope()
    with jax.default_matmul_precision("highest"):
        want = ref.gmu(feed["x"], feed["m"],
                       np.asarray(scope.get("g.gate_w")),
                       np.asarray(scope.get("g.out_w")))
    assert _rel(got, want) < 1e-4


# ------------------------------------------------------------- the model ---
def _build(amp=False, cfg=SMALL):
    pt.reset()
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = 11
    toks = pt.layers.data("toks", shape=[T], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[T, 1], dtype=np.int32)
    logits = models.phi4flash_lm(
        toks, vocab_size=cfg["vocab_size"],
        num_hidden_layers=cfg["published"]["num_hidden_layers"],
        mb_per_layer=cfg["mb_per_layer"],
        sliding_window=cfg["sliding_window"], dim=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        ffn_dim=cfg["intermediate_size"], state_size=cfg["mamba_d_state"],
        conv_kernel=cfg["mamba_d_conv"], expand=cfg["mamba_expand"],
        dt_rank=cfg["mamba_dt_rank"], layer_ids=cfg["layer_ids"])
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    if amp:
        main.set_amp("bfloat16")
    return main, startup, logits, loss


def _feed(seed=5):
    start = _rng(seed).randint(0, 64, (B, 1))
    seq = (start + np.arange(T + 1)) % 64
    return {"toks": seq[:, :-1].astype(np.int32),
            "labels": seq[:, 1:, None].astype(np.int32)}


def _first_step(amp=False):
    """The program's logits, cost and every gradient on seeded weights
    (zeros and ones moved off their start, so that every tensor's gradient
    tells), beside the reference's."""
    main, startup, logits, loss = _build(amp)
    pairs = append_backward(loss)
    exe = pt.Executor()
    exe.run(startup)
    scope, r = pt.global_scope(), _rng(9)
    params = main.parameters()
    for p in params:
        scope.set(p.name, jnp.asarray(
            np.asarray(scope.get(p.name))
            + 0.05 * r.randn(*p.shape).astype(np.float32)))
    values = [np.asarray(scope.get(p.name)) for p in params]
    feed = _feed()
    got = exe.run(main, feed=feed,
                  fetch_list=[loss, logits] + [g for _, g in pairs])
    want_cost, want_grads = ref.loss_and_grads(SMALL, values, feed)
    by_name = {p.name: g for p, g in zip(params, want_grads)}
    return {"names": [p.name for p in params], "cost": float(got[0]),
            "want_cost": float(want_cost), "logits": got[1],
            "want_logits": ref.logits(SMALL, values, feed["toks"]),
            "errs": {p.name: _rel(g, by_name[p.name])
                     for (p, _), g in zip(pairs, got[2:])}}


@pytest.fixture(scope="module")
def float32_step():
    with jax.default_matmul_precision("highest"):
        return _first_step(False)


def test_float32_model_matches_the_reference_cost_and_logits(float32_step):
    r = float32_step
    # a LayerNorm's two carry a counter behind their names (`mix_norm.w_2`)
    assert [re.sub(r"_\d+$", "", n.split(".", 1)[1]) for n in r["names"]] \
        == TENSORS
    assert len(r["names"]) == len(TENSORS) == len(r["errs"]) == 86
    assert _rel(r["logits"], r["want_logits"]) < 1e-4
    assert abs(r["cost"] - r["want_cost"]) < 2e-4 * abs(r["want_cost"])


@pytest.mark.parametrize("at", range(len(TENSORS)), ids=TENSORS)
def test_float32_gradient_matches_the_reference(float32_step, at):
    """float32 on the CPU at the highest matmul precision, both sides: EVERY
    gradient within 2e-4 of its rms, the tied table's (a gather's scatter
    plus the head's GEMM), the producers of M (h4.mamba.*) and of k and v
    (h5.attn.wqkv) among them. A gradient that is missing, doubled or handed
    to the wrong parameter reads ~1."""
    name = float32_step["names"][at]
    assert float32_step["errs"][name] < 2e-4, (name,
                                               float32_step["errs"][name])


@pytest.mark.parametrize("control", phi4flash_controls.CONTROLS)
def test_the_reference_tells_a_wrong_program(control):
    """Each of the four wrong programs of `tests/phi4flash_controls.py` reads
    far outside what the right one is held to (every gradient 2e-4)."""
    with jax.default_matmul_precision("highest"), \
            phi4flash_controls.applied(control):
        r = _first_step(False)
    worst = max(r["errs"].values())
    if control == "bf16_state":     # T 32 is one chunk: no carry to round
        assert worst < 2e-4
        x, dt, A, Bm, Cm, D, _ = _scan_operands(256)
        with phi4flash_controls.applied(control):
            wrong = ssm_ops.selective_scan(x, dt, A, Bm, Cm, D)
        right = _token_loop(x, dt, A, Bm, Cm, D)
        assert _rel(wrong[:, :128], right[:, :128]) < 1e-5
        assert _rel(wrong[:, 128:], right[:, 128:]) > 1e-4
    else:
        assert worst > 0.05, worst
        assert abs(r["cost"] - r["want_cost"]) > 1e-4 * abs(r["want_cost"])


def test_bf16_amp_model_stays_near_the_reference():
    r = _first_step(True)
    assert _rel(r["logits"], r["want_logits"]) < 0.03
    assert abs(r["cost"] - r["want_cost"]) < 2e-3 * abs(r["want_cost"])
    assert sorted(r["errs"].values())[len(r["errs"]) // 2] < 0.05


def test_the_stream_is_float32_and_the_head_is_the_table():
    prog, *_ = _build(True)
    ops = prog.global_block().ops
    made_by = {name: o.type for o in ops for outs in o.outputs.values()
               for name in outs}
    adds = [o for o in ops if o.type == "elementwise_add"
            and made_by.get(o.inputs["Y"][0]) == "cast"]
    assert len(adds) == 2 * SMALL["num_hidden_layers"]
    assert made_by[adds[0].inputs["X"][0]] == "lookup_table"
    table, = [o.inputs["W"][0] for o in ops if o.type == "lookup_table"]
    head, = [o for o in ops if o.type == "matmul"]
    assert head.inputs["Y"] == [table] and head.attrs["transpose_Y"]
    assert [p.name for p in prog.parameters()].count(table) == 1
    # M has one producer and one reader here, k and v one producer and two
    memory = [o.outputs["Memory"][0] for o in ops if o.type == "mamba1_mixer"]
    readers = [o for o in ops if o.type == "silu_gate" and "Gate" in o.inputs]
    assert [o.inputs["X"][0] for o in readers] == [memory[1]]


# ------------------------------------------- the whole map, shapes alone ---
def test_the_32_layer_map():
    kinds = models.phi4flash_layer_kinds(32, 2)
    assert [kinds.count(k) for k in ("mamba", "window", "gmu", "cross",
                                     "full")] == [9, 8, 7, 7, 1]
    assert kinds[:4] == ["mamba", "window", "mamba", "window"]
    assert kinds[14:20] == ["mamba", "window", "mamba", "full", "gmu", "cross"]
    assert [k for _, k in ref.held_layers(
        dict(num_hidden_layers=32, mb_per_layer=2))] == kinds
    assert [k for _, k in ref.held_layers(SMALL)] == [
        "mamba", "window", "mamba", "full", "gmu", "cross"]


@pytest.mark.parametrize("layer_ids,vocab,want", [
    (None, 200064, 3_852_562_944), ([0, 1, 16, 17, 18, 19], 25008, 697_094_272)],
    ids=["published_3.853B", "the_cell_697M"])
def test_parameter_count_at_published_widths(layer_ids, vocab, want):
    """The builder's own count from the Program's shapes, nothing allocated:
    9 x 119.90 M + 9 x 98.32 M + 7 x 104.87 M + 7 x 91.77 M + 512.16 M."""
    pt.reset()
    toks = pt.layers.data("toks", shape=[8192], dtype=np.int32)
    models.phi4flash_lm(toks, vocab_size=vocab, layer_ids=layer_ids)
    params = pt.default_main_program().parameters()
    assert sum(int(np.prod(p.shape)) for p in params) == want
    by_layer = {}
    for p in params:
        key = p.name.split(".")[1]
        by_layer[key] = by_layer.get(key, 0) + int(np.prod(p.shape))
    held = layer_ids or range(32)
    kinds = models.phi4flash_layer_kinds(32, 2)
    sizes = {"mamba": 119_895_040, "window": 98_322_304, "full": 98_322_304,
             "gmu": 104_867_840, "cross": 91_766_144}
    assert [by_layer[f"h{i}"] for i in held] == [sizes[kinds[i]] for i in held]
    assert by_layer["tok_emb"] == vocab * 2560 and by_layer["final_norm"] == 5120


def test_a_part_needs_the_layers_it_reads():
    pt.reset()
    toks = pt.layers.data("toks", shape=[T], dtype=np.int32)
    for ids, needs in (([0, 1, 6], 4), ([0, 1, 4, 7], 5)):
        with pytest.raises(ValueError, match=f"reads layer {needs}"):
            models.phi4flash_lm(toks, vocab_size=96, num_hidden_layers=8,
                                dim=64, num_heads=8, num_kv_heads=4,
                                ffn_dim=96, layer_ids=ids)
    with pytest.raises(ValueError, match="in order"):
        models.phi4flash_lm(toks, vocab_size=96, num_hidden_layers=8, dim=64,
                            num_heads=8, num_kv_heads=4, ffn_dim=96,
                            layer_ids=[1, 0])


def test_the_config_module_trains_through_the_trainer():
    """`paddle_tpu train --config configs/phi4flash.py`'s module at small
    sizes: the cost falls."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs",
                        "phi4flash.py")
    spec = importlib.util.spec_from_file_location("phi4flash_config", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    pt.reset()
    model = module.get_model(
        layer_ids=(0, 1, 4, 5, 6, 7), model_layers=8, dim=64, heads=8,
        kv_heads=4, ffn_dim=96, window=8, state_size=4, dt_rank=4, seqlen=T,
        vocab=96, batch=2, steps=12, amp=None)
    costs = []
    pt.Trainer(model["cost"]).train(
        model["reader"], num_passes=1,
        event_handler=lambda e: costs.append(float(e.cost))
        if isinstance(e, pt.trainer.EndIteration) else None)
    assert len(costs) == 12 and costs[-1] < costs[0]
