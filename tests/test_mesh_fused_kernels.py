"""Fused Pallas kernels under a device mesh (VERDICT r4 weak #2).

The written policy (ops/mesh_dispatch.py): a Mosaic pallas_call cannot
be auto-partitioned by GSPMD, so under a ParallelExecutor mesh every
fused-kernel dispatch shard_maps itself over the dp axis — per-shard
kernels at the local batch, replicated weights, psum'd weight
cotangents. These tests prove, on the 8-virtual-device CPU mesh at
IN-WINDOW shapes (fused-LSTM H>=384; the Bahdanau decoder family):

- training under dp (and dp x mp) meshes with the fused kernels ON
  matches single-device training with the XLA scan formulations —
  losses AND updated weights (i.e. the psum'd dW/dWx/dv/... are right);
- the fused path actually DISPATCHED under the mesh (spy assertions —
  a silent fallback to the scan fails the test, not just runs slow);
- the bench-default NMT geometry dispatches fused under a dp4 mesh at
  the per-shard batch (trace-only, jax.eval_shape).

Reference analogue: test_CompareTwoNets.cpp (single-vs-multi numeric
equivalence) + the MultiGradientMachine replica contract.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import models, parallel as pp
from paddle_tpu.core.lod import LoDArray
from paddle_tpu.flags import FLAGS
from paddle_tpu.ops import bahdanau_kernels as bk
from paddle_tpu.ops import mesh_dispatch, pallas_kernels


@pytest.fixture
def fused_interpret():
    FLAGS.fused_rnn_interpret = True
    FLAGS.fused_attention_interpret = True
    yield
    FLAGS.fused_rnn_interpret = False
    FLAGS.fused_attention_interpret = False


class _Spy:
    """Counts calls through a module attribute, preserving behavior."""

    def __init__(self, mod, name):
        self.mod, self.name, self.calls = mod, name, 0
        self.orig = getattr(mod, name)

    def __enter__(self):
        def wrapped(*a, **k):
            self.calls += 1
            return self.orig(*a, **k)
        setattr(self.mod, self.name, wrapped)
        return self

    def __exit__(self, *exc):
        setattr(self.mod, self.name, self.orig)


def _train_lstm(mesh, steps=3, hidden=512, fused=False):
    """Build + train the benchmark LSTM (stacked_lstm2 inside) on a
    fixed corpus; returns (losses, final w of the first lstm kernel).
    mesh=None -> single-device Executor. Same init via fixed seed."""
    B, T, vocab = 64, 6, 120
    pt.reset()
    FLAGS.use_fused_rnn = fused
    try:
        words = pt.layers.data("words", shape=[-1], dtype=np.int32,
                               lod_level=1, append_batch_size=False)
        label = pt.layers.data("label", shape=[1], dtype=np.int32)
        logits = models.lstm_benchmark_net(
            words, vocab_size=vocab, emb_dim=128, hidden=hidden, max_len=T)
        loss = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, label))
        pt.optimizer.Adam(learning_rate=2e-3).minimize(loss)
        pt.default_startup_program().random_seed = 11
        exe = (pt.Executor() if mesh is None
               else pp.ParallelExecutor(mesh, shard_optimizer_state=True))
        exe.run(pt.default_startup_program())
        rng = np.random.RandomState(3)
        seqs = [rng.randint(0, vocab, (T,)).astype(np.int32)
                for _ in range(B)]
        feed = {"words": LoDArray.from_sequences(seqs, capacity=B * T,
                                                 max_seqs=B),
                "label": rng.randint(0, 2, (B, 1)).astype(np.int32)}
        losses = []
        for _ in range(steps):
            (l,) = exe.run(feed=feed, fetch_list=[loss])
            losses.append(float(l))
        w = None
        for k in pt.global_scope().keys():
            if "stacked_lstm" in k or "lstm" in k.lower():
                w = np.asarray(pt.global_scope().get(k))
                break
        assert w is not None, list(pt.global_scope().keys())
        return losses, w
    finally:
        FLAGS.use_fused_rnn = True


def test_fused_lstm_dp8_matches_single_device(fused_interpret):
    """dp8 mesh + fused LSTM kernels (in-window H=512) == single-device
    run of the SAME fused kernels, through training steps — isolates
    the mesh machinery (shard_map wrap + psum'd dW): a missing/wrong
    psum is off by ~dp x, not by rounding. Tolerance covers the f32
    reduction-order difference (per-shard dW partials summed by psum vs
    one full-batch einsum), which Adam amplifies step over step."""
    ref_losses, ref_w = _train_lstm(None, fused=True)
    mesh = pp.make_mesh((8,), ("dp",))
    with _Spy(pallas_kernels, "_lstm_pallas_raw") as spy:
        par_losses, par_w = _train_lstm(mesh, fused=True)
    assert spy.calls >= 1, "fused LSTM kernel did not dispatch under dp8"
    np.testing.assert_allclose(par_losses, ref_losses, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(par_w, ref_w, rtol=5e-3, atol=5e-3)


def test_fused_lstm_dp8_matches_scan_one_step(fused_interpret):
    """One step (before optimizer-state feedback compounds rounding):
    dp8 mesh + fused kernels matches the single-device XLA SCAN — the
    cross-formulation equivalence at tight tolerance."""
    ref_losses, _ = _train_lstm(None, steps=1, fused=False)
    mesh = pp.make_mesh((8,), ("dp",))
    par_losses, _ = _train_lstm(mesh, steps=1, fused=True)
    np.testing.assert_allclose(par_losses, ref_losses, rtol=2e-4, atol=2e-4)


def test_fused_lstm_dp_mp_mesh(fused_interpret):
    """Same equivalence under a 2-axis (dp4, mp2) mesh — the fused
    kernels shard over dp and replicate over mp."""
    ref_losses, ref_w = _train_lstm(None, fused=True)
    mesh = pp.make_mesh((4, 2), ("dp", "mp"))
    with _Spy(pallas_kernels, "_lstm_pallas_raw") as spy:
        par_losses, par_w = _train_lstm(mesh, fused=True)
    assert spy.calls >= 1, "fused LSTM kernel did not dispatch under dp4,mp2"
    np.testing.assert_allclose(par_losses, ref_losses, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(par_w, ref_w, rtol=5e-3, atol=5e-3)


def _train_nmt(mesh, steps=3, fused=False):
    B, S, vocab, H = 16, 10, 100, 128
    pt.reset()
    FLAGS.use_fused_attention = fused
    try:
        src = pt.layers.data("src", shape=[-1], dtype=np.int32,
                             lod_level=1, append_batch_size=False)
        trg_in = pt.layers.data("trg_in", shape=[-1], dtype=np.int32,
                                lod_level=1, append_batch_size=False)
        label = pt.layers.data("label", shape=[-1], dtype=np.int32,
                               lod_level=1, append_batch_size=False)
        logits = models.seq2seq_attention(
            src, trg_in, src_vocab=vocab, trg_vocab=vocab, emb_dim=H,
            enc_hidden=H, dec_hidden=H, src_max_len=S, trg_max_len=S)
        tok_loss = pt.layers.softmax_with_cross_entropy(logits, label)
        loss = pt.layers.mean(pt.layers.sequence_pool(tok_loss, "sum"))
        pt.optimizer.Adam(learning_rate=2e-3).minimize(loss)
        pt.default_startup_program().random_seed = 11
        exe = (pt.Executor() if mesh is None
               else pp.ParallelExecutor(mesh, shard_optimizer_state=True))
        exe.run(pt.default_startup_program())
        rng = np.random.RandomState(5)
        pack = lambda seqs: LoDArray.from_sequences(  # noqa: E731
            seqs, capacity=B * S, max_seqs=B)
        seqs = [rng.randint(2, vocab, (S,)).astype(np.int32)
                for _ in range(B)]
        feed = {"src": pack(seqs), "trg_in": pack(seqs),
                "label": pack(seqs)}
        losses = []
        for _ in range(steps):
            (l,) = exe.run(feed=feed, fetch_list=[loss])
            losses.append(float(l))
        w = np.asarray(pt.global_scope().get("s2s.dec_wa_dec")
                       if pt.global_scope().has("s2s.dec_wa_dec") else
                       next(pt.global_scope().get(k)
                            for k in pt.global_scope().keys()
                            if "dec" in k))
        return losses, w
    finally:
        FLAGS.use_fused_attention = True


def test_fused_decoder_dp2_matches_single_device(fused_interpret):
    """dp2 mesh + fused Bahdanau decoder == single-device fused decoder
    through training (psum'd dWx/dWh/dv/dWaDec/dbias correct), plus a
    one-step cross-check against the XLA scan."""
    ref_losses, ref_w = _train_nmt(None, fused=True)
    mesh = pp.make_mesh((2,), ("dp",), devices=jax.devices()[:2])
    bk.reset_dispatch_stats()
    par_losses, par_w = _train_nmt(mesh, fused=True)
    assert bk.dispatch_stats["fused_calls"] >= 1, bk.dispatch_stats
    np.testing.assert_allclose(par_losses, ref_losses, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(par_w, ref_w, rtol=5e-3, atol=5e-3)
    scan_losses, _ = _train_nmt(None, steps=1, fused=False)
    mesh_losses, _ = _train_nmt(mesh, steps=1, fused=True)
    np.testing.assert_allclose(mesh_losses, scan_losses,
                               rtol=5e-4, atol=5e-4)


def test_bench_geometry_dispatches_fused_under_mesh(fused_interpret):
    """The bench-default NMT geometry (bs256, S=T=50, H=512, C=1024,
    bf16) keeps the FUSED path under a dp4 mesh: per-shard batch 64 is
    in-window, and the shard_map wrap traces end-to-end (fwd + bwd,
    jax.eval_shape — no compute). The day multi-chip hardware appears,
    `train --mesh dp4` on the NMT config runs exactly this path."""
    mesh = pp.make_mesh((4,), ("dp",), devices=jax.devices()[:4])
    B, S, T, E, C, A, H = 256, 50, 50, 512, 1024, 512, 512
    dt = jnp.bfloat16
    shapes = (
        jax.ShapeDtypeStruct((B, S, C), dt),
        jax.ShapeDtypeStruct((B, S, A), dt),
        jax.ShapeDtypeStruct((B, S), jnp.bool_),
        jax.ShapeDtypeStruct((T, B, E), dt),
        jax.ShapeDtypeStruct((T, B), jnp.float32),
        jax.ShapeDtypeStruct((B, H), dt),
        jax.ShapeDtypeStruct((H, A), dt),
        jax.ShapeDtypeStruct((A,), dt),
        jax.ShapeDtypeStruct((E + C, 3 * H), dt),
        jax.ShapeDtypeStruct((H, 3 * H), dt),
        jax.ShapeDtypeStruct((3 * H,), dt),
    )
    assert mesh_dispatch.local_batch(B) == B  # no mesh active yet
    with mesh_dispatch.active_mesh(mesh, "dp"):
        assert mesh_dispatch.local_batch(B) == 64
        assert bk.fused_decoder_eligible(
            mesh_dispatch.local_batch(B), S, A, C, dt)
        bk.reset_dispatch_stats()

        def loss(enc_b, ep, *rest):
            return jnp.sum(bk.fused_attention_decoder(
                enc_b, ep, *rest).astype(jnp.float32))

        jax.eval_shape(jax.grad(loss, argnums=(0, 1)), *shapes)
        assert bk.dispatch_stats["fused_calls"] >= 1, bk.dispatch_stats
        assert bk.dispatch_stats["scan_bwd"] >= 1, bk.dispatch_stats
    assert mesh_dispatch.current() is None


def test_local_batch_fallback_non_divisible(fused_interpret):
    """A batch the dp axis does not divide falls back to the scan (the
    eligibility sees local_batch == 0) instead of crashing in shard_map."""
    mesh = pp.make_mesh((8,), ("dp",))
    with mesh_dispatch.active_mesh(mesh, "dp"):
        assert mesh_dispatch.local_batch(60) == 0
        assert not pallas_kernels.lstm_supported(
            mesh_dispatch.local_batch(60), 512, "sigmoid", "tanh", "tanh",
            None)
        assert not bk.fused_decoder_eligible(
            mesh_dispatch.local_batch(60), 50, 512, 1024, jnp.bfloat16)


def test_fused_lstm_dp1_mesh(fused_interpret):
    """A dp=1 mesh (ParallelExecutor() on a single-device host) runs
    the fused kernels UNWRAPPED — the psum axis must not be bound then,
    or the backward traces a psum over an unbound axis name and crashes
    on the first step (caught in round-5 review)."""
    mesh = pp.make_mesh((1,), ("dp",), devices=jax.devices()[:1])
    losses, _ = _train_lstm(mesh, steps=2, fused=True)
    assert np.isfinite(losses).all() and losses[1] < losses[0], losses


@pytest.mark.parametrize("pair", [False, True], ids=["heads", "pairs"])
def test_flash_attention_shard_maps_under_dp_mesh(monkeypatch, pair):
    """The flash dispatcher wraps its kernel in shard_map under a dp
    mesh (kernel monkeypatched to the jnp reference — the real Mosaic
    kernel is TPU-only): per-shard local shapes, output parity vs
    unsharded, and gradients flow. `pair`: the pair form's two outputs
    leave the wrap sharded as its one does."""
    from paddle_tpu.ops import flash_ops

    calls = []
    reference = flash_ops.paired_attention if pair else flash_ops._reference

    def fake_kernel(q, k, v, causal, window=0, pair=False):
        calls.append(tuple(q.shape))
        return reference(q, k, v, causal, window)

    monkeypatch.setattr(flash_ops, "_flash_kernel", fake_kernel)
    monkeypatch.setattr(flash_ops, "flash_eligible",
                        lambda q, k=None, window=0: True)
    rng = np.random.RandomState(0)
    # T 128: the dispatcher counts the kernels' pairs by their blocks, which
    # divide a 128-aligned sequence (what `flash_eligible` admits)
    mk = lambda: jnp.asarray(rng.randn(16, 128, 4, 64) * 0.3, jnp.float32)
    q, k, v = mk(), mk(), mk()
    total = lambda out: sum(  # noqa: E731
        jnp.sum(o ** 2) for o in jax.tree.leaves(out))
    ref = reference(q, k, v, True)
    g_ref = jax.grad(lambda q: total(reference(q, k, v, True)))(q)
    mesh = pp.make_mesh((8,), ("dp",))
    kw = {"pair": True} if pair else {}
    with mesh_dispatch.active_mesh(mesh, "dp"):
        out = flash_ops.flash_attention(q, k, v, causal=True, **kw)
        g = jax.grad(lambda q: total(
            flash_ops.flash_attention(q, k, v, causal=True, **kw)))(q)
    assert jax.tree.structure(out) == jax.tree.structure(ref)
    for o, r in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)
    assert calls and calls[0][0] == 16 // 8, calls  # per-shard batch


def _fused_weight_grads(family, rng):
    """(loss_fn, args, weight_argnums) for one fused family at a small
    eligible geometry (interpret-mode kernels on the CPU)."""
    f32 = lambda *shape: jnp.asarray(rng.randn(*shape) * 0.3, jnp.float32)
    T, B, H = 6, 32, 128
    mask = jnp.asarray(
        (np.arange(T)[:, None] < rng.randint(2, T + 1, B)[None, :])
        .astype(np.float32))
    if family in ("lstm", "gru"):
        G = 4 if family == "lstm" else 3
        fused = (pallas_kernels.lstm_fused if family == "lstm"
                 else pallas_kernels.gru_fused)

        def loss(x, w, b):
            h_seq, _ = fused(x, mask, w, bias=b)
            return jnp.sum(h_seq ** 2)

        return loss, (f32(T, B, G * H), f32(H, G * H), f32(G * H)), (1, 2)
    S, A, C, E = 10, 128, 128, 16
    enc_mask = jnp.asarray(np.arange(S)[None, :]
                           < rng.randint(3, S + 1, B)[:, None])

    def loss(enc, ep, trg, h0, wa_dec, v, wx, wh, bias):
        return jnp.sum(bk.fused_attention_decoder(
            enc, ep, enc_mask, trg, mask, h0, wa_dec, v, wx, wh, bias) ** 2)

    args = (f32(B, S, C), f32(B, S, A), f32(T, B, E), f32(B, H),
            f32(H, A), f32(A), f32(E + C, 3 * H), f32(H, 3 * H),
            f32(3 * H))
    return loss, args, (4, 5, 6, 7, 8)


@pytest.mark.parametrize("family", ["lstm", "gru", "bahdanau"])
def test_fused_weight_gradients_under_dp_match_single_device(family):
    """The gradient itself, not a loss after Adam: under a dp4 mesh each
    shard's custom-VJP backward yields a partial weight cotangent and
    shard_map's transpose sums them — exactly once. On jax 0.9.0 the
    backwards' own psum (written for an older jax) made every fused
    weight gradient dp times too large, and every loss-level test above
    passed regardless, because Adam divides the scale back out (found on
    four real chips by chip_smoke.py --four-chips, PR 21)."""
    loss, args, wrt = _fused_weight_grads(family, np.random.RandomState(0))
    want = jax.grad(loss, argnums=wrt)(*args)
    mesh = pp.make_mesh((4,), ("dp",), devices=jax.devices()[:4])
    with mesh_dispatch.active_mesh(mesh, "dp"):
        got = jax.jit(jax.grad(loss, argnums=wrt))(*args)
    for g, w in zip(got, want):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=0, atol=1e-4 * scale)
