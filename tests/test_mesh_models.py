"""The headline models train under an explicit device mesh.

Reference scale-out table: benchmark/README.md:72-96 (the 4-GPU
columns). On the 8-virtual-device CPU mesh at tiny shapes: dp batch
sharding, Megatron mp (transformer_lm mp_axis) and ZeRO-sharded
optimizer state through `ParallelExecutor`, the executor `paddle_tpu
train --mesh` builds. Dispatch engagement of the fused kernels under a
mesh is asserted by tests/test_mesh_fused_kernels.py; these prove each
model's whole training step runs, and where its batch and state live.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import models, parallel, regularizer
from paddle_tpu.core.lod import LoDArray
from paddle_tpu.flags import FLAGS


def _pack(rng, vocab, batch, seqlen):
    seqs = [rng.randint(2, vocab, (seqlen,)).astype(np.int32)
            for _ in range(batch)]
    return LoDArray.from_sequences(
        seqs, capacity=batch * seqlen, max_seqs=batch)


def _lstm(batch, hidden, seqlen, amp, vocab=300):
    """The reference's RNN benchmark (benchmark/paddle/rnn/rnn.py: Adam,
    L2 decay, global-norm clipping), as configs/lstm_benchmark.py builds
    it, with a fixed feed."""
    words = pt.layers.data("words", shape=[-1], dtype=np.int32,
                           lod_level=1, append_batch_size=False)
    label = pt.layers.data("label", shape=[1], dtype=np.int32)
    logits = models.lstm_benchmark_net(
        words, vocab_size=vocab, emb_dim=128, hidden=hidden, max_len=seqlen)
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, label))
    pt.optimizer.Adam(
        learning_rate=2e-3,
        regularization=regularizer.L2Decay(8e-4),
        grad_clip=pt.optimizer.GradientClipByGlobalNorm(25.0),
    ).minimize(loss)
    if amp:
        pt.default_main_program().set_amp("bfloat16")
    rng = np.random.RandomState(0)
    feed = {"words": _pack(rng, vocab, batch, seqlen),
            "label": rng.randint(0, 2, (batch, 1)).astype(np.int32)}
    return loss, feed


def _nmt(batch, hidden, seqlen, amp, vocab=300):
    src = pt.layers.data("src", shape=[-1], dtype=np.int32, lod_level=1,
                         append_batch_size=False)
    trg_in = pt.layers.data("trg_in", shape=[-1], dtype=np.int32,
                            lod_level=1, append_batch_size=False)
    label = pt.layers.data("label", shape=[-1], dtype=np.int32,
                           lod_level=1, append_batch_size=False)
    logits = models.seq2seq_attention(
        src, trg_in, src_vocab=vocab, trg_vocab=vocab, emb_dim=hidden,
        enc_hidden=hidden, dec_hidden=hidden,
        src_max_len=seqlen, trg_max_len=seqlen)
    tok_loss = pt.layers.softmax_with_cross_entropy(logits, label)
    loss = pt.layers.mean(pt.layers.sequence_pool(tok_loss, "sum"))
    pt.optimizer.Adam(learning_rate=5e-4).minimize(loss)
    if amp:
        pt.default_main_program().set_amp("bfloat16")
    rng = np.random.RandomState(0)
    trg = _pack(rng, vocab, batch, seqlen)
    feed = {"src": _pack(rng, vocab, batch, seqlen),
            "trg_in": trg, "label": trg}
    return loss, feed


def _transformer(batch, hidden, seqlen, amp, vocab=300):
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    logits = models.transformer_lm(
        toks, vocab_size=vocab, dim=hidden, num_heads=hidden // 64,
        num_layers=2, max_len=seqlen, mp_axis="mp")
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    pt.optimizer.Adam(learning_rate=3e-4).minimize(loss)
    if amp:
        pt.default_main_program().set_amp("bfloat16")
    rng = np.random.RandomState(0)
    feed = {"toks": rng.randint(0, vocab, (batch, seqlen)).astype(np.int32),
            "labels": rng.randint(0, vocab,
                                  (batch, seqlen, 1)).astype(np.int32)}
    return loss, feed


def _mesh_executor(spec):
    return pt.parallel.ParallelExecutor(
        parallel.mesh_from_spec(spec), shard_optimizer_state=True)


@pytest.mark.parametrize("build,spec,batch,hidden,seqlen,amp,interpret", [
    (_transformer, "dp2,mp2", 4, 128, 128, True, None),
    (_lstm, "dp8", 16, 128, 16, True, None),
    # H=512 is inside the fused-LSTM window and the per-shard batch 32/4=8
    # passes eligibility: the shapes the fused kernels engage at, not only
    # below-window toys
    (_lstm, "dp4", 32, 512, 8, False, "fused_rnn_interpret"),
    # the fused Bahdanau decoder: A=C=H=128, per-shard batch 8
    (_nmt, "dp2", 16, 128, 10, False, "fused_attention_interpret"),
], ids=["transformer-dp2,mp2", "lstm-dp8", "lstm-dp4-fused-window",
        "nmt-dp2-fused-decoder"])
def test_model_trains_under_mesh(monkeypatch, build, spec, batch, hidden,
                                 seqlen, amp, interpret):
    if interpret:
        monkeypatch.setattr(FLAGS, interpret, True)
    pt.reset()
    loss, feed = build(batch, hidden, seqlen, amp)
    exe = _mesh_executor(spec)
    exe.run(pt.default_startup_program())
    losses = [float(exe.run(pt.default_main_program(), feed=feed,
                            fetch_list=[loss])[0]) for _ in range(2)]
    assert np.all(np.isfinite(losses)), losses
    # the step trained: Adam moved the parameters between the two runs
    assert losses[1] != losses[0]


def test_mesh_rejects_non_dividing_batch():
    """dp8 over a batch of 12: the shards would be ragged (and the fused
    kernels would fall back to the scan), so the step is refused by the
    sharding itself, never silently replicated."""
    pt.reset()
    loss, feed = _lstm(12, 128, 8, amp=False)
    exe = _mesh_executor("dp8")
    exe.run(pt.default_startup_program())
    with pytest.raises(ValueError, match="divisible by 8|does not divide"):
        exe.run(pt.default_main_program(), feed=feed, fetch_list=[loss])


@pytest.mark.parametrize("n", [8, 4])  # 4: what chip_smoke --four-chips runs
def test_dp_step_is_sharded_not_replicated(n):
    """Fixed global batch, dp1 against dp<n>. The regression class this
    guards is an accidental full replication (every device running the
    whole batch). On real chips that shows as a rate; a CPU mesh
    timeshares its cores, so a wall-clock ratio there measures the box.
    What a CPU run CAN show is where the batch lives and what the program
    contains: dp<n> agrees with dp1 on the loss, every device holds one
    n-th of the batch and of the ZeRO-sharded optimizer state, the
    per-device program carries the per-shard batch and never the global
    one (the one forward the step holds, under differentiation, and its
    transpose), and it all-reduces gradients. Real Nx needs real chips
    (`chip_smoke.py --four-chips`)."""
    B, H, T = 64, 256, 16
    losses = {}
    for spec in ("dp1", f"dp{n}"):
        pt.reset()
        loss, feed = _lstm(B, H, T, amp=False)
        pt.default_startup_program().random_seed = 5
        exe = _mesh_executor(spec)
        exe.run(pt.default_startup_program())
        prog = pt.default_main_program()
        losses[spec] = [float(exe.run(prog, feed=feed, fetch_list=[loss])[0])
                        for _ in range(3)]
    np.testing.assert_allclose(losses[f"dp{n}"], losses["dp1"], rtol=1e-4)

    # where things live after the dp<n> steps (exe/prog are the dp<n> ones)
    scope = pt.global_scope()
    sharded_state = 0
    for v in prog.persistables():
        a = scope.get(v.name)
        assert len({s.device for s in a.addressable_shards}) == n, v.name
        if exe._state_sharding(prog, v.name).spec != \
                jax.sharding.PartitionSpec():
            sharded_state += 1
            assert all(s.data.shape[0] == a.shape[0] // n
                       for s in a.addressable_shards), v.name
    assert sharded_state > 0  # the Adam moments ride the dp axis
    placed = jax.device_put(feed["label"],
                            exe._feed_sharding(feed["label"]))
    assert [s.data.shape for s in placed.addressable_shards] == \
        [(B // n, 1)] * n

    # what the per-device program contains
    persist = sorted(v.name for v in prog.persistables()
                     if scope.has(v.name))
    fn = exe._compile(prog, feed, [loss.name], persist)
    donated, kept = exe._split_state(
        prog, {name: scope.get(name) for name in persist})
    with exe._device_context(), exe._trace_context():
        text = fn.lower(donated, kept, feed,
                        jnp.uint32(0)).compile().as_text()
    assert f"f32[{T},{B // n},{4 * H}]" in text   # per-shard scan input
    assert f"f32[{T},{B},{4 * H}]" not in text    # never the global batch
    # nor the whole batch with the scan split over its hidden axis: where
    # GSPMD took the step before the gradients were held to the parameters'
    # layout (`ParallelExecutor._place_grad`), an all-to-all a scan step
    assert f"f32[{T},{B},{H // n}]" not in text
    assert "all-to-all" not in text
    assert "all-reduce" in text
