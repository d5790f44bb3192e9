"""stacked_lstm2: both stacked layers + inter-layer projection in one op.

Reference structure: benchmark/paddle/rnn/rnn.py (2x stacked LSTM) —
the hot config of the reference's headline RNN benchmark. The single
both-layers scan must match the two-dynamic_lstm formulation exactly
when fed the same weights.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu.core.lod import LoDArray


def _feed(B=4, Tmax=10, F=12, seed=0):
    rng = np.random.RandomState(seed)
    seqs = [rng.randn(rng.randint(4, Tmax), F).astype(np.float32) * 0.3
            for _ in range(B)]
    return {"x": LoDArray.from_sequences(seqs, capacity=B * Tmax,
                                         max_seqs=B),
            "y": rng.randn(B, 1).astype(np.float32)}


def _build(stacked, H=8, F=12):
    pt.reset()
    pt.default_startup_program().random_seed = 3
    x = pt.layers.data("x", shape=[F], lod_level=1)
    y = pt.layers.data("y", shape=[1])
    proj1 = pt.layers.fc(x, size=4 * H, bias_attr=False,
                         param_attr=pt.ParamAttr(name="proj1"))
    if stacked:
        h2 = pt.layers.stacked_lstm2(proj1, size=4 * H,
                                     param_attr=pt.ParamAttr(name="s"),
                                     bias_attr=pt.ParamAttr(name="sb"))
    else:
        h1 = pt.layers.dynamic_lstm(proj1, size=4 * H,
                                    param_attr=pt.ParamAttr(name="s.w1"),
                                    bias_attr=pt.ParamAttr(name="sb.b1"))
        p2 = pt.layers.fc(h1, size=4 * H, bias_attr=False,
                          param_attr=pt.ParamAttr(name="s.wx2"))
        h2 = pt.layers.dynamic_lstm(p2, size=4 * H,
                                    param_attr=pt.ParamAttr(name="s.w2"),
                                    bias_attr=pt.ParamAttr(name="sb.b2"))
    pooled = pt.layers.sequence_pool(h2, "last")
    pred = pt.layers.fc(pooled, size=1, param_attr=pt.ParamAttr(name="out"))
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return loss


def test_stacked_matches_two_layer_formulation():
    """Same weight names -> identical init -> identical losses over a
    few Adam steps between the fused op and the two-op formulation."""
    feed = _feed()
    results = {}
    for stacked in (False, True):
        loss = _build(stacked)
        exe = pt.Executor()
        exe.run(pt.default_startup_program())
        ls = []
        for _ in range(4):
            (l,) = exe.run(feed=feed, fetch_list=[loss])
            ls.append(float(l))
        results[stacked] = ls
    np.testing.assert_allclose(results[True], results[False],
                               rtol=1e-5, atol=1e-6)
    assert results[True][-1] < results[True][0]


def test_stacked_lstm_in_benchmark_net():
    """lstm_benchmark_net routes through the stacked op and trains."""
    pt.reset()
    from paddle_tpu import models

    words = pt.layers.data("words", shape=[-1], dtype=np.int32,
                           lod_level=1, append_batch_size=False)
    label = pt.layers.data("label", shape=[1], dtype=np.int32)
    logits = models.lstm_benchmark_net(words, vocab_size=50, emb_dim=8,
                                       hidden=8, max_len=8)
    loss = pt.layers.mean(
        pt.layers.softmax_with_cross_entropy(logits, label))
    pt.optimizer.Adam(learning_rate=0.01).minimize(loss)
    ops = [op.type for op in pt.default_main_program().global_block().ops]
    assert "stacked_lstm2" in ops
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    rng = np.random.RandomState(0)
    seqs = [rng.randint(0, 50, (6,)).astype(np.int32) for _ in range(4)]
    feed = {"words": LoDArray.from_sequences(seqs, capacity=32, max_seqs=4),
            "label": rng.randint(0, 2, (4, 1)).astype(np.int32)}
    ls = []
    for _ in range(10):
        (l,) = exe.run(feed=feed, fetch_list=[loss])
        ls.append(float(l))
    assert np.isfinite(ls).all() and ls[-1] < ls[0]


def _build_n(stacked, N=3, H=8, F=12):
    """N-layer book-structure stack (understand_sentiment) as ONE op vs
    the per-layer fc+dynamic_lstm build, shared parameter names."""
    pt.reset()
    pt.default_startup_program().random_seed = 3
    x = pt.layers.data("x", shape=[F], lod_level=1)
    y = pt.layers.data("y", shape=[1])
    proj1 = pt.layers.fc(x, size=4 * H, bias_attr=False,
                         param_attr=pt.ParamAttr(name="proj1"))
    if stacked:
        fc_seq, h_seq = pt.layers.stacked_lstm(
            proj1, size=4 * H, stacked_num=N,
            param_attr=pt.ParamAttr(name="s"),
            bias_attr=pt.ParamAttr(name="sb"))
    else:
        fc_prev = proj1
        h_prev = pt.layers.dynamic_lstm(
            proj1, size=4 * H, param_attr=pt.ParamAttr(name="s.w0"),
            bias_attr=pt.ParamAttr(name="sb.b0"))
        for i in range(N - 1):
            fc_prev = pt.layers.fc(
                [fc_prev, h_prev], size=4 * H,
                param_attr=[pt.ParamAttr(name=f"s.wa{i}"),
                            pt.ParamAttr(name=f"s.wb{i}")],
                bias_attr=pt.ParamAttr(name=f"sb.fb{i}"))
            h_prev = pt.layers.dynamic_lstm(
                fc_prev, size=4 * H,
                param_attr=pt.ParamAttr(name=f"s.w{i + 1}"),
                bias_attr=pt.ParamAttr(name=f"sb.b{i + 1}"))
        fc_seq, h_seq = fc_prev, h_prev
    pooled_fc = pt.layers.sequence_pool(fc_seq, "max")
    pooled_h = pt.layers.sequence_pool(h_seq, "max")
    pred = pt.layers.fc([pooled_fc, pooled_h], size=1,
                        param_attr=[pt.ParamAttr(name="out_a"),
                                    pt.ParamAttr(name="out_b")])
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return loss


import pytest


def test_stacked_n_matches_per_layer_build():
    """The N-layer single-op stack reproduces the book's per-layer
    fc([fc_prev, lstm_prev]) + dynamic_lstm build exactly (same weight
    names -> identical init -> identical losses over Adam steps)."""
    feed = _feed()
    results = {}
    for stacked in (False, True):
        loss = _build_n(stacked)
        exe = pt.Executor()
        exe.run(pt.default_startup_program())
        ls = []
        for _ in range(4):
            (l,) = exe.run(feed=feed, fetch_list=[loss])
            ls.append(float(l))
        results[stacked] = ls
    np.testing.assert_allclose(results[True], results[False],
                               rtol=2e-5, atol=2e-5)


def test_stacked_n_fused_path_matches_scan():
    """The fused multi-layer branch (per-layer Pallas kernels + batched
    inter-layer matmuls) vs the single all-layers scan, at an in-window
    geometry (H=512, B=8) with a dispatch spy — the fused branch must
    actually ENGAGE, not silently compare scan to scan."""
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.ops import pallas_kernels

    feed = _feed(B=8)
    results = {}
    kernel_calls = {False: 0, True: 0}
    orig = pallas_kernels._lstm_pallas_raw
    for interp in (False, True):
        FLAGS.fused_rnn_interpret = interp

        def spy(*a, **k):
            kernel_calls[interp] += 1
            return orig(*a, **k)

        pallas_kernels._lstm_pallas_raw = spy
        try:
            loss = _build_n(True, H=512)
            exe = pt.Executor()
            exe.run(pt.default_startup_program())
            ls = []
            for _ in range(3):
                (l,) = exe.run(feed=feed, fetch_list=[loss])
                ls.append(float(l))
            results[interp] = ls
        finally:
            pallas_kernels._lstm_pallas_raw = orig
            FLAGS.fused_rnn_interpret = False
    assert kernel_calls[True] >= 3, kernel_calls  # one kernel per layer
    assert kernel_calls[False] == 0, kernel_calls
    np.testing.assert_allclose(results[True], results[False],
                               rtol=2e-4, atol=2e-4)
