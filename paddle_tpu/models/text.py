"""Text model zoo: the RNN benchmark + sentiment nets.

Reference: benchmark/paddle/rnn/rnn.py (2x stacked LSTM text classifier
on IMDB — the headline LSTM benchmark, BASELINE.md) and the book
understand_sentiment nets (stacked_lstm_net / conv_net).
"""

from __future__ import annotations

import paddle_tpu.layers as layers


def lstm_benchmark_net(words, vocab_size, emb_dim=128, hidden=512,
                       class_dim=2, max_len=None, sharded_embedding_axis=None):
    """Benchmark LSTM text classifier (reference: benchmark/paddle/rnn/

    rnn.py — embedding → 2 stacked LSTM (hidden 128-1280) → last-step
    pool → softmax). `sharded_embedding_axis` switches the table to a
    vocab-sharded table over that mesh axis (large-model mode).

    `max_len` (scan length): None is always safe (scans the LoD capacity);
    pass the bucketed max sequence length to avoid scanning padding —
    sequences longer than max_len would be silently truncated."""
    if sharded_embedding_axis:
        from ..parallel.sharded_embedding import sharded_embedding

        emb = sharded_embedding(words, size=[vocab_size, emb_dim],
                                mesh_axis=sharded_embedding_axis)
    else:
        emb = layers.embedding(words, size=[vocab_size, emb_dim])
    proj1 = layers.fc(emb, size=hidden * 4, bias_attr=False)
    # both stacked layers + the inter-layer projection in one op: the
    # op dispatches per-layer fused kernels where eligible, else a
    # single both-layers scan (the small-cell dispatch-floor lever —
    # PERF.md r4)
    lstm2 = layers.stacked_lstm2(proj1, size=hidden * 4, max_len=max_len)
    pooled = layers.sequence_pool(lstm2, "last")
    return layers.fc(pooled, size=class_dim)


def stacked_lstm_net(words, vocab_size, emb_dim=128, hid_dim=128,
                     stacked_num=3, class_dim=2, max_len=None,
                     use_stacked_op=False):
    """Reference: fluid tests book understand_sentiment stacked_lstm_net.

    `use_stacked_op` routes the whole stack through the single
    layers.stacked_lstm op (exact-parity tested against this per-layer
    build, tests/test_stacked_lstm.py). Off by default: at the book
    scale the formulations were measurement-indistinguishable (0.79x-
    1.30x across identical runs; measured in an early round on a link
    that is gone; not re-measured), so the book keeps the reference's
    own structure."""
    emb = layers.embedding(words, size=[vocab_size, emb_dim])
    fc1 = layers.fc(emb, size=hid_dim * 4)
    if use_stacked_op:
        fc_seq, lstm_seq = layers.stacked_lstm(
            fc1, size=hid_dim * 4, stacked_num=stacked_num,
            max_len=max_len)
    else:
        fc_seq = fc1
        lstm_seq = layers.dynamic_lstm(fc1, size=hid_dim * 4,
                                       max_len=max_len)
        for _ in range(2, stacked_num + 1):
            fc_seq = layers.fc([fc_seq, lstm_seq], size=hid_dim * 4)
            lstm_seq = layers.dynamic_lstm(fc_seq, size=hid_dim * 4,
                                           max_len=max_len)
    fc_last = layers.sequence_pool(fc_seq, "max")
    lstm_last = layers.sequence_pool(lstm_seq, "max")
    return layers.fc([fc_last, lstm_last], size=class_dim)


def word2vec_net(words_list, dict_size, emb_dim=32):
    """Reference: book word2vec (N-gram LM): 4 context words → next word.

    words_list: 4 dense int variables."""
    embs = [
        layers.embedding(w, size=[dict_size, emb_dim],
                         param_attr="shared_emb_w")
        for w in words_list
    ]
    concat = layers.concat(embs, axis=1)
    hidden = layers.fc(concat, size=256, act="sigmoid")
    return layers.fc(hidden, size=dict_size)
