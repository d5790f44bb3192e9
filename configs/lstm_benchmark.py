"""`paddle_tpu train --config` module: the reference's headline RNN
benchmark (benchmark/paddle/rnn/rnn.py — embedding 128, 2x stacked LSTM
hidden 512, batch 128, sequence length 100, vocab 30 k, Adam with L2
decay and global-norm clipping, bf16 AMP). Only builders from
`paddle_tpu.models`; weights and data come from `seed`.

The reader is synthetic and learnable: a sequence's tokens are drawn
from sixteen ids in the half of the vocabulary its label names, so a few
tens of steps move the loss. `chip_smoke.py` drives this file through the CLI; its
CPU rehearsal passes smaller sizes to `get_model`.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models, regularizer


def get_model(hidden=512, batch=128, seqlen=100, vocab=30000, emb_dim=128,
              steps=40, seed=7):
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed
    words = pt.layers.data("words", shape=[-1], dtype=np.int32,
                           lod_level=1, append_batch_size=False)
    label = pt.layers.data("label", shape=[1], dtype=np.int32)
    logits = models.lstm_benchmark_net(
        words, vocab_size=vocab, emb_dim=emb_dim, hidden=hidden,
        max_len=seqlen)
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, label))
    pt.optimizer.Adam(
        learning_rate=2e-3,
        regularization=regularizer.L2Decay(8e-4),
        grad_clip=pt.optimizer.GradientClipByGlobalNorm(25.0),
    ).minimize(loss)
    main.set_amp("bfloat16")

    def reader():
        rng = np.random.RandomState(seed)
        half = vocab // 2
        for _ in range(steps):
            labels = rng.randint(0, 2, batch)
            yield [(rng.randint(0, 16, seqlen).astype(np.int32) + half * y,
                    np.array([y], np.int32)) for y in labels]

    return {"cost": loss, "reader": reader, "feed_order": [words, label],
            "num_passes": 1}
