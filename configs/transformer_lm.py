"""`paddle_tpu train --config` module: the decoder-only transformer LM
at GPT-2 small's widths (dim 768, 12 heads, 12 layers, T 1024; vocab
32 k, Adam, bf16 AMP) through the
flash-attention dispatcher. Only builders from `paddle_tpu.models`;
weights and data come from `seed`.

The reader is synthetic and learnable: every sequence counts upward
from a random start inside a 512-token slice of the vocabulary, so the
next token is a function of the current one and the loss falls within a
few steps. `chip_smoke.py` drives this file through the CLI; its CPU
rehearsal passes smaller sizes to `get_model`.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models


def get_model(dim=768, heads=12, layers=12, seqlen=1024, vocab=32000,
              batch=8, steps=10, seed=7):
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    logits = models.transformer_lm(
        toks, vocab_size=vocab, dim=dim, num_heads=heads,
        num_layers=layers, max_len=seqlen)
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    pt.optimizer.Adam(learning_rate=3e-4).minimize(loss)
    main.set_amp("bfloat16")

    def reader():
        rng = np.random.RandomState(seed)
        span = min(512, vocab)
        for _ in range(steps):
            start = rng.randint(0, span, (batch, 1))
            seq = (start + np.arange(seqlen + 1)) % span
            yield {"toks": seq[:, :-1].astype(np.int32),
                   "labels": seq[:, 1:, None].astype(np.int32)}

    return {"cost": loss, "reader": reader, "num_passes": 1}
