"""`paddle_tpu train --config` module: the Phi-4-mini-flash-shaped decoder LM
(`paddle_tpu.models.phi4flash_lm`) at `microsoft/Phi-4-mini-flash-reasoning`'s
published widths (hidden 2560; Mamba-1 mixers of 5120 channels, 16 states, 4
taps and a step projected through rank 160; differential attention, 40 query
heads over 20 K/V heads of 64 in pairs, under a window of 512 in the
self-decoder and whole at layer 17; a cross-decoder whose gated memory units
read layer 16's scan output and whose attention layers read layer 17's keys
and values; LayerNorm; dense MLPs of width 10 240; the head tied to the token
table), cut to ONE chip of an 8-chip slice that holds six layers as a pipeline
stage: published layers 0, 1, 16, 17, 18, 19 of 32 (every kind of layer, each
reader of the scan output and of the keys and values once) and an eighth of
the vocabulary (25 008 rows). 697.1 M parameters, one sequence of 8192 tokens
a step. Adam 3e-4, bf16 AMP with float32 master weights; cost = next-token
cross-entropy. Only builders from `paddle_tpu.models`; weights and data come
from `seed`.

The reader is `configs/transformer_lm.py`'s: synthetic and learnable, every
sequence counts upward from a random start inside a 512-token slice of the
vocabulary. Tests pass smaller sizes to `get_model`.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models


def get_model(layer_ids=(0, 1, 16, 17, 18, 19), model_layers=32, dim=2560,
              heads=40, kv_heads=20, ffn_dim=10240, window=512, state_size=16,
              conv_kernel=4, expand=2, dt_rank=None, seqlen=8192, vocab=25008,
              batch=1, steps=10, learning_rate=3e-4, table_std=0.0025,
              stream_writer_gain=0.125, lam_std=0.0005, seed=7, amp="bfloat16"):
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    logits = models.phi4flash_lm(
        toks, vocab_size=vocab, num_hidden_layers=model_layers,
        sliding_window=window, dim=dim, num_heads=heads, num_kv_heads=kv_heads,
        ffn_dim=ffn_dim, state_size=state_size, conv_kernel=conv_kernel,
        expand=expand, dt_rank=dt_rank, layer_ids=layer_ids,
        # the start the benchmark's comparison made the builder keep (PERF.md
        # section 6, PR 57): table and stream-writing matrices an eighth of
        # the published 0.02 and of 1 / sqrt(model_layers), lam's vectors small
        table_std=table_std, lam_std=lam_std,
        out_scale=stream_writer_gain * model_layers ** -0.5)
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    pt.optimizer.Adam(learning_rate=learning_rate).minimize(loss)
    if amp:
        main.set_amp(amp)

    def reader():
        rng = np.random.RandomState(seed)
        span = min(512, vocab)
        for _ in range(steps):
            start = rng.randint(0, span, (batch, 1))
            seq = (start + np.arange(seqlen + 1)) % span
            yield {"toks": seq[:, :-1].astype(np.int32),
                   "labels": seq[:, 1:, None].astype(np.int32)}

    return {"cost": loss, "reader": reader, "num_passes": 1}
