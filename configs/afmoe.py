"""`paddle_tpu train --config` module: the Trinity-shaped decoder LM
(`paddle_tpu.models.afmoe_lm`) at `arcee-ai/Trinity-Mini`'s published widths
(hidden 2048; 32 query heads over 4 K/V heads of 128, RMSNorm on Q and K per
head, a sigmoid output gate; WINDOW layers of 2048 keys with rotary positions
(theta 1e4) and GLOBAL layers with no position signal, 3 : 1; four RMSNorms a
layer; the token table's rows x sqrt(2048); leading dense SwiGLU layers of
width 6144; 128 sigmoid-routed SwiGLU experts of width 1024, top 8 over all
128, gates renormalised x 2.826, beside one SwiGLU shared expert of 1024),
cut to ONE chip of an 8-chip expert-parallel deployment: published layers 1
to 5 (the second leading dense layer and the four routed layers behind it:
window, window | global, window, window: the model has 32), experts 0-15 of
each routed layer (the router still scores all 128 and a token chooses among
all; a pair that chose an absent expert adds nothing here) and an eighth of
the vocabulary (25 024 rows). 705.5 M parameters. Adam 3e-4, bf16 AMP with
float32 master weights, a float32 router; cost = next-token cross-entropy,
no auxiliary cost. Only builders from `paddle_tpu.models`; weights and data
come from `seed`.

The reader is `configs/transformer_lm.py`'s: synthetic and learnable, every
sequence counts upward from a random start inside a 512-token slice of the
vocabulary. Tests pass smaller sizes to `get_model`.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.models.afmoe import GLOBAL as G, WINDOW as W


def get_model(layer_types=(W, W, G, W, W), dense_layers=1, dim=2048, heads=32,
              kv_heads=4, head_dim=128, window=2048, dense_dim=6144,
              experts=128, held_experts=(0, 16), experts_per_token=8,
              expert_dim=1024, shared_expert_dim=1024, seqlen=8192,
              vocab=25024, batch=1, steps=10, seed=7, amp="bfloat16"):
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    logits, _ = models.afmoe_lm(
        toks, vocab_size=vocab, layer_types=layer_types,
        num_dense_layers=dense_layers, dim=dim, num_heads=heads,
        num_kv_heads=kv_heads, head_dim=head_dim, window=window,
        dense_dim=dense_dim, num_experts=experts,
        experts_per_token=experts_per_token, expert_dim=expert_dim,
        shared_expert_dim=shared_expert_dim, held_experts=held_experts)
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    pt.optimizer.Adam(learning_rate=3e-4).minimize(loss)
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
