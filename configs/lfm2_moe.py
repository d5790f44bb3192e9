"""`paddle_tpu train --config` module: the LFM2-shaped decoder LM
(`paddle_tpu.models.lfm2_moe_lm`) at `LiquidAI/LFM2-24B-A2B`'s published
widths (hidden 2048; gated short-convolution operators of 3 taps and
grouped-query attention layers, 32 query heads over 8 K/V heads of 64 with
RMSNorm on Q and K per head before the rotary (theta 1e6), 3 : 1; pre-norm
residuals; leading dense SwiGLU layers of width 11 776; 64 sigmoid-routed
SwiGLU experts of width 1536, top 4 over all 64 of score + choice bias, gates
renormalised over their sum + 1e-6, no shared expert), cut to ONE chip of an
8-chip expert-parallel deployment: published layers 1 to 5 (the second
leading dense layer and the four routed layers behind it: conv |
full_attention, conv, conv, conv: the model has 40), experts 0-7 of each
routed layer (the router still scores all 64 and a token chooses among all; a
pair that chose an absent expert adds nothing here) and an eighth of the
vocabulary (8192 rows). 486.1 M parameters, one sequence of 16 384 tokens a
step. Adam 3e-6 (a late fine-tuning rate: with no shared expert a routed
layer's only output is its held experts', and at the other configurations'
3e-4 two thirds of the pairs land on them inside 30 steps: PERF.md section 6,
PR 49), bf16 AMP with float32 master weights, a float32 router; cost
= next-token cross-entropy, no auxiliary cost. Only builders from
`paddle_tpu.models`; weights and data come from `seed`.

The reader is `configs/transformer_lm.py`'s: synthetic and learnable, every
sequence counts upward from a random start inside a 512-token slice of the
vocabulary. Tests pass smaller sizes to `get_model`.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.models.lfm2_moe import ATTENTION as A, CONV as C


def get_model(layer_types=(C, A, C, C, C), dense_layers=1, dim=2048, heads=32,
              kv_heads=8, conv_kernel=3, dense_dim=11776, experts=64,
              held_experts=(0, 8), experts_per_token=4, expert_dim=1536,
              model_layers=40, seqlen=16384, vocab=8192, batch=1, steps=10,
              learning_rate=3e-6, seed=7, amp="bfloat16"):
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    logits, _ = models.lfm2_moe_lm(
        toks, vocab_size=vocab, layer_types=layer_types,
        num_dense_layers=dense_layers, dim=dim, num_heads=heads,
        num_kv_heads=kv_heads, conv_kernel=conv_kernel, dense_dim=dense_dim,
        num_experts=experts, experts_per_token=experts_per_token,
        expert_dim=expert_dim, held_experts=held_experts,
        # a part of the 40-layer model: its stream-writing matrices' start
        out_scale=model_layers ** -0.5)
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    pt.optimizer.Adam(learning_rate=learning_rate).minimize(loss)
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
