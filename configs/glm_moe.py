"""`paddle_tpu train --config` module: the GLM-4.7-Flash-shaped decoder LM
(`paddle_tpu.models.glm_moe_lm`) at `zai-org/GLM-4.7-Flash`'s published
widths (hidden 2048; latent attention of 20 heads x 256 = 192 + 64 rotary,
a 768-wide query latent and a 512-wide key/value latent, rope theta 1e6; a
leading dense SwiGLU layer of width 10 240; 64 sigmoid-routed SwiGLU experts
of width 1536, top 4 over all 64, gates renormalised x 1.8, beside one SwiGLU
shared expert of 1536), cut to ONE chip of an 8-chip expert-parallel
deployment: the leading dense layer and the four routed layers behind it (the
model has 47), experts 0-7 of each routed layer (the router still scores all
64 and a token chooses among all; a pair that chose an absent expert adds
nothing here) and an eighth of the vocabulary (19 360 rows). 591.3 M
parameters. Adam 3e-4, bf16 AMP with float32 master weights, a float32
router; cost = next-token cross-entropy, no auxiliary cost; the multi-token-
prediction module is not built. Every matrix that writes to the residual
stream starts at 1 / sqrt(47) of its Glorot range (47 is the depth of the
model this chip holds a part of) and the residual stream is float32
(`models.glm_moe_lm`'s two settings). Only builders from `paddle_tpu.models`;
weights and data come from `seed`.

The reader is `configs/transformer_lm.py`'s: synthetic and learnable, every
sequence counts upward from a random start inside a 512-token slice of the
vocabulary. Tests pass smaller sizes to `get_model`.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models


def get_model(layers=5, first_k_dense=1, dim=2048, heads=20, q_rank=768,
              kv_rank=512, nope_dim=192, rope_dim=64, v_dim=256,
              dense_dim=10240, experts=64, held_experts=(0, 8),
              experts_per_token=4, expert_dim=1536, shared_expert_dim=1536,
              seqlen=8192, vocab=19360, model_layers=47, batch=1, steps=10,
              seed=7, amp="bfloat16"):
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    logits, _ = models.glm_moe_lm(
        toks, vocab_size=vocab, num_layers=layers,
        first_k_dense=first_k_dense, dim=dim, num_heads=heads, q_rank=q_rank,
        kv_rank=kv_rank, nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
        dense_dim=dense_dim, num_experts=experts,
        experts_per_token=experts_per_token, expert_dim=expert_dim,
        shared_expert_dim=shared_expert_dim, held_experts=held_experts,
        out_scale=model_layers ** -0.5)
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
