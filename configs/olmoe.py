"""`paddle_tpu train --config` module: the OLMoE-shaped routed-expert
decoder LM (`paddle_tpu.models.olmoe_lm`) at OLMoE-1B-7B's published
widths (hidden 2048, 16 heads x 128, 64 SwiGLU experts of width 1024,
top-8 without renormalisation, vocab 50304, T 4096), cut to ONE layer so
that weights, gradients and Adam's state fit one 16 GB chip (625.7 M
parameters; the published model has 16 layers). Adam 3e-4, bf16 AMP with
float32 master weights, a float32 router; cost = cross-entropy + 0.01 x
load balancing + 0.001 x router z-loss (the OLMoE paper's weights). Only
builders from `paddle_tpu.models`; weights and data come from `seed`.

The reader is `configs/transformer_lm.py`'s: synthetic and learnable,
every sequence counts upward from a random start inside a 512-token
slice of the vocabulary. Tests pass smaller sizes to `get_model`.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models


def get_model(dim=2048, heads=16, layers=1, experts=64, experts_per_token=8,
              expert_dim=1024, seqlen=4096, vocab=50304, batch=1, steps=10,
              seed=7, amp="bfloat16"):
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    logits, aux = models.olmoe_lm(
        toks, vocab_size=vocab, dim=dim, num_heads=heads, num_layers=layers,
        num_experts=experts, experts_per_token=experts_per_token,
        expert_dim=expert_dim)
    ce = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    loss = pt.layers.elementwise_add(ce, aux)
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
