"""`paddle_tpu train --config` module: the Nemotron-H-shaped hybrid decoder
LM (`paddle_tpu.models.nemotron_h_lm`) at NVIDIA-Nemotron-3-Nano-30B-A3B's
published widths (hidden 2688; Mamba-2 mixers of 64 heads x 64, 8 groups,
state 128, conv 4; attention of 32 query heads over 2 K/V heads x 128 with
no position signal; 128 sigmoid-routed relu^2 experts of width 1856, top 6,
gates renormalised x 2.5, beside a shared expert of 3712), cut to ONE chip
of a 16-chip expert-parallel deployment: the first nine blocks of the
published pattern (`MEMEM*EME`; the model has 52), experts 0-7 of each E
block (the router still scores all 128 and a token chooses among all; a
pair that chose an absent expert adds nothing here) and an eighth of the
vocabulary (16 384 rows). 667 M parameters. Adam 3e-4, bf16 AMP with
float32 master weights, a float32 router, float32 decays and state in the
scan; cost = cross-entropy, no auxiliary cost. Two departures from the
published `config.json`, both `models.nemotron_h_lm`'s (its docstring has
the readings): every matrix that writes to the residual stream starts at
1 / sqrt(52) of its Glorot range (the published `rescale_prenorm_residual`
names `out_proj` alone; 52 is the depth of the model this chip holds a part
of), and the residual stream is float32 (the published `residual_in_fp32`
is false). Only builders from `paddle_tpu.models`; weights and data come
from `seed`.

The reader is `configs/transformer_lm.py`'s: synthetic and learnable, every
sequence counts upward from a random start inside a 512-token slice of the
vocabulary. Tests pass smaller sizes to `get_model`.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models


def get_model(pattern="MEMEM*EME", dim=2688, mamba_heads=64, mamba_head_dim=64,
              n_groups=8, state_size=128, heads=32, kv_heads=2, head_dim=128,
              experts=128, held_experts=(0, 8), experts_per_token=6,
              expert_dim=1856, shared_expert_dim=3712, seqlen=8192,
              vocab=16384, model_blocks=52, batch=1, steps=10, seed=7,
              amp="bfloat16"):
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    logits = models.nemotron_h_lm(
        toks, vocab_size=vocab, pattern=pattern, dim=dim,
        mamba_heads=mamba_heads, mamba_head_dim=mamba_head_dim,
        n_groups=n_groups, state_size=state_size, num_heads=heads,
        num_kv_heads=kv_heads, head_dim=head_dim, num_experts=experts,
        experts_per_token=experts_per_token, expert_dim=expert_dim,
        shared_expert_dim=shared_expert_dim, held_experts=held_experts,
        out_scale=model_blocks ** -0.5)
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
