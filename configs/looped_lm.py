"""`paddle_tpu train --config` module: the looped decoder LM
(`paddle_tpu.models.looped_lm`; `transformers` model_type `ouro`): ONE stack
of layers run `turns` times with one set of weights through `layers.Repeat`
(a scan over a Program sub-block, rematerialised a turn at a time, all but
the last), the closing norm, the head and a float32 exit gate read after
every turn, and
the cost the expected cross-entropy over the exits less `exit_beta` x the
exit distribution's entropy. The layer is `ByteDance/Ouro-2.6B`'s: four
RMSNorms with the residual adding a normed branch, 16 heads of 128 with
rotary positions (theta 1e6), SwiGLU of width 5632, vocab 49 152, four turns.
The DEFAULTS here are tiny (a CPU trains them in seconds), like
`configs/transformer_lm.py`'s; Ouro-2.6B's widths at 8 of its 48 layers and T
4096, as the benchmark runs them on one 16 GB chip (612 M parameters, 13.1
GiB), are

    get_model(dim=2048, heads=16, head_dim=128, layers=8, ffn_dim=5632,
              turns=4, seqlen=4096, vocab=49152, batch=1, rope_theta=1e6)

Adam 3e-4, bf16 AMP with float32 master weights, the gate, the exit
distribution and the expected cost in float32; the exit gate starts at zero and the head at half its Glorot range
(`models.looped_lm`'s docstring says why). Only builders from
`paddle_tpu.models`; weights and data come from `seed`.

The reader is `configs/transformer_lm.py`'s: synthetic and learnable, every
sequence counts upward from a random start inside a 512-token slice of the
vocabulary.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models


def get_model(dim=64, heads=4, head_dim=16, layers=2, ffn_dim=96, turns=4,
              seqlen=64, vocab=256, batch=4, steps=40, seed=7,
              rope_theta=1e6, rms_eps=1e-6, exit_beta=0.05, amp="bfloat16"):
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    loss, _, _ = models.looped_lm(
        toks, labels, vocab_size=vocab, dim=dim, num_heads=heads,
        head_dim=head_dim, num_layers=layers, ffn_dim=ffn_dim, turns=turns,
        rope_theta=rope_theta, rms_eps=rms_eps, exit_beta=exit_beta)
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
