"""`paddle_tpu train --config` module: Keye-VL-2.0-30B-A3B's language model
(`paddle_tpu.models.keye_lm`) at the published widths (hidden 2048; 32 query
heads over 4 K/V heads of 128, RMSNorm on Q and K per head, a three-axis
rotary of theta 1e7 over sections 16 / 24 / 24; a learned sparse attention:
an indexer of 16 heads of 64 scores every causal key and a row attends its
2048 best; 128 softmax-routed SwiGLU experts of width 768, top 8
renormalised, no shared expert), cut to ONE chip of 8 that share each layer:
four of the 48 layers (a pipeline stage), experts 0-15 of each layer (the
router still scores all 128 and a token chooses among all; a pair that chose
an absent expert adds nothing here) and an eighth of the vocabulary (18 992
rows). 465.4 M parameters held, 456.3 M trained: the indexer's three matrices
a layer are frozen (the kept sets are discrete; the language-model cost gives
them no gradient). Adam, bf16 AMP with float32 master weights, a float32
router; cost = next-token cross-entropy, no auxiliary cost. ONE sequence of
16 384 tokens a step. Only builders from `paddle_tpu.models`; weights and data
come from `seed`. The vision tower is not built.

The reader: the other configurations' counting data (every sequence counts
upward from a random start inside a 512-token slice of the vocabulary), and
in every sequence `image_spans` image spans of an `image_grid` x `image_grid`
grid (four of 32 x 32: 1024 tokens each, a quarter of the tokens), their
starts from the seed, with the positions of `span_positions`. A span's rows
are the token table's. Tests pass smaller sizes to `get_model`.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models


def span_positions(seqlen, starts, grid):
    """int32 [3, seqlen]: (temporal, height, width) of each token by the
    Qwen2-VL rule. A text token advances all three axes by one. An image
    span of grid x grid tokens that begins at token `s` (one of `starts`,
    ascending, spans apart) at position P gives its token at grid row r,
    column c the triple (P, P + r, P + c); the next text token is at P +
    grid."""
    pos = np.zeros((3, seqlen), np.int32)
    at = p = 0
    r, c = np.divmod(np.arange(grid * grid), grid)
    for s in starts:
        if s < at or s + grid * grid > seqlen:
            raise ValueError(f"span at {s}: spans of {grid * grid} tokens "
                             f"apart inside {seqlen}")
        pos[:, at:s] = p + np.arange(s - at)
        p += s - at
        pos[:, s:s + grid * grid] = p + np.stack([0 * r, r, c])
        p, at = p + grid, s + grid * grid
    pos[:, at:] = p + np.arange(seqlen - at)
    return pos


def span_starts(rng, seqlen, spans, grid):
    """`spans` ascending starts from `rng`: the text tokens that are left
    are cut at uniform random places."""
    text = seqlen - spans * grid * grid
    if text < 0:
        raise ValueError(f"{spans} spans of {grid} x {grid} tokens do not "
                         f"fit {seqlen}")
    cuts = np.sort(rng.randint(0, text + 1, spans))
    return [int(c) + i * grid * grid for i, c in enumerate(cuts)]


def get_model(layers=4, layer_ids=None, published_layers=48, dim=2048,
              heads=32, kv_heads=4, head_dim=128, index_heads=16,
              index_head_dim=64, topk=2048, experts=128, held_experts=(0, 16),
              experts_per_token=8, expert_dim=768, rope_theta=1e7,
              mrope_section=(16, 24, 24), seqlen=16384, vocab=18992, batch=1,
              image_spans=4, image_grid=32, steps=10, seed=7, amp="bfloat16",
              learning_rate=3e-4):
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    positions = pt.layers.data("positions", shape=[3, seqlen], dtype=np.int32)
    logits, _ = models.keye_lm(
        toks, positions, vocab_size=vocab, num_layers=published_layers,
        layer_ids=tuple(range(layers)) if layer_ids is None else layer_ids,
        dim=dim, num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
        index_heads=index_heads, index_head_dim=index_head_dim, topk=topk,
        num_experts=experts, experts_per_token=experts_per_token,
        expert_dim=expert_dim, held_experts=held_experts,
        rope_theta=rope_theta, mrope_section=mrope_section)
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    pt.optimizer.Adam(learning_rate=learning_rate).minimize(loss)
    main.set_amp(amp)

    def reader():
        rng = np.random.RandomState(seed)
        span = min(512, vocab)
        for _ in range(steps):
            start = rng.randint(0, span, (batch, 1))
            seq = (start + np.arange(seqlen + 1)) % span
            pos = np.stack([span_positions(
                seqlen, span_starts(rng, seqlen, image_spans, image_grid),
                image_grid) for _ in range(batch)])
            yield {"toks": seq[:, :-1].astype(np.int32),
                   "labels": seq[:, 1:, None].astype(np.int32),
                   "positions": pos}

    return {"cost": loss, "reader": reader, "num_passes": 1}
