"""What one training step's causal grouped-query attention needs in a model
whose depth is a PATTERN of block kinds, for `nemotron.flash_roofline`.

`kernels/flash_attention.py` multiplies by `num_hidden_layers`, which here
counts blocks of every kind (9, one of them attention). This file keeps that
file's convention to the letter (a multiply and an add count separately; two
matmuls forward and four backward over the T (T + 1) / 2 causal pairs a head;
recomputed scores and a forward emitted twice count nothing; each tensor
once, 2 bytes an element, the statistics left out) and differs in two
counts: the attention blocks are the "*" of `hybrid_override_pattern`, and
K, V, dK and dV have `num_key_value_heads` heads, not `num_attention_heads`.
(This repo's kernels write dK and dV once a QUERY head in float32 and a
reduction after them sums each group: that is the kernels' way, not the
step's need; the reduction's time is in `nemotron.attn_device_ms`, not
here.)"""

from __future__ import annotations

BYTES_PER_ELEMENT = 2  # bf16 activations


def flops_and_bytes(config: dict, cell: dict):
    """(FLOPs, bytes) of one step: all attention blocks, the whole batch."""
    blocks = config["hybrid_override_pattern"].count("*")
    heads = int(config["num_attention_heads"])
    kv_heads = int(config["num_key_value_heads"])
    head_dim = int(config["head_dim"])
    batch, seqlen = int(cell["batch"]), int(cell["seqlen"])
    pairs = seqlen * (seqlen + 1) // 2
    flops = blocks * batch * heads * 6 * 2 * pairs * head_dim
    q_like = batch * seqlen * heads * head_dim      # Q, O, dO, dQ
    kv_like = batch * seqlen * kv_heads * head_dim  # K, V, dK, dV
    elements = (2 * q_like + 2 * kv_like) + (4 * q_like + 4 * kv_like)
    return float(flops), float(blocks * elements * BYTES_PER_ELEMENT)
