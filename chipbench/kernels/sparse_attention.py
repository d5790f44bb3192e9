"""What one training step's learned sparse attention needs from its attention
kernels, for `kernel.sparse_attn_roofline`: causal attention in which a row
attends only the `sa_config.topk` keys it KEEPS, at `num_attention_heads` (32)
query heads over `num_key_value_heads` (4) K/V heads of `head_dim` (128), at
the cell's T, in every one of the `num_hidden_layers` layers.

`kernels/flash_attention.py`'s convention to the letter (a multiply and an add
count separately; only what the mathematics needs counts; two matmuls forward
and four backward over a head's query-key pairs; the backward's recomputed
scores count nothing; each tensor once, 2 bytes an element: the forward reads
Q, K, V and writes O, the backward reads Q, K, V, O, dO and writes dQ, dK, dV;
K, V, dK and dV counted at the K/V heads), with ONE difference: the pairs are
the KEPT pairs, min(topk, t + 1) a row (topk (topk + 1) / 2 + (T - topk) topk
a sequence: 31 458 304 of the 134 225 920 causal ones at T 16 384, topk 2048),
not the causal ones. That is the same work whatever implements it: a form that
computes every causal pair and masks reads about the kept share (0.234 here) of
what it would read as a dense causal kernel, and a later kernel that skips
what is not kept reads more with no count restated. One tensor more than that
file counts: the kept sets themselves, one bit a (row, key), int32 [T, T /
32], read once forward and once backward (the softmax statistics stay left
out). The indexer's scores and the selection are not these kernels' work."""

from __future__ import annotations

BYTES_PER_ELEMENT = 2  # bf16 activations


def kept_pairs(seqlen: int, topk: int) -> int:
    """The (row, key) pairs one causal sequence keeps."""
    k = min(int(topk), int(seqlen))
    return k * (k + 1) // 2 + (seqlen - k) * k


def flops_and_bytes(config: dict, cell: dict):
    """(FLOPs, bytes) of one step: all layers, the whole batch."""
    layers, heads = int(config["num_hidden_layers"]), int(
        config["num_attention_heads"])
    kv_heads, head_dim = int(config["num_key_value_heads"]), int(
        config["head_dim"])
    batch, seqlen = int(cell["batch"]), int(cell["seqlen"])
    pairs = kept_pairs(seqlen, config["sa_config"]["topk"])
    flops = layers * batch * heads * 6 * 2 * pairs * head_dim
    q_like = batch * seqlen * heads * head_dim      # Q, O, dO, dQ
    kv_like = batch * seqlen * kv_heads * head_dim  # K, V, dK, dV
    elements = (2 * q_like + 2 * kv_like) + (4 * q_like + 4 * kv_like)
    kept_bits = 2 * batch * seqlen * (-(-seqlen // 4096) * 128) * 4
    return float(flops), float(
        layers * (elements * BYTES_PER_ELEMENT + kept_bits))
