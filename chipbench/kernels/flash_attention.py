"""What one training step's causal self-attention needs, for the flash
kernels' roofline share (`layer_metrics/kernel.flash_roofline.py`).

Convention, as `flops.py`'s: a multiply and an add count separately, and
only what the mathematics needs counts.

- Forward: two matmuls over [T, T, D] per head (scores = Q K^T, out = P V).
- Backward: four (dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q). The
  recomputation of the scores that a flash backward makes (once in the dQ
  kernel, once in the dK/dV kernel), and a forward kernel that is emitted
  twice, count nothing: they are time the kernels spend, not work the step
  needs, so they lower the share.
- The causal mask halves every one of the six: T (T + 1) / 2 of the T^2
  query-key pairs.
- Bytes: each tensor once, in the activations' type (bf16 under AMP, 2
  bytes): the forward reads Q, K, V and writes O (4); the backward reads Q,
  K, V, O, dO and writes dQ, dK, dV (8). The softmax statistics (two
  float32 per query and head) are left out: 1/D of a tensor.

Sizes: the configuration's, under GPT-2's keys (`n_embd`, `n_head`,
`n_layer`) or the `transformers` library's common ones (`hidden_size`,
`num_attention_heads`, `num_hidden_layers`, `head_dim`); `batch` and
`seqlen` are the cell's. Grouped-query attention (fewer K/V heads) changes
the bytes only; it is counted when `num_key_value_heads` is there."""

from __future__ import annotations

BYTES_PER_ELEMENT = 2  # bf16 activations


def _sizes(config: dict):
    heads = config.get("n_head", config.get("num_attention_heads"))
    layers = config.get("n_layer", config.get("num_hidden_layers"))
    width = config.get("n_embd", config.get("hidden_size"))
    head_dim = config.get("head_dim") or width // heads
    kv_heads = config.get("num_key_value_heads") or heads
    return int(layers), int(heads), int(kv_heads), int(head_dim)


def flops_and_bytes(config: dict, cell: dict):
    """(FLOPs, bytes) of one step: all layers, the whole batch."""
    layers, heads, kv_heads, head_dim = _sizes(config)
    batch, seqlen = int(cell["batch"]), int(cell["seqlen"])
    pairs = seqlen * (seqlen + 1) // 2          # causal query-key pairs
    one_matmul = 2 * pairs * head_dim           # per head and sequence
    flops = layers * batch * heads * 6 * one_matmul
    q_like = batch * seqlen * heads * head_dim      # Q, O, dO, dQ
    kv_like = batch * seqlen * kv_heads * head_dim  # K, V, dK, dV
    elements = (2 * q_like + 2 * kv_like) + (4 * q_like + 4 * kv_like)
    return float(flops), float(layers * elements * BYTES_PER_ELEMENT)
