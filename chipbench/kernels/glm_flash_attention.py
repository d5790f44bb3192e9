"""What one training step's causal attention needs in latent attention's
EXPANDED form, for `glm.flash_roofline`: every layer of the model runs the
packed kernels with Q, K, V and O at one head size, `qk_nope_head_dim` +
`qk_rope_head_dim` = `v_head_dim` (256 at GLM-4.7-Flash), and as many K/V
heads as query heads (the latent's up-projection materialises them per head).

`kernels/flash_attention.py` reads the head size from `head_dim` or
`hidden_size / num_attention_heads` (102.4 here: attention is 5120 wide at a
hidden size of 2048), so this configuration brings its own count. That file's
convention to the letter: a multiply and an add count separately; two matmuls
forward (scores = Q K^T, out = P V) and four backward (dV, dP, dQ, dK) over
the T (T + 1) / 2 causal pairs a head; the backward's recomputed scores count
nothing; each tensor once, 2 bytes an element: the forward reads Q, K, V and
writes O (4), the backward reads Q, K, V, O, dO and writes dQ, dK, dV (8);
the statistics are left out."""

from __future__ import annotations

BYTES_PER_ELEMENT = 2  # bf16 activations


def flops_and_bytes(config: dict, cell: dict):
    """(FLOPs, bytes) of one step: all layers, the whole batch."""
    layers = int(config["num_hidden_layers"])
    heads = int(config["num_attention_heads"])
    qk_dim = int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])
    v_dim = int(config["v_head_dim"])
    batch, seqlen = int(cell["batch"]), int(cell["seqlen"])
    pairs = seqlen * (seqlen + 1) // 2          # causal query-key pairs
    # Q K^T, dP's twin dS K and dS^T Q contract or write qk_dim lanes; P V,
    # P^T dO and dO V^T v_dim lanes: three matmuls of each width
    flops = layers * batch * heads * 3 * 2 * pairs * (qk_dim + v_dim)
    qk_like = batch * seqlen * heads * qk_dim   # Q, K, dQ, dK
    v_like = batch * seqlen * heads * v_dim     # V, O, dO, dV
    elements = (2 * qk_like + 2 * v_like) + (4 * qk_like + 4 * v_like)
    return float(flops), float(layers * elements * BYTES_PER_ELEMENT)
