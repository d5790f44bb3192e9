"""What one training step's attention kernels need where WINDOW and GLOBAL
layers sit in one model (`layer_types`), for `trinity.flash_roofline`: every
layer runs the packed flash kernels at `num_attention_heads` query heads over
`num_key_value_heads` K/V heads of `head_dim`; a `full_attention` layer is
causal, a `sliding_attention` layer causal within `sliding_window` keys.

`kernels/flash_attention.py`'s convention to the letter (a multiply and an add
count separately; two matmuls forward (scores = Q K^T, out = P V) and four
backward (dV, dP, dQ, dK); the backward's recomputed scores count nothing;
only what the mathematics needs counts: a key block the kernel visits and
masks is time it spends, not work the step needs; each tensor once, 2 bytes an
element: the forward reads Q, K, V and writes O, the backward reads Q, K, V,
O, dO and writes dQ, dK, dV; the statistics are left out), with a layer's
query-key pairs BY ITS KIND:

- `full_attention`: T (T + 1) / 2 of the T^2 pairs;
- `sliding_attention`: position i sees min(i + 1, W) keys: W (W + 1) / 2 +
  (T - W) W pairs (T (T + 1) / 2 where W >= T). At T 8192, W 2048: 14 681 088
  against 33 558 528, 43.75 %: a window layer computed as plain causal would
  read about 44 % of what it should.

K, V, dK and dV are counted at the K/V heads (4 of 32 here): what a group of
query heads shares is read once."""

from __future__ import annotations

BYTES_PER_ELEMENT = 2  # bf16 activations
WINDOW = "sliding_attention"


def pairs(seqlen: int, window=None) -> int:
    """Query-key pairs of one head of one sequence."""
    if window is None or window >= seqlen:
        return seqlen * (seqlen + 1) // 2
    return window * (window + 1) // 2 + (seqlen - window) * window


def flops_and_bytes(config: dict, cell: dict, kinds=None):
    """(FLOPs, bytes) of one step: the layers of `kinds` (default: all of
    `layer_types`), the whole batch."""
    kinds = list(config["layer_types"]) if kinds is None else list(kinds)
    heads = int(config["num_attention_heads"])
    kv_heads = int(config["num_key_value_heads"])
    head_dim = int(config["head_dim"])
    batch, seqlen = int(cell["batch"]), int(cell["seqlen"])
    flops = 0
    for kind in kinds:
        window = int(config["sliding_window"]) if kind == WINDOW else None
        flops += batch * heads * 6 * 2 * pairs(seqlen, window) * head_dim
    q_like = batch * seqlen * heads * head_dim      # Q, O, dO, dQ
    kv_like = batch * seqlen * kv_heads * head_dim  # K, V, dK, dV
    elements = (2 * q_like + 2 * kv_like) + (4 * q_like + 4 * kv_like)
    return float(flops), float(len(kinds) * elements * BYTES_PER_ELEMENT)
