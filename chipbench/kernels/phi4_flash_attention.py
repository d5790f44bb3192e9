"""What one training step's attention kernels need where a layer's attention
is DIFFERENTIAL and only some layers are attention layers, for
`phi4.flash_roofline`: each "window", "full" or "cross" layer the
configuration holds (`reference.py:held_layers`) runs the packed flash kernels
FOUR times ((q_1, k_1, v_1), (q_1, k_1, v_2), (q_2, k_2, v_1), (q_2, k_2,
v_2): the kernels take one width for Q, K and V, so a pair's value of twice
the head's width is two launches, and the scores are computed in both), each
launch at `num_attention_heads` / 2 (20) query heads over
`num_key_value_heads` / 2 (10) K/V heads of hidden_size / num_attention_heads
= 64 lanes, causal, at the cell's T.

`kernels/flash_attention.py`'s convention for a launch (a multiply and an add
count separately; two matmuls forward and four backward over the query-key
pairs the mask KEEPS; the backward's recomputed scores count nothing; each
tensor once, 2 bytes an element: the forward reads Q, K, V and writes O, the
backward reads Q, K, V, O, dO and writes dQ, dK, dV; K, V, dK and dV counted
at the K/V heads), times four launches a layer: what the launches are asked
for, NOT what a kernel that read a 128-wide value would need (two score
matmuls of six fewer a pair of launches: the saving a later fused kernel
shows as a higher share of a smaller count). What the masks halve: a causal
layer keeps T (T + 1) / 2 of the T^2 pairs; the "window" layer keeps W (W + 1)
/ 2 + (T - W) W (`sliding_window` W keys a query, fewer at the start). At heads
of 64 the program repeats K and V to the query heads in front of the kernels,
so the kernels move more K/V bytes than are counted here: time they spend,
not work the step needs."""

from chipbench.kernels import selective_scan

BYTES_PER_ELEMENT = 2  # bf16 activations
LAUNCHES = 4
ATTENTION = ("window", "full", "cross")


def kept_pairs(seqlen: int, window: int = 0) -> int:
    if not window or window >= seqlen:
        return seqlen * (seqlen + 1) // 2
    return window * (window + 1) // 2 + (seqlen - window) * window


def flops_and_bytes(config: dict, cell: dict):
    """(FLOPs, bytes) of one step: the attention layers, the whole batch."""
    heads = int(config["num_attention_heads"])
    head_dim = int(config["hidden_size"]) // heads
    q_heads, kv_heads = heads // 2, int(config["num_key_value_heads"]) // 2
    batch, seqlen = int(cell["batch"]), int(cell["seqlen"])
    q_like = batch * seqlen * q_heads * head_dim      # Q, O, dO, dQ
    kv_like = batch * seqlen * kv_heads * head_dim    # K, V, dK, dV
    elements = (2 * q_like + 2 * kv_like) + (4 * q_like + 4 * kv_like)
    flops = bytes_ = 0.0
    for kind in selective_scan.held_kinds(config):
        if kind not in ATTENTION:
            continue
        pairs = kept_pairs(
            seqlen, int(config["sliding_window"]) if kind == "window" else 0)
        flops += LAUNCHES * batch * q_heads * 6 * 2 * pairs * head_dim
        bytes_ += LAUNCHES * elements * BYTES_PER_ELEMENT
    return flops, bytes_
