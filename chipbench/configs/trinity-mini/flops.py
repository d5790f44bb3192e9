"""Model FLOPs per trained token of the Trinity family (`flops_family`
"afmoe"), by `chipbench/flops.py`'s convention: a multiply and an add count
separately, a train step is 3x the forward pass, recomputed work counts
nothing, and for the sparse experts only the ACTIVE parameters count
(`configs/nemotron-3-nano-30b-a3b/flops.py`'s and
`configs/glm-4.7-flash/flops.py`'s convention for a chip's share: the experts
THIS CHIP computes under even routing).

Per layer, forward, in FLOPs a token (d = hidden_size, H query heads, KV K/V
heads, D = head_dim, W = sliding_window):

- attention: the five projections (W_q and the gate's W_g [d, H D], W_k and
  W_v [d, KV D], W_o [H D, d]): 2 x (3 d H D + 2 d KV D); the kernels' two
  matmuls over the keys a query SEES, 4 H D a (query, key) pair: a global
  layer's token sees (T + 1) / 2 keys on average (2 T H D, as
  `configs/glm-4.7-flash/flops.py` counts a causal layer), a window layer's
  (W (W + 1) / 2 + (T - W) W) / T (all of the earlier ones while there are
  fewer than W, then W), and min(W, T) decides which.
- the dense FFN (the first `num_dense_layers` layers): three matrices, 2 x 3 d
  f_dense.
- a routed layer: the router 2 d E over ALL the experts it scores; the shared
  expert 2 x 3 d f_s; the routed experts THIS CHIP computes: a token's k pairs
  land on a held expert with probability held / E each (even routing, which
  fresh weights give), three d x f matrices a pair: k held / E x 2 x 3 d f.
- The untied head over this chip's slice: 2 d V.

Norms, rotary, the gate's sigmoid, softmax and the embedding gather are left
out, as everywhere in `flops.py`.

At the cell's sizes (d 2048; H 32, KV 4, D 128, W 2048; f_dense 6144; E 128,
held 16, k 8, f 1024, one shared expert; V 25 024; layers window, window,
global, window, window, the first dense; T 8192): the projections 54 525 952 a
layer, x 5; the kernels 67 117 056 for the global layer (4 H D x 4096.5) and
29 362 176 for each window layer (4 H D x 1792.125); the dense FFN 75 497 472;
a routed layer 524 288 + 12 582 912 + 12 582 912, x 4; the head 102 498 304:
forward 737 951 744, 2 213 855 232 FLOPs a trained token (18.14 TFLOP a
step of 8192 tokens).
"""

WINDOW = "sliding_attention"


def keys_seen(seqlen: int, window=None) -> float:
    """The mean number of keys a query of a causal layer attends to."""
    if window is None or window >= seqlen:
        return (seqlen + 1) / 2
    return (window * (window + 1) / 2 + (seqlen - window) * window) / seqlen


def attention_flops_per_token(config: dict, seqlen: int, kind: str) -> float:
    d, H = config["hidden_size"], config["num_attention_heads"]
    KV, D = config["num_key_value_heads"], config["head_dim"]
    projections = 2 * (3 * d * H * D + 2 * d * KV * D)
    window = config["sliding_window"] if kind == WINDOW else None
    return projections + 4 * H * D * keys_seen(seqlen, window)


def forward_flops_per_token(config: dict, seqlen: int) -> float:
    d = config["hidden_size"]
    kinds, dense = config["layer_types"], config["num_dense_layers"]
    lo, hi = config["held_experts"]
    f = config["moe_intermediate_size"]
    pairs_here = config["num_experts_per_tok"] * (hi - lo) / config["router_experts"]
    routed = (2 * d * config["router_experts"]
              + 6 * d * config["num_shared_experts"] * f
              + pairs_here * 6 * d * f)
    return (sum(attention_flops_per_token(config, seqlen, k) for k in kinds)
            + dense * 6 * d * config["intermediate_size"]
            + (len(kinds) - dense) * routed
            + 2 * d * config["vocab_size"])


def train_flops_per_item(config: dict, cell: dict) -> float:
    return 3.0 * forward_flops_per_token(config, int(cell["seqlen"]))
