"""Model FLOPs per trained token of the LFM2 family (`flops_family`
"lfm2_moe"), by `chipbench/flops.py`'s convention: a multiply and an add count
separately, a train step is 3x the forward pass, recomputed work counts
nothing, and for the sparse experts only the ACTIVE parameters count
(`configs/glm-4.7-flash/flops.py`'s and `configs/trinity-mini/flops.py`'s
convention for a chip's share: the experts THIS CHIP computes under even
routing).

Per layer, forward, in FLOPs a token (d = hidden_size, H query heads, KV K/V
heads, D = d / H):

- a `conv` operator: the in-projection [d, 3 d] and the out-projection [d, d]:
  2 x 4 d^2. The gates and the K taps between them (about (2 K + 2) d a token,
  16 k of 33.6 M) are left out, as every elementwise pass is.
- a `full_attention` operator: the four projections (W_q and W_o [d, H D], W_k
  and W_v [d, KV D]): 2 x (2 d H D + 2 d KV D); the kernels' two matmuls over
  the keys a query SEES, 4 H D a (query, key) pair, (T + 1) / 2 keys on average
  under the causal mask: 2 (T + 1) H D.
- the dense FFN (the first `num_dense_layers` layers): three matrices, 2 x 3 d
  f_dense.
- a routed layer: the router 2 d E over ALL the experts it scores; the routed
  experts THIS CHIP computes: a token's k pairs land on a held expert with
  probability held / E each (even routing, which fresh weights give), three d
  x f matrices a pair: k held / E x 2 x 3 d f. No shared expert.
- The untied head over this chip's slice: 2 d V.

Norms, rotary, softmax and the embedding gather are left out, as everywhere in
`flops.py`.

At the cell's sizes (d 2048; H 32, KV 8, D 64; f_dense 11 776; E 64, held 8, k
4, f 1536; V 8192; layers conv | attention, conv, conv, conv, the first dense;
T 16 384): a conv operator 33 554 432, x 4 = 134 217 728 (30.5 %); the
attention layer's projections 20 971 520 and kernels 67 112 960 (4 H D x
8192.5), together 20.0 %; the dense FFN 144 703 488 (32.9 %); a routed layer
262 144 + 9 437 184, x 4 = 38 797 312 (8.8 %); the head 33 554 432 (7.6 %):
forward 439 357 440, 1 318 072 320 FLOPs a trained token (21.6 TFLOP a step of
16 384 tokens).
"""

CONV, ATTENTION = "conv", "full_attention"


def operator_flops_per_token(config: dict, seqlen: int, kind: str) -> float:
    d, H = config["hidden_size"], config["num_attention_heads"]
    if kind == CONV:
        return 2 * 4 * d * d
    if kind != ATTENTION:
        raise ValueError(f"layer_types: unknown kind {kind!r}")
    KV, D = config["num_key_value_heads"], d // H
    projections = 2 * (2 * d * H * D + 2 * d * KV * D)
    return projections + 4 * H * D * (seqlen + 1) / 2


def forward_flops_per_token(config: dict, seqlen: int) -> float:
    d = config["hidden_size"]
    kinds, dense = config["layer_types"], config["num_dense_layers"]
    lo, hi = config["held_experts"]
    f = config["moe_intermediate_size"]
    pairs_here = config["num_experts_per_tok"] * (hi - lo) / config["router_experts"]
    routed = 2 * d * config["router_experts"] + pairs_here * 6 * d * f
    return (sum(operator_flops_per_token(config, seqlen, k) for k in kinds)
            + dense * 6 * d * config["intermediate_size"]
            + (len(kinds) - dense) * routed
            + 2 * d * config["vocab_size"])


def train_flops_per_item(config: dict, cell: dict) -> float:
    return 3.0 * forward_flops_per_token(config, int(cell["seqlen"]))
