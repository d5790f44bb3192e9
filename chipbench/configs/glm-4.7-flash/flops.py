"""Model FLOPs per trained token of the GLM-4.7-Flash family (`flops_family`
"glm_moe"), by `chipbench/flops.py`'s convention: a multiply and an add count
separately, a train step is 3x the forward pass, recomputed work counts
nothing, and for the sparse experts only the ACTIVE parameters count
(`configs/nemotron-3-nano-30b-a3b/flops.py`'s convention for a chip's share:
the experts THIS CHIP computes under even routing).

Per layer, forward, in FLOPs a token (d = hidden_size, H heads, n + R = the
Q/K head, D_v the V head):

- latent attention: the five projections, 2 x (d r_q + r_q H (n + R) +
  d (r + R) + r H (n + D_v) + H D_v d); causal attention two matmuls over T x
  H heads halved by the mask: T H (n + R) + T H D_v (= 2 T H D at n + R = D_v).
- the dense FFN (the first `first_k_dense_replace` layers): three matrices,
  2 x 3 d f_dense.
- a routed layer: the router 2 d E over ALL the experts it scores; the shared
  expert 2 x 3 d f_s; the routed experts THIS CHIP computes: a token's k pairs
  land on a held expert with probability held / E each (even routing, which
  fresh weights give), three d x f matrices a pair: k held / E x 2 x 3 d f.
- The untied head over this chip's slice: 2 d V.

Norms, rotary, gates, softmax and the embedding gather are left out, as
everywhere in `flops.py`.

At the cell's sizes (d 2048; H 20, r_q 768, r 512, n 192, R 64, D_v 256;
f_dense 10 240; E 64, held 8, k 4, f 1536, one shared expert; V 19 360; 5
layers, the first dense; T 8192): latent attention 43 515 904 + 83 886 080 a
layer, x 5 = 637 009 920; the dense FFN 125 829 120; a routed layer 262 144 +
18 874 368 + 9 437 184, x 4 = 114 294 784; the head 79 298 560: forward
956 432 384, 2 869 297 152 FLOPs a trained token.
"""


def attention_flops_per_token(config: dict, seqlen: int) -> float:
    d, H = config["hidden_size"], config["num_attention_heads"]
    r_q, r = config["q_lora_rank"], config["kv_lora_rank"]
    n, R = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    Dv = config["v_head_dim"]
    projections = 2 * (d * r_q + r_q * H * (n + R) + d * (r + R)
                       + r * H * (n + Dv) + H * Dv * d)
    return projections + seqlen * H * (n + R) + seqlen * H * Dv


def forward_flops_per_token(config: dict, seqlen: int) -> float:
    d = config["hidden_size"]
    layers, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    lo, hi = config["held_experts"]
    f = config["moe_intermediate_size"]
    pairs_here = config["num_experts_per_tok"] * (hi - lo) / config["router_experts"]
    routed = (2 * d * config["router_experts"]
              + 6 * d * config["n_shared_experts"] * f
              + pairs_here * 6 * d * f)
    return (layers * attention_flops_per_token(config, seqlen)
            + dense * 6 * d * config["intermediate_size"]
            + (layers - dense) * routed
            + 2 * d * config["vocab_size"])


def train_flops_per_item(config: dict, cell: dict) -> float:
    return 3.0 * forward_flops_per_token(config, int(cell["seqlen"]))
