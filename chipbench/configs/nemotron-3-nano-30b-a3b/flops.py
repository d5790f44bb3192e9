"""Model FLOPs per trained token of the Nemotron-H family (`flops_family`
"nemotron_h"), by `chipbench/flops.py`'s convention: a multiply and an add
count separately, a train step is 3x the forward pass, recomputed work
counts nothing, and for the sparse experts only the ACTIVE parameters count.

Per block, forward, in FLOPs a token (d = hidden_size):

- M: in_proj 2 d (2 d_in + 2 G N + H); out_proj 2 d_in d; the scan as the
  chunked form computes it, the causal mask halving what lies inside a chunk
  of Q tokens: C_i . B_j 2 N x G groups x (Q + 1) / 2 pairs, the weighted
  sum over x_j 2 P x H heads x (Q + 1) / 2, a token's part of its chunk's
  state 2 H P N, and its reading of the carried state 2 H P N
  (`kernels/mamba2_scan.py` counts the same). d_in = H P.
- *: Q and O 2 x 2 d H_q D, K and V 2 x 2 d H_kv D; causal attention two
  matmuls over T x H_q D halved by the mask: 2 T H_q D.
- E: the router 2 d E over ALL the experts it scores; the shared expert 2 x
  2 d f_s; the routed experts THIS CHIP computes: a token's k pairs land on
  a held expert with probability held / E each (even routing, which fresh
  weights give), two d x f matrices a pair: k held / E x 2 x 2 d f.
- The untied head over this chip's slice: 2 d V.

The conv (2 K channels), norms, gates, softmax and the embedding gather are
left out, as everywhere in `flops.py`.

At the cell's sizes (d 2688; H 64, P 64, G 8, N 128, Q 128; 32 x 128 over 2
K/V heads; E 128, held 8, k 6, f 1856, f_s 3712; V 16 384; pattern
MEMEM*EME; T 8192): M 80 172 032 (55 394 304 + 22 020 096 + 2 757 632), *
46 792 704 + 67 108 864, E 48 082 944 (688 128 + 39 911 424 + 7 483 392),
head 88 080 384: forward 715 001 856, 2 145 005 568 FLOPs a trained token.
"""


def mamba_scan_flops_per_token(config: dict) -> float:
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N, Q = config["n_groups"], config["ssm_state_size"], config["chunk_size"]
    inside = (2 * N * G + 2 * P * H) * (Q + 1) / 2
    return inside + 4 * H * P * N


def forward_flops_per_token(config: dict, seqlen: int) -> float:
    d = config["hidden_size"]
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
    d_in = H * P
    mamba = (2 * d * (2 * d_in + 2 * G * N + H) + 2 * d_in * d
             + mamba_scan_flops_per_token(config))
    q_width = config["num_attention_heads"] * config["head_dim"]
    kv_width = config["num_key_value_heads"] * config["head_dim"]
    attention = 2 * d * (2 * q_width + 2 * kv_width) + 2 * seqlen * q_width
    lo, hi = config["held_experts"]
    pairs_here = config["num_experts_per_tok"] * (hi - lo) / config["router_experts"]
    experts = (2 * d * config["router_experts"]
               + 4 * d * config["moe_shared_expert_intermediate_size"]
               + pairs_here * 4 * d * config["moe_intermediate_size"])
    per_kind = {"M": mamba, "*": attention, "E": experts}
    return (sum(per_kind[kind] for kind in config["hybrid_override_pattern"])
            + 2 * d * config["vocab_size"])


def train_flops_per_item(config: dict, cell: dict) -> float:
    return 3.0 * forward_flops_per_token(config, int(cell["seqlen"]))
