"""Model FLOPs per trained token of the OLMoE family (`flops_family`
"olmoe"), by `chipbench/flops.py`'s convention: a multiply and an add count
separately, a train step is 3x the forward pass, recomputed work counts
nothing, and for the sparse experts only the ACTIVE parameters count (the
8 experts a token is sent to, not the 64 that exist).

Per layer, in MACs a token: Q, K, V, O projections 4 d^2; the router d x
E; the token's k experts, three d x f matrices each (gate, up, down): k x 3
d f. Causal attention: two matmuls over T x d, halved by the mask: 2 T d
FLOPs a token. The untied head: d x V MACs. Norms, rotary, softmax and the
embedding gather are left out, as everywhere in `flops.py`.

At the published sizes (d 2048, f 1024, E 64, k 8, V 50304), one layer, T
4096: 3 x [2 x (16 777 216 + 131 072 + 50 331 648) + 16 777 216 +
206 045 184] = 1 071 906 816 FLOPs a token.
"""


def forward_flops_per_token(config: dict, seqlen: int) -> float:
    d, f = config["hidden_size"], config["intermediate_size"]
    experts, k = config["num_experts"], config["num_experts_per_tok"]
    layer_macs = 4 * d * d + experts * d + k * 3 * d * f
    return (config["num_hidden_layers"] * (2 * layer_macs + 2 * seqlen * d)
            + 2 * d * config["vocab_size"])


def train_flops_per_item(config: dict, cell: dict) -> float:
    return 3.0 * forward_flops_per_token(config, int(cell["seqlen"]))
