"""Model FLOPs per trained token of Keye-VL-2.0's language model
(`flops_family` "keye_vl"), by `chipbench/flops.py`'s convention: a multiply
and an add count separately, a train step is 3x the forward pass of what is
TRAINED, recomputed work counts nothing, for the sparse experts only the
ACTIVE parameters count (the experts THIS CHIP computes under even routing),
and for the sparse attention only the KEPT (row, key) pairs: what the
mathematics needs, whatever computes it (a form that computes every causal
pair and masks is counted the same work as one that skips).

Per layer, forward, in FLOPs a token (d = hidden_size, H query heads, KV K/V
heads, D = head_dim; the indexer's Hi heads of Di; k_a = `sa_config.topk`):

- attention's four projections (W_q and W_o [d, H D], W_k and W_v [d, KV D]):
  2 x (2 d H D + 2 d KV D).
- the kernels' two matmuls over the keys a row KEEPS, 4 H D a (row, key)
  pair: a row keeps min(k_a, t + 1), on average (k_a (k_a + 1) / 2 + (T - k_a)
  k_a) / T (1920.06 at T 16 384, k_a 2048; every causal key at T <= k_a).
- the INDEXER, forward only and ONCE a step (it is frozen: no backward): its
  three projections 2 d (Hi Di + Di + Hi), and its scores over every causal
  key, 2 Hi Di a pair, (T + 1) / 2 pairs a row. The relu, the weighted sum
  over the heads and the selection itself (compares and counts on the vector
  unit) are left out, as every elementwise pass is.
- the router 2 d E over ALL the experts it scores; the routed experts THIS
  CHIP computes: a token's k pairs land on a held expert with probability
  held / E each (even routing, which fresh weights give), three d x f
  matrices a pair: k held / E x 2 x 3 d f. No shared expert.
- The untied head over this chip's slice: 2 d V.

Norms, rotary, softmax and the embedding gather are left out, as everywhere
in `flops.py`.

At the cell's sizes (d 2048; H 32, KV 4, D 128; Hi 16, Di 64, k_a 2048; E
128, held 16, k 8, f 768; V 18 992; four layers; T 16 384): a layer's
projections 37 748 736, kept pairs 31 458 304 (16 384 x 1920.06), router
524 288, held experts 9 437 184: 79 168 512 trained, x 4; the head 77 791 232:
trained forward 394 465 280; the indexer a layer 4 521 984 + 16 778 240 =
21 300 224, x 4 = 85 200 896. 3 x 394 465 280 + 85 200 896 = 1 268 596 736
FLOPs a trained token (20.78 TFLOP a step of 16 384 tokens).
"""


def kept_per_row(seqlen: int, topk: int) -> float:
    """The keys a row keeps on average: every causal key of the first topk
    rows, topk of each later one."""
    k = min(topk, seqlen)
    return (k * (k + 1) / 2 + (seqlen - k) * k) / seqlen


def trained_forward_flops_per_token(config: dict, seqlen: int) -> float:
    d, H = config["hidden_size"], config["num_attention_heads"]
    KV, D = config["num_key_value_heads"], config["head_dim"]
    lo, hi = config["held_experts"]
    E, f = config["router_experts"], config["moe_intermediate_size"]
    layer = (2 * (2 * d * H * D + 2 * d * KV * D)
             + 4 * H * D * kept_per_row(seqlen, config["sa_config"]["topk"])
             + 2 * d * E
             + config["num_experts_per_tok"] * (hi - lo) / E * 6 * d * f)
    return config["num_hidden_layers"] * layer + 2 * d * config["vocab_size"]


def indexer_forward_flops_per_token(config: dict, seqlen: int) -> float:
    d, sa = config["hidden_size"], config["sa_config"]
    Hi, Di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return config["num_hidden_layers"] * (
        2 * d * (Hi * Di + Di + Hi) + 2 * Hi * Di * (seqlen + 1) / 2)


def train_flops_per_item(config: dict, cell: dict) -> float:
    seqlen = int(cell["seqlen"])
    return (3.0 * trained_forward_flops_per_token(config, seqlen)
            + indexer_forward_flops_per_token(config, seqlen))
