"""Model FLOPs per trained token of the looped family (`flops_family`
"looped_lm"), by `chipbench/flops.py`'s convention: a multiply and an add
count separately, a train step is 3x the forward pass, and recomputed work
counts nothing (`layers.Repeat` runs every turn's forward a second time in
the backward pass: a quarter of what the chip executes, none of it counted
here).

Forward, in FLOPs a token (d = hidden_size, H heads of D, f =
intermediate_size, L layers, K = total_ut_steps turns, V = vocab_size):

- a layer application: the four attention projections [d, H D] and [H D, d]
  and the three FFN matrices [d, f], 2 x (4 d H D + 3 d f); the kernels' two
  matmuls over the keys a causal query sees, (T + 1) / 2 on average:
  4 H D (T + 1) / 2 = 2 H D (T + 1) (`flops.py:transformer_lm` counts 2 T d)
- a turn: L layer applications, the head 2 d V (one weight, read after EVERY
  turn) and the exit gate 2 d
- the model: K turns. The exit distribution is a handful of FLOPs a token.

Norms, rotary, softmax and the embedding gather are left out, as everywhere in
`flops.py`.

At the cell's sizes (d 2048, H 16 x D 128, f 5632, L 8, K 4, V 49 152, T
4096): a layer application 2 x 51 380 224 + 2 x 2048 x 4097 = 119 541 760;
a turn 8 x that + 201 326 592 + 4096 = 1 157 664 768; forward 4 630 659 072,
13 891 977 216 FLOPs a trained token: 56.9 TFLOP a step of 4096 tokens (75.9
executed, with a turn recomputed).
"""


def forward_flops_per_token(config: dict, seqlen: int) -> float:
    d, f = config["hidden_size"], config["intermediate_size"]
    width = config["num_attention_heads"] * config["head_dim"]
    layer = 2 * (4 * d * width + 3 * d * f) + 2 * width * (seqlen + 1)
    turn = (config["num_hidden_layers"] * layer
            + 2 * d * config["vocab_size"] + 2 * d)
    return float(config["total_ut_steps"] * turn)


def train_flops_per_item(config: dict, cell: dict) -> float:
    return 3.0 * forward_flops_per_token(config, int(cell["seqlen"]))
