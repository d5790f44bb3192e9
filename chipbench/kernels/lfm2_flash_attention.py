"""What one training step's attention kernels need where only SOME layers are
attention layers (`layer_types`: "full_attention" among "conv" operators), for
`lfm2.flash_roofline`: each such layer runs the packed flash kernels at
`num_attention_heads` (32) query heads over `num_key_value_heads` (8) K/V heads
of hidden_size / num_attention_heads = 64 lanes (the config has no `head_dim`
key), causal, at the cell's T.

`kernels/flash_attention.py`'s convention to the letter, and its function (a
multiply and an add count separately; two matmuls forward and four backward
over the T (T + 1) / 2 causal query-key pairs of a head; the backward's
recomputed scores count nothing; each tensor once, 2 bytes an element: the
forward reads Q, K, V and writes O, the backward reads Q, K, V, O, dO and
writes dQ, dK, dV; K, V, dK and dV counted at the K/V heads: what a group of
query heads shares is read once), with the number of layers the count of
"full_attention" in `layer_types`, where that file would count every layer.
At heads of 64 the program repeats K and V to the query heads in front of the
kernels (two heads a lane block: no K/V block to share), so the kernels move
more K/V bytes than are counted here: time they spend, not work the step
needs."""

from chipbench.kernels import flash_attention

ATTENTION = "full_attention"


def attention_layers(config: dict) -> int:
    return sum(kind == ATTENTION for kind in config["layer_types"])


def flops_and_bytes(config: dict, cell: dict):
    """(FLOPs, bytes) of one step: the attention layers, the whole batch."""
    return flash_attention.flops_and_bytes(
        dict(config, num_hidden_layers=attention_layers(config)), cell)
