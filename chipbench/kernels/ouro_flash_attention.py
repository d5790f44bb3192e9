"""What one training step's attention kernels need in a looped model (one
stack of `num_hidden_layers` layers run `total_ut_steps` times), for
`ouro.flash_roofline`: `kernels/flash_attention.py`'s convention to the letter
(a multiply and an add count separately; two matmuls forward and four
backward over the T (T + 1) / 2 causal query-key pairs of a head; the
backward's recomputed scores count nothing; each tensor once, 2 bytes an
element: the forward reads Q, K, V and writes O, the backward reads Q, K, V,
O, dO and writes dQ, dK, dV), at `num_attention_heads` heads of `head_dim`,
over K x L LAYER APPLICATIONS, one forward and one backward each.

`layers.Repeat` runs every turn's forward a second time in the backward pass
(a turn is rematerialised from its saved carry): that forward's FLOPs and
bytes count NOTHING here, as recomputed work counts nothing in `flops.py`,
while its time is in the seconds the reader divides by. A sound reading
therefore stands under what the same kernels read in a model whose layers run
once (olmoe-1b-7b's cell, the same head size and length) by about the
recomputed forward's share of the kernels' time."""

from chipbench.kernels import flash_attention


def flops_and_bytes(config: dict, cell: dict):
    """(FLOPs, bytes) of one step: K x L layer applications, the whole
    batch."""
    applications = int(config["total_ut_steps"]) * int(config["num_hidden_layers"])
    return flash_attention.flops_and_bytes(
        dict(config, num_hidden_layers=applications), cell)
