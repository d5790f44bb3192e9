"""What one training step's HELD routed experts need from the grouped matmul,
for `glm.gmm_roofline`: a chip's share of SwiGLU experts (three stacks), which
neither `kernels/moe_grouped_matmul.py` (three stacks, every expert held,
every pair a row) nor `kernels/nemotron_grouped_matmul.py` (a share, two
stacks, a pattern of block kinds) counts.

Those files' convention to the letter (a multiply and an add count separately;
only what the mathematics needs counts; the share's recomputed forward counts
nothing; each tensor once, 2 bytes an element), with these counts:

- Rows: only the (token, slot) pairs that chose a held expert. The kernels
  are handed T x k rows and group sizes that sum to fewer; the rows behind
  them need nothing. `rows` is what the window's counter
  (`pt_moe_held_pairs_total`) gives a step over all routed layers; without
  it, even routing: tokens x k x held / scored experts a layer.
- Three matmuls an expert (gate and up, d -> f; down, f -> d), f the published
  1536. Forward 2 x rows x d x f each, the backward two more of that size
  each (the rows' gradient and the weights'): 3 x 3 x 2 = 18 x rows x d x f.
- Bytes: weights: each of the three [held, d, f] stacks read once forward and
  once backward, its gradient written once: 3 x 3 x held d f elements a
  layer; activations, forward: x [rows, d] read by gate and by up (counted
  once), their outputs [rows, f] written (2 f), the hidden h [rows, f] read,
  y [rows, d] written: 2 d + 3 f a row; backward: dy read and dh written by
  the down matmul's row gradient, h read for its weight gradient, d_gate and
  d_up read and dx written by their row gradients (dx once), x read for their
  weight gradients (once): 3 d + 4 f a row. silu x up between the matmuls and
  its gradient are not the kernels' traffic.
  So bytes = 2 x (9 held d f x layers + rows x (5 d + 7 f)).

Sizes: `hidden_size`, `moe_intermediate_size`, `held_experts`,
`router_experts`, `num_experts_per_tok`, `num_hidden_layers` less
`first_k_dense_replace` routed layers; `batch` and `seqlen` are the cell's."""

from __future__ import annotations

BYTES_PER_ELEMENT = 2  # bf16 under AMP


def routed_layers(config: dict) -> int:
    return int(config["num_hidden_layers"]) - int(config["first_k_dense_replace"])


def flops_and_bytes(config: dict, cell: dict, rows: float | None = None):
    """(FLOPs, bytes) of one step: all routed layers, the whole batch.
    `rows`: the held pairs of one step, summed over the routed layers."""
    layers = routed_layers(config)
    d, f = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    lo, hi = config["held_experts"]
    if rows is None:
        rows = (layers * int(cell["batch"]) * int(cell["seqlen"])
                * int(config["num_experts_per_tok"])
                * (hi - lo) / int(config["router_experts"]))
    flops = 18 * rows * d * f
    elements = 9 * layers * (hi - lo) * d * f + rows * (5 * d + 7 * f)
    return float(flops), float(elements * BYTES_PER_ELEMENT)
