"""What one training step's HELD routed experts need from the grouped matmul,
for `nemotron.gmm_roofline`: a chip's share of relu^2 experts without a gate
matrix, which `kernels/moe_grouped_matmul.py` (three stacks, every expert
held, every pair a row) does not count.

That file's convention to the letter (a multiply and an add count separately;
only what the mathematics needs counts; a forward emitted twice and the
share's recomputed forward count nothing; each tensor once, 2 bytes an
element), with three counts of its own:

- Rows: only the (token, slot) pairs that chose a held expert. The kernels
  are handed T x k rows and group sizes that sum to fewer; the rows behind
  them need nothing. `rows` is what the window's counter
  (`pt_moe_held_pairs_total`) gives a step over all routed blocks; without
  it, even routing: tokens x k x held / scored experts a block.
- Two matmuls an expert (up, d -> f; down, f -> d), f the published 1856:
  the 1920 the op pads to for the kernel's lane tiling is the kernel's way,
  not the step's need. Forward 2 x rows x d x f each, the backward two more
  of that size each: 2 x 3 x 2 = 12 x rows x d x f.
- Bytes: weights: each of the two [held, d, f] stacks read once forward and
  once backward, its gradient written once: 2 x 3 x held d f elements a
  block; activations, forward: x [rows, d] read, the up output [rows, f]
  written, the hidden h [rows, f] read, y [rows, d] written: 2 d + 2 f a
  row; backward: dy read and dh written by the down matmul's row gradient, h
  read for its weight gradient (dy counted once), d_up read and dx written by
  the up matmul's row gradient, x read for its weight gradient: 3 d + 3 f a
  row. relu^2 between the matmuls and its gradient are not the kernels'
  traffic.
  So bytes = 2 x (6 held d f x blocks + rows x (5 d + 5 f)).

Sizes: `hidden_size`, `moe_intermediate_size`, `held_experts`,
`router_experts`, `num_experts_per_tok`, the "E" of
`hybrid_override_pattern`; `batch` and `seqlen` are the cell's."""

from __future__ import annotations

BYTES_PER_ELEMENT = 2  # bf16 under AMP


def flops_and_bytes(config: dict, cell: dict, rows: float | None = None):
    """(FLOPs, bytes) of one step: all routed blocks, the whole batch.
    `rows`: the held pairs of one step, summed over the routed blocks."""
    blocks = config["hybrid_override_pattern"].count("E")
    d, f = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    lo, hi = config["held_experts"]
    if rows is None:
        rows = (blocks * int(cell["batch"]) * int(cell["seqlen"])
                * int(config["num_experts_per_tok"])
                * (hi - lo) / int(config["router_experts"]))
    flops = 12 * rows * d * f
    elements = 6 * blocks * (hi - lo) * d * f + rows * (5 * d + 5 * f)
    return float(flops), float(elements * BYTES_PER_ELEMENT)
