"""What one training step's routed experts need from the grouped matmul,
for its roofline share (`layer_metrics/kernel.gmm_roofline.py`).

Convention, as `flops.py`'s: a multiply and an add count separately, and
only what the mathematics needs counts.

- Rows: every (token, slot) pair is computed (dropless): tokens x k rows,
  tokens = batch x seqlen, k = `num_experts_per_tok`.
- FLOPs: three matmuls a layer (gate and up, d -> f; down, f -> d), each
  2 x rows x d x f forward; the backward is two more of the same size for
  each (the rows' gradient against the transposed weights, and the weights'
  gradient): 3 x 3 x 2 = 18 x rows x d x f. A forward that the step emits
  twice counts once.
- Bytes, each tensor once, in the types the step uses (bf16 activations and
  bf16 copies of the float32 master weights under AMP, 2 bytes; the weights'
  gradients leave the kernels in bf16 too):
  weights: each of the three [E, d, f] stacks is read once forward and once
  backward, and its gradient written once: 3 x 3 x E d f elements;
  activations, forward: the sorted rows x [rows, d] are read (once: gate and
  up share them), gate and up outputs [rows, f] written, the hidden h [rows,
  f] read, the output y [rows, d] written: 2 d + 3 f a row; backward: dy read
  and dh written by the down matmul's row gradient, h and dy read for its
  weight gradient (dy counted once), d_gate and d_up read and dx written
  (one sum) by the row gradients, x read for the two weight gradients:
  3 d + 4 f a row. The elementwise silu * up between the matmuls and its
  gradient are not the kernels' traffic and are left out.
  So bytes = 2 x (9 E d f + rows x (5 d + 7 f)) a layer.

Sizes: the configuration's, under the `transformers` library's keys
(`hidden_size`, `intermediate_size` = one expert's width, `num_experts`,
`num_experts_per_tok`, `num_hidden_layers`); `batch` and `seqlen` are the
cell's."""

from __future__ import annotations

BYTES_PER_ELEMENT = 2  # bf16 under AMP


def flops_and_bytes(config: dict, cell: dict):
    """(FLOPs, bytes) of one step: all layers, the whole batch."""
    layers = int(config["num_hidden_layers"])
    d, f = int(config["hidden_size"]), int(config["intermediate_size"])
    experts, k = int(config["num_experts"]), int(config["num_experts_per_tok"])
    rows = int(cell["batch"]) * int(cell["seqlen"]) * k
    flops = layers * 18 * rows * d * f
    elements = 9 * experts * d * f + rows * (5 * d + 7 * f)
    return float(flops), float(layers * elements * BYTES_PER_ELEMENT)
