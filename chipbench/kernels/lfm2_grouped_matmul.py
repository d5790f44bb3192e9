"""What one training step's HELD routed experts need from the grouped matmul,
for `lfm2.gmm_roofline`: a chip's share of SwiGLU experts (three stacks) of
width `moe_intermediate_size` (1536) behind `num_dense_layers` dense layers, no
shared expert. `kernels/glm_grouped_matmul.py` counts the same op and is called
for it; it reads the number of routed layers from a key this configuration does
not have (`first_k_dense_replace`), which this file hands it from
`num_dense_layers` (as `kernels/trinity_grouped_matmul.py` does). That file's
convention (a multiply and an add count separately; only what the mathematics
needs counts; the share's recomputed forward counts nothing; each tensor once,
2 bytes an element), in short:

- Rows: only the (token, slot) pairs that chose a held expert. `rows` is what
  the window's counter (`pt_moe_held_pairs_total`) gives a step over all
  routed layers; without it, even routing: tokens x k x held / scored experts
  a layer (8 192 a layer at T 16 384, k 4, 8 of 64).
- Three matmuls an expert (W1 and W3, d -> f; W2, f -> d). Forward 2 x rows x d
  x f each, the backward two more of that size each: 18 x rows x d x f.
- Bytes: weights: each of the three [held, d, f] stacks read once forward and
  once backward, its gradient written once: 9 held d f elements a layer;
  activations: 2 d + 3 f a row forward, 3 d + 4 f backward (as that file
  derives them). So bytes = 2 x (9 held d f x layers + rows x (5 d + 7 f))."""

from __future__ import annotations

from chipbench.kernels import glm_grouped_matmul


def _as_glm(config: dict) -> dict:
    return dict(config, first_k_dense_replace=config["num_dense_layers"])


def routed_layers(config: dict) -> int:
    return glm_grouped_matmul.routed_layers(_as_glm(config))


def flops_and_bytes(config: dict, cell: dict, rows: float | None = None):
    """(FLOPs, bytes) of one step: all routed layers, the whole batch.
    `rows`: the held pairs of one step, summed over the routed layers."""
    return glm_grouped_matmul.flops_and_bytes(_as_glm(config), cell, rows)
