"""What one training step's Mamba-2 scans need, for the scan's roofline share
(`layer_metrics/kernel.ssm_scan_roofline.py`).

Convention, as `flops.py`'s: a multiply and an add count separately, and
only what the mathematics needs counts. The mathematics is the chunked (SSD)
form of the selective scan (Dao & Gu 2024) at the configuration's
`chunk_size` Q, which every implementation of Mamba-2 computes; per token
and M block, forward:

- inside the chunk, halved by the causal mask ((Q + 1) / 2 of the Q tokens
  before and at a token): the scores C_i . B_j, 2 N a pair and group (G
  groups); the weighted sum over x_j, 2 P a pair and head (H heads);
- the token's part of its chunk's final state, dt x B^T: 2 H P N;
- the token's reading of the state carried into its chunk, S C: 2 H P N.
  The T / Q steps of the recurrence over chunk states (2 H P N a chunk), the
  decays' exponentials and the mask's multiplications are left out, as
  softmax is for attention.
- Backward: twice the forward's matmuls (each matmul's two operands'
  gradients), so a step is 3 x the forward. What a backward recomputes of
  the forward (this repo's scan recomputes all of it under `jax.checkpoint`)
  and a forward that the step emits twice count nothing: they are time the
  scan spends, not work the step needs, so they lower the share.
- Bytes, each tensor once in the type the step uses: forward reads x [T, H
  P], B and C [T, G N] (bf16, 2 bytes) and dt [T, H] (float32) and writes y
  [T, H P] (float32: the gated norm reads it unrounded); backward reads the
  same four and dy (float32) and writes dx, dB, dC (bf16) and d dt
  (float32). The per-head A and D are a few hundred bytes.

Sizes: the configuration's, under `nemotron_h`'s keys (`mamba_num_heads`,
`mamba_head_dim`, `n_groups`, `ssm_state_size`, `chunk_size`; the number of
M blocks is the count of "M" in `hybrid_override_pattern`); `batch` and
`seqlen` are the cell's."""

from __future__ import annotations


def forward_flops_per_token(config: dict) -> float:
    H, P = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    G, N = int(config["n_groups"]), int(config["ssm_state_size"])
    Q = int(config["chunk_size"])
    return (2 * N * G + 2 * P * H) * (Q + 1) / 2 + 4 * H * P * N


def flops_and_bytes(config: dict, cell: dict):
    """(FLOPs, bytes) of one step: all M blocks, the whole batch."""
    blocks = config["hybrid_override_pattern"].count("M")
    tokens = int(cell["batch"]) * int(cell["seqlen"])
    H, P = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    G, N = int(config["n_groups"]), int(config["ssm_state_size"])
    x, bc, dt = H * P, 2 * G * N, H
    forward = 2 * (x + bc) + 4 * dt + 4 * x
    backward = 2 * (x + bc) + 4 * dt + 4 * x + 2 * (x + bc) + 4 * dt
    return (float(blocks * tokens * 3 * forward_flops_per_token(config)),
            float(blocks * tokens * (forward + backward)))
