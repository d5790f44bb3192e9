"""What one training step's gated short convolutions need, for
`kernel.short_conv_roofline`: the op between a short-conv operator's two GEMMs
(`paddle_tpu/ops/short_conv_ops.py:gated_short_conv`), `C * conv_K(B * X)` over
the in-projection's output [T, 3 d], at every "conv" layer of `layer_types`.

The count is of the op's OPERANDS AND RESULTS, whatever implements it (the
program's own `pt_short_conv_bytes` counts the same, from the traced shapes):

- Bytes, each tensor once in the type the step uses (bf16 under AMP, 2 bytes):
  the forward reads bcx [T, 3 d] and writes y [T, d]; the backward reads bcx
  and dy [T, d] and writes dbcx [T, 3 d]: 11 T d elements an operator. The
  taps [K, d] are float32, read forward and backward, and their float32
  gradient is written once: 12 K d bytes, a few tens of kilobytes. The halo
  rows a kernel's blocks read twice (16 of every 256) and the backward's
  partial sums of dw count nothing: bytes the kernels move, not bytes the op
  needs.
- FLOPs, a multiply and an add counting separately: forward B * X, K taps of a
  multiply and an add, C *: 2 K + 2 a (token, lane); the backward forms z and
  the convolution again (they count nothing: recomputation), and needs dy * C,
  K taps for dz, dz * X, dz * B, dy * conv and K multiply-adds for dw: 4 K + 4.
  6 K + 6 in all; at K 3, 24 a (token, lane) against 22 bytes: memory-bound on
  any chip whose FLOP-to-byte ratio is over 1.1 (a v5e's is 240).

Sizes: `hidden_size`, `conv_L_cache`, the count of "conv" in `layer_types`;
`batch` and `seqlen` are the cell's."""

from __future__ import annotations

BYTES_PER_ELEMENT = 2  # bf16 under AMP
CONV = "conv"


def operators(config: dict) -> int:
    return sum(kind == CONV for kind in config["layer_types"])


def flops_and_bytes(config: dict, cell: dict):
    """(FLOPs, bytes) of one step: every operator, the whole batch."""
    d, K = int(config["hidden_size"]), int(config["conv_L_cache"])
    tokens = int(cell["batch"]) * int(cell["seqlen"])
    n = operators(config)
    flops = n * tokens * d * (6 * K + 6)
    bytes_ = n * (11 * tokens * d * BYTES_PER_ELEMENT + 12 * K * d)
    return float(flops), float(bytes_)
