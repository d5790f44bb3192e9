"""What one training step's Mamba-1 selective scans need, for
`kernel.selective_scan_roofline`: the op `paddle_tpu/ops/ssm_ops.py:
selective_scan` at every "mamba" layer the configuration holds
(`reference.py:held_layers`), over C = `mamba_expand` x `hidden_size` channels
and N = `mamba_d_state` states:

    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n S_t[c, n] C_t[n] + D[c] x_t[c]

The count is of the op's OPERANDS AND RESULTS and of the recurrence's own
arithmetic, whatever implements it (the program's own `pt_selective_scan_bytes`
counts the same bytes, from the traced shapes):

- Bytes, each tensor once in the type the step uses: x, B, C, y, dy and their
  gradients bf16 (2 bytes), dt and its gradient float32. Forward: read x, dt,
  B, C, write y. Backward: read those and dy, write the five gradients. A [C,
  N] and D [C] float32 are read twice and their gradients written once. The
  state each chunk starts from (kept between forward and backward), the
  forward a checkpoint runs again and the lane-spread copies of B and C the
  kernels read count nothing: bytes an implementation moves, not bytes the op
  needs.
- Operations, a multiply, an add and an exp each counting one: forward 9 C N a
  token (dt A, exp, the decay times the state, dt x, times B, the add, times C,
  the sum over n, and the D x term's share), backward twice that; recomputation
  counts nothing.

THE BOUND IT READS AGAINST IS MEMORY'S: 27 C N operations against about 36 C
bytes a token, 12 operations a byte at N 16, under a v5e's 240 FLOPs a byte of
its MXU peak. But this work is elementwise and runs on the VECTOR unit, for
which `peaks.json` has no row (the bf16 peak there is the MXU's): a scan that
is bound by the vector unit reads a low share of a memory roofline it could
never reach, and the share says how far the op is from the bytes it must move,
not from the arithmetic it must do.

Sizes: `hidden_size`, `mamba_expand`, `mamba_d_state`, the count of "mamba"
among the held layers; `batch` and `seqlen` are the cell's."""

from __future__ import annotations

import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
REFERENCE = os.path.join(_HERE, os.pardir, "configs",
                         "phi-4-mini-flash-reasoning", "reference.py")


def held_kinds(config: dict):
    """The kinds of the layers the configuration holds, by `reference.py`'s
    own map."""
    spec = importlib.util.spec_from_file_location("phi4flash_kinds", REFERENCE)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return [kind for _, kind in ref.held_layers(config)]


def flops_and_bytes(config: dict, cell: dict):
    """(operations, bytes) of one step: every mixer, the whole batch."""
    C = int(config["mamba_expand"]) * int(config["hidden_size"])
    N = int(config["mamba_d_state"])
    tokens = int(cell["batch"]) * int(cell["seqlen"])
    mixers = held_kinds(config).count("mamba")
    forward = tokens * ((2 * C + 2 * N) * 2 + 4 * C)
    backward = tokens * ((4 * C + 4 * N) * 2 + 8 * C)
    bytes_ = mixers * (forward + backward + 3 * 4 * (C * N + C))
    return float(mixers * tokens * 27 * C * N), float(bytes_)
