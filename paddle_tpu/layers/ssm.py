"""Layer DSL of the sequence mixers that are neither attention nor an RNN:
the Mamba-2 mixer (ops/ssm_ops.py), the sequence mixer of the hybrid Mamba-2
/ attention decoders (Nemotron-H and its kin), and the gated short-convolution
operator (ops/short_conv_ops.py) of the convolution / attention hybrids
(LFM2 and its kin), and Mamba-1's mixer with the gated memory unit that reads
its scan's output in a later layer (the SambaY decoders: Phi-4-mini-flash and
its kin). Beyond the 2017 reference's layer set.
"""

from __future__ import annotations

import numpy as np

from ..core.program import default_startup_program
from ..initializer import (ConstantInitializer, Initializer,
                           UniformInitializer, XavierInitializer)
from ..param_attr import ParamAttr
from .helper import LayerHelper

__all__ = ["mamba2_mixer", "short_conv_operator", "mamba1_mixer",
           "gated_memory_unit"]


class _Mamba2Init(Initializer):
    """A_log or dt_bias as the Mamba family draws them (the `mamba2_init`
    startup op)."""

    def __init__(self, kind: str):
        self.kind = kind

    def __call__(self, var, startup=None):
        b = (startup or default_startup_program()).global_block()
        b.create_var(var.name, var.shape, var.dtype, persistable=True)
        b.append_op("mamba2_init", outputs={"Out": [var.name]},
                    attrs={"shape": list(var.shape), "kind": self.kind})


def mamba2_mixer(input, num_heads: int, head_dim: int, n_groups: int,
                 state_size: int, conv_kernel: int = 4, chunk: int = 128,
                 epsilon: float = 1e-5, param_attr=None, name=None):
    """input [B, T, d] -> [B, T, d]: Mamba-2's mixer (Dao & Gu 2024), bias-
    free projections. With H = `num_heads`, P = `head_dim`, d_in = H P, G =
    `n_groups`, N = `state_size`:

        [z | xBC | dt] = h W_in           W_in [d, 2 d_in + 2 G N + H]
        xBC = silu(causal depthwise conv_K(xBC) + b_conv)  -> x, B, C
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
        out = group_rms(y * silu(z), w_n) W_out    (RMS inside each group)

    computed in chunks of `chunk` tokens (any T). Parameters: `<name>.in_w`,
    `.conv_w` [K, d_in + 2 G N], `.conv_b`, `.dt_bias`, `.A_log`, `.D` [H],
    `.norm_w` [d_in], `.out_w` [d_in, d]. A_log starts at log U(1, 16),
    dt_bias at the inverse softplus of a log-uniform draw in [0.001, 0.1], D
    and the norm's scale at one, the conv at U(+-1/sqrt(K)) with a zero bias
    (the family's initialisers); the projections keep the DSL's Glorot.
    param_attr may be a mapping {"in_w" | ... | "out_w": attr}
    (`ParamAttr.derive`): a caller's initialiser wins."""
    helper = LayerHelper("mamba2_mixer", name=name)
    d = int(input.shape[-1])
    H, P, G, N, K = (int(num_heads), int(head_dim), int(n_groups),
                     int(state_size), int(conv_kernel))
    if H % G:
        raise ValueError(f"{H} heads do not split into {G} groups")
    d_in, conv_dim = H * P, H * P + 2 * G * N

    def param(suffix, shape, init):
        return helper.create_parameter(
            ParamAttr.derive(param_attr, helper.name, suffix), shape,
            default_initializer=init)

    bound = 1.0 / np.sqrt(K)
    inputs = {
        "X": [input],
        "InW": [param("in_w", (d, 2 * d_in + 2 * G * N + H),
                      XavierInitializer())],
        "ConvW": [param("conv_w", (K, conv_dim),
                        UniformInitializer(-bound, bound))],
        "ConvB": [param("conv_b", (conv_dim,), ConstantInitializer(0.0))],
        "DtBias": [param("dt_bias", (H,), _Mamba2Init("dt_bias"))],
        "ALog": [param("A_log", (H,), _Mamba2Init("A_log"))],
        "D": [param("D", (H,), ConstantInitializer(1.0))],
        "NormW": [param("norm_w", (d_in,), ConstantInitializer(1.0))],
        "OutW": [param("out_w", (d_in, d), XavierInitializer())],
    }
    out = helper.create_tmp_variable(input.dtype, input.shape)
    helper.append_op(
        type="mamba2_mixer", inputs=inputs, outputs={"Out": [out]},
        attrs={"num_heads": H, "head_dim": P, "n_groups": G,
               "state_size": N, "epsilon": float(epsilon),
               "chunk": int(chunk)})
    return out


def short_conv_operator(input, hidden=None, kernel: int = 3, param_attr=None,
                        name=None):
    """input [B, T, d] -> [B, T, d]: a gated short convolution (LFM2's `conv`
    layers), bias-free, `hidden` wide (None: d):

        [B | C | X] = input W_in          W_in [d, 3 hidden], in that order
        c_t = sum_{k < kernel} w[k] (B * X)_{t - (kernel - 1) + k}
        out = (C * c) W_out               W_out [hidden, d]

    a causal depthwise convolution (zeros before the sequence's start, no
    activation) between two gates. Parameters: `<name>.in_w`, `.conv_w`
    [kernel, hidden], `.out_w`. The taps start at U(+-1/sqrt(kernel)) (a
    depthwise `Conv1d`'s default), the projections at the DSL's Glorot.
    param_attr may be a mapping {"in_w" | "conv_w" | "out_w": attr}
    (`ParamAttr.derive`): a caller's initialiser wins. Under amp the two
    projections run in the amp dtype and what lies between them reads and
    writes it with float32 arithmetic inside (one pass forward, one
    backward)."""
    helper = LayerHelper("short_conv_operator", name=name)
    d = int(input.shape[-1])
    width, K = d if hidden is None else int(hidden), int(kernel)
    if K < 1:
        raise ValueError(f"kernel {kernel}: at least one tap")

    def param(suffix, shape, init):
        return helper.create_parameter(
            ParamAttr.derive(param_attr, helper.name, suffix), shape,
            default_initializer=init)

    bound = 1.0 / np.sqrt(K)
    inputs = {
        "X": [input],
        "InW": [param("in_w", (d, 3 * width), XavierInitializer())],
        "ConvW": [param("conv_w", (K, width),
                        UniformInitializer(-bound, bound))],
        "OutW": [param("out_w", (width, d), XavierInitializer())],
    }
    out = helper.create_tmp_variable(input.dtype, input.shape)
    helper.append_op(type="short_conv_operator", inputs=inputs,
                     outputs={"Out": [out]})
    return out


class _Mamba1ALog(Initializer):
    """A_log[c, n] = log(n + 1) (the `mamba1_init` startup op)."""

    def __call__(self, var, startup=None):
        b = (startup or default_startup_program()).global_block()
        b.create_var(var.name, var.shape, var.dtype, persistable=True)
        b.append_op("mamba1_init", outputs={"Out": [var.name]},
                    attrs={"shape": list(var.shape)})


def mamba1_mixer(input, state_size: int = 16, conv_kernel: int = 4,
                 expand: int = 2, dt_rank=None, emit_memory: bool = False,
                 param_attr=None, name=None):
    """input [B, T, d] -> [B, T, d]: Mamba-1's mixer (Gu & Dao 2023). With
    d_in = `expand` d, N = `state_size`, K = `conv_kernel`, R = `dt_rank`
    (None: ceil(d / 16)):

        [x | z] = u W_in                  W_in [d, 2 d_in], no bias
        x = silu(causal depthwise conv_K(x) + b_conv)
        [r | B | C] = x W_x               W_x [d_in, R + 2 N], no bias
        dt = softplus(r W_dt + b_dt)      W_dt [R, d_in];  A = -exp(A_log)
        S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
        y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] x_t[c]             S_0 = 0
        out = (y * silu(z)) W_out         W_out [d_in, d], no bias

    a decay for every channel and state (`ops/ssm_ops.py:selective_scan`).
    With `emit_memory` returns (out, y): the scan's output WITH the D x term,
    in front of the gate, for the `gated_memory_unit`s of later layers.
    Parameters: `<name>.in_w`, `.conv_w` [K, d_in], `.conv_b`, `.x_w`,
    `.dt_w`, `.dt_b`, `.A_log` [d_in, N], `.D`, `.out_w`. A_log[c, n] starts
    at log(n + 1), dt_b at the inverse softplus of a log-uniform draw in
    [0.001, 0.1], W_dt at U(+-R^-0.5), D at one, the taps at U(+-1/sqrt(K))
    with a zero bias (the family's initialisers); the other projections keep
    the DSL's Glorot. param_attr may be a mapping {"in_w" | ... | "out_w":
    attr} (`ParamAttr.derive`): a caller's initialiser wins. The inner
    scopes of the op's rows: `in_proj`, `conv`, `dt_bc`, `scan`, `gate`,
    `out_proj`."""
    helper = LayerHelper("mamba1_mixer", name=name)
    d = int(input.shape[-1])
    N, K = int(state_size), int(conv_kernel)
    d_in = int(expand) * d
    R = -(-d // 16) if dt_rank is None else int(dt_rank)

    def param(suffix, shape, init):
        return helper.create_parameter(
            ParamAttr.derive(param_attr, helper.name, suffix), shape,
            default_initializer=init)

    taps, dt_bound = 1.0 / np.sqrt(K), R ** -0.5
    inputs = {
        "X": [input],
        "InW": [param("in_w", (d, 2 * d_in), XavierInitializer())],
        "ConvW": [param("conv_w", (K, d_in), UniformInitializer(-taps, taps))],
        "ConvB": [param("conv_b", (d_in,), ConstantInitializer(0.0))],
        "XW": [param("x_w", (d_in, R + 2 * N), XavierInitializer())],
        "DtW": [param("dt_w", (R, d_in),
                      UniformInitializer(-dt_bound, dt_bound))],
        "DtB": [param("dt_b", (d_in,), _Mamba2Init("dt_bias"))],
        "ALog": [param("A_log", (d_in, N), _Mamba1ALog())],
        "D": [param("D", (d_in,), ConstantInitializer(1.0))],
        "OutW": [param("out_w", (d_in, d), XavierInitializer())],
    }
    out = helper.create_tmp_variable(input.dtype, input.shape)
    memory = helper.create_tmp_variable(
        input.dtype, tuple(input.shape[:-1]) + (d_in,))
    helper.append_op(type="mamba1_mixer", inputs=inputs,
                     outputs={"Out": [out], "Memory": [memory]})
    return (out, memory) if emit_memory else out


def gated_memory_unit(input, memory, param_attr=None, name=None):
    """input [B, T, d], memory [B, T, m] -> [B, T, d]: a gated memory unit
    (Ren et al. 2025, SambaY): an earlier layer's memory, here a Mamba-1
    mixer's scan output, gated by this layer's input,

        out = (memory * silu(u W_g)) W_o     W_g [d, m], W_o [m, d], no bias

    no convolution and no scan of its own. An `fc`, a `silu_gate`, an `fc`,
    whose ops' scopes carry `<name>.gate_proj`, `<name>.gate`,
    `<name>.out_proj`. param_attr may be a mapping {"gate_w" | "out_w":
    attr}."""
    from .nn import fc, silu_gate

    helper = LayerHelper("gated_memory_unit", name=name)
    d, m = int(input.shape[-1]), int(memory.shape[-1])

    def proj(inp, scope, weight, size):
        return fc(inp, size=size, num_flatten_dims=2, bias_attr=False,
                  param_attr=ParamAttr.derive(param_attr, helper.name, weight),
                  name=f"{helper.name}.{scope}")

    gated = silu_gate(memory, proj(input, "gate_proj", "gate_w", m),
                      name=f"{helper.name}.gate")
    return proj(gated, "out_proj", "out_w", d)
