"""Quantized-matmul op kernels: int8×int8→int32 tiled GEMM + dequant.

The low-precision serving fast path (ROADMAP item 2): serving is
bandwidth-bound well below the MXU ceiling, so the win is BYTES — int8
weights stream at 1 B/elem (vs 2 bf16 / 4 f32) and the activation side
quantizes on the fly against a CALIBRATED per-tensor scale, so the MXU
sees an int8×int8 contraction accumulating in int32 with the dequantize
epilogue (`acc * (sx * sw[col])`) fused into the same kernel.

Two lowerings, one legality model:

- `_quant_matmul_pallas`: the TPU Pallas kernel — (block_m, block_n)
  output tiles over a full-K panel, int8 io tiles, int32 accumulator,
  per-column f32 scale epilogue. Tile legality (int8's (32, 128)
  minimum tile, divide-the-array, VMEM working set) and the default
  tile live in tune/space.py `quant_matmul_*`;
- `_quant_matmul_ref`: the jnp reference (CPU/correctness) — an exact
  int32 contraction via dot_general, bit-identical math to the tile
  kernel since integer adds are associative (no float reorder hazard).

The dispatch asks tune/space.pick for its tile like the other fused
kernels, and is a HOT PATH under the zero-cost lint
(tests/test_quant.py): no per-call scale
recomputation, no host syncs — scales arrive as traced arrays/attrs
computed once at convert time (quant/convert.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op

INT8_MAX = 127.0


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------ lowerings --
def _quant_matmul_ref(xq, wq):
    """Reference int8×int8→int32 contraction (exact; any backend)."""
    return jax.lax.dot_general(
        xq, wq, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def _qmm_kernel(x_ref, w_ref, out_ref):
    out_ref[:, :] = jax.lax.dot_general(
        x_ref[:, :], w_ref[:, :], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _quant_matmul_pallas(xq, wq, block_m: int, block_n: int):
    """Tiled int8 GEMM: grid over (M/block_m, N/block_n) output tiles,
    each tile contracting a full-K int8 panel into an int32 block."""
    from jax.experimental import pallas as pl

    M, K = xq.shape
    _, N = wq.shape
    grid = (M // block_m, N // block_n)
    return pl.pallas_call(
        _qmm_kernel,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j: (i, j)),
        interpret=_interpret(),
    )(xq, wq)


def quant_matmul(xq, wq):
    """int8 [M, K] × int8 [K, N] → int32 [M, N] at the tile
    tune/space.py picks for the shape (one pick per TRACE: the jit
    cache makes it per-shape, not per-call); a shape with no legal tile
    falls back to the reference contraction (XLA handles it)."""
    from ..tune import space

    M, K = xq.shape
    _, N = wq.shape
    cfg = space.pick("quant_matmul",
                     {"M": int(M), "K": int(K), "N": int(N)}, "int8")
    if cfg is None:
        return _quant_matmul_ref(xq, wq)
    return _quant_matmul_pallas(xq, wq, int(cfg["block_m"]),
                                int(cfg["block_n"]))


# ---------------------------------------------------------------- ops ----
def _dequant_epilogue(acc, x_scale, w_scale, out_dtype):
    """acc int32 [M, N] → float [M, N]: one fused scale per column."""
    return (acc.astype(jnp.float32)
            * (x_scale * w_scale)[None, :]).astype(out_dtype)


def _quantize_act(x, x_scale):
    """Activation fake-int8: round/clip against the CALIBRATED scale
    (an attr baked at convert time — never recomputed per call)."""
    xf = x.astype(jnp.float32)
    return jnp.clip(jnp.round(xf / x_scale), -INT8_MAX,
                    INT8_MAX).astype(jnp.int8)


@register_op("quantized_mul")
def quantized_mul_kernel(ctx):
    """The int8 rewrite of `mul` (quant/convert.py): X stays a float
    activation and quantizes on the fly against the calibration-time
    `x_scale` attr; Y is the int8 weight payload; Scale is the
    per-output-channel f32 weight scale var. Emits the compute dtype
    (bf16 under amp, f32 otherwise) so downstream unquantized ops see
    exactly what the fp program would hand them.

    HOT PATH (zero-cost lint): every scale here is a traced array or a
    python float attr — no absmax recomputation, no numpy, no .item().
    """
    from .. import amp

    x = ctx.input("X")
    wq = ctx.input("Y")
    w_scale = ctx.input("Scale")
    x_scale = ctx.attr("x_scale", 1.0)
    xd = ctx.attr("x_num_col_dims", 1)
    xs = x.shape
    x2 = x.reshape((int(np.prod(xs[:xd])), -1)) \
        if x.ndim > 2 or xd != 1 else x
    xq = _quantize_act(x2, x_scale)
    acc = quant_matmul(xq, wq)
    amp_dt = ctx.env.get(amp.AMP_KEY)
    out_dtype = jnp.dtype(amp_dt) if amp_dt is not None else jnp.float32
    out = _dequant_epilogue(acc, jnp.float32(x_scale), w_scale, out_dtype)
    out_shape = tuple(xs[:xd]) + (wq.shape[1],)
    if out.shape != out_shape:
        out = out.reshape(out_shape)
    ctx.set_output("Out", out)


@register_op("quantized_matmul")
def quantized_matmul_kernel(ctx):
    """The int8 rewrite of 2-D `matmul` sites whose Y is a persistable
    weight (transpose handled at convert time by transposing the stored
    int8 payload, so the runtime contraction is always [M,K]x[K,N])."""
    from .. import amp

    x = ctx.input("X")
    wq = ctx.input("Y")
    w_scale = ctx.input("Scale")
    x_scale = ctx.attr("x_scale", 1.0)
    xq = _quantize_act(x, x_scale)
    acc = quant_matmul(xq, wq)
    amp_dt = ctx.env.get(amp.AMP_KEY)
    out_dtype = jnp.dtype(amp_dt) if amp_dt is not None else jnp.float32
    ctx.set_output("Out", _dequant_epilogue(
        acc, jnp.float32(x_scale), w_scale, out_dtype))


# ------------------------------------------------- convert-time helpers --
def quantize_weight(w: np.ndarray):
    """Per-output-channel symmetric int8 quantization of a [K, N]
    weight: returns (int8 payload, f32 per-column scale [N]). Runs ONCE
    at convert time (quant/convert.py) — never on the dispatch path."""
    w = np.asarray(w, np.float32)
    absmax = np.max(np.abs(w), axis=0)
    scale = np.where(absmax > 0, absmax / INT8_MAX, 1.0).astype(np.float32)
    q = np.clip(np.round(w / scale[None, :]), -INT8_MAX,
                INT8_MAX).astype(np.int8)
    return q, scale


def act_scale(absmax: float) -> float:
    """Calibrated activation scale from a recorded absmax range."""
    return float(absmax) / INT8_MAX if absmax > 0 else 1.0
