"""Flash attention: fused block-wise attention for long sequences.

Reference lineage: the reference (2017) predates transformer attention —
its fused-kernel philosophy lives in cuda/include/hl_lstm.h:42; this is
the modern long-context analogue (SURVEY.md §5.7's "seam for future
CP/ring-attention"). XLA's unfused attention materializes the [B, H, T, T]
score matrix in HBM (16 GB at T=32k bf16 — impossible); flash attention
streams K/V blocks through VMEM with an online softmax, O(T) memory.

Compute path: on TPU, JAX's Pallas TPU flash kernel
(jax.experimental.pallas.ops.tpu.flash_attention — public JAX library
code, used the way lax.conv uses XLA) with its custom VJP; anywhere else,
the jnp reference formulation. Layout here is [B, T, H, D] (the
framework's sequence-parallel convention, parallel/ring_attention.py);
the kernel's [B, H, T, D] transpose happens at the boundary and XLA
folds it into the kernel's operand layout.

`paddle_tpu.parallel.ulysses_attention` routes its per-device full-
sequence attention through here, so the SP path gets the fused kernel
for free.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.registry import register_op

NEG_INF = -1e30  # large-finite mask fill (inf would NaN the softmax grads)


def scaled_dot_product_attention(q, k, v, causal: bool = False):
    """[B, T, H, D] attention, plain jnp — the numerical oracle for the
    flash kernel AND for ring/Ulysses sequence parallelism (re-exported
    by paddle_tpu.parallel; single implementation lives here)."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


_reference = scaled_dot_product_attention


def _shapes_flash_ok(q, k) -> bool:
    """Backend-independent shape rules (separately testable): 128-aligned
    q AND kv sequence lengths (the kernel's block divisibility — default
    blocks are 128 and clamp to the sequence), lane-aligned head dim."""
    Tq, Dq = q.shape[1], q.shape[3]
    Tk = k.shape[1]
    return Tq % 128 == 0 and Tk % 128 == 0 and Dq in (64, 128, 256)


# Dispatch policy (round 3, benchmarks/flash_block_tuning.json): with
# v5e-tuned block sizes the kernel BEATS XLA's fused attention fwd+bwd
# from T=1024 up — 1.4-1.5x at T=1-2k, 2.0x at 4k, 2.6x at 8k, 3.5x at
# 16k (the library's all-128 default blocks were why round 2 measured
# 0.59-0.71x). Below the measured window, or when the shape rules fail,
# XLA keeps the job; the score-bytes rule stays as the memory-capability
# route for shapes outside the measured-win window (XLA stops compiling
# outright around several GB of scores).
_FLASH_MIN_T = 1024
_SCORE_BYTES_THRESHOLD = 1.5e9


def _prefers_flash(q, k) -> bool:
    import numpy as np

    from . import mesh_dispatch

    B, Tq, H, _ = q.shape
    Tk = k.shape[1]
    if Tq >= _FLASH_MIN_T and Tk >= _FLASH_MIN_T:
        return True  # measured-win regime with tuned blocks
    # the shard_map'd kernel runs at the PER-SHARD batch (B/dp under a
    # mesh), so the score-buffer rule must see that batch too — same
    # eligibility discipline as the decoder/RNN kernels. local_batch
    # returns 0 when dp does not divide B; flash_attention falls back
    # to the XLA formulation for that case anyway.
    Bl = mesh_dispatch.local_batch(B)
    if Bl == 0:
        return False
    # scores inherit the input dtype in the reference formulation: f32
    # inputs double the buffer vs bf16
    itemsize = np.dtype(q.dtype).itemsize
    return Bl * H * Tq * Tk * itemsize > _SCORE_BYTES_THRESHOLD


def flash_eligible(q, k=None) -> bool:
    k = q if k is None else k
    return (
        jax.default_backend() == "tpu"
        and _shapes_flash_ok(q, k)
        and _prefers_flash(q, k)
    )


def _v5e_block_sizes(Tq: int, Tk: int, dtype=None):
    """Block choice for the TPU kernel. Consult order (tune/overrides):
    forced/tuned {block_q, block_k} for this (Tq, Tk, dtype, device) —
    validated against the shared legality predicate
    (tune/space.flash_block_legal: blocks must DIVIDE the 128-aligned
    sequence) — else the v5e-tuned analytic default
    (benchmarks/flash_block_tuning.json): 512-wide q/k blocks win up to
    T=4096, 1024 from 8192; repeated-trial medians confirm 512/512 at
    T=1024/2048 (1.4-1.5x over XLA). The default rounds its target down
    to the largest 128-multiple divisor (e.g. T=1280 → 256)."""
    import numpy as np

    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    from ..tune import overrides as tune_overrides
    from ..tune.space import flash_block_legal

    def blk(T):
        if T % 128:
            # _flash_kernel is gate-free (benchmarks call it directly);
            # without this check b would decrement to 0 and `T % 0` raise
            raise ValueError(
                f"flash kernel requires a 128-aligned sequence, got T={T}"
            )
        b = min(T, 512 if T < 8192 else 1024)
        while T % b:
            b -= 128
        return b

    qb, kb = 0, 0
    ov = tune_overrides.lookup(
        "flash_attention", {"Tq": Tq, "Tk": Tk},
        np.dtype(dtype).name if dtype is not None else "bfloat16")
    if ov is not None:
        oq = int(ov.config.get("block_q", 0))
        ok = int(ov.config.get("block_k", 0))
        if flash_block_legal(oq, ok, Tq, Tk):
            qb, kb = oq, ok
        elif ov.source in ("forced", "env"):
            import warnings

            warnings.warn(
                f"forced flash blocks q={oq} k={ok} do not divide "
                f"Tq={Tq} Tk={Tk}; using the analytic default",
                stacklevel=2)
    if not qb:
        qb, kb = blk(Tq), blk(Tk)
    return BlockSizes(
        block_q=qb, block_k_major=kb, block_k=kb, block_b=1,
        block_q_major_dkv=qb, block_k_major_dkv=kb,
        block_k_dkv=kb, block_q_dkv=qb,
        block_k_major_dq=kb, block_k_dq=kb, block_q_dq=qb,
    )


def _flash_kernel(q, k, v, causal: bool):
    """Direct fused-kernel call, no dispatch gate (benchmarks and the
    eligible path both come through here)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as _tpu_flash,
    )

    bhtd = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # noqa: E731
    o = _tpu_flash(
        bhtd(q), bhtd(k), bhtd(v), causal=causal,
        sm_scale=float(1.0 / math.sqrt(q.shape[-1])),
        block_sizes=_v5e_block_sizes(q.shape[1], k.shape[1], q.dtype),
    )
    return jnp.transpose(o, (0, 2, 1, 3))


def flash_attention(q, k, v, causal: bool = False):
    """[B, T, H, D] attention. From T=1024 the v5e-block-tuned fused
    kernel is the fast path (1.4-3.5x over XLA's fused attention fwd+bwd,
    benchmarks/flash_block_tuning.json) as well as the O(T)-memory path;
    below that window XLA keeps the job unless the score buffer would
    exceed the memory threshold. Numerics: bf16 io with f32
    online-softmax accumulation inside the kernel (matches the reference
    formulation to bf16 eps)."""
    if q.ndim != 4:
        raise ValueError(f"expected [B, T, H, D], got {q.shape}")
    if not flash_eligible(q, k):
        return _reference(q, k, v, causal)
    from . import mesh_dispatch

    am = mesh_dispatch.current()
    if am is not None and am.dp > 1:
        # mesh policy (ops/mesh_dispatch.py): a bare pallas_call cannot
        # be GSPMD-partitioned, so the kernel shard_maps over dp (batch
        # dim 0; no weights -> no cotangent psums). Under an mp axis the
        # wrap replicates heads (a resharding GSPMD inserts); sharding
        # heads over mp inside the wrap is a future multi-chip lever.
        # A batch dp does not divide falls back to the XLA formulation,
        # which GSPMD partitions natively.
        if q.shape[0] % am.dp:
            return _reference(q, k, v, causal)
        import functools

        call = mesh_dispatch.shard_batch(
            functools.partial(_flash_kernel, causal=causal),
            (0, 0, 0), ((0, 4),))
        return call(q, k, v)
    return _flash_kernel(q, k, v, causal)


@register_op("flash_attention")
def flash_attention_kernel(ctx):
    """Program-IR face of the dispatcher: Q/K/V are [B, T, E] packed
    multi-head projections; num_heads splits E. Used by
    layers.multi_head_attention (models/transformer.py)."""
    from .. import amp

    # under amp Q and K may arrive float32 (from rms_norm / rotary, which
    # emit float32); the kernel's io is the amp dtype, like V's
    q, k, v = amp.cast_inputs(ctx, ctx.input("Q"), ctx.input("K"),
                              ctx.input("V"))
    heads = ctx.attr("num_heads")
    causal = ctx.attr("causal", True)
    B, T, E = q.shape
    if E % heads:
        raise ValueError(f"hidden dim {E} not divisible by heads {heads}")
    D = E // heads
    split = lambda x: x.reshape(B, x.shape[1], heads, D)  # noqa: E731
    o = flash_attention(split(q), split(k), split(v), causal=causal)
    ctx.set_output("Out", o.reshape(B, T, E))
