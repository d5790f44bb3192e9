"""Automatic mixed precision (bf16 compute AND activations, f32 masters).

TPU analogue of the reference's half-precision support
(paddle/math/float16.h:70 and the fp16 GEMM paths in paddle/cuda): on the
MXU the fast matmul/conv datatype is bfloat16, which — unlike fp16 — keeps
fp32's exponent range, so no loss scaling is needed.

Design (v5e roofline-driven — see PERF.md): parameters, optimizer state,
batch-norm statistics and losses stay float32; MXU op *inputs* are cast to
the amp dtype AND their outputs stay in the amp dtype, so activations flow
through the network at 2 bytes/element. ResNet-scale models are
HBM-bandwidth-bound on TPU, so halving activation traffic — not the MXU
math itself — is most of AMP's win; casting each op's result back to f32
(the previous design) forfeited it. Where f32 masters meet bf16 activations
in an elementwise op (bias adds), the f32 side casts DOWN (`harmonize`),
overriding numpy's promote-to-f32 rule. Numerically-sensitive kernels
(batch_norm stats, softmax/log, losses) upcast internally and emit f32.

Enabled per-Program via `Program.set_amp("bfloat16")` after building it, or
the `pt.amp_guard()` context around the *run* calls; the executor reads the
setting at run time and threads it into the traced env under `@AMP@`,
where kernels pick it up via `cast_inputs`/`harmonize`.
"""

from __future__ import annotations

import contextlib

import jax.numpy as jnp

AMP_KEY = "@AMP@"

# --------------------------------------------------------------------------
# The dtype-policy table: which op families may drop precision.
#
# ONE place for the "may this site compute below f32?" judgment, consulted
# by BOTH precision passes — amp (bf16 compute, cast_inputs below) and the
# post-training int8 converter (quant/convert.py). Before this table the
# policy lived implicitly in which kernels called cast_inputs, and the
# quant pass would have had to re-derive (and could silently drift from)
# the batch_norm/softmax exclusions. Now a site is:
#
#   "low"    — MXU-bound, numerically tolerant: amp casts its inputs down,
#              and the quant converter may rewrite it to an int8 kernel
#              when it carries a persistable weight (LOW_PRECISION_OPS ∩
#              QUANTIZABLE_OPS);
#   "high"   — numerically sensitive (stats, exps/logs, losses): the
#              kernel upcasts internally, cast_inputs is a no-op even if
#              called, and the quant converter must leave it alone;
#   "follow" — dtype-transparent (elementwise glue, reshapes): follows
#              whatever dtype its inputs already carry via harmonize.
# --------------------------------------------------------------------------

# MXU ops whose kernels call cast_inputs: inputs drop to the amp dtype.
LOW_PRECISION_OPS = frozenset({
    "mul", "matmul", "conv2d", "conv2d_transpose", "fused_conv_bn",
    "flash_attention", "lookup_table",
    # the routed FFN's three grouped expert matmuls. Its ROUTER is the
    # exception inside the op: logits, softmax and top-k are float32 from
    # float32 inputs whatever this table says (ops/moe_ops.py:route never
    # calls cast_inputs); a bf16 router sends 3.5 % of tokens to another
    # expert set where a float32 one sends 2.0 % (ISSUE 26's experiment)
    "moe_ffn",
    # the state-space mixer's two projections and the scan's matmuls; its
    # decays and carried state are float32 inside the op (ops/ssm_ops.py)
    "mamba2_mixer",
    # the gated short-convolution operator's two projections; the gates and
    # the taps between them are float32 inside the op (ops/short_conv_ops.py)
    "short_conv_operator",
    # Mamba-1's mixer: its four projections; the taps, dt, the scan's decays
    # and state are float32 inside the op (ops/ssm_ops.py)
    "mamba1_mixer",
})

# The subset of low-precision sites the int8 converter may rewrite: dense
# weight-carrying GEMMs with a quantized lowering (ops/quant_kernels.py).
# conv2d lowers through im2col+mul in this runtime, so the mul sites are
# the conv sites too; fused_conv_bn folds BN stats and must stay fp.
QUANTIZABLE_OPS = frozenset({"mul", "matmul"})

# Numerically sensitive: upcast internally, emit f32, never quantized.
# batch_norm/softmax live HERE and only here — amp and quant both read
# this set, so the exclusions cannot drift between the two passes.
# rms_norm: float32 arithmetic always, and float32 out for every norm a
# router or a projection may read (a model's stream norms, the latent norms,
# a closing norm, a user's). The norm an attention layer marked as the LAST
# op in front of its kernel (ops/nn_ops.py: QK_EMIT_ATTR "kernel") emits the
# amp dtype instead: the float32 value rounded once, where the kernel's own
# cast_inputs rounded it before; one marked "float32" feeds a rotary, which
# does that rounding.
HIGH_PRECISION_OPS = frozenset({
    "batch_norm", "layer_norm", "softmax", "log_softmax",
    "cross_entropy", "softmax_with_cross_entropy", "mean",
    "reduce_mean", "huber_loss", "smooth_l1", "squared_l2_norm",
    "l2_normalize", "exp", "log", "rms_norm", "moe_aux_loss",
    "exit_expected_cost",
})


def precision_policy(op_type: str) -> str:
    """'low' | 'high' | 'follow' for one op type (see table above)."""
    if op_type in HIGH_PRECISION_OPS:
        return "high"
    if op_type in LOW_PRECISION_OPS:
        return "low"
    return "follow"


def cast_inputs(ctx, *arrays):
    """Cast float32 arrays to the program's amp dtype (no-op otherwise).

    Consults precision_policy: a kernel on the HIGH_PRECISION list gets
    its inputs back untouched even if it (mistakenly) calls this — the
    exclusion table, not the call site, decides who drops precision."""
    dtype = ctx.env.get(AMP_KEY)
    op = getattr(ctx, "op", None)
    if dtype is not None and op is not None \
            and precision_policy(op.type) == "high":
        dtype = None
    out = []
    for a in arrays:
        if (
            dtype is not None
            and hasattr(a, "dtype")
            and a.dtype == jnp.float32
        ):
            a = a.astype(dtype)
        out.append(a)
    return out[0] if len(out) == 1 else tuple(out)


def harmonize(ctx, x, y):
    """AMP meeting rule for binary elementwise ops: when an f32 array (a
    master-weight bias/scale) meets an amp-dtype activation, cast the f32
    side DOWN instead of numpy-promoting the activation up — otherwise one
    bias add re-materializes the whole activation at 4 bytes/element."""
    dtype = ctx.env.get(AMP_KEY)
    if dtype is None:
        return x, y
    amp_dt = jnp.dtype(dtype)
    dx = getattr(x, "dtype", None)
    dy = getattr(y, "dtype", None)
    if dx == amp_dt and dy == jnp.float32:
        y = y.astype(amp_dt)
    elif dy == amp_dt and dx == jnp.float32:
        x = x.astype(amp_dt)
    return x, y


@contextlib.contextmanager
def amp_guard(dtype: str = "bfloat16", main_program=None):
    """Enable amp on the current (or given) main program for the block.

    The flag is read at *run* time (the executor threads it into the traced
    env per compile), so wrap the `exe.run(...)` calls — or simply call
    `program.set_amp(...)` once after building. Wrapping only the layer-
    construction code would be a no-op: the guard restores the previous
    setting on exit, before any run happens."""
    from .core.program import default_main_program

    prog = main_program or default_main_program()
    prev = prog.amp_dtype
    prog.set_amp(dtype)
    try:
        yield
    finally:
        prog.set_amp(prev)
