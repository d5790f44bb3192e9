"""The four wrong programs the LFM2 comparison has to catch (ISSUE 49, 7 (c)):
each is `paddle_tpu` with one function replaced, so that the Program, the
weights and the reference stay what they are and only the arithmetic under
test is wrong. `tests/test_lfm2_moe.py` applies each at the small size and
expects the comparison with `tests/lfm2_moe_reference.py` to fail; on the chip
the same replacements run under `chipbench/run.py` at the published widths
(PERF.md section 6, PR 49, has what caught each).

    taps_reversed   the convolution runs from the sequence's end: tap K - 1
                    reads the token itself and tap k position t + (K - 1) - k
                    (a tap that reads t + 1: not causal)
    no_b_gate       B is left out: c = conv(X), not conv(B * X)
    keys_unrotated  the K heads skip the rotary (Q still turns)
    bf16_router     the router's input and weight are rounded to bf16
"""

import contextlib

import jax.numpy as jnp

CONTROLS = ("taps_reversed", "no_b_gate", "keys_unrotated", "bf16_router")


@contextlib.contextmanager
def applied(name: str, kv_heads: int):
    """`paddle_tpu` with the control `name` in place; `kv_heads` tells the K
    heads' rotary from the Q heads' (the counts differ)."""
    from paddle_tpu.ops import moe_ops, nn_ops, short_conv_ops

    if name in ("taps_reversed", "no_b_gate"):
        module, attr = short_conv_ops, "gated_short_conv"
        right = short_conv_ops.gated_short_conv
        if name == "taps_reversed":
            def wrong(bcx, w):
                return right(bcx[:, ::-1], w)[:, ::-1]
        else:
            def wrong(bcx, w):
                d = w.shape[1]
                return right(bcx.at[..., :d].set(1.0), w)
    elif name == "keys_unrotated":
        module, attr = nn_ops, "qk_assemble"
        right = nn_ops.qk_assemble

        def wrong(x, scale, eps, whole, theta, R, out_dtype, *rest):
            if x.shape[2] == kv_heads:
                theta = None
            return right(x, scale, eps, whole, theta, R, out_dtype, *rest)
    elif name == "bf16_router":
        module, attr = moe_ops, "route"
        right = moe_ops.route

        def wrong(x, router_w, *args, **kw):
            return right(x.astype(jnp.bfloat16).astype(jnp.float32),
                         router_w.astype(jnp.bfloat16).astype(jnp.float32),
                         *args, **kw)
    else:
        raise ValueError(f"unknown control {name!r}: one of {CONTROLS}")
    setattr(module, attr, wrong)
    try:
        yield
    finally:
        setattr(module, attr, right)
