"""The four wrong programs the Phi-4-mini-flash comparison has to catch (ISSUE
57, 7 (c)): each is `paddle_tpu` with one function or constant replaced, so
that the Program, the weights and the reference stay what they are and only
the arithmetic under test is wrong. `tests/test_phi4flash.py` applies each at
the small size and expects the comparison with `tests/phi4flash_reference.py`
to fail; on the chip the same replacements run under `chipbench/run.py` at the
published widths (PERF.md section 6, PR 57, has what caught each).

    memory_after_gate  the mixer hands on y * silu(z), its scan output BEHIND
                       the gate, where the memory units read y in front of it
    lam_zero           lam is left at zero: the second softmax is dropped
    no_window          the windowed layers attend to every earlier key
    bf16_state         the scan's state is carried from chunk to chunk in bf16
"""

import contextlib

import jax.numpy as jnp

CONTROLS = ("memory_after_gate", "lam_zero", "no_window", "bf16_state")


@contextlib.contextmanager
def applied(name: str):
    """`paddle_tpu` with the control `name` in place."""
    from paddle_tpu.ops import flash_ops, ssm_ops

    if name == "memory_after_gate":
        module, attr = ssm_ops, "mamba1_mixer"
        right = ssm_ops.mamba1_mixer

        def wrong(h, in_w, *rest, **kw):
            out, y = right(h, in_w, *rest, **kw)
            z = jnp.dot(h.astype(in_w.dtype), in_w,
                        preferred_element_type=jnp.float32)[..., y.shape[-1]:]
            return out, ssm_ops.silu_gate(y, z.astype(y.dtype))
    elif name == "lam_zero":
        module, attr = flash_ops, "diff_lambda"
        right = flash_ops.diff_lambda

        def wrong(lq1, lk1, lq2, lk2, lam_init):
            return 0.0 * right(lq1, lk1, lq2, lk2, lam_init)
    elif name == "no_window":
        module, attr = flash_ops, "flash_attention"
        right = flash_ops.flash_attention

        def wrong(q, k, v, causal=False, window=0, **kw):
            return right(q, k, v, causal=causal, window=0, **kw)
    elif name == "bf16_state":
        module, attr = ssm_ops, "_CARRY_DTYPE"
        right, wrong = ssm_ops._CARRY_DTYPE, jnp.bfloat16
    else:
        raise ValueError(f"unknown control {name!r}: one of {CONTROLS}")
    setattr(module, attr, wrong)
    try:
        yield
    finally:
        setattr(module, attr, right)
