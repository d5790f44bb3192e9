"""Op kernel registry.

Reference: paddle/framework/op_registry.h:62,148 (`OpRegistry::CreateOp`,
`REGISTER_OP`) maps op type → OperatorWithKernel with per-place kernels.
On TPU there is exactly one "place" that matters (everything is staged into
XLA), so a kernel is a pure Python function

    kernel(ctx: OpContext) -> None

that reads input values from `ctx` (jnp arrays / LoDArray pytrees), computes
with jax/jnp/pallas, and assigns outputs. Gradients come from jax.grad over
the traced program (core/executor.py), so no REGISTER_OP(grad) pairing is
needed — that entire grad-op-desc machinery (framework/backward.cc,
grad_op_desc_maker.h) collapses into one functional transform.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

_KERNELS: Dict[str, Callable] = {}
# op type -> the INPUT slots its kernel rebinds in the env (see register_op)
_WRITES: Dict[str, Any] = {}


class OpContext:
    """Execution context handed to a kernel: op descriptor + value env."""

    def __init__(self, op, env: Dict[str, Any], executor=None, block=None):
        self.op = op
        self.env = env
        self.executor = executor
        self.block = block

    # inputs ---------------------------------------------------------------
    def input(self, slot: str, idx: int = 0):
        names = self.op.inputs.get(slot, [])
        if not names:
            return None
        return self.env[names[idx]]

    def inputs(self, slot: str) -> List[Any]:
        return [self.env[n] for n in self.op.inputs.get(slot, [])]

    def has_input(self, slot: str) -> bool:
        return bool(self.op.inputs.get(slot))

    def input_name(self, slot: str, idx: int = 0) -> str:
        return self.op.inputs[slot][idx]

    # outputs --------------------------------------------------------------
    def set_output(self, slot: str, value, idx: int = 0) -> None:
        self.env[self.op.outputs[slot][idx]] = value

    def output_name(self, slot: str, idx: int = 0) -> str:
        return self.op.outputs[slot][idx]

    def has_output(self, slot: str) -> bool:
        return bool(self.op.outputs.get(slot))

    # attrs ----------------------------------------------------------------
    def attr(self, name: str, default=None):
        return self.op.attrs.get(name, default)

    # rng ------------------------------------------------------------------
    def rng(self):
        """Deterministic per-op PRNG key. The executor threads a base key

        through the env under "@RNG@"; each draw folds in a fresh counter so
        re-tracing (the abstract pass before a sparse_update program's
        differentiated one) reproduces identical randomness."""
        import jax

        key = self.env["@RNG@"]
        counter = self.env.get("@RNG_COUNTER@", 0)
        self.env["@RNG_COUNTER@"] = counter + 1
        return jax.random.fold_in(key, counter)


def register_op(type_name: str, writes=()) -> Callable:
    """Decorator: @register_op("mul") def mul_kernel(ctx): ...

    `writes` names the INPUT slots whose variables the kernel rebinds in
    the env: the reference's in-place in/out pairs (an optimizer op's
    Param and moments, batch_norm's running statistics), which the op's
    declared outputs do not show. A callable `writes(op)` returns the
    slots where they depend on the op's attrs (batch_norm writes nothing
    under is_test). The Executor donates what a program rebinds, so a
    kernel that assigns `ctx.env[<input name>]` states it here."""

    def deco(fn):
        if type_name in _KERNELS:
            raise ValueError(f"op {type_name!r} already registered")
        _KERNELS[type_name] = fn
        if writes:
            _WRITES[type_name] = writes
        return fn

    return deco


def written_names(op) -> List[str]:
    """Every variable name `op` may rebind: its declared outputs plus the
    input slots its kernel registered as written."""
    names = op.output_names()
    slots = _WRITES.get(op.type, ())
    if callable(slots):
        slots = slots(op)
    for slot in slots:
        names.extend(op.inputs.get(slot, ()))
    return names


def get_kernel(type_name: str) -> Callable:
    try:
        return _KERNELS[type_name]
    except KeyError:
        raise NotImplementedError(
            f"No kernel registered for op {type_name!r}; registered: "
            f"{sorted(_KERNELS)}"
        ) from None


def registered_ops() -> List[str]:
    return sorted(_KERNELS)
