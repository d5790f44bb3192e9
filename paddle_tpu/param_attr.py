"""ParamAttr: per-parameter configuration.

Reference: python/paddle/v2/fluid/param_attr.py — name, initializer,
learning_rate multiplier, regularizer, trainable, gradient clip; same fields
here, consumed by LayerHelper.create_parameter (layers/helper.py). The
`update_hooks` field carries the Gen-1 ParameterAttribute(update_hooks=...)
seam (trainer_config_helpers/attrs.py HookAttribute →
paddle/parameter/ParameterUpdaterHook.cpp).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional


@dataclass
class StaticPruningHook:
    """Mask-based static sparsity maintained across optimizer updates.

    Reference: paddle/parameter/ParameterUpdaterHook.cpp:39
    (StaticPruningHook: `generateMask` sorts |w| at init time and zeroes
    the smallest `sparsity_ratio` fraction; `update()` re-applies the mask
    after every optimizer step so pruned weights stay zero). TPU design:
    the mask is a persistable `<param>@PRUNE_MASK` variable computed by a
    startup-program op from the freshly initialized weights, and an
    `apply_mask` op appended to the optimizer slice multiplies it back in
    each step — everything stays inside the jitted train step.
    """

    sparsity_ratio: float = 0.8

    def mask_name(self, param) -> str:
        return f"{param.name}@PRUNE_MASK"

    def append_startup(self, param, main_block, startup_program) -> None:
        """Create the mask variable and its init op (runs after the
        param's initializer op in the startup program)."""
        mask = main_block.create_var(
            self.mask_name(param), tuple(param.shape), param.dtype,
            persistable=True,
        )
        sb = startup_program.global_block()
        sb.create_var(mask.name, tuple(param.shape), param.dtype,
                      persistable=True)
        sb.append_op(
            "prune_mask_init",
            inputs={"Param": [param.name]},
            outputs={"Out": [mask.name]},
            attrs={"sparsity_ratio": float(self.sparsity_ratio)},
        )
        # Reference StaticPruningHook::init masks the param immediately
        # after generateMask (paraVec->dotMul(maskVec_)); without this the
        # first forward runs unpruned until the first optimizer step.
        sb.append_op(
            "apply_mask",
            inputs={"Param": [param.name], "Mask": [mask.name]},
            outputs={"ParamOut": [param.name]},
        )

    def append_update(self, helper, param) -> None:
        mask = helper.main_program.global_block().var(self.mask_name(param))
        helper.append_op(
            type="apply_mask",
            inputs={"Param": [param], "Mask": [mask]},
            outputs={"ParamOut": [param]},
        )


@dataclass
class ParamAttr:
    name: Optional[str] = None
    initializer: Any = None
    learning_rate: float = 1.0
    regularizer: Any = None
    trainable: bool = True
    gradient_clip: Any = None
    update_hooks: Optional[List[Any]] = None

    @staticmethod
    def derive(attr, base_default: str, suffix: str):
        """Per-weight attr for multi-parameter layers (MHA projections,
        stacked_lstm2 weights): keep every field of a caller-supplied
        attr but derive a distinct `{base}.{suffix}` name — passing the
        attr through unchanged would tie the weights into ONE shared
        parameter. attr=None derives from `base_default`; attr=False
        passes through (explicit "no parameter"). A mapping {suffix: attr}
        gives single weights of the layer an attr of their own (an
        initialiser for the one matrix that writes to a residual stream);
        a suffix it does not name gets the default."""
        import dataclasses

        if isinstance(attr, dict):
            attr = attr.get(suffix)
        if attr is None:
            return ParamAttr(name=f"{base_default}.{suffix}")
        if attr is False:
            return False
        attr = ParamAttr.to_attr(attr)
        base = attr.name or base_default
        return dataclasses.replace(attr, name=f"{base}.{suffix}")

    @staticmethod
    def to_attr(arg) -> "ParamAttr":
        if arg is None:
            return ParamAttr()
        if isinstance(arg, ParamAttr):
            return arg
        if isinstance(arg, str):
            return ParamAttr(name=arg)
        if isinstance(arg, (list, tuple)):
            return [ParamAttr.to_attr(a) for a in arg]
        if arg is False:
            return False  # explicit "no parameter" (e.g. bias_attr=False)
        raise TypeError(f"cannot convert {arg!r} to ParamAttr")
