"""Optimizer front-end: builds backward + update ops into the program.

Reference: python/paddle/v2/fluid/optimizer.py — Optimizer.minimize(:204)
appends backward ops then per-parameter update ops, managing accumulator
state; subclasses SGD/Momentum/Adagrad/Adam/Adamax/DecayedAdagrad (:228-528).
Gen-1 equivalents: paddle/parameter/FirstOrderOptimizer.h (9 optimizer
classes), OptimizerWithGradientClipping (:346), AverageOptimizer
(AverageOptimizer.h) and LearningRateScheduler (LearningRateScheduler.cpp).

All of those capabilities live here: 9+ optimizers, L1/L2 regularization
(regularizer.py), value/norm/global-norm gradient clipping, LR schedules,
and ModelAverage. State (moments, lr, step) is made of persistable vars so
checkpointing captures the full training state.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.backward import append_backward
from ..core.program import (
    Variable,
    default_main_program,
    default_startup_program,
    unique_name,
)
from ..initializer import ConstantInitializer
from ..layers.helper import LayerHelper

__all__ = [
    "SGD",
    "Momentum",
    "Adagrad",
    "Adadelta",
    "RMSProp",
    "DecayedAdagrad",
    "Adam",
    "Adamax",
    "Ftrl",
    "SGDOptimizer",
    "MomentumOptimizer",
    "AdagradOptimizer",
    "AdadeltaOptimizer",
    "RMSPropOptimizer",
    "DecayedAdagradOptimizer",
    "AdamOptimizer",
    "AdamaxOptimizer",
    "FtrlOptimizer",
    "GradientClipByValue",
    "GradientClipByNorm",
    "GradientClipByGlobalNorm",
    "ExponentialDecay",
    "NaturalExpDecay",
    "InverseTimeDecay",
    "PolynomialDecay",
    "PiecewiseDecay",
    "ModelAverage",
]


# ---------------------------------------------------------- LR schedules ---
class LRSchedule:
    """Reference: Gen-1 LearningRateScheduler.cpp policies ('exp', 'poly',

    'discexp', 'linear', 'pass_manual') and fluid learning-rate decay."""

    def __call__(self, step, base_lr):
        raise NotImplementedError


class ExponentialDecay(LRSchedule):
    def __init__(self, decay_steps, decay_rate, staircase=False):
        self.decay_steps, self.decay_rate, self.staircase = (
            decay_steps,
            decay_rate,
            staircase,
        )

    def __call__(self, step, base_lr):
        import jax.numpy as jnp

        p = step / self.decay_steps
        if self.staircase:
            p = jnp.floor(p)
        return base_lr * jnp.power(self.decay_rate, p)


class NaturalExpDecay(LRSchedule):
    def __init__(self, decay_steps, decay_rate, staircase=False):
        self.decay_steps, self.decay_rate, self.staircase = (
            decay_steps,
            decay_rate,
            staircase,
        )

    def __call__(self, step, base_lr):
        import jax.numpy as jnp

        p = step / self.decay_steps
        if self.staircase:
            p = jnp.floor(p)
        return base_lr * jnp.exp(-self.decay_rate * p)


class InverseTimeDecay(LRSchedule):
    def __init__(self, decay_steps, decay_rate, staircase=False):
        self.decay_steps, self.decay_rate, self.staircase = (
            decay_steps,
            decay_rate,
            staircase,
        )

    def __call__(self, step, base_lr):
        import jax.numpy as jnp

        p = step / self.decay_steps
        if self.staircase:
            p = jnp.floor(p)
        return base_lr / (1.0 + self.decay_rate * p)


class PolynomialDecay(LRSchedule):
    def __init__(self, decay_steps, end_learning_rate=1e-4, power=1.0, cycle=False):
        self.decay_steps = decay_steps
        self.end_lr = end_learning_rate
        self.power = power
        self.cycle = cycle

    def __call__(self, step, base_lr):
        import jax.numpy as jnp

        if self.cycle:
            div = jnp.maximum(jnp.ceil(step / self.decay_steps), 1.0)
            decay_steps = div * self.decay_steps
        else:
            decay_steps = self.decay_steps
            step = jnp.minimum(step, decay_steps)
        frac = jnp.power(1.0 - step / decay_steps, self.power)
        return (base_lr - self.end_lr) * frac + self.end_lr


class PiecewiseDecay(LRSchedule):
    def __init__(self, boundaries: Sequence[int], values: Sequence[float]):
        assert len(values) == len(boundaries) + 1
        self.boundaries, self.values = list(boundaries), list(values)

    def __call__(self, step, base_lr):
        import jax.numpy as jnp

        lr = jnp.asarray(self.values[-1], jnp.float32)
        for b, v in zip(reversed(self.boundaries), reversed(self.values[:-1])):
            lr = jnp.where(step < b, v, lr)
        return lr


# ------------------------------------------------------ gradient clipping --
class GradientClipByValue:
    """Reference: fluid clip.py ClipByValue."""

    def __init__(self, max, min=None):
        self.max = max
        self.min = -max if min is None else min

    def apply_one(self, helper: LayerHelper, param, grad):
        out = helper.create_tmp_variable(grad.dtype, grad.shape)
        helper.append_op(
            type="clip", inputs={"X": [grad]}, outputs={"Out": [out]},
            attrs={"min": self.min, "max": self.max},
        )
        return out


class GradientClipByNorm:
    def __init__(self, clip_norm):
        self.clip_norm = clip_norm

    def apply_one(self, helper, param, grad):
        out = helper.create_tmp_variable(grad.dtype, grad.shape)
        helper.append_op(
            type="clip_by_norm", inputs={"X": [grad]}, outputs={"Out": [out]},
            attrs={"max_norm": self.clip_norm},
        )
        return out


class GradientClipByGlobalNorm:
    def __init__(self, clip_norm):
        self.clip_norm = clip_norm

    def apply_all(self, helper, params_grads):
        grads = [g for _, g in params_grads]
        outs = [helper.create_tmp_variable(g.dtype, g.shape) for g in grads]
        helper.append_op(
            type="clip_by_global_norm",
            inputs={"X": grads},
            outputs={"Out": outs},
            attrs={"max_global_norm": self.clip_norm},
        )
        return [(p, o) for (p, _), o in zip(params_grads, outs)]


# -------------------------------------------------------------- Optimizer --
class Optimizer:
    op_type: str = ""

    def __init__(
        self,
        learning_rate: float = 0.001,
        regularization=None,
        grad_clip=None,
        lr_schedule: Optional[LRSchedule] = None,
        name: Optional[str] = None,
    ):
        self.base_lr = learning_rate
        self.regularization = regularization
        self.grad_clip = grad_clip
        self.lr_schedule = lr_schedule
        self.name = name or unique_name(self.op_type or "opt")
        self._accumulators: Dict[str, Dict[str, Variable]] = {}

    # -- state helpers ---------------------------------------------------
    def _add_accumulator(self, helper, name, param, fill=0.0, shape=None):
        acc_name = f"{self.name}.{name}.{param.name}"
        shape = shape if shape is not None else param.shape
        acc = helper.main_program.global_block().create_var(
            acc_name, tuple(shape), param.dtype, persistable=True
        )
        # marks the var as shardable optimizer state (ZeRO-style, see
        # parallel/data_parallel.py shard_optimizer_state)
        acc.is_optimizer_state = True
        # a param with its own sharding (e.g. mp-sharded embedding) passes
        # it to same-shaped accumulators — state stays co-located with the
        # param instead of being re-sharded over dp every step
        pspec = getattr(param, "sharding", None)
        if pspec is not None and tuple(shape) == tuple(param.shape):
            acc.sharding = pspec
        ConstantInitializer(fill)(acc, helper.startup_program)
        self._accumulators.setdefault(name, {})[param.name] = acc
        return acc

    def _lr_var(self, helper) -> Variable:
        """Create the (possibly scheduled) learning-rate variable + step."""
        block = helper.main_program.global_block()
        if self.lr_schedule is not None:
            step = block.create_var(
                f"{self.name}.step", (), np.float32, persistable=True
            )
            ConstantInitializer(0.0)(step, helper.startup_program)
            helper.append_op(
                type="increment", inputs={"X": [step]},
                outputs={"Out": [step]}, attrs={"step": 1.0},
            )
            sched_lr = helper.create_tmp_variable(np.float32, ())
            helper.append_op(
                type="lr_schedule",
                inputs={"Step": [step]},
                outputs={"Out": [sched_lr]},
                attrs={"schedule": self.lr_schedule, "base_lr": self.base_lr},
            )
            return sched_lr
        lr = block.create_var(f"{self.name}.lr", (), np.float32, persistable=True)
        ConstantInitializer(self.base_lr)(lr, helper.startup_program)
        return lr

    # -- per-optimizer hooks ---------------------------------------------
    def _create_accumulators(self, helper, params):
        pass

    def _append_update_op(self, helper, param, grad, lr):
        raise NotImplementedError

    # -- main entry -------------------------------------------------------
    def minimize(
        self,
        loss: Variable,
        startup_program=None,
        parameter_list=None,
        no_grad_set=None,
    ) -> List[Tuple[Variable, Variable]]:
        helper = LayerHelper(
            self.name,
            main_program=loss.block.program,
            startup_program=startup_program or default_startup_program(),
        )
        block = loss.block.program.global_block()
        opt_pass_start = len(block.ops)
        params_grads = append_backward(loss, parameter_list, no_grad_set)

        # regularization: grad += decay(param)  (fluid regularizer.py).
        # sparse_update params skip it: decay over the whole table would
        # densify the SelectedRows grad and defeat the row-wise update
        # (the reference's sparse remote updater likewise applies no decay
        # trainer-side — RemoteParameterUpdater.h:265)
        new_pg = []
        for p, g in params_grads:
            reg = p.regularizer or self.regularization
            if reg is not None and not getattr(p, "sparse_update", False):
                g = reg.append_decay(p, g)
            new_pg.append((p, g))
        params_grads = new_pg

        # clipping (fluid clip.py; Gen-1 OptimizerWithGradientClipping).
        # sparse_update grads pass through unclipped (same densification
        # rationale as regularization above)
        def _dense_pg():
            return [pg for pg in params_grads
                    if not getattr(pg[0], "sparse_update", False)]

        def _sparse_pg():
            return [pg for pg in params_grads
                    if getattr(pg[0], "sparse_update", False)]

        if isinstance(self.grad_clip, GradientClipByGlobalNorm):
            params_grads = (
                self.grad_clip.apply_all(helper, _dense_pg()) + _sparse_pg()
            )
        elif self.grad_clip is not None:
            params_grads = [
                (p, g) if getattr(p, "sparse_update", False)
                else (p, self.grad_clip.apply_one(helper, p, g))
                for p, g in params_grads
            ]
        else:
            pg2 = []
            for p, g in params_grads:
                if p.grad_clip is not None and \
                        not getattr(p, "sparse_update", False):
                    if isinstance(p.grad_clip, GradientClipByGlobalNorm):
                        raise ValueError(
                            "per-param global-norm clip unsupported; set it on the optimizer"
                        )
                    g = p.grad_clip.apply_one(helper, p, g)
                pg2.append((p, g))
            params_grads = pg2

        lr = self._lr_var(helper)
        self._create_accumulators(helper, [p for p, _ in params_grads])
        for p, g in params_grads:
            plr = lr
            mult = p.optimize_attr.get("learning_rate", 1.0)
            if mult != 1.0:
                plr = helper.create_tmp_variable(np.float32, ())
                helper.append_op(
                    type="scale", inputs={"X": [lr]}, outputs={"Out": [plr]},
                    attrs={"scale": mult},
                )
            self._append_update_op(helper, p, g, plr)
            # ParameterUpdaterHook (Gen-1 update_hooks, e.g. static
            # pruning): runs after the update so masked weights stay
            # masked whatever the optimizer wrote
            for hook in getattr(p, "update_hooks", None) or []:
                hook.append_update(helper, p)
        # mark the backward+update slice so io._prune_for_inference and
        # Program test-clones can drop it wholesale (fluid marks these with
        # op_role=Optimize; same idea)
        for op in block.ops[opt_pass_start:]:
            op.attrs["is_optimizer_op"] = True
        return params_grads


class SGDOptimizer(Optimizer):
    op_type = "sgd"

    def _append_update_op(self, helper, param, grad, lr):
        helper.append_op(
            type="sgd",
            inputs={"Param": [param], "Grad": [grad], "LearningRate": [lr]},
            outputs={"ParamOut": [param]},
        )


class MomentumOptimizer(Optimizer):
    op_type = "momentum"

    def __init__(self, learning_rate=0.001, momentum=0.9, use_nesterov=False, **kw):
        super().__init__(learning_rate, **kw)
        self.momentum = momentum
        self.use_nesterov = use_nesterov

    def _create_accumulators(self, helper, params):
        for p in params:
            self._add_accumulator(helper, "velocity", p)

    def _append_update_op(self, helper, param, grad, lr):
        v = self._accumulators["velocity"][param.name]
        helper.append_op(
            type="momentum",
            inputs={"Param": [param], "Grad": [grad], "Velocity": [v],
                    "LearningRate": [lr]},
            outputs={"ParamOut": [param], "VelocityOut": [v]},
            attrs={"mu": self.momentum, "use_nesterov": self.use_nesterov},
        )


class AdagradOptimizer(Optimizer):
    op_type = "adagrad"

    def __init__(self, learning_rate=0.001, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self.epsilon = epsilon

    def _create_accumulators(self, helper, params):
        for p in params:
            self._add_accumulator(helper, "moment", p)

    def _append_update_op(self, helper, param, grad, lr):
        m = self._accumulators["moment"][param.name]
        helper.append_op(
            type="adagrad",
            inputs={"Param": [param], "Grad": [grad], "Moment": [m],
                    "LearningRate": [lr]},
            outputs={"ParamOut": [param], "MomentOut": [m]},
            attrs={"epsilon": self.epsilon},
        )


class AdadeltaOptimizer(Optimizer):
    op_type = "adadelta"

    def __init__(self, learning_rate=1.0, rho=0.95, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self.rho, self.epsilon = rho, epsilon

    def _create_accumulators(self, helper, params):
        for p in params:
            self._add_accumulator(helper, "avg_squared_grad", p)
            self._add_accumulator(helper, "avg_squared_update", p)

    def _append_update_op(self, helper, param, grad, lr):
        g2 = self._accumulators["avg_squared_grad"][param.name]
        u2 = self._accumulators["avg_squared_update"][param.name]
        helper.append_op(
            type="adadelta",
            inputs={"Param": [param], "Grad": [grad],
                    "AvgSquaredGrad": [g2], "AvgSquaredUpdate": [u2],
                    "LearningRate": [lr]},
            outputs={"ParamOut": [param]},
            attrs={"rho": self.rho, "epsilon": self.epsilon},
        )


class RMSPropOptimizer(Optimizer):
    op_type = "rmsprop"

    def __init__(self, learning_rate=0.001, decay=0.95, momentum=0.0, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self.decay, self.momentum, self.epsilon = decay, momentum, epsilon

    def _create_accumulators(self, helper, params):
        for p in params:
            self._add_accumulator(helper, "mean_square", p)
            self._add_accumulator(helper, "moment", p)

    def _append_update_op(self, helper, param, grad, lr):
        ms = self._accumulators["mean_square"][param.name]
        mom = self._accumulators["moment"][param.name]
        helper.append_op(
            type="rmsprop",
            inputs={"Param": [param], "Grad": [grad], "MeanSquare": [ms],
                    "Moment": [mom], "LearningRate": [lr]},
            outputs={"ParamOut": [param]},
            attrs={"decay": self.decay, "momentum": self.momentum,
                   "epsilon": self.epsilon},
        )


class DecayedAdagradOptimizer(Optimizer):
    op_type = "decayed_adagrad"

    def __init__(self, learning_rate=0.001, decay=0.95, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self.decay, self.epsilon = decay, epsilon

    def _create_accumulators(self, helper, params):
        for p in params:
            self._add_accumulator(helper, "moment", p)

    def _append_update_op(self, helper, param, grad, lr):
        m = self._accumulators["moment"][param.name]
        helper.append_op(
            type="decayed_adagrad",
            inputs={"Param": [param], "Grad": [grad], "Moment": [m],
                    "LearningRate": [lr]},
            outputs={"ParamOut": [param]},
            attrs={"decay": self.decay, "epsilon": self.epsilon},
        )


class AdamOptimizer(Optimizer):
    op_type = "adam"

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, **kw):
        super().__init__(learning_rate, **kw)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, helper, params):
        for p in params:
            self._add_accumulator(helper, "moment1", p)
            self._add_accumulator(helper, "moment2", p)
            self._add_accumulator(helper, "beta1_pow", p, fill=self.beta1, shape=())
            self._add_accumulator(helper, "beta2_pow", p, fill=self.beta2, shape=())

    def _append_update_op(self, helper, param, grad, lr):
        a = self._accumulators
        helper.append_op(
            type="adam",
            inputs={
                "Param": [param], "Grad": [grad], "LearningRate": [lr],
                "Moment1": [a["moment1"][param.name]],
                "Moment2": [a["moment2"][param.name]],
                "Beta1Pow": [a["beta1_pow"][param.name]],
                "Beta2Pow": [a["beta2_pow"][param.name]],
            },
            outputs={"ParamOut": [param]},
            attrs={"beta1": self.beta1, "beta2": self.beta2,
                   "epsilon": self.epsilon},
        )


class AdamaxOptimizer(Optimizer):
    op_type = "adamax"

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, **kw):
        super().__init__(learning_rate, **kw)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, helper, params):
        for p in params:
            self._add_accumulator(helper, "moment", p)
            self._add_accumulator(helper, "inf_norm", p)
            self._add_accumulator(helper, "beta1_pow", p, fill=self.beta1, shape=())

    def _append_update_op(self, helper, param, grad, lr):
        a = self._accumulators
        helper.append_op(
            type="adamax",
            inputs={
                "Param": [param], "Grad": [grad], "LearningRate": [lr],
                "Moment": [a["moment"][param.name]],
                "InfNorm": [a["inf_norm"][param.name]],
                "Beta1Pow": [a["beta1_pow"][param.name]],
            },
            outputs={"ParamOut": [param]},
            attrs={"beta1": self.beta1, "beta2": self.beta2,
                   "epsilon": self.epsilon},
        )


class FtrlOptimizer(Optimizer):
    op_type = "ftrl"

    def __init__(self, learning_rate=0.001, l1=0.0, l2=0.0, lr_power=-0.5, **kw):
        super().__init__(learning_rate, **kw)
        self.l1, self.l2, self.lr_power = l1, l2, lr_power

    def _create_accumulators(self, helper, params):
        for p in params:
            self._add_accumulator(helper, "squared", p)
            self._add_accumulator(helper, "linear", p)

    def _append_update_op(self, helper, param, grad, lr):
        a = self._accumulators
        helper.append_op(
            type="ftrl",
            inputs={
                "Param": [param], "Grad": [grad], "LearningRate": [lr],
                "SquaredAccumulator": [a["squared"][param.name]],
                "LinearAccumulator": [a["linear"][param.name]],
            },
            outputs={"ParamOut": [param]},
            attrs={"l1": self.l1, "l2": self.l2, "lr_power": self.lr_power},
        )


# -------------------------------------------------------- model averaging --
class ModelAverage:
    """Parameter averaging (reference: paddle/parameter/AverageOptimizer.h;

    v1 trainer_config_helpers optimizers.py ModelAverage). Keeps a sliding
    window of parameter values via a restarting accumulator: the window
    length is clamp(average_window_rate * num_updates, min_average_window,
    max_average_window), matching the reference's semantics. `apply()`
    swaps averaged values in, `restore()` swaps them back — for eval.

    Between the two this object holds the live parameter arrays. Run
    only evaluation programs there: they rebind nothing, so the Executor
    donates nothing and the held arrays are alive at `restore()`; a
    training step in between would consume (donate) the averaged values
    and `restore()` would put the older parameters back over its update."""

    def __init__(
        self,
        average_window_rate: float = 0.15,
        min_average_window: int = 10000,
        max_average_window: int = 10**9,
        program=None,
    ):
        self.program = program or default_main_program()
        helper = LayerHelper("model_average", main_program=self.program)
        self.pairs = []
        attrs = {
            "average_window": average_window_rate,
            "min_average_window": min_average_window,
            "max_average_window": max_average_window,
        }
        for p in self.program.parameters():
            gb = self.program.global_block()
            s = gb.create_var(f"@AVG@.{p.name}", p.shape, p.dtype, persistable=True)
            ConstantInitializer(0.0)(s, helper.startup_program)
            n = gb.create_var(f"@AVG_N@.{p.name}", (), np.float32, persistable=True)
            ConstantInitializer(0.0)(n, helper.startup_program)
            t = gb.create_var(f"@AVG_T@.{p.name}", (), np.float32, persistable=True)
            ConstantInitializer(0.0)(t, helper.startup_program)
            helper.append_op(
                type="average_accumulate",
                inputs={"Param": [p], "Sum": [s], "Count": [n], "Total": [t]},
                outputs={},
                attrs=attrs,
            )
            self.pairs.append((p, s, n))

    def apply(self, executor, scope=None):
        from ..core.executor import global_scope

        scope = scope or global_scope()
        self._backup = {}
        for p, s, n in self.pairs:
            self._backup[p.name] = scope.get(p.name)
            cnt = max(float(np.asarray(scope.get(n.name))), 1.0)
            scope.set(p.name, np.asarray(scope.get(s.name)) / cnt)

    def restore(self, executor, scope=None):
        from ..core.executor import global_scope

        scope = scope or global_scope()
        for name, val in self._backup.items():
            scope.set(name, val)


# convenient aliases (v2 API names)
SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adagrad = AdagradOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
Adam = AdamOptimizer
Adamax = AdamaxOptimizer
Ftrl = FtrlOptimizer
