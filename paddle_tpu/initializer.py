"""Parameter initializers.

Reference: python/paddle/v2/fluid/initializer.py (Constant/Uniform/Normal/
Xavier/MSRA) — each appends an init op to the *startup program*, executed
once by the Executor before training. The same pattern is kept: an
Initializer instance, given a parameter Variable, appends the matching
random/fill op to the startup program's block 0.
"""

from __future__ import annotations

import math

import numpy as np

from .core.program import Program, Variable, default_startup_program


class Initializer:
    def __call__(self, var: Variable, startup: Program = None):
        raise NotImplementedError


class ConstantInitializer(Initializer):
    def __init__(self, value: float = 0.0):
        self.value = value

    def __call__(self, var, startup=None):
        startup = startup or default_startup_program()
        b = startup.global_block()
        b.create_var(var.name, var.shape, var.dtype, persistable=True)
        b.append_op(
            "fill_constant",
            outputs={"Out": [var.name]},
            attrs={"shape": list(var.shape), "value": self.value,
                   "dtype": np.dtype(var.dtype).name},
        )


class UniformInitializer(Initializer):
    def __init__(self, low=-1.0, high=1.0, seed=0):
        self.low, self.high, self.seed = low, high, seed

    def __call__(self, var, startup=None):
        startup = startup or default_startup_program()
        b = startup.global_block()
        b.create_var(var.name, var.shape, var.dtype, persistable=True)
        b.append_op(
            "uniform_random",
            outputs={"Out": [var.name]},
            attrs={"shape": list(var.shape), "min": self.low, "max": self.high,
                   "dtype": np.dtype(var.dtype).name},
        )


class NormalInitializer(Initializer):
    def __init__(self, loc=0.0, scale=1.0, seed=0):
        self.loc, self.scale, self.seed = loc, scale, seed

    def __call__(self, var, startup=None):
        startup = startup or default_startup_program()
        b = startup.global_block()
        b.create_var(var.name, var.shape, var.dtype, persistable=True)
        b.append_op(
            "gaussian_random",
            outputs={"Out": [var.name]},
            attrs={"shape": list(var.shape), "mean": self.loc, "std": self.scale,
                   "dtype": np.dtype(var.dtype).name},
        )


def _fan_in_out(var: Variable):
    shape = var.shape
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    recept = int(np.prod(shape[2:]))
    return shape[1] * recept, shape[0] * recept


class XavierInitializer(Initializer):
    """Reference: fluid initializer.py XavierInitializer (Glorot)."""

    def __init__(self, uniform=True, fan_in=None, fan_out=None, seed=0,
                 gain=1.0):
        """`gain` multiplies the Glorot range: 1 / sqrt(layers) on the
        projections that write to a residual stream is GPT-2's scheme
        (`rescale_prenorm_residual` in `transformers`)."""
        self.uniform, self.fan_in, self.fan_out = uniform, fan_in, fan_out
        self.gain = gain

    def __call__(self, var, startup=None):
        fi, fo = _fan_in_out(var)
        fi = self.fan_in or fi
        fo = self.fan_out or fo
        if self.uniform:
            limit = self.gain * math.sqrt(6.0 / (fi + fo))
            UniformInitializer(-limit, limit)(var, startup)
        else:
            std = self.gain * math.sqrt(2.0 / (fi + fo))
            NormalInitializer(0.0, std)(var, startup)


class MSRAInitializer(Initializer):
    """Reference: fluid initializer.py MSRAInitializer (He)."""

    def __init__(self, uniform=True, fan_in=None, seed=0):
        self.uniform, self.fan_in = uniform, fan_in

    def __call__(self, var, startup=None):
        fi, _ = _fan_in_out(var)
        fi = self.fan_in or fi
        if self.uniform:
            limit = math.sqrt(6.0 / fi)
            UniformInitializer(-limit, limit)(var, startup)
        else:
            NormalInitializer(0.0, math.sqrt(2.0 / fi))(var, startup)


Constant = ConstantInitializer
Uniform = UniformInitializer
Normal = NormalInitializer
Xavier = XavierInitializer
MSRA = MSRAInitializer
