"""Generic control flow: While loops and conditional branches.

Reference: paddle/operators/while_op.cc (block-attr subprogram looped while
a bool condition var holds), conditional_block_op.cc / cond_op.cc (branch
subprograms), and the Fluid `While` / `layers.cond` front-ends
(python/paddle/v2/fluid/layers/control_flow.py). The dynamic-RNN stack the
reference builds FROM While (lod_rank_table / shrink_rnn_memory) is covered
by recurrent_group; this module is the general machinery.

TPU design: sub-blocks traced into `jax.lax.while_loop` / `jax.lax.cond`
bodies — compiled control flow, no host round-trips. While-carried values
are declared functionally via `loop.update(outer_var, new_var)` instead of
in-place assigns; reads of the outer var inside the block see the carried
value.

Which loop: `While` runs until a condition falls and is forward-only
(inference, decoding, data logic); a TRAINABLE loop of a fixed count over a
whole stream (one stack of layers run K times with one set of weights) is
`Repeat`, a bounded `lax.scan` that rematerialises a turn at a time, all
but the last; a recurrence over a SEQUENCE axis (per-step slices, masks,
LoD) is `recurrent_group`.
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..core.program import Variable, default_main_program, unique_name
from .helper import LayerHelper

__all__ = ["While", "Repeat", "cond"]


class While:
    """Compiled while-loop over a sub-block.

    Usage::

        i = pt.layers.fill_constant([1], np.int32, 0)
        s = pt.layers.fill_constant([1], np.float32, 0.0)
        c = pt.layers.less_than(i, n)          # initial condition
        loop = pt.layers.While(cond=c)
        with loop.block():
            i2 = pt.layers.increment(i)        # reads see carried values
            s2 = pt.layers.elementwise_add(s, x)
            loop.update(i, i2)
            loop.update(s, s2)
            loop.update(c, pt.layers.less_than(i2, n))
        i_fin, s_fin, _ = loop()               # finals, update order

    The condition is an updated loop var: its value entering the op
    decides iteration 1, the value computed in the block decides the next
    — exactly the reference While semantics (cond computed before the op,
    recomputed at block end).

    NOT reverse-mode differentiable (lax.while_loop limitation — an
    unbounded loop cannot be rematerialized on TPU): use it for inference/
    decoding/data logic. A trainable loop of a fixed count is `Repeat`
    (below); a trainable recurrence over a sequence belongs in
    recurrent_group (bounded scan), which is also how the reference's
    trainable dynamic RNNs are built on top of while_op rather than raw
    while backward."""

    def __init__(self, cond: Variable, name=None):
        self.helper = LayerHelper("while_loop", name=name)
        self.cond = cond
        self._updates: List[Tuple[Variable, Variable]] = []
        self._block = None
        self._done = False

    @contextlib.contextmanager
    def block(self):
        prog = self.helper.main_program
        with prog.block_guard() as b:
            self._block = b
            yield
        self._complete()

    def update(self, outer: Variable, new: Variable) -> None:
        """Declare a loop-carried value: inside the block, reads of

        `outer` see the carried value; after the loop, its final value is
        returned. The condition var itself must be updated or the loop
        never terminates."""
        if self._done:
            raise RuntimeError(
                "update() after the block() has closed — the loop op is "
                "already emitted; declare all carried values inside the "
                "with-block")
        for o, _ in self._updates:
            if o.name == outer.name:
                raise ValueError(f"{outer.name} updated twice")
        self._updates.append((outer, new))

    def _complete(self):
        if not any(o.name == self.cond.name for o, _ in self._updates):
            raise ValueError(
                "While condition var must be updated inside the block "
                "(otherwise the loop cannot terminate)")
        helper = self.helper
        parent = helper.block
        self.outputs = [
            parent.create_var(
                unique_name(f"{helper.name}.out"), tuple(o.shape), o.dtype
            )
            for o, _ in self._updates
        ]
        parent.append_op(
            "while_loop",
            inputs={
                "Cond": [self.cond.name],
                "Carried": [o.name for o, _ in self._updates],
            },
            outputs={"Out": [v.name for v in self.outputs]},
            attrs={
                "sub_block": self._block.idx,
                "carried": [o.name for o, _ in self._updates],
                "updates": [n.name for _, n in self._updates],
            },
        )
        self._done = True

    def __call__(self):
        if not self._done:
            raise RuntimeError("call after the block() has closed")
        return tuple(self.outputs)


class Repeat:
    """A sub-block run `times` times, one after the other, that trains.

    Usage::

        loop = pt.layers.Repeat(times=4)       # a Python int, fixed here
        with loop.block():
            h2 = stack(h)                      # reads of h see the carry
            loop.update(h, h2)                 # carried, as While.update
            loop.turn_output(cost_of(h2))      # stacked over the turns
        h_fin, costs = loop()                  # finals, then stacks [4, ...]

    One `repeat` op. Parameters made inside the block live in the global
    block, once (`LayerHelper.create_parameter`), and every turn reads the
    same ones: under `append_backward` their gradient is the sum over the
    turns, with nothing for the optimizer to know. The op lowers to a
    `jax.lax.scan` of length `times`. With `remat=True`, the default, what
    the loop keeps for the backward pass is the carries of turns 1..K-1 and
    the stacked outputs: the backward pass runs each of those turns again,
    one at a time, before it transposes it, so K turns hold one turn's
    activations. The LAST turn is not run again: it is differentiated where
    it stands, and its residuals, which the backward pass would have rebuilt
    first of all, live from the forward pass to the backward pass across the
    ops between them. A step runs the block 2 K - 1 times forward and K times
    backward. `remat=False` keeps every turn's activations (the same values;
    K times the memory) and runs the block K times each way; `times=1` is the
    block alone either way. The compiled step holds the block's kernels, with
    `remat`, three times forward (the loop, the last turn, a turn run again)
    and twice backward (the last turn, the loop) whatever `times` is.

    A carry keeps its shape and dtype: `update(h, h2)` with another shape or
    dtype raises here, at build time, as do `update` / `turn_output` after
    the block has closed and a `times` under 1. Dropout in the block draws
    anew each turn (one outer counter for the loop, a fold-in per turn, as
    `While`)."""

    def __init__(self, times: int, remat: bool = True, name=None):
        if isinstance(times, bool) or not isinstance(times, (int, np.integer)) \
                or times < 1:
            raise ValueError(f"Repeat(times={times!r}): a whole number of "
                             f"turns, at least 1, fixed when the Program is "
                             f"built")
        self.helper = LayerHelper("repeat", name=name)
        self.times, self.remat = int(times), bool(remat)
        self._updates: List[Tuple[Variable, Variable]] = []
        self._turn_outputs: List[Variable] = []
        self._block = None
        self._done = False

    @contextlib.contextmanager
    def block(self):
        prog = self.helper.main_program
        with prog.block_guard() as b:
            self._block = b
            yield
        self._complete()

    def _open(self, what: str) -> None:
        if self._done:
            raise RuntimeError(
                f"{what}() after the block() has closed — the repeat op is "
                "already emitted; declare carries and turn outputs inside "
                "the with-block")

    def update(self, outer: Variable, new: Variable) -> None:
        """Declare a carried value: the first turn reads `outer` as it
        enters the op, every later turn reads the `new` of the turn before,
        and the last `new` is returned."""
        self._open("update")
        if any(o.name == outer.name for o, _ in self._updates):
            raise ValueError(f"{outer.name} updated twice")
        if tuple(outer.shape) != tuple(new.shape) or \
                np.dtype(outer.dtype) != np.dtype(new.dtype):
            raise ValueError(
                f"Repeat carries {outer.name} as {tuple(outer.shape)} "
                f"{np.dtype(outer.dtype).name}, and the block hands on "
                f"{new.name} as {tuple(new.shape)} "
                f"{np.dtype(new.dtype).name}: a carry keeps its shape and "
                f"dtype over the turns")
        self._updates.append((outer, new))

    def turn_output(self, var: Variable) -> None:
        """Declare a value of the block that every turn gives out: returned
        stacked in turn order, `[times, ...]`."""
        self._open("turn_output")
        if any(v.name == var.name for v in self._turn_outputs):
            raise ValueError(f"{var.name} is a turn output already")
        self._turn_outputs.append(var)

    def _complete(self):
        if not self._updates and not self._turn_outputs:
            raise ValueError("Repeat: the block declared neither a carried "
                             "value (update) nor a turn output")
        helper = self.helper
        parent = helper.block
        finals = [
            parent.create_var(unique_name(f"{helper.name}.out"),
                              tuple(o.shape), o.dtype)
            for o, _ in self._updates
        ]
        stacks = [
            parent.create_var(unique_name(f"{helper.name}.turns"),
                              (self.times,) + tuple(v.shape), v.dtype)
            for v in self._turn_outputs
        ]
        self.outputs = finals + stacks
        parent.append_op(
            "repeat",
            inputs={"Carried": [o.name for o, _ in self._updates]},
            outputs={"Out": [v.name for v in finals],
                     "Turns": [v.name for v in stacks]},
            attrs={
                "sub_block": self._block.idx,
                "times": self.times,
                "carried": [o.name for o, _ in self._updates],
                "updates": [n.name for _, n in self._updates],
                "turn_outputs": [v.name for v in self._turn_outputs],
                "remat": self.remat,
            },
        )
        self._done = True

    def __call__(self):
        """The carried values' finals in `update` order, then the stacked
        turn outputs in `turn_output` order."""
        if not self._done:
            raise RuntimeError("call after the block() has closed")
        return tuple(self.outputs)


def cond(pred: Variable, true_fn, false_fn, name=None):
    """Compiled two-way branch (reference: conditional_block_op.cc /

    cond_op.cc; modern fluid layers.cond). `true_fn`/`false_fn` build
    their sub-networks in separate sub-blocks and return a Variable or a
    tuple of Variables with matching shapes/dtypes; both branches run
    under lax.cond's tracing but only one executes."""
    helper = LayerHelper("cond", name=name)
    prog = helper.main_program

    def trace(fn):
        with prog.block_guard() as b:
            outs = fn()
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        return b, list(outs)

    tb, t_outs = trace(true_fn)
    fb, f_outs = trace(false_fn)
    if len(t_outs) != len(f_outs):
        raise ValueError("cond branches must return the same number of vars")
    parent = helper.block
    outputs = [
        parent.create_var(
            unique_name(f"{helper.name}.out"), tuple(v.shape), v.dtype
        )
        for v in t_outs
    ]
    parent.append_op(
        "cond",
        inputs={"Pred": [pred.name]},
        outputs={"Out": [v.name for v in outputs]},
        attrs={
            "true_block": tb.idx,
            "false_block": fb.idx,
            "true_outs": [v.name for v in t_outs],
            "false_outs": [v.name for v in f_outs],
        },
    )
    return outputs[0] if len(outputs) == 1 else tuple(outputs)
