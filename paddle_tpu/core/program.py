"""Program IR: Program → Block → Operator / Variable.

Reference: paddle/framework/framework.proto:33-146 defines the
OpDesc/VarDesc/BlockDesc/ProgramDesc protobuf IR; the Python front-end mirrors
it in python/paddle/v2/fluid/framework.py (Variable :125, Operator :350,
Block :621, Program :789).

The TPU rebuild keeps the same three-level structure but as plain Python
dataclasses: the IR is *traced into one XLA program* by the Executor
(executor.py) rather than interpreted op-by-op, so the IR's job is purely
front-end bookkeeping — names, shapes, parameter-ness, and op attributes.
Protobuf round-tripping (for save_inference_model parity) is provided by
`Program.to_dict()/from_dict()` since the IR is the serialization boundary.
"""

from __future__ import annotations

import contextlib
import copy
import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

_unique_counter = itertools.count()


def unique_name(prefix: str) -> str:
    return f"{prefix}_{next(_unique_counter)}"


def reset_unique_name() -> None:
    global _unique_counter
    _unique_counter = itertools.count()


@dataclass
class Variable:
    """Symbolic tensor in a Block (reference: fluid framework.py:125).

    shape uses -1 for the batch dimension. lod_level>0 marks ragged inputs
    (LoDArray at runtime, see core/lod.py).
    """

    block: "Block"
    name: str
    shape: tuple
    dtype: Any = np.float32
    lod_level: int = 0
    persistable: bool = False
    is_parameter: bool = False
    trainable: bool = True
    initializer: Any = None  # callable (rng, shape, dtype) -> np/jnp array
    op: Optional["Operator"] = None  # producer op
    stop_gradient: bool = False
    # sparse feed slot (reference: SparseBinaryScanner/SparseFloatScanner,
    # py_paddle/dataprovider_converter.py:154,184): "binary" | "float".
    # Runtime value is a core/sparse.py SparseArray.
    sparse_format: Optional[str] = None
    # parameter receives SelectedRows (row-wise) gradients instead of a
    # dense grad (reference: framework/selected_rows.h; embedding
    # is_sparse=True). Set by layers.embedding; consumed by the autodiff
    # lowering (core/executor.py) and optimizer ops.
    sparse_update: bool = False

    # regularization / clipping attributes (set by ParamAttr)
    regularizer: Any = None
    grad_clip: Any = None
    optimize_attr: Dict[str, Any] = field(default_factory=lambda: {"learning_rate": 1.0})

    @property
    def grad_name(self) -> str:
        return grad_var_name(self.name)

    def __repr__(self):
        return f"Var({self.name}, shape={self.shape}, lod={self.lod_level})"


def grad_var_name(name: str) -> str:
    """Reference: paddle/framework/grad_op_desc_maker.h GradVarName — `x@GRAD`."""
    return name + "@GRAD"


@dataclass
class Operator:
    """Op node (reference: framework.proto OpDesc, fluid framework.py:350)."""

    type: str
    inputs: Dict[str, List[str]]
    outputs: Dict[str, List[str]]
    attrs: Dict[str, Any] = field(default_factory=dict)

    def input_names(self) -> List[str]:
        return [n for ns in self.inputs.values() for n in ns]

    def output_names(self) -> List[str]:
        return [n for ns in self.outputs.values() for n in ns]

    def __repr__(self):
        return f"Op({self.type}: {self.inputs} -> {self.outputs})"


class Block:
    """Straight-line op list + symbol table (reference: BlockDesc,

    fluid framework.py:621). Control flow ops hold *sub-blocks* in attrs
    (reference: operators/while_op.cc block attr) which map to lax.scan /
    while_loop bodies at trace time."""

    def __init__(self, program: "Program", idx: int, parent_idx: int = -1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.ops: List[Operator] = []
        self.vars: Dict[str, Variable] = {}

    # -- vars ---------------------------------------------------------------
    def create_var(self, name=None, shape=(), dtype=np.float32, **kw) -> Variable:
        name = name or unique_name("tmp")
        if name in self.vars:
            return self.vars[name]
        v = Variable(self, name, tuple(shape), dtype, **kw)
        self.vars[name] = v
        return v

    def create_parameter(self, name, shape, dtype=np.float32, initializer=None, **kw) -> Variable:
        v = self.create_var(
            name,
            shape,
            dtype,
            persistable=True,
            is_parameter=True,
            initializer=initializer,
            **kw,
        )
        self.program.global_block().vars.setdefault(name, v)
        return v

    def var(self, name: str) -> Variable:
        b: Optional[Block] = self
        while b is not None:
            if name in b.vars:
                return b.vars[name]
            b = self.program.blocks[b.parent_idx] if b.parent_idx >= 0 else None
        raise KeyError(f"Variable {name!r} not found in block {self.idx}")

    def has_var(self, name: str) -> bool:
        try:
            self.var(name)
            return True
        except KeyError:
            return False

    # -- ops ----------------------------------------------------------------
    def append_op(self, type: str, inputs=None, outputs=None, attrs=None) -> Operator:
        def _norm(d):
            out = {}
            for k, v in (d or {}).items():
                if isinstance(v, (list, tuple)):
                    out[k] = [x.name if isinstance(x, Variable) else x for x in v]
                else:
                    out[k] = [v.name if isinstance(v, Variable) else v]
            return out

        op = Operator(type, _norm(inputs), _norm(outputs), dict(attrs or {}))
        self.ops.append(op)
        for name in op.output_names():
            if name in self.vars and self.vars[name].op is None:
                self.vars[name].op = op
        self.program.bump_version()
        return op


class Program:
    """Reference: fluid framework.py:789. Holds blocks; block 0 is global."""

    def __init__(self):
        self.blocks: List[Block] = [Block(self, 0)]
        self._current_block_idx = 0
        self._version = 0
        self.random_seed: int = 0
        # mixed-precision compute dtype (None = full f32); see paddle_tpu/amp.py
        self.amp_dtype: Optional[str] = None
        # rematerialization policy for the backward pass (None = XLA default);
        # see core/executor.py _run_autodiff and pt.memory_optimize
        self.remat_policy: Optional[str] = None
        # per-step statistics its layers registered (add_step_statistic)
        self.step_statistics: List[dict] = []

    def add_step_statistic(self, var, counter: str, labels=None,
                           index_label: str = "index", help: str = "") -> None:
        """A layer's per-step count that should reach the metrics registry
        (`obs.metrics.registry()`) from inside the compiled step: `var` (an
        integer vector the step computes) is summed over steps into the
        labelled counter family `counter`, element i under `labels` plus
        {index_label: i}. The Trainer fetches it in the step's own
        `Executor.run`, folds it on the device in the accumulator dispatch
        it already issues and publishes it at the host syncs it already
        pays (trainer.py:_PassStats); a program with none runs as before."""
        self.step_statistics.append({
            "var": var.name, "counter": counter, "labels": dict(labels or {}),
            "index_label": index_label, "help": help})

    def set_amp(self, dtype: Optional[str] = "bfloat16") -> None:
        """Enable/disable bf16 mixed-precision compute for MXU ops.

        The executor keys its compile cache on the amp setting, so toggling
        (e.g. amp_guard around run calls in a loop) reuses both compiled
        variants rather than recompiling."""
        self.amp_dtype = dtype

    # -- structure ----------------------------------------------------------
    def global_block(self) -> Block:
        return self.blocks[0]

    def current_block(self) -> Block:
        return self.blocks[self._current_block_idx]

    def create_block(self) -> Block:
        b = Block(self, len(self.blocks), parent_idx=self._current_block_idx)
        self.blocks.append(b)
        self._current_block_idx = b.idx
        return b

    def rollback(self) -> None:
        self._current_block_idx = self.current_block().parent_idx

    @contextlib.contextmanager
    def block_guard(self):
        b = self.create_block()
        try:
            yield b
        finally:
            self.rollback()

    def bump_version(self):
        self._version += 1

    @property
    def version(self) -> int:
        return self._version

    # -- queries ------------------------------------------------------------
    def parameters(self) -> List[Variable]:
        return [v for v in self.global_block().vars.values() if v.is_parameter]

    def persistables(self) -> List[Variable]:
        return [v for v in self.global_block().vars.values() if v.persistable]

    def clone(self, for_test: bool = False) -> "Program":
        """Deep copy; for_test=True additionally drops the backward+optimizer
        slice and flips is_test attrs (fluid framework.py Program.clone)."""
        p = copy.deepcopy(self)
        if for_test:
            for b in p.blocks:
                b.ops = [
                    op
                    for op in b.ops
                    if op.type != "autodiff" and not op.attrs.get("is_optimizer_op")
                ]
                for op in b.ops:
                    if "is_test" in op.attrs:
                        op.attrs["is_test"] = True
            p.bump_version()
        return p

    # -- serialization (model_format parity) --------------------------------
    def to_dict(self) -> dict:
        def var_d(v: Variable):
            d = {
                "name": v.name,
                "shape": list(v.shape),
                "dtype": np.dtype(v.dtype).name,
                "lod_level": v.lod_level,
                "persistable": v.persistable,
                "is_parameter": v.is_parameter,
            }
            # sparse semantics must survive the round-trip: a restored
            # program silently losing sparse_update would densify the
            # embedding gradient; losing sparse_format would break feeding
            if v.sparse_update:
                d["sparse_update"] = True
            if v.sparse_format:
                d["sparse_format"] = v.sparse_format
            return d

        return {
            "version": 1,
            # the compute dtype is part of what was built: a bf16 model
            # saved for serving must not come back as an f32 one
            **({"amp_dtype": self.amp_dtype} if self.amp_dtype else {}),
            "blocks": [
                {
                    "idx": b.idx,
                    "parent_idx": b.parent_idx,
                    "vars": [var_d(v) for v in b.vars.values()],
                    "ops": [
                        {
                            "type": op.type,
                            "inputs": op.inputs,
                            "outputs": op.outputs,
                            "attrs": {
                                k: v
                                for k, v in op.attrs.items()
                                if _json_safe(v)
                            },
                        }
                        for op in b.ops
                    ],
                }
                for b in self.blocks
            ],
        }

    @staticmethod
    def from_dict(d: dict) -> "Program":
        p = Program()
        p.blocks = []
        for bd in d["blocks"]:
            b = Block(p, bd["idx"], bd["parent_idx"])
            for vd in bd["vars"]:
                b.create_var(
                    vd["name"],
                    tuple(vd["shape"]),
                    np.dtype(vd["dtype"]),
                    lod_level=vd["lod_level"],
                    persistable=vd["persistable"],
                    is_parameter=vd["is_parameter"],
                    sparse_update=vd.get("sparse_update", False),
                    sparse_format=vd.get("sparse_format"),
                )
            for od in bd["ops"]:
                b.ops.append(Operator(od["type"], od["inputs"], od["outputs"], od["attrs"]))
            p.blocks.append(b)
        p._current_block_idx = 0
        p.amp_dtype = d.get("amp_dtype")
        return p


def _json_safe(v) -> bool:
    if isinstance(v, (bool, int, float, str, type(None))):
        return True
    if isinstance(v, (list, tuple)):
        return all(_json_safe(x) for x in v)
    return False


# -- default program / scope-like globals (fluid framework.py end) ----------
_main_program = Program()
_startup_program = Program()


def default_main_program() -> Program:
    return _main_program


def default_startup_program() -> Program:
    return _startup_program


@contextlib.contextmanager
def program_guard(main: Program, startup: Optional[Program] = None):
    global _main_program, _startup_program
    old_m, old_s = _main_program, _startup_program
    _main_program = main
    if startup is not None:
        _startup_program = startup
    try:
        yield
    finally:
        _main_program, _startup_program = old_m, old_s


def reset_default_programs():
    global _main_program, _startup_program
    _main_program = Program()
    _startup_program = Program()
    reset_unique_name()
