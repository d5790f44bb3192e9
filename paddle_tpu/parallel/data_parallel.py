"""Data-parallel (and model-parallel-annotated) program execution.

This is the TPU collapse of three reference subsystems (SURVEY.md §2.3):

- MultiGradientMachine's per-GPU trainer threads with ring gradient
  scatter/gather (gserver/gradientmachines/MultiGradientMachine.h:63-110)
- the C++ parameter server path: RemoteParameterUpdater →
  ParameterClient2.sendAndReceiveParameter → ParameterServer2 block-sharded
  SGD (pserver/ParameterServer2.cpp:682,908)
- the Fluid DistributeTranspiler program rewrite into send/recv + pserver
  subprograms (python/paddle/v2/fluid/distribute_transpiler.py:77)

All three exist to do one thing: sum gradients across replicas and apply
the update once. Under GSPMD that entire machinery is *one sharding
annotation*: feeds are sharded over the `dp` mesh axis, parameters are
replicated (or sharded over `mp` for large embeddings — the reference's
"sparse parameters live on pservers" large-model mode), and XLA inserts
the psum/all_gather collectives over ICI. Async-SGD (ParameterServer2.cpp
:457) is intentionally dropped: on a dedicated synchronous fabric, sync
SGD strictly dominates — documented behavioral difference.

ParallelExecutor runs the SAME Program as core.Executor — parallelism is
a deployment property, not a model property, which is the design insight
the reference's transpiler approximated by rewriting programs.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.executor import Executor, rebound_persistables
from ..core.lod import LoDArray
from ..core.program import Program
from .mesh import DP, make_mesh


class ParallelExecutor(Executor):
    """Executor with a Mesh: feeds sharded over `dp`, params replicated

    unless a Variable carries `.sharding` (a PartitionSpec) — e.g. a vocab-
    sharded embedding table (parallel/sharded_embedding.py)."""

    # the Trainer must not single-device-prefetch feeds this executor
    # will shard over the mesh, and its mesh-committed fetches cannot
    # join the single-device jitted metric accumulator — the pipelined
    # loop degrades to the per-step host accumulation path here
    prefetch_by_default = False
    device_metric_accumulation = False
    # run_window's lax.scan carries single-device state and stacked
    # committed feeds; neither survives the mesh's explicit sharded
    # placement (_place_inputs) without threading shardings through the
    # scan carry — the Trainer falls back to the per-step loop here
    # (loudly) until the window path is mesh-aware (ROADMAP item 3 note)
    scan_window_supported = False

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        batch_axis: str = DP,
        shard_optimizer_state: bool = False,
    ):
        super().__init__()
        self.mesh = mesh or make_mesh()
        self.batch_axis = batch_axis
        # ZeRO-1 expressed as GSPMD (SURVEY.md §5.8: "sharded optimizer
        # state replaces the pserver's parameter-block sharding"): optimizer
        # accumulators are sharded over the dp axis; XLA keeps their update
        # shard-local and inserts the all-gather on the state→param path.
        # HBM for optimizer state drops by ~dp_size.
        self.shard_optimizer_state = shard_optimizer_state

    def _trace_context(self):
        """Declare the mesh to the fused-kernel dispatch layer: pallas
        calls cannot be auto-partitioned by GSPMD, so eligible kernels
        shard_map themselves over the batch axis (ops/mesh_dispatch.py
        — the written pallas-under-mesh policy) and eligibility windows
        evaluate at the per-shard batch."""
        from ..ops import mesh_dispatch

        return mesh_dispatch.active_mesh(self.mesh, self.batch_axis)

    # -- sharding rules -----------------------------------------------------
    def _state_sharding(self, program: Program, name: str) -> NamedSharding:
        gb = program.global_block()
        if name in gb.vars:
            var = gb.vars[name]
            spec = getattr(var, "sharding", None)
            if spec is not None:
                return NamedSharding(self.mesh, spec)
            if (
                self.shard_optimizer_state
                and getattr(var, "is_optimizer_state", False)
                and len(var.shape) >= 1
                and var.shape[0] != -1
                and var.shape[0] % self.mesh.shape[self.batch_axis] == 0
            ):
                return NamedSharding(
                    self.mesh,
                    PartitionSpec(
                        self.batch_axis, *([None] * (len(var.shape) - 1))
                    ),
                )
        return NamedSharding(self.mesh, PartitionSpec())

    def _feed_sharding(self, value) -> Any:
        def shard_leaf(leaf):
            if leaf.ndim == 0:
                return NamedSharding(self.mesh, PartitionSpec())
            return NamedSharding(
                self.mesh,
                PartitionSpec(self.batch_axis, *([None] * (leaf.ndim - 1))),
            )

        if isinstance(value, LoDArray):
            # ragged feeds: shard the flat token axis and the seq axis.
            # Sequences may straddle shard boundaries; segment reductions
            # then ride ICI collectives (correct, and cheap vs the scan).
            return LoDArray(
                shard_leaf(value.data),
                shard_leaf(value.seq_ids),
                shard_leaf(value.lengths),
                NamedSharding(self.mesh, PartitionSpec()),
                None if value.sub_seq_ids is None else shard_leaf(value.sub_seq_ids),
            )
        return shard_leaf(value)

    def _place_grad(self, program: Program, name: str, grad):
        """A parameter's gradient lives where the parameter does (replicated
        unless the Variable carries `.sharding`): the psum over `dp` ends
        here. Without it GSPMD carries the ZeRO-sharded moments' layout back
        through the weight gradients into the differentiated forward, and
        splits a scan over its hidden axis, collectives in every iteration,
        with the whole batch on every device."""
        return jax.lax.with_sharding_constraint(
            grad, self._state_sharding(program, name))

    # -- Executor hooks -----------------------------------------------------
    @property
    def _multiprocess(self) -> bool:
        return len(self.mesh.devices.reshape(-1)) > jax.local_device_count()

    def _place_inputs(self, program, state, feed, seed):
        """Cross-process placement (DCN path, SURVEY §5.8): jit cannot
        reshard an input onto devices this process cannot address, so host
        values are device_put explicitly onto their global shardings.
        Every process passes the same host value; device_put ships only
        the local shards (the reference's trainer feeding its pserver
        shard). Arrays already global (previous steps' outputs) pass
        through untouched."""
        if not self._multiprocess:
            return state, feed, seed

        def is_placed(v):
            return isinstance(v, jax.Array) and not v.is_fully_addressable

        def put(v, sharding):
            return v if is_placed(v) else jax.device_put(np.asarray(v), sharding)

        state = {
            n: put(v, self._state_sharding(program, n))
            for n, v in state.items()
        }

        def put_feed(v):
            sh = self._feed_sharding(v)
            if isinstance(v, LoDArray):
                leaves, treedef = jax.tree.flatten(v)
                shs = treedef.flatten_up_to(sh)
                return treedef.unflatten(
                    [put(leaf, s) for leaf, s in zip(leaves, shs)]
                )
            return put(v, sh)

        feed = {k: put_feed(v) for k, v in feed.items()}
        seed = put(seed, NamedSharding(self.mesh, PartitionSpec()))
        return state, feed, seed

    def run_startup(self, program, scope=None):
        """Parameter init runs single-device; every process must produce
        the SAME host values (asserted by the cross-process device_put on
        the first parallel step), so an unseeded init program gets one
        chief-broadcast seed instead of per-host np.random draws —
        without this, default-seed multi-process init diverges and dies
        with an opaque assert at the first step."""
        restore = None
        if self._multiprocess and getattr(program, "random_seed", 0) == 0:
            from jax.experimental import multihost_utils

            seed = int(multihost_utils.broadcast_one_to_all(
                np.uint32(np.random.randint(1, 2**31 - 1))
            ))
            restore, program.random_seed = 0, seed
        try:
            return Executor(self.place).run(program, scope=scope)
        finally:
            if restore is not None:
                program.random_seed = restore

    def _draw_seed(self, program) -> int:
        """Every process must use the SAME per-run seed (the seed scalar
        is device_put across processes, and SPMD dropout masks must
        agree): broadcast one base from the chief once, then advance a
        local counter — all processes call run() in lockstep, so the
        sequence stays aligned without a per-step collective."""
        if not self._multiprocess or program.random_seed != 0:
            return Executor._draw_seed(self, program)
        if not hasattr(self, "_seed_base"):
            from jax.experimental import multihost_utils

            self._seed_base = int(multihost_utils.broadcast_one_to_all(
                np.uint32(np.random.randint(1, 2**30))
            ))
            self._seed_calls = 0
        self._seed_calls += 1
        return (self._seed_base + self._seed_calls) % (2**31 - 1)

    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, as_numpy=None):
        """Init-style programs (they CREATE persistables the scope does
        not hold yet) cannot be mesh-compiled — the output tree would
        have to declare shardings for values that don't exist — so the
        documented `exe.run(startup_program)` idiom delegates to the
        local-device startup path instead of dying in a pytree error."""
        from ..core.executor import global_scope
        from ..core.program import default_main_program

        prog = program or default_main_program()
        scope_ = scope or global_scope()
        # only a call with no feed and no fetch can be init-style: a step
        # does not list the program's persistables to find out
        if not feed and not fetch_list and any(
                not scope_.has(v.name) for v in prog.persistables()):
            return self.run_startup(prog, scope=scope_)
        return super().run(prog, feed=feed, fetch_list=fetch_list,
                           scope=scope_, return_numpy=return_numpy,
                           as_numpy=as_numpy)

    def _place_kept(self, program: Program, scope, kept):
        """The step returns only the persistables it rebinds, so the ones
        it only reads (a learning rate, every parameter of an inference
        program) never come back placed: put them on the mesh here and
        leave them in the scope, or every run would reshard them anew.
        A value already on this mesh was placed here or by a step."""
        for name, val in kept.items():
            if getattr(getattr(val, "sharding", None), "mesh", None) \
                    == self.mesh:
                continue
            if self._multiprocess or not isinstance(val, jax.Array):
                val = np.asarray(val)
            kept[name] = jax.device_put(
                val, self._state_sharding(program, name))
            scope.set(name, kept[name])
        return kept

    def _cache_key_prefix(self) -> tuple:
        return ("par", id(self.mesh))

    def _device_context(self):
        return self.mesh

    def _compile(self, program: Program, feed, fetch_names, persist_names):
        raw = self._raw_step(program, fetch_names)
        rebound = rebound_persistables(program)
        donated_shardings = {
            n: self._state_sharding(program, n)
            for n in persist_names if n in rebound
        }
        kept_shardings = {
            n: self._state_sharding(program, n)
            for n in persist_names if n not in rebound
        }
        feed_shardings = {k: self._feed_sharding(v) for k, v in feed.items()}
        # the donated state comes back under the shardings it went in
        # with, so every output aliases its input; the extras (what the
        # split could not know) are left to the compiler
        return jax.jit(
            raw,
            in_shardings=(
                donated_shardings,
                kept_shardings,
                feed_shardings,
                NamedSharding(self.mesh, PartitionSpec()),
            ),
            out_shardings=(None, donated_shardings, None),
            donate_argnums=(0,),
        )
