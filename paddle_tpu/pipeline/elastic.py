"""Elastic sharded checkpoints: background commit + resume-with-resharding.

Two halves of the same fleet-scale story (ROADMAP item 1; the reference's
Go pserver survived worker churn via etcd-backed checkpoint/recovery —
service.go:346):

1. **Background sharded commit.** io.save_checkpoint(sharded=True) was
   pinned to the training thread because its cross-process barriers must
   run on the thread every process blocks on. Single-process (one
   controller driving the whole mesh — this framework's normal TPU
   topology), there are no barriers, so the commit can ride the
   trainer's `_CheckpointWriter` double buffer. The snapshot owns its
   values: one jitted copy of the persistable tree at submit (one
   dispatch, no host sync; a second state's worth of device memory
   for as long as the commit is in flight), because a training step
   donates the buffers it rebinds and the scope's arrays are dead the
   moment the next step dispatches. The device→host copy of each
   unique shard (`np.asarray(shard.data)`) happens on the writer
   thread, not the step loop. The step loop blocks only when the
   PREVIOUS commit is still in flight (the submit/drain contract tests
   assert).

2. **Resume-with-resharding.** `sharded_meta.json` records global
   shapes plus the slice each shard covers, so the loader can assemble
   full host arrays no matter which mesh wrote them; the *restoring*
   world then re-slices onto its own mesh (dp8 → dp4x2, or a changed
   chip count). `reshard_scope_to_mesh` is the explicit placement step;
   the save-time world is recorded so a cross-world restore is
   observable (`pt_ckpt_reshard_total`).
"""

from __future__ import annotations

import logging
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import io
from ..core.executor import Scope, global_scope
from ..core.program import Program, default_main_program

logger = logging.getLogger("paddle_tpu.pipeline")

RESHARD_COUNTER = "pt_ckpt_reshard_total"
_RESHARD_HELP = ("checkpoint restores whose saving world (device/process "
                 "count) differed from the restoring world")


def declare_reshard_counter() -> None:
    """Declare-at-construction (obs registry contract): the family
    exists at 0 before any elastic restore happens. Called from the
    PipelineExecutor and Trainer constructors, and on first import here,
    so it survives reset_metrics + re-construction in any order."""
    from ..obs import metrics as obs

    obs.registry().declare_counter(RESHARD_COUNTER, _RESHARD_HELP)


def count_reshard() -> None:
    from ..obs import metrics as obs

    obs.registry().counter_inc(RESHARD_COUNTER, help=_RESHARD_HELP)


@jax.jit
def _copy_tree(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def snapshot_scope(
    main_program: Optional[Program] = None,
    scope: Optional[Scope] = None,
) -> Scope:
    """A snapshot of the persistable slice of the scope that owns its
    values: the device arrays are copied on the device by one jitted
    call (enqueued behind the steps already dispatched, so the values
    are this step's, with no host sync), host values are held as they
    are. The returned Scope is safe to serialize from another thread
    while training goes on donating the live scope's buffers."""
    program = main_program or default_main_program()
    scope = scope or global_scope()
    values = {v.name: scope.get(v.name)
              for v in program.persistables() if scope.has(v.name)}
    on_device = {n: v for n, v in values.items() if isinstance(v, jax.Array)}
    values.update(_copy_tree(on_device))
    snap = Scope()
    for n, v in values.items():
        snap.set(n, v)
    return snap


def submit_sharded_save(
    writer,
    checkpoint_dir: str,
    trainer_args: Optional[Dict[str, Any]] = None,
    main_program: Optional[Program] = None,
    scope: Optional[Scope] = None,
    max_num_checkpoints: int = 3,
) -> None:
    """Hand a sharded checkpoint commit to a `_CheckpointWriter`-style
    background writer (submit/drain double buffer). Blocks only on an
    in-flight previous commit; the capture is one device-side copy
    (`snapshot_scope`).

    Multi-process saves must stay on the training thread (their
    barriers deadlock if even one process commits from a side thread) —
    callers gate on jax.process_count()==1; this re-checks loudly."""
    if jax.process_count() > 1:
        raise NotImplementedError(
            "background sharded commit is single-process only: the "
            "multi-process save barriers must run on the thread every "
            "process is blocking on (CheckpointConfig(background=False) "
            "for multi-process sharded saves)")
    program = main_program or default_main_program()
    snap = snapshot_scope(program, scope)
    writer.submit(lambda: io.save_checkpoint(
        checkpoint_dir,
        trainer_args=trainer_args,
        main_program=program,
        scope=snap,
        max_num_checkpoints=max_num_checkpoints,
        sharded=True,
    ))


def current_world() -> Dict[str, int]:
    return {
        "device_count": int(jax.device_count()),
        "process_count": int(jax.process_count()),
    }


def reshard_scope_to_mesh(
    main_program: Optional[Program] = None,
    scope: Optional[Scope] = None,
    mesh=None,
    batch_axis: str = "dp",
) -> int:
    """Place restored host arrays onto `mesh`: vars carrying an explicit
    `.sharding` PartitionSpec keep it (axes the mesh lacks degrade to
    replicated, with one warning), everything else is replicated. The
    ZeRO re-slice of optimizer state is re-derived by the next
    ParallelExecutor step from ITS mesh — exactly why the checkpoint
    stores global arrays, not placement. Returns vars placed."""
    from jax.sharding import NamedSharding, PartitionSpec

    if mesh is None:
        raise ValueError("reshard_scope_to_mesh needs a target mesh")
    program = main_program or default_main_program()
    scope = scope or global_scope()
    axis_names = set(mesh.axis_names)
    warned = False
    n = 0
    for v in program.persistables():
        if not scope.has(v.name):
            continue
        val = scope.get(v.name)
        spec = getattr(v, "sharding", None)
        if spec is not None:
            used = {a for d in tuple(spec) if d is not None
                    for a in (d if isinstance(d, (tuple, list)) else (d,))}
            if not used <= axis_names:
                if not warned:
                    warned = True
                    logger.warning(
                        "reshard: dropping sharding axes %s absent from "
                        "the target mesh %s (vars fall back to "
                        "replicated)", sorted(used - axis_names),
                        sorted(axis_names))
                spec = None
        sharding = NamedSharding(mesh, spec or PartitionSpec())
        scope.set(v.name, jax.device_put(np.asarray(val), sharding))
        n += 1
    return n


def gather_handoff_rows(arrays, rows: int):
    """Device→host gather of the first ROWS rows of each array in a
    prefix-state tuple — the serving sibling of the checkpoint path
    above: state saved on one world (the prefill replica's mp/dp mesh)
    travels as plain host arrays, exactly like `sharded_meta.json`
    restores, so the admitting world never needs to know the saving
    mesh. One jax.device_get moves the whole tuple (a single d2h fence
    for the handoff, mirroring the scheduler's one-fence step loop);
    mesh-sharded prefix outputs all-gather here, which IS the reshard:
    the decode replica re-places from host onto its own devices."""
    host = jax.device_get(tuple(arrays))
    return tuple(np.asarray(a)[:rows] for a in host)


def restore_handoff_rows(arrays, mesh=None, batch_axis: str = "dp"):
    """Host→device placement of handoff state rows onto the ADMITTING
    world — `reshard_scope_to_mesh` for a prefix-state tuple instead of
    a program scope. With a mesh, rows are replicated across it (the
    decode pool is slot-indexed, not batch-sharded — the pool_admit
    dynamic-update owns distribution); without one, a plain device_put.
    A cross-world restore is observable via the same counter the
    checkpoint path increments."""
    if mesh is None:
        return tuple(jax.device_put(np.asarray(a)) for a in arrays)
    from jax.sharding import NamedSharding, PartitionSpec

    count_reshard()
    sharding = NamedSharding(mesh, PartitionSpec())
    return tuple(jax.device_put(np.asarray(a), sharding) for a in arrays)


def load_checkpoint_resharded(
    checkpoint_dir: str,
    main_program: Optional[Program] = None,
    scope: Optional[Scope] = None,
    mesh=None,
) -> Dict[str, Any]:
    """load_checkpoint + explicit placement onto a (possibly different)
    mesh. The newest-VALID-serial fallback, quarantine, and torn-shard
    handling all come from io.load_checkpoint; this adds only the
    device placement step for the restoring world."""
    args = io.load_checkpoint(checkpoint_dir, main_program, scope)
    if mesh is not None:
        reshard_scope_to_mesh(main_program, scope, mesh)
    return args


declare_reshard_counter()
