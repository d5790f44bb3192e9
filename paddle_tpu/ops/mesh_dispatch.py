"""Explicit batch-sharding policy for the fused Pallas kernels.

THE pallas-under-GSPMD rule for this framework: a Mosaic `pallas_call`
is an opaque custom call to the XLA SPMD partitioner — it cannot be
automatically partitioned, and relying on the partitioner means either
a lowering error or a silent full replication (all-gather the batch,
run the whole kernel on every device) the day a mesh appears. So under
a `ParallelExecutor` mesh every fused-kernel dispatch is wrapped in
`jax.shard_map` over the data-parallel axis — jax's own documented
pattern for pallas + sharding:

- batch-sharded operands in, batch-sharded activations out;
- weights replicated in; their cotangents are per-shard partial sums,
  and shard_map's transpose psums the cotangent of every replicated
  input over the mesh axis — in jax 0.9.0 also with check_vma off
  (pallas calls don't carry replication rules, so it is off here). The
  custom-VJP backwards therefore return the per-shard partial and
  nothing else. They used to psum it themselves, for an older jax that
  did not; on 0.9.0 that counted every weight gradient dp times over,
  which Adam's scale invariance hid from the loss-level tests until
  `chip_smoke.py --four-chips` compared gradients (PR 21;
  tests/test_mesh_fused_kernels.py now does so on the CPU mesh);
- eligibility is evaluated at the PER-SHARD batch (`local_batch`):
  what the kernel actually sees inside shard_map. Non-divisible or
  ineligible-at-local-batch configs fall back to the XLA scan
  formulations, which GSPMD partitions natively.

The executor threads the active mesh here via `active_mesh(...)` around
its trace (`core/executor.py` / `parallel/data_parallel.py`); op
kernels consult `current()`/`local_batch()` at trace time, exactly like
the FLAGS-based dispatch they sit next to.

Covered families: the fused LSTM/GRU kernels (pallas_kernels.py), the
fused Bahdanau decoder (bahdanau_kernels.py), and flash attention
(flash_ops.py — wrapped over dp only; it has no weight operands, so no
cotangent psums, and under an mp axis the wrap replicates heads — a
GSPMD-inserted reshard; head-sharding inside the wrap is a named
multi-chip lever).

Reference counterpart: MultiGradientMachine ran one replica per GPU
and ring-reduced gradients (gserver/gradientmachines/
MultiGradientMachine.h:63-110) — shard_map over dp + psum'd weight
cotangents is that same contract, expressed inside one SPMD program.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import NamedTuple, Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P


class ActiveMesh(NamedTuple):
    mesh: Mesh
    batch_axis: str

    @property
    def dp(self) -> int:
        return self.mesh.shape[self.batch_axis]


_ACTIVE: contextvars.ContextVar[Optional[ActiveMesh]] = \
    contextvars.ContextVar("pt_active_mesh", default=None)


@contextlib.contextmanager
def active_mesh(mesh: Mesh, batch_axis: str):
    """Executor hook: declares the mesh the current trace runs under."""
    tok = _ACTIVE.set(ActiveMesh(mesh, batch_axis))
    try:
        yield
    finally:
        _ACTIVE.reset(tok)


def current() -> Optional[ActiveMesh]:
    return _ACTIVE.get()


@contextlib.contextmanager
def no_mesh():
    """Clear the active-mesh context for code that is ALREADY running
    per-shard (inside its own shard_map): eligibility there must see
    the true local shapes, not divide them by dp a second time, and a
    nested shard_batch wrap would be an error. parallel/ring_attention
    brackets its per-shard inner attention with this."""
    tok = _ACTIVE.set(None)
    try:
        yield
    finally:
        _ACTIVE.reset(tok)


def dp_size() -> int:
    am = _ACTIVE.get()
    return am.dp if am is not None else 1


def local_batch(B: int) -> int:
    """The batch each kernel instance sees: B under no mesh, B/dp under
    a mesh, 0 (= every eligibility check fails -> scan fallback) when
    the dp axis does not divide the batch."""
    am = _ACTIVE.get()
    if am is None or am.dp == 1:
        return B
    return B // am.dp if B % am.dp == 0 else 0


def shard_batch(fn, batch_dims, out_dims, out_tree=None):
    """Wrap `fn` in shard_map over the active dp axis (identity without
    a mesh). `batch_dims[i]` is the batch dimension of positional arg i
    (None = replicated, e.g. weights); `out_dims` gives each flattened
    output's (batch_dim, ndim) — callers know their output ranks
    statically. `out_tree` (a treedef from jax.tree.structure on an
    example output) restores structure; None = single array output. The
    wrapped fn's custom-VJP backward returns per-shard partial
    cotangents for replicated inputs; shard_map's transpose sums them
    (see module docstring)."""
    am = _ACTIVE.get()
    if am is None or am.dp == 1:
        return fn
    ax = am.batch_axis

    def spec(d, ndim):
        if d is None:
            return P()
        return P(*(ax if i == d else None for i in range(ndim)))

    out_flat = [spec(d, nd) for d, nd in out_dims]
    out_specs = (out_flat[0] if out_tree is None
                 else jax.tree.unflatten(out_tree, out_flat))

    def wrapped(*args):
        in_specs = tuple(
            spec(d, arg.ndim) for arg, d in zip(args, batch_dims))
        return jax.shard_map(
            fn, mesh=am.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)(*args)

    return wrapped
