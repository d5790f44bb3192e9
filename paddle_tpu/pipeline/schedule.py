"""GPipe micro-batch schedule as ONE jitted lax.scan over the stage grid.

PR 6 made the step loop "a scan over steps"; this is the same move one
level down — the stage grid of GPipe (Huang et al.; PAPERS.md) is a scan
over T = M + K - 1 *ticks*. At tick t, stage s processes microbatch
m = t - s (masked out when m is outside [0, M): those are the fill/drain
bubble cells). The K per-tick stage bodies are Python-unrolled (K is
static), so XLA sees one fused tick program; `jax.value_and_grad`
through the scan IS the backward drain — the reverse-mode scan replays
ticks in reverse order, which is exactly GPipe's backward schedule, with
no hand-written grad routing.

Cross-stage activations ride the scan carry as device-resident boundary
buffers (never a host round-trip). On a mesh with a `pp` axis and
shape-homogeneous boundaries (the transformer case) the buffers are
stacked on a leading stage axis sharded over `pp`, so each boundary
lives on the pp slice that computes it; the microbatch axis composes
with the existing `dp` axis via batch-dim sharding constraints.

Determinism contract (the fixed-seed A/B in tests/test_pipeline.py):
for a fixed microbatch count M, params after a step are bit-identical
for every stage count K. Two mechanisms make this exact rather than
approximate: (1) masked accumulations add literal 0.0 for bubble cells
(x + 0.0 is exact in IEEE 754), and the reverse scan visits microbatch
gradient contributions in the same (descending) order for every K;
(2) RNG draws are keyed by (microbatch, global op index) — the probe
records each stage's op-counter offset so stage boundaries do not
reshuffle the per-op fold_in sequence. A parameter consumed by ops in
*different* stages (tied weights across a cut) interleaves its gradient
accumulation differently per K and voids the bitwise guarantee; the
balancer keeps whole params inside one stage, but a user cut can split
them — documented, not detected.

The guarantee is additionally sensitive to WHERE a cut lands, not just
what it separates: a cut between an op and the immediate consumer of
its freshly produced temporary (e.g. through the middle of an fc's
mul / bias-add pair) forces that cotangent across the scan carry,
which denies XLA the fusion it applies in the unstaged build and
reassociates the upstream gradient reductions (~1e-7 relative noise on
every upstream param — measured, deterministic per build, and not a
bug in either build). partition._narrow_cuts therefore snaps automatic
cuts to the narrowest nearby boundary (the transformer residual
stream), which restores exact bitwise identity; hand-placed
stage_boundary() markers are trusted as-is.
"""

from __future__ import annotations

import contextlib
import logging
import weakref
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.executor import (
    Executor, _BlockRunner, _REMAT_POLICIES,
)
from ..core.lod import LoDArray
from ..core.program import Program, grad_var_name
from .partition import StagedProgram, split_program

logger = logging.getLogger("paddle_tpu.pipeline")

SCHEDULES = ("gpipe", "1f1b")


class PipelineExecutor(Executor):
    """Executor that runs training programs as a K-stage, M-microbatch
    pipeline. Same `run()` / `run_window()` surface as the base Executor
    (the `_raw_step` override keeps the (donated, kept, feed, seed) signature,
    so the Trainer's fused scan windows compose: a window is a scan over
    steps of a scan over ticks). Programs without an `autodiff` op
    (inference, startup) fall through to the unstaged base path.

    schedule="1f1b": same tick grid, but each stage body is wrapped in
    jax.checkpoint so the backward drain *recomputes* stage forwards
    instead of keeping all M activation sets live — GPipe's schedule
    with 1F1B's peak-memory profile (true interleaved 1F1B needs
    per-stage manual placement, which this jax build's GSPMD-only mesh
    support cannot express; see HAS_SHARD_MAP in tests/conftest.py).
    """

    def __init__(
        self,
        place=None,
        num_stages: int = 2,
        num_microbatches: int = 4,
        mesh=None,
        schedule: str = "gpipe",
    ):
        super().__init__(place)
        if int(num_stages) < 1:
            raise ValueError(f"num_stages must be >= 1, got {num_stages}")
        if int(num_microbatches) < 1:
            raise ValueError(
                f"num_microbatches must be >= 1, got {num_microbatches}")
        if schedule not in SCHEDULES:
            raise ValueError(
                f"unknown pipeline schedule {schedule!r}; choose from "
                f"{SCHEDULES}")
        self.num_stages = int(num_stages)
        self.num_microbatches = int(num_microbatches)
        self.mesh = mesh
        self.schedule = schedule
        self._partitions: Dict[Any, Any] = {}
        self._dispatched = False
        self._warned_hetero = False
        if mesh is not None:
            axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            pp = axis_sizes.get("pp", 1)
            if pp > 1 and self.num_stages % pp:
                raise ValueError(
                    f"num_stages={self.num_stages} is not divisible by the "
                    f"mesh pp axis ({pp}) — stages cannot be laid out on "
                    "the pp slices")
            # same seam as ParallelExecutor (data_parallel.py): the
            # window path and the host-side prefetcher commit carries to
            # ONE device, which would gather mesh-resident state. The
            # trainer's loud fallback names this executor as the scaled
            # alternative; the meshless PipelineExecutor keeps all three.
            self.prefetch_by_default = False
            self.device_metric_accumulation = False
            self.scan_window_supported = False
        _register_pipeline_metrics(self)

    # -- executor hooks ------------------------------------------------
    def _cache_key_prefix(self) -> tuple:
        return (
            "pipe", self.num_stages, self.num_microbatches, self.schedule,
            id(self.mesh) if self.mesh is not None else 0,
        )

    def _device_context(self):
        if self.mesh is not None:
            return contextlib.nullcontext()
        return super()._device_context()

    def _trace_context(self):
        if self.mesh is not None:
            from ..ops import mesh_dispatch

            return mesh_dispatch.active_mesh(self.mesh, "dp")
        return super()._trace_context()

    # -- partition cache -----------------------------------------------
    def _staged(self, program: Program, fetch_names) -> StagedProgram:
        key = (id(program), program.version, self.num_stages,
               tuple(fetch_names))
        hit = self._partitions.get(key)
        if hit is None:
            staged = split_program(
                program, num_stages=self.num_stages,
                extra_targets=list(fetch_names))
            # strong program ref: the key uses id(program)
            self._partitions[key] = (program, staged)
            return staged
        return hit[1]

    # -- the staged step ------------------------------------------------
    def _raw_step(self, program: Program, fetch_names):
        has_autodiff = any(
            op.type == "autodiff" for op in program.global_block().ops)
        if not has_autodiff:
            # inference / startup / eval programs run unstaged
            return super()._raw_step(program, fetch_names)
        self._dispatched = True
        staged = self._staged(program, fetch_names)
        return self._staged_step(program, staged, list(fetch_names))

    def _staged_step(self, program, staged, fetch_names):
        runner = _BlockRunner(program)
        block = program.global_block()
        record = self._donation_record()
        K = staged.num_stages
        M = self.num_microbatches
        T = M + K - 1
        loss_name = staged.loss_name
        param_names = list(staged.param_names)
        mesh = self.mesh
        amp = program.amp_dtype
        stages = staged.stages

        # producing stage of every forward output (targets are collected
        # at their producing stage with that stage's active mask)
        produced_at: Dict[str, int] = {}
        for st in stages:
            for op in st.ops:
                for n in op.output_names():
                    produced_at.setdefault(n, st.index)
        targets = [
            n for n in dict.fromkeys(
                [loss_name, *staged.tail_fwd_names, *fetch_names])
            if n in produced_at
        ]

        remat_policy = getattr(program, "remat_policy", None)
        stage_remat = bool(remat_policy) or self.schedule == "1f1b"
        policy = _REMAT_POLICIES[remat_policy] if remat_policy else None

        axis_sizes = (dict(zip(mesh.axis_names, mesh.devices.shape))
                      if mesh is not None else {})
        dp_size = axis_sizes.get("dp", 1)
        pp_size = axis_sizes.get("pp", 1)

        def constrain(x, spec_list):
            """Best-effort GSPMD constraint; skipped off-mesh or when the
            named dim does not divide (XLA would reject the sharding)."""
            if mesh is None or not any(spec_list):
                return x
            from jax.sharding import NamedSharding, PartitionSpec

            for d, ax in enumerate(spec_list):
                if ax is not None and x.shape[d] % axis_sizes.get(ax, 1):
                    return x
            return lax.with_sharding_constraint(
                x, NamedSharding(mesh, PartitionSpec(*spec_list)))

        def raw(donated: Dict[str, Any], kept: Dict[str, Any],
                feed: Dict[str, Any], seed):
            state = {**kept, **donated}
            for n, v in feed.items():
                if isinstance(v, LoDArray):
                    raise NotImplementedError(
                        f"pipeline: LoD feed {n!r} — variable-length "
                        "batches cannot be split into fixed microbatches")
            missing = [p for p in param_names if p not in state]
            if missing:
                raise KeyError(
                    f"pipeline: params {missing} not in scope — run the "
                    "startup program first")
            # ---- microbatch split: (B, ...) -> (M, B//M, ...) --------
            feeds_mb: Dict[str, Any] = {}
            for n, v in feed.items():
                if getattr(v, "ndim", 0) < 1 or v.shape[0] % M:
                    raise ValueError(
                        f"pipeline: feed {n!r} batch dim "
                        f"{getattr(v, 'shape', ())} is not divisible by "
                        f"microbatches={M}")
                mb = jnp.reshape(v, (M, v.shape[0] // M) + tuple(v.shape[1:]))
                feeds_mb[n] = constrain(
                    mb, [None, "dp" if dp_size > 1 else None]
                    + [None] * (mb.ndim - 2))
            base_key = jax.random.PRNGKey(seed)

            # ---- probe: abstract chain of the K stages on microbatch 0.
            # Recovers (a) boundary avals (buffer shapes are not static
            # metadata: -1 batch dims resolve only at trace time), (b)
            # per-stage RNG op-counter offsets (the determinism contract
            # above), (c) target avals for scalar/stacked classification.
            # jax.eval_shape = zero FLOPs; the side effects are trace-time
            # Python (counter ints), exactly what we need to capture.
            rng_offsets = [0] * K

            def probe():
                env: Dict[str, Any] = {}
                env.update(state)
                env.update({n: feeds_mb[n][0] for n in feeds_mb})
                env["@RNG@"] = base_key
                env["@RNG_COUNTER@"] = 0
                env["@AMP@"] = amp
                outs = []
                for s, st in enumerate(stages):
                    rng_offsets[s] = env.get("@RNG_COUNTER@", 0)
                    runner.run_ops(st.ops, env, dict(env), block)
                    if s < K - 1:
                        outs.append([env[n] for n in st.out_names])
                return outs, {n: env[n] for n in targets}

            bound_avals, target_avals = jax.eval_shape(probe)
            scalar_t = [n for n in targets
                        if int(np.prod(target_avals[n].shape)) <= 1]
            stacked_t = [n for n in targets if n not in scalar_t]

            # homogeneous boundaries + a pp axis -> stack the K-1 buffers
            # (plus one unused pad slot so K divides pp) on a leading
            # stage axis sharded over pp: boundary s is device-resident
            # on the pp slice that owns stage s
            sigs = [tuple((tuple(a.shape), str(a.dtype)) for a in bo)
                    for bo in bound_avals]
            stacked_mode = (
                pp_size > 1 and K >= 2
                and all(s == sigs[0] for s in sigs)
            )
            if pp_size > 1 and K >= 2 and not stacked_mode \
                    and not self._warned_hetero:
                self._warned_hetero = True
                logger.warning(
                    "pipeline: boundary signatures differ across stages; "
                    "activation buffers stay pp-replicated (stacked "
                    "pp-sharded buffers need homogeneous boundaries)")

            def run_stage(s, env_sub):
                st = stages[s]

                def f(env_in):
                    env = dict(env_in)
                    env["@RNG_COUNTER@"] = rng_offsets[s]
                    env["@AMP@"] = amp
                    runner.run_ops(st.ops, env, dict(env), block)
                    bound = [env[n] for n in st.out_names] if s < K - 1 \
                        else []
                    tvals = {n: env[n] for n in targets
                             if produced_at[n] == s}
                    return bound, tvals

                if stage_remat:
                    # drain recomputes the stage forward instead of
                    # holding M activation sets (1F1B memory profile)
                    f = jax.checkpoint(f, policy=policy)
                return f(env_sub)

            def fwd(pvals):
                state_env = dict(state)
                state_env.update(pvals)
                scal0 = {n: jnp.zeros((), jnp.float32) for n in scalar_t}
                stk0 = {
                    n: jnp.zeros(
                        (M,) + tuple(target_avals[n].shape),
                        target_avals[n].dtype)
                    for n in stacked_t
                }
                if stacked_mode:
                    bufs0 = [
                        constrain(
                            jnp.zeros((K,) + shape, dtype),
                            ["pp", "dp" if dp_size > 1 and len(shape)
                             else None] + [None] * max(len(shape) - 1, 0))
                        for (shape, dtype) in sigs[0]
                    ]
                else:
                    bufs0 = [
                        {n: constrain(
                            jnp.zeros(a.shape, a.dtype),
                            ["dp" if dp_size > 1 else None]
                            + [None] * (len(a.shape) - 1))
                         for n, a in zip(stages[s].out_names, bound_avals[s])}
                        for s in range(K - 1)
                    ]

                def tick(carry, t):
                    prev, scal, stk = carry
                    # stage s READS boundary s-1 as of tick START (prev:
                    # the value stage s-1 wrote LAST tick — that is what
                    # makes m = t - s line up) and WRITES into bufs; an
                    # in-place update would leak this tick's stage-s
                    # output into stage s+1 a tick early
                    bufs = list(prev)
                    scal = dict(scal)
                    stk = dict(stk)
                    for s in range(K):  # static unroll: one fused tick
                        st = stages[s]
                        m_idx = t - s
                        active = jnp.logical_and(m_idx >= 0, m_idx < M)
                        m_c = jnp.clip(m_idx, 0, M - 1)
                        env_sub = {
                            n: state_env[n] for n in st.state_names
                            if n in state_env
                        }
                        for n in st.feed_names:
                            env_sub[n] = lax.dynamic_index_in_dim(
                                feeds_mb[n], m_c, 0, keepdims=False)
                        if s > 0:
                            if stacked_mode:
                                for j, n in enumerate(st.in_names):
                                    env_sub[n] = prev[j][s - 1]
                            else:
                                env_sub.update(prev[s - 1])
                        env_sub["@RNG@"] = jax.random.fold_in(base_key, m_c)
                        bound, tvals = run_stage(s, env_sub)
                        if s < K - 1:
                            if stacked_mode:
                                for j, v in enumerate(bound):
                                    new = jnp.where(active, v, prev[j][s])
                                    bufs[j] = bufs[j].at[s].set(new)
                            else:
                                bufs[s] = {
                                    n: jnp.where(active, v, prev[s][n])
                                    for n, v in zip(st.out_names, bound)
                                }
                        for n, v in tvals.items():
                            if n in scal:
                                scal[n] = scal[n] + jnp.where(
                                    active,
                                    jnp.reshape(v, ()).astype(jnp.float32),
                                    jnp.float32(0.0))
                            else:
                                old = lax.dynamic_index_in_dim(
                                    stk[n], m_c, 0, keepdims=False)
                                stk[n] = lax.dynamic_update_index_in_dim(
                                    stk[n], jnp.where(active, v, old),
                                    m_c, 0)
                    return (tuple(bufs), scal, stk), None

                (_, scal, stk), _ = lax.scan(
                    tick, (tuple(bufs0), scal0, stk0), jnp.arange(T))
                loss_mean = scal[loss_name] / M
                return loss_mean, (scal, stk, target_avals)

            pvals = {p: state[p] for p in param_names}
            (loss_mean, (scal, stk, tavals)), grads = jax.value_and_grad(
                fwd, has_aux=True)(pvals)

            # ---- optimizer tail: runs ONCE on the microbatch-mean loss
            # and accumulated grads — plain grad-accumulation semantics,
            # identical for every K (the A/B baseline is K=1, same M)
            env: Dict[str, Any] = {}
            env.update(state)
            env.update(feed)  # tail ops may read full-batch feeds
            for p in param_names:
                env[grad_var_name(p)] = grads[p]
            for n in scalar_t:
                mean = scal[n] / M
                env[n] = jnp.reshape(mean, tavals[n].shape).astype(
                    tavals[n].dtype)
            for n in stacked_t:
                v = stk[n]
                env[n] = jnp.reshape(v, (M * v.shape[1],) + v.shape[2:])
            env["@RNG@"] = jax.random.fold_in(base_key, M)
            env["@RNG_COUNTER@"] = 0
            env["@AMP@"] = amp
            runner.run_ops(staged.tail_ops, env, dict(env), block)

            fetches = []
            for n in fetch_names:
                if n not in env:
                    raise KeyError(
                        f"pipeline fetch {n!r} not produced by the staged "
                        "step (forward activations, persistables and tail "
                        "outputs are fetchable)")
                fetches.append(env[n])
            return (fetches,) + self._state_outputs(
                program, env, donated, kept, record)

        return raw


# -- observability -----------------------------------------------------------

def _register_pipeline_metrics(ex: PipelineExecutor) -> None:
    """Declare-at-construction: the bubble/occupancy families exist (at
    0) from the moment the executor does, before any step runs — a
    scraper never sees them appear mid-flight. Values are pure schedule
    math (K, M are static), so scraping NEVER syncs the device."""
    from ..obs import metrics as obs
    from .elastic import declare_reshard_counter

    # the elastic-restore counter is part of the same scrape contract:
    # re-declare here so it exists at 0 after any reset_metrics
    declare_reshard_counter()

    ref = weakref.ref(ex)

    def collect():
        e = ref()
        if e is None:
            return []
        k, m = e.num_stages, e.num_microbatches
        t = m + k - 1
        live = bool(e._dispatched)
        bubble = (k - 1) / t if live else 0.0
        occ = m / t if live else 0.0
        return [
            ("pt_pipeline_bubble_fraction", "gauge",
             "analytic GPipe bubble (K-1)/(M+K-1) of the active schedule "
             "(0 before the first staged dispatch)",
             [(None, bubble)]),
            ("pt_pipeline_stage_occupancy", "gauge",
             "fraction of schedule ticks each stage spends on real "
             "microbatches, M/(M+K-1) (0 before the first staged "
             "dispatch)",
             [({"stage": str(s)}, occ) for s in range(k)]),
        ]

    obs.registry().add_collector(collect)
    # keep the collector reachable exactly as long as the executor is
    ex._metrics_collector = collect
