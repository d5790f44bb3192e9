"""Training driver: pass/batch loops, events, testing, checkpoint cadence.

Reference surface:
- Gen-1 `Trainer::train/trainOnePass` (paddle/trainer/Trainer.cpp:265,496):
  pass loop → batch loop → forwardBackward → updater, per-pass Tester::test
  and ParameterUtil::saveParameters cadence.
- v2 `SGD.train(reader, event_handler)` (python/paddle/v2/trainer.py:137-216)
  with events (python/paddle/v2/event.py): BeginPass/EndPass and
  BeginIteration/EndIteration carrying cost + metrics.

TPU design: one Trainer over the (main, startup) program pair; each step is
one jitted program execution (Executor compile-caches per feed shape). Test
programs are `main.clone(for_test=True)`. Checkpoints capture the full
persistable Scope slice (optimizer state included) plus reader position
metadata, so preemption-resume continues mid-training (go/pserver
checkpointing design parity, §5.3/§5.4 of SURVEY.md).

Pipelined hot path (PERF.md "Async dispatch and the host-sync budget"):
the step loop never reads a fetch back to host per step. Fetches stay as
device arrays (`Executor.run(as_numpy=False)`), a jitted on-device
accumulator folds cost/metrics/non-finite-count, and the host fences the
dispatch queue only every `sync_every` steps (and at pass end). Batches
arrive through a DevicePrefetcher by default, and checkpoint commits run
on a background writer thread over a `jax.device_get` snapshot — the loop
blocks only if the previous checkpoint is still in flight. EndIteration
carries a lazy cost in cadence mode: handlers that format/compare it pay
the sync, handlers that only look at ids pay nothing. The ONLY sanctioned
`float(np.asarray(...))` sync points are `_host_read_step` /
`_PassStats.sync` / `_LazyScalar.materialize` — a lint test greps the
step loop for strays.
"""

from __future__ import annotations

import logging
import queue
import signal
import threading
import time
import weakref
from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import io
from . import profiler
from .obs import metrics as obs_metrics
from .obs import trace as obs_trace
from .core.executor import Executor, Scope, accum_fold, global_scope
from .flags import FLAGS
from .core.place import Place
from .core.program import (
    Program,
    Variable,
    default_main_program,
    default_startup_program,
    grad_var_name,
)
from .data.feeder import DataFeeder
from .resilience import NonFiniteError, PreemptedError, faults
from .resilience.guard import StepGuard

__all__ = [
    "BeginPass",
    "EndPass",
    "BeginIteration",
    "EndIteration",
    "CheckpointConfig",
    "Trainer",
]


# -- events (python/paddle/v2/event.py) -------------------------------------

class BeginPass:
    def __init__(self, pass_id: int):
        self.pass_id = pass_id


class EndPass:
    def __init__(self, pass_id: int, metrics: Dict[str, float]):
        self.pass_id = pass_id
        self.metrics = metrics


class BeginIteration:
    def __init__(self, pass_id: int, batch_id: int):
        self.pass_id = pass_id
        self.batch_id = batch_id


class EndIteration:
    """cost/metrics are plain floats on per-step-sync cadences and
    _LazyScalar wrappers otherwise — float()/format()/comparison/numpy
    coercion materialize them transparently, so existing handlers keep
    working; handlers that never touch them never fence dispatch."""

    def __init__(self, pass_id, batch_id, step, cost, metrics):
        self.pass_id = pass_id
        self.batch_id = batch_id
        self.step = step  # global step
        self.cost = cost
        self.metrics = metrics


class _LazyScalar:
    """A scalar fetch still living on device. Reading it (float, format,
    str, comparison, numpy coercion) is a host sync — it fences the XLA
    dispatch queue up to the step that produced it — so the pipelined
    loop hands these to event handlers instead of eagerly syncing."""

    __slots__ = ("_value", "_host", "_on_sync", "_index")

    def __init__(self, value, on_sync: Optional[Callable] = None,
                 index: Optional[int] = None):
        self._value = value
        self._host: Optional[float] = None
        self._on_sync = on_sync
        # index: the scalar is row `index` of a stacked per-window fetch.
        # The slice happens at materialize time, NOT construction — an
        # eager ys[i] would dispatch one device op per step and hand the
        # scan window's dispatch saving right back
        self._index = index

    def materialize(self) -> float:
        if self._host is None:
            if self._on_sync is not None:
                self._on_sync()
            with profiler.timer("lazyRead"):
                v = np.asarray(self._value)
            self._host = float(v if self._index is None else v[self._index])
            self._value = None  # drop the device ref once read
        return self._host

    def __float__(self):
        return self.materialize()

    def __format__(self, spec):
        return format(self.materialize(), spec)

    def __str__(self):
        return str(self.materialize())

    def __repr__(self):
        if self._host is None:
            return "<lazy device scalar (unread)>"
        return repr(self._host)

    def __array__(self, dtype=None):  # np.isfinite(event.cost) etc.
        return np.asarray(self.materialize(), dtype=dtype)

    def __eq__(self, other):
        return self.materialize() == float(other)

    def __lt__(self, other):
        return self.materialize() < float(other)

    def __le__(self, other):
        return self.materialize() <= float(other)

    def __gt__(self, other):
        return self.materialize() > float(other)

    def __ge__(self, other):
        return self.materialize() >= float(other)

    def __hash__(self):
        return hash(self.materialize())

    def __add__(self, other):
        return self.materialize() + other

    __radd__ = __add__

    def __sub__(self, other):
        return self.materialize() - other

    def __rsub__(self, other):
        return other - self.materialize()

    def __mul__(self, other):
        return self.materialize() * other

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self.materialize() / other

    def __rtruediv__(self, other):
        return other / self.materialize()


# One on-device accumulator fold: O(1) tiny-op dispatch per step, zero
# host work. The math lives in core.executor.accum_fold — the SAME pure
# function the windowed executor folds inside its lax.scan carry, so the
# per-step and scan-window cadences cannot drift numerically.
_accum_update = partial(jax.jit, static_argnames="skip_nonfinite")(accum_fold)


class _PassStats:
    """Per-pass cost/metric accumulation with explicit host-sync points.

    device=True (base Executor): state lives on device, `update` enqueues
    one jitted fold, `sync` is THE d2h fence. device=False
    (ParallelExecutor — mesh-committed fetches can't join a single-device
    accumulator): every update materializes, i.e. the legacy per-step
    behavior. Either way the host-side bookkeeping (steps seen / bad
    seen) feeds the StepGuard's window observation."""

    def __init__(self, n_metrics: int, skip_nonfinite: bool,
                 device: bool = True, on_sync: Optional[Callable] = None,
                 statistics: Sequence[dict] = ()):
        self.device = device
        self.skip_nonfinite = bool(skip_nonfinite)
        self.on_sync = on_sync
        self.steps = 0         # steps folded in
        self.synced_steps = 0  # steps whose outcome the host has seen
        self.synced_bad = 0
        self.n_metrics = n_metrics
        self.host = (0, 0.0, [0.0] * n_metrics, 0)  # (n, Σcost, Σm, bad)
        # the program's step statistics (Program.add_step_statistic) ride
        # behind the metrics: int32 vectors summed in the same fold, read
        # in the same sync, and published there as labelled counters
        self.statistics = [dict(s) for s in statistics]
        self.stat_sums = [np.zeros(s["shape"], np.int64)
                          for s in self.statistics]
        if device:
            z = jnp.zeros((), jnp.int32)
            zf = jnp.zeros((), jnp.float32)
            self.state = (z, zf, [zf] * n_metrics + [
                jnp.zeros(s["shape"], jnp.int32) for s in self.statistics], z)

    def update(self, cost, metrics) -> None:
        """`metrics`: the step's metric fetches, then its statistics."""
        self.steps += 1
        if self.device:
            self.state = _accum_update(
                self.state, cost, list(metrics),
                skip_nonfinite=self.skip_nonfinite)
            return
        # host path: one sync per step by construction
        if self.on_sync is not None:
            self.on_sync()
        c = float(np.asarray(cost))
        finite = bool(np.isfinite(c))
        good = finite or not self.skip_nonfinite
        n, cs, ms, bad = self.host
        if good:
            n += 1
            cs += c
            ms = [m + float(np.asarray(v)) for m, v in zip(ms, metrics)]
            self._publish([t + np.asarray(v) for t, v in zip(
                self.stat_sums, metrics[self.n_metrics:])])
        self.host = (n, cs, ms, bad + (0 if finite else 1))

    def publish_step(self, values, cost: float) -> None:
        """A per-step sync path has this step's statistics on the host
        already (the cost read fenced the step): publish them now, in step
        with what the device fold does, so that the next `sync` finds
        nothing left to add."""
        if self.statistics and (np.isfinite(cost) or not self.skip_nonfinite):
            self._publish([t + np.asarray(v) for t, v in zip(
                self.stat_sums, values)])

    def _publish(self, totals) -> None:
        """Hand what the statistics grew by since the last call to the
        metrics registry. `totals`: the pass's running sums; the device's
        are int32, so a difference is taken modulo 2^32."""
        from .obs import metrics as obs_metrics

        reg = obs_metrics.registry()
        for stat, old, new in zip(self.statistics, self.stat_sums, totals):
            new = np.asarray(new, np.int64)
            grown = (new - old) % 2**32
            for i in np.flatnonzero(grown):
                reg.counter_inc(
                    stat["counter"], float(grown[i]), help=stat["help"],
                    labels={**stat["labels"], stat["index_label"]: int(i)})
        self.stat_sums = [np.asarray(t, np.int64) for t in totals]

    def absorb_window(self, new_state, k: int) -> None:
        """Scan-window path: the executor folded k steps into the
        accumulator INSIDE its compiled window — adopt the returned
        carry. No dispatch, no sync; `sync` stays the only fence."""
        assert self.device, "scan windows require the device accumulator"
        self.state = new_state
        self.steps += int(k)

    def pending(self) -> int:
        return self.steps - self.synced_steps

    def note_observed(self, bad: bool) -> None:
        """A per-step sync path already told the guard about this step —
        advance the window markers so the next cadence sync doesn't
        re-report it."""
        self.synced_steps += 1
        if bad:
            self.synced_bad += 1

    def sync(self):
        """Materialize the accumulator (the sanctioned d2h fence) and
        return (n_good, n_bad) for the window since the previous sync."""
        if self.device:
            if self.on_sync is not None:
                self.on_sync()
            n, cs, ms, bad = jax.device_get(self.state)
            self.host = (int(n), float(cs),
                         [float(m) for m in ms[:self.n_metrics]], int(bad))
            self._publish(ms[self.n_metrics:])
        delta_total = self.steps - self.synced_steps
        # per-step observation tracks cost-only finiteness (mirroring the
        # device counter); clamp so a grads-only bad verdict from the
        # stats path can never push the window delta negative
        delta_bad = max(0, self.host[3] - self.synced_bad)
        delta_bad = min(delta_bad, delta_total)
        self.synced_steps = self.steps
        self.synced_bad = self.host[3]
        return delta_total - delta_bad, delta_bad

    def pass_metrics(self, metric_names: Sequence[str]) -> Dict[str, float]:
        n, cost_sum, msums, _ = self.host
        out = {"cost": cost_sum / n if n else float("nan")}
        denom = max(n, 1)
        for k, s in zip(metric_names, msums):
            out[k] = s / denom
        return out


def _poison_feed(feed: Dict[str, Any]) -> Dict[str, Any]:
    """faults `executor.step` action=corrupt: NaN-poison the first feed
    slot with a floating dtype (deterministic non-finite injection — the
    chaos-test counterpart of a bad batch / overflowed loss)."""
    def _is_float(a):
        return hasattr(a, "dtype") and np.issubdtype(
            np.dtype(a.dtype), np.floating)

    out = dict(feed)
    for k in sorted(out):
        if any(_is_float(l) for l in jax.tree_util.tree_leaves(out[k])):
            out[k] = jax.tree_util.tree_map(
                lambda a: a * np.nan if _is_float(a) else a, out[k])
            return out
    return out


def _poison_window_slot(feed: Dict[str, Any], i: int) -> Dict[str, Any]:
    """Windowed counterpart of _poison_feed: NaN-poison step i of the
    stacked window in the first float feed slot (fault injection must hit
    exactly one step so the guard's ≤1-window detection bound is what the
    chaos test actually measures)."""
    def _is_float(a):
        return hasattr(a, "dtype") and np.issubdtype(
            np.dtype(a.dtype), np.floating)

    out = dict(feed)
    for k in sorted(out):
        if any(_is_float(l) for l in jax.tree_util.tree_leaves(out[k])):
            out[k] = jax.tree_util.tree_map(
                lambda a: a.at[i].set(a[i] * np.nan) if _is_float(a) else a,
                out[k])
            return out
    return out


class _CheckpointWriter:
    """Single background checkpoint committer.

    The step loop hands it a host snapshot (already `jax.device_get`,
    so the device is not involved) and keeps training while the
    npz+sha256+atomic-rename commit — the existing io.save_checkpoint
    machinery — runs on this thread. `submit` waits for the PREVIOUS
    commit first: at most one snapshot is being written while the next
    one is being captured (the double buffer), so checkpoint cadence can
    never queue unbounded host copies. A failed commit surfaces on the
    training thread at the next submit/drain."""

    def __init__(self):
        self._q: "queue.Queue" = queue.Queue(maxsize=1)
        self._idle = threading.Event()
        self._idle.set()
        self._exc: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        # commit accounting for the unified metrics registry
        # (pt_ckpt_commits_total / pt_ckpt_failures_total gauges)
        self.commits = 0
        self.failures = 0

    def _loop(self):
        while True:
            fn = self._q.get()
            try:
                fn()
                self.commits += 1
            except BaseException as e:  # surfaced on the training thread
                self.failures += 1
                self._exc = e
            finally:
                self._idle.set()

    def submit(self, fn: Callable[[], Any]) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="ptpu-ckpt-writer")
            self._thread.start()
        self.drain()  # block only if the previous commit is in flight
        if obs_trace._armed:
            # hand the submitting thread's correlation ids (step/window)
            # across to the writer thread: the commit span then links to
            # the step that snapshotted it in the exported timeline
            ctx = obs_trace.get_context()
            inner = fn

            def fn():
                obs_trace.set_context(**ctx)
                with obs_trace.span("checkpointCommit", cat="ckpt"):
                    inner()
        self._idle.clear()
        self._q.put(fn)

    def drain(self) -> None:
        """Wait until no commit is in flight; re-raise a failed one."""
        self._idle.wait()
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise RuntimeError(
                "background checkpoint write failed") from exc


class CheckpointConfig:
    """Cadence flags (Gen-1 `saving_period`/`saving_period_by_batches`/
    `save_dir`, Trainer.cpp:60-64)."""

    def __init__(
        self,
        checkpoint_dir: str,
        epoch_interval: int = 1,
        step_interval: int = 0,
        max_num_checkpoints: int = 3,
        sharded: bool = False,
        background: bool = True,
    ):
        self.checkpoint_dir = checkpoint_dir
        self.epoch_interval = epoch_interval
        self.step_interval = step_interval
        self.max_num_checkpoints = max_num_checkpoints
        # orbax-style per-shard format: each process writes only the
        # shards it owns (required for multi-process training — a plain
        # gathered npz would race across writers and cannot read
        # non-addressable arrays)
        self.sharded = sharded
        # background=True hands the disk commit to a writer thread over a
        # device_get snapshot, so the step loop stalls only for the d2h
        # copy, not the serialization+fsync. Single-process sharded saves
        # background too, via a device-side copy of the state (the steps
        # that follow donate the live buffers) whose d2h happens on the
        # writer thread. Multi-process
        # sharded saves stay synchronous: their cross-process barriers
        # must run on the thread every process is blocking on.
        self.background = background


class Trainer:
    """Drives training of `fetch_list[0]` (the cost) over a reader.

    reader yields batches of sample tuples aligned with `feed_order`
    (DataFeeder handles dense/ragged conversion), or — if `feed_order` is
    None — ready feed dicts.
    """

    def __init__(
        self,
        cost: Variable,
        main_program: Optional[Program] = None,
        startup_program: Optional[Program] = None,
        place: Optional[Place] = None,
        scope: Optional[Scope] = None,
        checkpoint_config: Optional[CheckpointConfig] = None,
        executor: Optional[Executor] = None,
        step_guard: Optional[StepGuard] = None,
    ):
        self.cost = cost
        self.main_program = main_program or default_main_program()
        self.startup_program = startup_program or default_startup_program()
        self.scope = scope or global_scope()
        self.exe = executor or Executor(place)
        self.test_program = self.main_program.clone(for_test=True)
        self.checkpoint_config = checkpoint_config
        # non-finite containment (resilience.StepGuard): explicit, or
        # the default policy when FLAGS.step_guard is on
        if step_guard is None and FLAGS.step_guard:
            step_guard = StepGuard()
        self.step_guard = step_guard
        self._stop = False
        self._preempt_signal: Optional[int] = None
        self.step = 0  # global batch counter across passes
        self.start_pass = 0
        self._resume_batch = 0  # first batch to run in the resumed pass
        self._initialized = False
        self._ckpt_writer = _CheckpointWriter()
        # host-sync accounting: every sanctioned d2h fence (per-step
        # reads, cadence syncs, lazy-cost materializations) increments
        # this — tests/test_async_trainer.py asserts the async loop
        # fences strictly less often than the sync loop
        self.host_sync_count = 0
        # host-dispatch accounting: every Executor.run / run_window the
        # step loop issues. The scan-window acceptance test is counted in
        # THIS unit: K fused steps = 1 dispatch (tests/test_scan_trainer.py
        # asserts scan < async dispatches)
        self.host_dispatch_count = 0
        self._register_obs_gauges()

    def _register_obs_gauges(self) -> None:
        """Publish the trainer's counter surface into the unified
        metrics registry (ISSUE 8): the SAME numbers bench and the A/B
        tests assert on become scrapeable/loggable. Registered through a
        weakref so a dead trainer's series disappears instead of pinning
        the object; a newer trainer takes the names over."""
        reg = obs_metrics.registry()
        ref = weakref.ref(self)

        def read(fn):
            def _get():
                t = ref()
                return None if t is None else float(fn(t))
            return _get

        reg.gauge("pt_trainer_step", read(lambda t: t.step),
                  help="global step counter of the live trainer")
        reg.gauge("pt_trainer_dispatches_total",
                  read(lambda t: t.host_dispatch_count),
                  help="XLA program dispatches issued by the step loop")
        reg.gauge("pt_trainer_syncs_total",
                  read(lambda t: t.host_sync_count),
                  help="host d2h fences paid by the step loop")
        reg.gauge("pt_ckpt_commits_total",
                  read(lambda t: t._ckpt_writer.commits),
                  help="background checkpoint commits completed")
        reg.gauge("pt_ckpt_failures_total",
                  read(lambda t: t._ckpt_writer.failures),
                  help="background checkpoint commits that failed")
        reg.gauge("pt_guard_skipped_total",
                  read(lambda t: t.step_guard.skipped
                       if t.step_guard else 0),
                  help="non-finite steps skipped by the StepGuard")
        reg.gauge("pt_guard_rollbacks_total",
                  read(lambda t: t.step_guard.rollbacks
                       if t.step_guard else 0),
                  help="StepGuard checkpoint rollbacks performed")
        # elastic-restore accounting is a counter owned by io/pipeline;
        # re-declaring here keeps it scrapeable at 0 from the moment a
        # trainer exists, whatever reset_metrics/construction order ran
        from .pipeline.elastic import declare_reshard_counter

        declare_reshard_counter()

    # -- periodic stats line (ISSUE 8: training runs get the same
    # observability surface serving scrapes) ------------------------------
    def _log_stats(self) -> None:
        g = self.step_guard.stats() if self.step_guard is not None else {}
        logging.getLogger("paddle_tpu.stats").info(
            "step=%d dispatches=%d syncs=%d ckpt_commits=%d "
            "ckpt_failures=%d guard_skipped=%d guard_rollbacks=%d "
            "trace_dropped=%d",
            self.step, self.host_dispatch_count, self.host_sync_count,
            self._ckpt_writer.commits, self._ckpt_writer.failures,
            g.get("skipped", 0), g.get("rollbacks", 0),
            obs_trace.dropped_total())

    def _maybe_log_stats(self, k: int = 1) -> None:
        """Emit the stats line when the last k steps crossed a multiple
        of FLAGS.stats_period (host-side ints only — no device sync)."""
        sp = FLAGS.stats_period
        if sp and (self.step // sp) > ((self.step - k) // sp):
            self._log_stats()

    # uniform counter surface: bench, the A/B tests, and the serving
    # layer's /stats read dispatch/sync totals under the same names
    @property
    def dispatches_total(self) -> int:
        return self.host_dispatch_count

    @property
    def syncs_total(self) -> int:
        return self.host_sync_count

    # -- lifecycle ---------------------------------------------------------
    def init(self) -> "Trainer":
        """Run startup (parameter init), or resume from the newest checkpoint
        if checkpoint_config points at one (init_model_path/start_pass
        parity, ParamUtil.h:105-111)."""
        self.exe.run_startup(self.startup_program, scope=self.scope)
        cc = self.checkpoint_config
        if cc and io.get_latest_checkpoint_serial(cc.checkpoint_dir) >= 0:
            args = io.load_checkpoint(
                cc.checkpoint_dir, self.main_program, self.scope
            )
            self.step = int(args.get("step", 0))
            if args.get("mid_pass"):
                # step_interval checkpoint: re-enter the interrupted pass and
                # skip the batches already trained (deterministic readers
                # replay; the Go-master equivalent re-dispatches tasks)
                self.start_pass = int(args.get("pass_id", 0))
                self._resume_batch = int(args.get("batch_id", -1)) + 1
            else:
                self.start_pass = int(args.get("pass_id", -1)) + 1
        self._initialized = True
        return self

    def stop(self):
        """Callable from an event handler to end training (v2 trainer.stop)."""
        self._stop = True

    # -- sync-cadence resolution -------------------------------------------
    def _count_sync(self) -> None:
        self.host_sync_count += 1

    def _resolve_sync_every(self, log_interval: Optional[int]) -> int:
        """Host-sync cadence of the step loop. Explicit `log_interval`
        wins, then FLAGS.sync_every (PT_FLAGS_SYNC_EVERY), then auto:
        a StepGuard-armed run keeps the exact per-step check (its tests
        and semantics are step-granular), everything else follows
        log_period — the cadence at which anyone looks at the numbers."""
        if log_interval is not None:
            return max(1, int(log_interval))
        if FLAGS.sync_every > 0:
            return int(FLAGS.sync_every)
        if self.step_guard is not None:
            return 1
        return max(1, int(FLAGS.log_period))

    def _resolve_scan_window(self, scan_window: Optional[int]) -> int:
        """Window size K of the fused (lax.scan) step loop. Explicit
        `scan_window` wins, then FLAGS.scan_window (PT_FLAGS_SCAN_WINDOW /
        CLI --scan_window). 0 = the per-step loop. Resolution only — the
        executor-capability and param-stats gates live in _train."""
        k = scan_window if scan_window is not None else FLAGS.scan_window
        return max(0, int(k))

    # -- training ----------------------------------------------------------
    def train(
        self,
        reader: Callable,
        num_passes: int,
        feed_order: Optional[Sequence[Variable]] = None,
        event_handler: Optional[Callable] = None,
        fetch_metrics: Optional[Dict[str, Variable]] = None,
        test_reader: Optional[Callable] = None,
        prefetch_to_device: Optional[int] = None,
        log_interval: Optional[int] = None,
        scan_window: Optional[int] = None,
    ) -> Dict[str, float]:
        """Pass/batch loop. Returns the final EndPass metrics dict.

        prefetch_to_device enables the async double-buffered host→device
        pipeline (DataProvider.h:375 parity) with that queue depth —
        batch N+1's transfer overlaps batch N's compute. Default (None):
        FLAGS.prefetch_to_device (2) on executors that don't own input
        placement themselves; 0 disables.

        log_interval sets the host-sync cadence: cost/metrics accumulate
        on device and are read back every `log_interval` steps (and at
        pass end). Default (None) resolves via FLAGS.sync_every /
        log_period; 1 is the fully synchronous legacy loop.

        scan_window=K fuses K steps into ONE compiled program (a
        lax.scan over a device-resident window of K stacked batches):
        one host dispatch per window, metric accumulator and non-finite
        counter inside the scan carry, host syncs only at window edges
        on the log_interval/sync_every cadence. Default (None) resolves
        via FLAGS.scan_window; 0 disables. Fixed-seed runs produce
        bit-identical parameters to the per-step loop; checkpoint
        cadence and StepGuard detection quantize to window boundaries,
        and events/stop() are delivered per window (a stop or SIGTERM
        finishes the in-flight window first).

        Preemption: while training runs (main thread only), SIGTERM and
        SIGINT are translated into finish-the-current-batch → emergency
        mid-pass checkpoint (when checkpoint_config is set) →
        PreemptedError; the CLI maps that to exit code 75 (EX_TEMPFAIL)
        so schedulers reschedule instead of paging. The background
        checkpoint writer is drained before the error propagates, so the
        emergency save is durable by exit 75. Resume rides the normal
        checkpoint machinery (`init()`)."""
        if not self._initialized:
            self.init()
        self._stop = False
        self._preempt_signal = None
        installed: Dict[int, Any] = {}
        if threading.current_thread() is threading.main_thread():
            def _on_preempt(signum, frame):
                self._preempt_signal = signum
                self._stop = True

            for s in (signal.SIGTERM, signal.SIGINT):
                try:
                    installed[s] = signal.signal(s, _on_preempt)
                except (ValueError, OSError):  # exotic embeddings
                    pass
        try:
            return self._train(reader, num_passes, feed_order,
                               event_handler, fetch_metrics, test_reader,
                               prefetch_to_device, log_interval,
                               scan_window)
        finally:
            for s, h in installed.items():
                signal.signal(s, h)

    # the ONLY per-step d2h fence, and deliberately not inlined in _train:
    # the lint test asserts the step loop body contains no raw
    # float(np.asarray(...)) readbacks outside the sanctioned helpers
    def _host_read_step(self, cost_dev, metric_devs) -> tuple:
        self._count_sync()
        cost = float(np.asarray(cost_dev))
        return cost, [float(np.asarray(v)) for v in metric_devs]

    def _train(
        self,
        reader: Callable,
        num_passes: int,
        feed_order: Optional[Sequence[Variable]] = None,
        event_handler: Optional[Callable] = None,
        fetch_metrics: Optional[Dict[str, Variable]] = None,
        test_reader: Optional[Callable] = None,
        prefetch_to_device: Optional[int] = None,
        log_interval: Optional[int] = None,
        scan_window: Optional[int] = None,
    ) -> Dict[str, float]:
        handler = event_handler or (lambda e: None)
        feeder = DataFeeder(feed_order) if feed_order is not None else None
        metric_items = sorted((fetch_metrics or {}).items())
        metric_names = [k for k, _ in metric_items]
        # what the program's layers registered to be counted every step
        # rides behind the metrics in the same fetch, fold and sync
        gb = self.main_program.global_block()
        statistics = [
            dict(s, shape=tuple(gb.var(s["var"]).shape))
            for s in getattr(self.main_program, "step_statistics", ())]
        fetch_list = [self.cost] + [v for _, v in metric_items] + [
            gb.var(s["var"]) for s in statistics]
        last_metrics: Dict[str, float] = {}
        guard = self.step_guard
        device_acc = getattr(self.exe, "device_metric_accumulation", True)
        if prefetch_to_device is None:
            prefetch_to_device = (
                FLAGS.prefetch_to_device
                if getattr(self.exe, "prefetch_by_default", True) else 0)
        sync_every = self._resolve_sync_every(log_interval)
        scan_k = self._resolve_scan_window(scan_window)
        if scan_k and not (
                getattr(self.exe, "scan_window_supported", False)
                and device_acc):
            # mesh executors own input placement and their committed
            # fetches can't ride a single-device scan carry — the window
            # path is explicitly disabled there until it is threaded
            # through the mesh (loud, not silent: perf knobs that no-op
            # quietly cost days of confusion)
            logging.getLogger("paddle_tpu.trainer").warning(
                "scan_window=%d requested but %s does not support fused "
                "step windows — falling back to the per-step loop. For "
                "fused multi-step dispatch at scale, the meshless "
                "pipeline.PipelineExecutor supports scan windows (a "
                "window there is a scan over steps of the stage-grid "
                "scan); see `paddle_tpu train --mesh dp2,pp2 "
                "--microbatches M`",
                scan_k, type(self.exe).__name__)
            scan_k = 0
        if scan_k and FLAGS.show_param_stats_period:
            logging.getLogger("paddle_tpu.trainer").warning(
                "scan_window disabled: show_param_stats_period needs "
                "per-step gradient fetches the fused window does not "
                "surface")
            scan_k = 0

        for pass_id in range(self.start_pass, num_passes):
            handler(BeginPass(pass_id))
            acc = _PassStats(len(metric_items),
                             skip_nonfinite=guard is not None,
                             device=device_acc, on_sync=self._count_sync,
                             statistics=statistics)
            skip_until = self._resume_batch
            self._resume_batch = 0  # only the resumed pass skips
            if scan_k:
                last_batch_id, interrupted_mid_pass = self._scan_pass(
                    pass_id, reader, feeder, scan_k, acc, fetch_list,
                    metric_names, handler, guard, sync_every, skip_until,
                    prefetch_to_device)
            else:
                last_batch_id, interrupted_mid_pass = self._step_pass(
                    pass_id, reader, feeder, acc, fetch_list, metric_names,
                    handler, guard, sync_every, skip_until,
                    prefetch_to_device)
            # pass end: materialize whatever the cadence hasn't yet
            if acc.pending() or acc.device:
                with profiler.timer("hostSync"):
                    n_good, n_bad = acc.sync()
                if guard is not None and not guard.observe_window(
                        n_good, n_bad, scope=self.scope):
                    if guard.wants_rollback():
                        self._rollback(guard)
            last_metrics = acc.pass_metrics(metric_names)
            if test_reader is not None and self._preempt_signal is None:
                # a preempted run skips the evaluation pass: the grace
                # window between SIGTERM and SIGKILL is for the
                # emergency checkpoint, not for metrics
                test_metrics = self.test(test_reader, feed_order, fetch_metrics)
                last_metrics.update({f"test_{k}": v for k, v in test_metrics.items()})
            handler(EndPass(pass_id, last_metrics))
            cc = self.checkpoint_config
            if self._stop:
                # interrupted mid-pass: checkpoint must record the batch
                # position so resume re-enters this pass, not the next one.
                # A stop() issued from the EndPass handler (canonical v2
                # early-stop) left the pass COMPLETE — save end-of-pass.
                if cc:
                    if interrupted_mid_pass:
                        # batch_id may be -1 (stopped before the first
                        # batch): resume then re-enters this pass at 0
                        self._save_checkpoint(pass_id, batch_id=last_batch_id)
                    else:
                        self._save_checkpoint(pass_id)
                break
            if cc and cc.epoch_interval and (pass_id + 1) % cc.epoch_interval == 0:
                self._save_checkpoint(pass_id)
        # every submitted checkpoint must be durable before we report
        # completion — and before exit 75 hands the job back to the
        # scheduler (the emergency save is the resume point)
        self._ckpt_writer.drain()
        if self._preempt_signal is not None:
            try:
                signame = signal.Signals(self._preempt_signal).name
            except ValueError:
                signame = f"signal {self._preempt_signal}"
            raise PreemptedError(
                signame, checkpointed=self.checkpoint_config is not None)
        return last_metrics

    def _step_pass(
        self,
        pass_id: int,
        reader: Callable,
        feeder: Optional[DataFeeder],
        acc: "_PassStats",
        fetch_list,
        metric_names,
        handler: Callable,
        guard: Optional[StepGuard],
        sync_every: int,
        skip_until: int,
        prefetch_to_device: int,
    ):
        """One pass of the per-step (PR 5 pipelined) loop. Returns
        (last_batch_id, interrupted_mid_pass) for the shared pass-end
        logic in _train."""
        last_batch_id = -1
        interrupted_mid_pass = False
        if prefetch_to_device:
            from .data.feeder import DevicePrefetcher

            batches = iter(
                DevicePrefetcher(reader, feeder, depth=prefetch_to_device)
            )
        else:
            batches = reader()
        for batch_id, data in enumerate(batches):
            if self._stop:
                interrupted_mid_pass = True
                break
            last_batch_id = batch_id
            if batch_id < skip_until:
                continue
            self._maybe_log_stats()
            if obs_trace._armed:
                # correlation ids for every span this step records —
                # prepareBatchData/forwardBackward/hostSync timers and
                # the checkpoint snapshot/commit all carry them; the
                # prefetcher producer thread tags the same batch index
                obs_trace.set_context(pass_id=pass_id, batch=batch_id,
                                      step=self.step + 1)
            handler(BeginIteration(pass_id, batch_id))
            with profiler.timer("prepareBatchData"):
                if prefetch_to_device:
                    feed = data  # already converted + on device
                else:
                    feed = feeder.feed(data) if feeder else data
            sp = FLAGS.show_param_stats_period
            want_stats = bool(sp) and (self.step + 1) % sp == 0
            step_fetch = list(fetch_list)
            stat_params = []
            if want_stats:
                # grad vars are jit temporaries, not scope residents —
                # fetch them explicitly on stats steps. Only params the
                # autodiff op actually differentiates have grad vars
                # (frozen/unconnected params do not).
                trained = set()
                for block in self.main_program.blocks:
                    for op in block.ops:
                        if op.type == "autodiff":
                            trained |= set(op.attrs.get("params", ()))
                stat_params = [
                    p.name
                    for p in self.main_program.parameters()
                    if p.name in trained
                ]
                step_fetch += [grad_var_name(p) for p in stat_params]
            if faults.fire("executor.step", step=self.step) == "corrupt":
                feed = _poison_feed(feed)
            # enqueue only: fetches stay on device, the span measures the
            # host's side of the step (split by the executor.* spans
            # inside it); device wait shows up in whatever fences next
            with profiler.timer("forwardBackward"):
                outs = self.exe.run(
                    self.main_program,
                    feed=feed,
                    fetch_list=step_fetch,
                    scope=self.scope,
                    as_numpy=False,
                )
            self.host_dispatch_count += 1
            cost_dev = outs[0]
            grads = None
            if want_stats:
                # reference: TrainerInternal.cpp:81-109 param stats dump
                grads = dict(zip(stat_params, outs[len(fetch_list):]))
                outs = outs[: len(fetch_list)]
                for pname, st in profiler.parameter_stats(
                    self.main_program, self.scope, grads=grads
                ).items():
                    print(f"  param {pname}: " + ", ".join(
                        f"{k}={v:.4g}" for k, v in st.items()))
            with profiler.timer("accumUpdate"):
                acc.update(cost_dev, outs[1:])
            metric_devs = outs[1:1 + len(metric_names)]
            # per-step sync: legacy cadence, a hot StepGuard (open
            # streak / cool-down), or a stats step (it prints anyway)
            per_step = (sync_every == 1 or want_stats
                        or (guard is not None and guard.in_cooldown()))
            if per_step:
                with profiler.timer("hostSync"):
                    cost, metric_vals = self._host_read_step(
                        cost_dev, metric_devs)
                    if acc.device:
                        acc.publish_step(outs[1 + len(metric_names):], cost)
                if guard is not None:
                    ok = guard.observe(cost, grads, scope=self.scope)
                    acc.note_observed(not np.isfinite(cost))
                    if not ok:
                        # non-finite step: it is consumed (step counter,
                        # events) but contributes nothing to the pass
                        # stats (the accumulator gated it out) and NEVER
                        # triggers the checkpoint cadence — poisoned
                        # params must not become the "last good
                        # checkpoint" a rollback would then restore
                        self.step += 1
                        handler(EndIteration(
                            pass_id, batch_id, self.step, cost, {}))
                        if guard.wants_rollback():
                            self._rollback(guard)
                        continue
                batch_metrics = dict(zip(metric_names, metric_vals))
                self.step += 1
                handler(EndIteration(
                    pass_id, batch_id, self.step, cost, batch_metrics))
            else:
                self.step += 1
                lazy_cost = _LazyScalar(cost_dev, self._count_sync)
                handler(EndIteration(
                    pass_id, batch_id, self.step, lazy_cost,
                    {k: _LazyScalar(v, self._count_sync)
                     for k, v in zip(metric_names, metric_devs)}))
                if acc.pending() >= sync_every:
                    with profiler.timer("hostSync"):
                        n_good, n_bad = acc.sync()
                    if guard is not None and not guard.observe_window(
                            n_good, n_bad, scope=self.scope):
                        if guard.wants_rollback():
                            self._rollback(guard)
                        continue  # dirty window: no checkpoint either
            cc = self.checkpoint_config
            if cc and cc.step_interval and self.step % cc.step_interval == 0:
                if guard is not None and acc.pending():
                    # the cadence landed between syncs: learn the
                    # window's outcome before persisting anything
                    with profiler.timer("hostSync"):
                        n_good, n_bad = acc.sync()
                    if not guard.observe_window(
                            n_good, n_bad, scope=self.scope):
                        if guard.wants_rollback():
                            self._rollback(guard)
                        continue
                self._save_checkpoint(pass_id, batch_id=batch_id)
        return last_batch_id, interrupted_mid_pass

    def _scan_pass(
        self,
        pass_id: int,
        reader: Callable,
        feeder: Optional[DataFeeder],
        scan_k: int,
        acc: "_PassStats",
        fetch_list,
        metric_names,
        handler: Callable,
        guard: Optional[StepGuard],
        sync_every: int,
        skip_until: int,
        prefetch_to_device: int,
    ):
        """One pass of the windowed (ISSUE 6) loop: the DevicePrefetcher
        stacks K committed batches to a leading window axis and the
        executor scans the train step over them in ONE dispatch. The
        accumulator state IS the scan carry, so cost/metrics/non-finite
        counts cross the host boundary only at window-edge syncs on the
        sync_every cadence. Checkpoint cadence quantizes to window
        boundaries; a hot StepGuard (open streak / cool-down) degrades to
        windows of 1 so recovery keeps step-granular semantics. stop()
        and SIGTERM finish the in-flight window, then the shared pass-end
        logic checkpoints at the window boundary."""
        from .data.feeder import DevicePrefetcher

        src = reader
        if skip_until:
            # resume mid-pass: deterministic readers replay — drop the
            # already-trained batches BEFORE windowing so windows align
            # to the resume point instead of straddling it
            def src():
                for i, b in enumerate(reader()):
                    if i >= skip_until:
                        yield b
        # depth counts windows here; ceil so the buffered batch count is
        # always >= the configured prefetch depth AND >= one full window
        depth = max(1, -(-max(1, prefetch_to_device) // scan_k)) + 1
        windows = iter(DevicePrefetcher(
            src, feeder, depth=depth, window=scan_k))
        next_batch = skip_until
        last_batch_id = skip_until - 1
        interrupted_mid_pass = False
        for win in windows:
            if self._stop:
                interrupted_mid_pass = True
                break
            k = win.k
            bids = list(range(next_batch, next_batch + k))
            next_batch += k
            self._maybe_log_stats(k)
            if obs_trace._armed:
                # window-granular correlation: the forwardBackward span
                # is ONE dispatch covering steps step+1..step+k; hostSync
                # and checkpointCommit spans inherit the same window id
                obs_trace.set_context(pass_id=pass_id, window=bids[0],
                                      batch=bids[0], step=self.step + 1,
                                      k=k)
            for b in bids:
                handler(BeginIteration(pass_id, b))
            feed = win.feed
            for i in range(k):
                if faults.fire("executor.step",
                               step=self.step + i) == "corrupt":
                    feed = _poison_window_slot(feed, i)
            dirty = False
            if guard is not None and guard.in_cooldown():
                # step-granular recovery: run this window's steps as K
                # windows of 1, syncing and observing the guard each step
                for i in range(k):
                    if not self._scan_one(pass_id, bids[i], win.slice(i),
                                          acc, fetch_list, metric_names,
                                          handler, guard):
                        dirty = True
                last_batch_id = bids[-1]
            else:
                with profiler.timer("forwardBackward"):
                    ys, acc_out = self.exe.run_window(
                        self.main_program,
                        feed=feed,
                        fetch_list=fetch_list,
                        scope=self.scope,
                        acc_state=acc.state,
                        skip_nonfinite=acc.skip_nonfinite,
                    )
                self.host_dispatch_count += 1
                acc.absorb_window(acc_out, k)
                for i in range(k):
                    self.step += 1
                    handler(EndIteration(
                        pass_id, bids[i], self.step,
                        _LazyScalar(ys[0], self._count_sync, index=i),
                        {m: _LazyScalar(v, self._count_sync, index=i)
                         for m, v in zip(metric_names, ys[1:])}))
                last_batch_id = bids[-1]
                if acc.pending() >= sync_every:
                    with profiler.timer("hostSync"):
                        n_good, n_bad = acc.sync()
                    if guard is not None and not guard.observe_window(
                            n_good, n_bad, scope=self.scope):
                        dirty = True  # rollback discards the whole window
                        if guard.wants_rollback():
                            self._rollback(guard)
            cc = self.checkpoint_config
            if dirty or not (cc and cc.step_interval):
                continue
            # cadence quantized to window boundaries: save once if ANY
            # step inside this window crossed a step_interval multiple
            if (self.step // cc.step_interval) > (
                    (self.step - k) // cc.step_interval):
                if guard is not None and acc.pending():
                    with profiler.timer("hostSync"):
                        n_good, n_bad = acc.sync()
                    if not guard.observe_window(
                            n_good, n_bad, scope=self.scope):
                        if guard.wants_rollback():
                            self._rollback(guard)
                        continue  # dirty window: no checkpoint either
                self._save_checkpoint(pass_id, batch_id=last_batch_id)
        return last_batch_id, interrupted_mid_pass

    def _scan_one(self, pass_id, batch_id, feed, acc, fetch_list,
                  metric_names, handler, guard: StepGuard) -> bool:
        """Guard-hot fallback: one step as a window of 1 — same compiled
        shape family as the scan path, but the accumulator syncs and the
        guard observes after every step, exactly the per-step-sync
        semantics recovery requires. Returns True iff the step was
        clean (a dirty step suppresses the window's checkpoint cadence,
        matching the per-step loop)."""
        with profiler.timer("forwardBackward"):
            ys, acc_out = self.exe.run_window(
                self.main_program, feed=feed, fetch_list=fetch_list,
                scope=self.scope, acc_state=acc.state,
                skip_nonfinite=acc.skip_nonfinite)
        self.host_dispatch_count += 1
        acc.absorb_window(acc_out, 1)
        self.step += 1
        with profiler.timer("hostSync"):
            n_good, n_bad = acc.sync()
        handler(EndIteration(
            pass_id, batch_id, self.step,
            _LazyScalar(ys[0], self._count_sync, index=0),
            {m: _LazyScalar(v, self._count_sync, index=0)
             for m, v in zip(metric_names, ys[1:])}))
        if guard is not None and not guard.observe_window(
                n_good, n_bad, scope=self.scope):
            if guard.wants_rollback():
                self._rollback(guard)
            return False
        return True

    # -- testing (paddle/trainer/Tester.cpp; v2 trainer.test) --------------
    def test(
        self,
        reader: Callable,
        feed_order: Optional[Sequence[Variable]] = None,
        fetch_metrics: Optional[Dict[str, Variable]] = None,
    ) -> Dict[str, float]:
        feeder = DataFeeder(feed_order) if feed_order is not None else None
        metric_items = sorted((fetch_metrics or {}).items())
        fetch_list = [self.cost] + [v for _, v in metric_items]
        sums = np.zeros(len(fetch_list))
        n = 0
        for data in reader():
            feed = feeder.feed(data) if feeder else data
            outs = self.exe.run(
                self.test_program, feed=feed, fetch_list=fetch_list, scope=self.scope
            )
            sums += np.array([float(np.asarray(o)) for o in outs])
            n += 1
        n = max(n, 1)
        out = {"cost": float(sums[0] / n)}
        for i, (k, _) in enumerate(metric_items):
            out[k] = float(sums[i + 1] / n)
        return out

    # -- non-finite recovery (resilience.StepGuard) -------------------------
    def _rollback(self, guard: StepGuard) -> None:
        """K consecutive non-finite steps: restore the newest VALID
        checkpoint (load_checkpoint quarantines corrupt serials itself)
        and enter the guard's reduced-LR cool-down. Training continues
        from the current reader position — the poisoned batch window is
        effectively skipped, which is the production trade the guard
        documents."""
        # an in-flight background save must land before we list serials:
        # it may BE the checkpoint we are about to restore
        self._ckpt_writer.drain()
        cc = self.checkpoint_config
        serial = (io.get_latest_checkpoint_serial(cc.checkpoint_dir)
                  if cc else -1)
        if serial < 0:
            raise NonFiniteError(
                f"{guard.bad_streak} consecutive non-finite steps and no "
                "checkpoint to roll back to (set checkpoint_config to "
                "make the StepGuard recoverable)")
        args = io.load_checkpoint(
            cc.checkpoint_dir, self.main_program, self.scope)
        self.step = int(args.get("step", self.step))
        guard.after_rollback(self.main_program, self.scope)

    # -- checkpointing ------------------------------------------------------
    def _save_checkpoint(self, pass_id: int, batch_id: Optional[int] = None) -> None:
        cc = self.checkpoint_config
        args = {"pass_id": pass_id, "step": self.step, "time": time.time()}
        if batch_id is not None:
            args.update({"mid_pass": True, "batch_id": batch_id})
        sharded = getattr(cc, "sharded", False)
        if not sharded and jax.process_count() > 1:
            # a gathered single-file save cannot read non-addressable
            # arrays and would race across writers; the per-shard format
            # is the only correct multi-process layout, so upgrade loudly
            # — once, from the chief (not every process on every save)
            if jax.process_index() == 0 and not getattr(
                self, "_warned_sharded_upgrade", False
            ):
                self._warned_sharded_upgrade = True
                logging.getLogger("paddle_tpu.trainer").warning(
                    "multi-process run: upgrading checkpoint save to the "
                    "sharded format (set CheckpointConfig(sharded=True) "
                    "to silence this)"
                )
            sharded = True
        if sharded and getattr(cc, "background", True) \
                and jax.process_count() == 1:
            # single-process sharded saves have no cross-process barriers,
            # so the commit rides the writer-thread double buffer. The
            # snapshot is one device-side copy of the state, so submit
            # latency is the drain of the PREVIOUS commit plus one
            # dispatch — the d2h copy of each unique shard happens on
            # the writer thread (pipeline/elastic.py)
            from .pipeline import elastic

            with profiler.timer("checkpointSnapshot"):
                elastic.submit_sharded_save(
                    self._ckpt_writer,
                    cc.checkpoint_dir,
                    trainer_args=args,
                    main_program=self.main_program,
                    scope=self.scope,
                    max_num_checkpoints=cc.max_num_checkpoints,
                )
            return
        if sharded or not getattr(cc, "background", True):
            # multi-process sharded saves barrier across processes —
            # every process must actually be executing the save, so they
            # stay on this thread (as does background=False by request)
            io.save_checkpoint(
                cc.checkpoint_dir,
                trainer_args=args,
                main_program=self.main_program,
                scope=self.scope,
                max_num_checkpoints=cc.max_num_checkpoints,
                sharded=sharded,
            )
            return
        # background: snapshot params to host NOW (the values of THIS
        # step — device_get waits for the dispatch queue, not the disk),
        # then hand the npz+sha256+atomic-rename commit to the writer
        with profiler.timer("checkpointSnapshot"):
            names = sorted(
                v.name for v in self.main_program.persistables()
                if self.scope.has(v.name)
            )
            snap = jax.device_get({n: self.scope.get(n) for n in names})
        host_scope = Scope()
        for n, v in snap.items():
            host_scope.set(n, v)
        program, max_keep = self.main_program, cc.max_num_checkpoints
        self._ckpt_writer.submit(lambda: io.save_checkpoint(
            cc.checkpoint_dir,
            trainer_args=args,
            main_program=program,
            scope=host_scope,
            max_num_checkpoints=max_keep,
            sharded=False,
        ))

    def save_params(self, dirname: str) -> None:
        io.save_params(dirname, self.main_program, self.scope)

    def save_inference_model(self, dirname, feeded_var_names, target_vars):
        io.save_inference_model(
            dirname, feeded_var_names, target_vars,
            main_program=self.main_program, scope=self.scope,
        )
