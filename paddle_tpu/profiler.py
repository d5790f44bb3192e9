"""Profiling: the span primitive, stat timers, trace contexts, parameter stats.

Reference surface:
- Gen-1 `REGISTER_TIMER*` RAII macros accumulating into a global StatSet
  (paddle/utils/Stat.h:63,114,230-242), printed as a table.
- Fluid profiler: push/pop ranges + python `profiler.profiler()` context
  (paddle/platform/profiler.h:25-118, fluid/profiler.py).
- Per-parameter value/grad stats (TrainerInternal.cpp:81-109).

ONE primitive, three sinks. `timer(name)` (= `StatSet.timer`) is the
single way the step path records a span (`record(name, seconds)` is its
entry point for a span that is known only once it has ended):

- Off (`FLAGS.enable_timers` false and `obs.trace` disarmed): two
  boolean tests, then the one shared no-op context object. No clock
  read, no allocation.
- On (either switch), the block
  (a) adds its duration to the `Stat` of that name (timers on),
  (b) records a span on the calling thread's `obs.trace` ring (armed),
  (c) is entered as a `jax.profiler.TraceAnnotation(name)`: while a
      `jax.profiler` capture runs (`profiler()`, `tracing(xprof_dir=)`,
      chipbench's traced run), the span is an event on its thread's line
      of `/host:CPU` in the `.xplane.pb`, in the device lines' own
      nanoseconds, so an idle gap of the device can be put down to it.

The step path's spans (thread; where; what the block covers):

| Span | Thread | Where | Covers |
|---|---|---|---|
| `prefetchWait` | trainer | `DevicePrefetcher.__iter__` | the step loop waiting in `q.get()` for a batch |
| `prepareBatchData` | trainer | `Trainer._step_pass` | in-loop `DataFeeder` (executors that place their own input) |
| `forwardBackward` | trainer | `_step_pass`, `_scan_pass` | the whole of `Executor.run` / `run_window` |
| `executor.prepare` | trainer | `Executor.run` / `run_window` | entry to just before the jitted call: feed normalisation, the step plan's key and lookup, state gather in plan order, seed, `_place_inputs`; only a call that builds its plan (the first, or after a name was added to the scope or the program edited) lists, sorts and splits the persistables and looks up or compiles the step function, and only a scope somebody else wrote since the last call is proven again to donate no buffer twice and walked for host values |
| `executor.call` | trainer | around `fn(donated, kept, feed, seed)` | the jitted call as Python sees it, and anything that blocks inside it (the first call of a shape traces and compiles here) |
| `executor.commit` | trainer | after the call to return | `check_nan_inf`, `scope.set` of every new state buffer and the release of the buffers they replace, `as_numpy` |
| `accumUpdate` | trainer | around `acc.update(...)` | the step's second dispatch (`accum_fold`) |
| `hostSync` | trainer | `_host_read_step`, `_PassStats.sync` | the periodic d2h read of the accumulator |
| `lazyRead` | the reader's | `_LazyScalar.materialize`, first read | a handler reading an event's lazy cost: the third fence |
| `prefetch.read` | `pt-prefetch` | around the reader's `next()` | the user's reader |
| `prefetch.batch` | `pt-prefetch` | `DataFeeder.feed` + `device_put` | converting and placing one batch |

Off that path, a handful of times a process and always on (`always=True`:
in the StatSet and the table `--dump_stats` prints whatever the flags), a
step program's build (`core/build.py`; each span's args say which program,
of what kind, built why, and what the compile cache said):

| Span | Thread | Where | Covers |
|---|---|---|---|
| `executor.build` | the caller's | `Executor._first_call`, inside the `executor.call` of a call that made its function; for a build `jax.jit` makes on its own (cause `jit_arguments`), handed over by `core/build.py`'s listener | the new function's first call: the phases below and the rest (argument handling, the executable's load, the first dispatch, whatever blocks) |
| `build.trace` | the caller's | `core/build.py`'s listener, when JAX says the phase ended (`record`: a span that ends then; no annotation) | the Python walk of the Program's ops into a jaxpr |
| `build.lower` | the caller's | the same | jaxpr to MLIR, Mosaic's lowering of the Pallas kernels in it |
| `build.compile` | the caller's | the same | XLA's backend compile, or the read of the persistent cache |
| `build.provenance` | the caller's | `_first_call`, with `FLAGS.enable_timers` only | `Executor._read_provenance` ahead of the call, less the three phases it fires itself: the optimized HLO as text and its parse |

Dispatch is async, so what a span measures depends on whether its block
reads a result back. `forwardBackward` is the host's side of a step:
62-68 ms for the 1 000-buffer GPT-2 small step on a v5e (ledger, PR 23),
not the device's 192 ms; the device's time surfaces in whichever block
fences next — `hostSync`, `lazyRead`, or `executor.call` itself when the
runtime makes the host wait for memory. To time device work in an ad-hoc
block, read a result inside it (e.g. `float(np.asarray(cost))`) —
otherwise the span measures the enqueue."""

from __future__ import annotations

import collections
import contextlib
import statistics
import threading
import time
from typing import Any, Dict, Optional

import numpy as np

from .flags import FLAGS
from .obs import trace as _trace


class Stat:
    __slots__ = ("name", "count", "total", "max", "samples", "_lock")

    def __init__(self, name: str, keep_samples: int = 0):
        self.name = name
        self.count = 0
        self.total = 0.0
        self.max = 0.0
        # opt-in raw-sample ring (the tune harness's median-of-k needs
        # the distribution, not just the running aggregate); None keeps
        # the default zero-overhead accumulator for serving timers
        self.samples = (
            collections.deque(maxlen=keep_samples) if keep_samples else None
        )
        # serving thread pool + background checkpoint writer land in the
        # same Stat concurrently; count/total updates must not tear
        self._lock = threading.Lock()

    def add(self, dt: float) -> None:
        with self._lock:
            self.count += 1
            self.total += dt
            self.max = max(self.max, dt)
            if self.samples is not None:
                self.samples.append(dt)

    @property
    def avg(self) -> float:
        return self.total / self.count if self.count else 0.0

    @property
    def median(self) -> float:
        """Median of the retained samples; falls back to avg when
        sample retention is off (keep_samples=0)."""
        if not self.samples:
            return self.avg
        with self._lock:
            return statistics.median(self.samples)


class _Timer:
    """One live `StatSet.timer` block (the on path only)."""

    __slots__ = ("_stat", "_name", "_traced", "_args", "_ann", "_t0")

    def __init__(self, stat: Optional[Stat], name: str, traced: bool,
                 args: Optional[Dict[str, Any]] = None):
        self._stat = stat
        self._name = name
        self._traced = traced
        self._args = args

    def __enter__(self):
        if self._traced:
            # ring + annotation; `args` is read when the block ends
            _trace._begin(self._name, "timer", self._args)
        else:
            self._ann = _trace._annotate(self._name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        if self._traced:
            _trace._end()
        else:
            self._ann.__exit__(None, None, None)
        if self._stat is not None:
            self._stat.add(dt)
        return False


class StatSet:
    """Named timer accumulator (reference: StatSet, Stat.h:230).

    `keep_samples=k` makes every Stat retain its last k raw timings
    (deque ring) so `Stat.median` is exact — used by tune/harness.py's
    median-of-k measurement loop.

    Thread-safe: `get` guards the dict insertion and `Stat.add` its own
    accumulation — the serving HTTP threads, the batcher worker, and
    the background checkpoint writer all hit one global set."""

    def __init__(self, keep_samples: int = 0):
        self.keep_samples = keep_samples
        self.stats: Dict[str, Stat] = {}
        self._lock = threading.Lock()

    def get(self, name: str) -> Stat:
        s = self.stats.get(name)
        if s is None:
            with self._lock:
                s = self.stats.get(name)
                if s is None:
                    s = self.stats[name] = Stat(name, self.keep_samples)
        return s

    def timer(self, name: str, always: bool = False,
              args: Optional[Dict[str, Any]] = None):
        """The span primitive (REGISTER_TIMER parity; module docstring).
        Off — timers off (and not `always`, the WITH_TIMER compile gate)
        and `obs.trace` disarmed — it returns the shared no-op context
        object; on, a block that feeds the Stat (timers), the calling
        thread's trace ring (armed; `args` are the span's, beside the
        thread's trace context) and the profiler's own timeline."""
        traced = _trace._armed
        timed = always or FLAGS.enable_timers
        if not (timed or traced):
            return _trace._NULL
        return _Timer(self.get(name) if timed else None, name, traced, args)

    def record(self, name: str, seconds: float,
               args: Optional[Dict[str, Any]] = None,
               start: Optional[float] = None) -> None:
        """A span nobody could stand around: it is known by its duration
        alone, when it ends, which is now (a phase of a build, reported by
        JAX as it finishes: `core/build.py`). `timer`'s `always` form less
        the annotation, which cannot be entered in the past. `start`, a
        `time.perf_counter()` reading, places the ring's span where its
        duration, taken on another clock, would cross a neighbour's."""
        self.get(name).add(seconds)
        if _trace._armed:
            _trace._ended(name, "timer", seconds, args, start)

    def print_all_status(self) -> str:
        """Formatted table (reference: StatSet::printAllStatus); adds a
        median column when sample retention is on."""
        med = bool(self.keep_samples)
        header = (f"{'name':<30}{'count':>8}{'total(s)':>12}"
                  f"{'avg(ms)':>10}{'max(ms)':>10}")
        if med:
            header += f"{'med(ms)':>10}"
        rows = [header]
        for name in sorted(self.stats):
            s = self.stats[name]
            row = (f"{name:<30}{s.count:>8}{s.total:>12.4f}"
                   f"{s.avg * 1e3:>10.3f}{s.max * 1e3:>10.3f}")
            if med:
                row += f"{s.median * 1e3:>10.3f}"
            rows.append(row)
        out = "\n".join(rows)
        print(out)
        return out

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        """Point-in-time snapshot for programmatic export (the unified
        metrics registry renders this in Prometheus text format);
        includes "median" when sample retention is on (the tune
        harness's median-of-k statistic, exported rather than private)."""
        out = {}
        for name, s in list(self.stats.items()):
            d = {"count": s.count, "total": s.total,
                 "avg": s.avg, "max": s.max}
            if s.samples is not None:
                d["median"] = s.median
            out[name] = d
        return out

    def reset(self) -> None:
        with self._lock:
            self.stats.clear()


_global_stats = StatSet()


def global_stat_set() -> StatSet:
    return _global_stats


def timer(name: str, always: bool = False,
          args: Optional[Dict[str, Any]] = None):
    return _global_stats.timer(name, always, args)


def record(name: str, seconds: float,
           args: Optional[Dict[str, Any]] = None,
           start: Optional[float] = None) -> None:
    _global_stats.record(name, seconds, args, start)


@contextlib.contextmanager
def profiler(output_dir: str = "/tmp/paddle_tpu_trace", state: str = "All"):
    """Deep-trace context (fluid profiler.profiler() parity): wraps

    jax.profiler.trace so kernels show up in XProf/TensorBoard. `state`
    is accepted for reference API parity ("CPU"/"GPU"/"All")."""
    import jax

    started = False
    try:
        jax.profiler.start_trace(output_dir)
        started = True
    except (RuntimeError, NotImplementedError):
        pass  # tracing unsupported on this backend — degrade to a no-op
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except (RuntimeError, NotImplementedError):
                pass


def parameter_stats(
    program=None, scope=None, grads: Optional[Dict[str, Any]] = None
) -> Dict[str, Dict[str, float]]:
    """Per-parameter value/gradient stats (TrainerInternal.cpp:81-109):

    mean/abs-max of each parameter; gradient stats come from `grads`
    (param name → array, fetched from the step — grad vars are jit
    temporaries, not scope residents) or, failing that, the scope."""
    from .core.executor import global_scope
    from .core.program import default_main_program, grad_var_name

    program = program or default_main_program()
    scope = scope or global_scope()
    grads = grads or {}
    out: Dict[str, Dict[str, float]] = {}
    for p in program.parameters():
        if not scope.has(p.name):
            continue
        v = np.asarray(scope.get(p.name))
        d = {"mean": float(v.mean()), "abs_max": float(np.abs(v).max())}
        g = grad_var_name(p.name)
        gv = None
        if p.name in grads:
            gv = np.asarray(grads[p.name])
        elif scope.has(g):
            gv = np.asarray(scope.get(g))
        if gv is not None:
            d["grad_mean"] = float(gv.mean())
            d["grad_abs_max"] = float(np.abs(gv).max())
        out[p.name] = d
    return out
