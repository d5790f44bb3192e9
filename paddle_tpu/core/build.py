"""A step program's build as a record: its phases and its cause.

`jax.jit` traces, lowers and compiles (or reads the persistent cache) inside
the first call of a new function, and no clock of the program's own sees
inside that call. JAX reports each of those parts as a duration event
(`jax.monitoring`), on the thread that paid for it, when it ends. This module
holds the program's ONE listener, registered when it is imported, and books
every event to the `Build` open on the firing thread. The jitted call itself
is left exactly as it is.

A `Build` is opened by `Executor._planned_fn`'s miss around the first call of
the function it made (`with build: fn(*args)`), on the calling thread:

- `program`: `<kind>.<n>`, the n-th build of that kind on its executor
  (`startup.1`, `step.1`, `step.2`); `kind`: `startup`, `step` or `window`.
- `cause`: what differs from the executor's last build of the same Program,
  the first of `CAUSES` that does; `first` where there was none. One cause
  more is found here and not there: `jit_arguments`, a build `jax.jit` makes
  ON ITS OWN inside a later call of a function that was built before. The
  executor's key holds what it can see (the names, the shapes, the Program);
  jit's own key also holds each argument's placement (committed to a device
  or not, its sharding), so a step whose state came from the startup program
  uncommitted and a step whose state came from a step lower and compile
  twice. No miss opens that build: the listener does, at the lowering event
  that bears the function's name of the thread's last build while none is
  open, and closes it at the compile event that follows. (Should jit trace
  again too, that trace is in `other`.)
- `phases`, seconds each: `trace` (the Python walk of the Program's ops into a
  jaxpr: the outermost trace only, the one that bears the step function's
  name; the jitted helpers inside it fire first and are within it), `lower`
  (jaxpr to MLIR, Mosaic's lowering of the Pallas kernels), `compile` (XLA's
  backend compile, or the read of the persistent cache), `provenance`
  (`Executor._read_provenance`'s block less the events inside it: the
  optimized HLO as text and its parse; with `FLAGS.enable_timers` only, absent
  otherwise) and `rest` (the build's wall time less the others: argument
  handling, the executable's load, the first execution's dispatch, whatever
  blocks). A traced run lowers and compiles inside the provenance block,
  ahead of the call, which then finds jit's caches full (on the chip: no
  second lowering, no second cache read): every event is booked to its own
  phase wherever it fires, so the phases never count a second twice and
  `compile` is what a listener outside counts.
- `cache`: `hit`, `miss` or `off` for the persistent compile cache at the
  build's first compile, and `cache_read_s`, the seconds JAX says the reads
  took.

An event that is not the open build's own (a helper's, an eager op's, the
trainer's `accum_fold`, anything while no build is open) goes to
`program="other"`, so that the seconds over all programs are the process's.

Sinks (the ones `profiler.timer` has): the spans `executor.build` (live: a
`TraceAnnotation` too) and `build.trace`, `build.lower`, `build.compile`,
`build.provenance` (handed over when their event fires, as spans that end
then: `profiler.record`), always on: a build happens a handful of times a
process, never on the steady step path. The registry, through
`obs.metrics._executor_families`: `pt_executor_build_seconds{program,kind,
phase}` (gauge; executors of one process that give two builds the same
`program` share its series, the seconds add) and `pt_executor_builds_total
{kind,cause}` (counter). Both are the process's, whatever became of the
executor."""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from typing import Dict, List, Optional, Tuple

from jax import monitoring

from .. import profiler

# what a build depends on, in the order a cause is looked for
CAUSES = ("scope_names", "feed_signature", "fetch_list", "program_version",
          "executor_key")

_PHASE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_seconds: Dict[Tuple[str, str, str], float] = {}   # (program, kind, phase)
_counts: Dict[Tuple[str, str], int] = {}           # (kind, cause)
_cache_events = {_CACHE_HIT: 0, _CACHE_MISS: 0}
_tls = threading.local()
logger = logging.getLogger("paddle_tpu.build")


def cause(before: Optional[tuple], now: tuple) -> str:
    """Why a build was made: `before` and `now` are what the executor's last
    build of the Program and this one depend on, in `CAUSES`' order."""
    if before is None:
        return "first"
    return next((c for c, a, b in zip(CAUSES, before, now) if a != b),
                CAUSES[-1])


class _Thread:
    """What the listener knows of one thread: the build open on it, the last
    one closed, and what the compile in flight has said of the cache."""

    __slots__ = ("open", "last", "asked", "hit", "read")

    def __init__(self):
        self.open = self.last = None
        self.asked = self.hit = False
        self.read = 0.0


def _thread() -> _Thread:
    st = getattr(_tls, "state", None)
    if st is None:
        st = _tls.state = _Thread()
    return st


def _add(program: str, kind: str, phase: str, secs: float) -> None:
    key = (program, kind, phase)
    with _lock:
        _seconds[key] = _seconds.get(key, 0.0) + secs


class Build:
    """One build of a step program (module docstring). A context manager
    around the new function's first call; `log` is its executor's list of
    builds, which it joins."""

    __slots__ = ("program", "kind", "cause", "fn_name", "phases", "cache",
                 "cache_read_s", "seconds", "args", "_log", "_t0", "_mark",
                 "_span", "_outer")

    def __init__(self, log: List["Build"], kind: str, cause: str,
                 fn_name: str):
        self.kind, self.cause, self.fn_name = kind, cause, fn_name
        self.program = f"{kind}.{1 + sum(b.kind == kind for b in log)}"
        self.phases: Dict[str, float] = {}
        self.cache, self.cache_read_s, self.seconds = "off", 0.0, None
        # the spans' args; `Executor._read_provenance` adds the `module`
        # label `pt_executor_instruction_scope` knows the program by
        self.args = {"program": self.program, "kind": kind, "cause": cause}
        self._log = log
        # no live span: until `__enter__`, and for good in a build of jit's
        # own, which the listener opens and closes
        self._span = self._outer = None
        log.append(self)

    def __enter__(self):
        self._span = profiler.timer("executor.build", always=True,
                                    args=self.args)
        self._span.__enter__()
        st = _thread()
        # a build of jit's own still open here lost its compile: let it go
        self._outer = st.open if st.open and st.open._span else None
        st.open = self
        self._t0 = self._mark = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._close(time.perf_counter() - self._t0)
        self._span.__exit__(*exc)
        st = _thread()
        st.open, st.last = self._outer, self
        return False

    @contextlib.contextmanager
    def provenance(self):
        """The block around `Executor._read_provenance`: its wall time less
        the phases JAX reports from inside it."""
        before, t0 = sum(self.phases.values()), time.perf_counter()
        try:
            yield
        finally:
            inside = sum(self.phases.values()) - before
            self._book("provenance",
                       max(0.0, time.perf_counter() - t0 - inside))

    def _own(self, phase: str, fun_name: str) -> bool:
        return fun_name == (self.fn_name if phase == "trace"
                            else f"jit({self.fn_name})")

    def _book(self, phase: str, secs: float, args=None) -> None:
        self.phases[phase] = self.phases.get(phase, 0.0) + secs
        _add(self.program, self.kind, phase, secs)
        # on the ring no phase starts before the one before it ended (JAX
        # took `secs` on a clock of its own)
        profiler.record("build." + phase, secs, args or self.args,
                        max(time.perf_counter() - secs, self._mark))
        self._mark = time.perf_counter()

    def _compiled(self, secs: float, cache: str, read: float) -> None:
        if "compile" not in self.phases:
            self.cache = self.args["cache"] = cache
        self.cache_read_s += read
        self._book("compile", secs,
                   dict(self.args, cache=cache, cache_read_s=read))

    def _close(self, seconds: float) -> None:
        self.seconds = seconds
        rest = max(0.0, seconds - sum(self.phases.values()))
        self.phases["rest"] = rest
        _add(self.program, self.kind, "rest", rest)
        with _lock:
            key = (self.kind, self.cause)
            _counts[key] = _counts.get(key, 0) + 1


def _on_duration(event: str, secs: float, fun_name: str = "", **_) -> None:
    # JAX calls this from inside its compile path: a fault in the books
    # must not become a fault of the compile
    try:
        _booked(event, secs, fun_name)
    except Exception:
        logger.exception("build record: %s of %r left unbooked", event,
                         fun_name)


def _booked(event: str, secs: float, fun_name: str) -> None:
    st = _thread()
    if event == _CACHE_READ:
        st.read += secs
        return
    phase = _PHASE_OF.get(event)
    if phase is None:
        return
    # what the compile that ends here heard of the cache (its events fire
    # inside it, before this one)
    cache = "hit" if st.hit else "miss" if st.asked else "off"
    read = st.read
    if phase == "compile":
        st.read, st.asked, st.hit = 0.0, False, False
    build = st.open
    if build is None and st.last is not None and phase == "lower" \
            and st.last._own(phase, fun_name):
        # jit builds a function of this thread's again, on its own
        build = st.open = Build(st.last._log, st.last.kind, "jit_arguments",
                                st.last.fn_name)
        build._t0 = build._mark = time.perf_counter() - secs
    if build is None or not build._own(phase, fun_name):
        _add("other", "other", phase, secs)
    elif phase != "compile":
        build._book(phase, secs)
    else:
        build._compiled(secs, cache, read)
        if build._span is None:   # jit's own: it ends with its compile
            seconds = time.perf_counter() - build._t0
            build._close(seconds)
            profiler.record("executor.build", seconds, build.args, build._t0)
            st.open, st.last = None, build


def _on_event(event: str, **_) -> None:
    if event == _CACHE_ASKED:
        _thread().asked = True
    elif event in _cache_events:
        if event == _CACHE_HIT:
            _thread().hit = True
        with _lock:
            _cache_events[event] += 1


monitoring.register_event_duration_secs_listener(_on_duration)
monitoring.register_event_listener(_on_event)


def compile_totals() -> Dict[str, float]:
    """The process so far, as a listener outside would count it: the seconds
    of every backend compile (or cache read) and the persistent cache's hits
    and misses."""
    with _lock:
        return {"compile_s": sum(s for (_, _, phase), s in _seconds.items()
                                 if phase == "compile"),
                "cache_hits": _cache_events[_CACHE_HIT],
                "cache_misses": _cache_events[_CACHE_MISS]}


def families() -> list:
    """The two registry families (module docstring), for
    `obs.metrics._executor_families`; nothing before the first event."""
    with _lock:
        seconds, counts = sorted(_seconds.items()), sorted(_counts.items())
    out = []
    if seconds:
        out.append((
            "pt_executor_build_seconds", "gauge",
            "seconds so far in building step programs, by program (<kind>.<n>"
            " on its executor; other: what no build owns), kind and phase "
            "(trace, lower, compile, provenance, rest)",
            [({"program": p, "kind": k, "phase": ph}, s)
             for (p, k, ph), s in seconds]))
    if counts:
        out.append((
            "pt_executor_builds_total", "counter",
            "step programs built, by kind and by what differed from the "
            "executor's last build of the Program (first, scope_names, "
            "feed_signature, fetch_list, program_version, executor_key, "
            "jit_arguments: jax.jit's own rebuild for arguments placed "
            "otherwise)",
            [({"kind": k, "cause": c}, n) for (k, c), n in counts]))
    return out
