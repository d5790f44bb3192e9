"""The step path's one span primitive (`profiler.StatSet.timer`) and its
three sinks: the StatSet, the obs.trace ring, the jax.profiler timeline
(ISSUE 24). Also the op scopes of `_BlockRunner.run_ops` and chipbench's
eight per-layer readers over the spans."""

import glob
import importlib.util
import os
import re
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import profiler
from paddle_tpu.flags import FLAGS
from paddle_tpu.obs import trace as obs_trace
from paddle_tpu.trainer import EndIteration, _LazyScalar

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every span of the table in profiler.py's docstring
TRAINER_SPANS = ["prefetchWait", "prepareBatchData", "forwardBackward",
                 "executor.prepare", "executor.call", "executor.commit",
                 "accumUpdate", "hostSync", "lazyRead"]
PREFETCH_SPANS = ["prefetch.read", "prefetch.batch"]
N_BATCHES = 8


@pytest.fixture
def timers():
    """The global StatSet, empty, with FLAGS.enable_timers on."""
    stats = profiler.global_stat_set()
    saved = FLAGS.enable_timers
    FLAGS.enable_timers = True
    stats.reset()
    try:
        yield stats
    finally:
        FLAGS.enable_timers = saved
        stats.reset()


def _tiny_trainer():
    """fc-tanh-fc regression under Adam, initialised (so the startup
    program's own Executor.run is behind us), and its reader."""
    prog, startup = pt.Program(), pt.Program()
    startup.random_seed = 11
    with pt.program_guard(prog, startup):
        x = pt.layers.data("x", shape=[16])
        y = pt.layers.data("y", shape=[1])
        hid = pt.layers.fc(x, size=32, act="tanh")
        pred = pt.layers.fc(hid, size=1)
        loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.Adam(learning_rate=0.01).minimize(loss)
    trainer = pt.Trainer(loss, main_program=prog, startup_program=startup)
    trainer.init()
    rng = np.random.RandomState(0)
    batches = [{"x": rng.randn(8, 16).astype(np.float32),
                "y": rng.randn(8, 1).astype(np.float32)}
               for _ in range(N_BATCHES)]
    return trainer, prog, lambda: iter(batches)


def _train(trainer, reader):
    """One pass, the cost read on every 4th step (a lazy read: the
    trainer's own sync cadence is 4 too)."""
    def handler(event):
        if isinstance(event, EndIteration) and event.step % 4 == 0:
            assert np.isfinite(float(event.cost))

    trainer.train(reader, num_passes=1, log_interval=4,
                  event_handler=handler)


# -- (a) the StatSet sink ---------------------------------------------------
def test_trainer_run_leaves_every_span_in_the_stat_set(timers):
    trainer, _, reader = _tiny_trainer()
    timers.reset()   # drop the startup run's executor.* spans
    _train(trainer, reader)
    stats = timers.as_dict()
    for name in TRAINER_SPANS + PREFETCH_SPANS:
        assert name in stats and stats[name]["count"] > 0, (name, sorted(stats))
    n = stats["forwardBackward"]["count"]
    assert n == N_BATCHES
    parts = ("executor.prepare", "executor.call", "executor.commit")
    assert all(stats[p]["count"] == n for p in parts), stats
    assert sum(stats[p]["total"] for p in parts) \
        <= stats["forwardBackward"]["total"]
    assert stats["accumUpdate"]["count"] == n
    assert stats["prefetch.batch"]["count"] == n
    assert stats["prefetch.read"]["count"] == n + 1   # the reader's end
    assert stats["prefetchWait"]["count"] == n + 1
    assert stats["lazyRead"]["count"] == N_BATCHES // 4


# -- (b) the jax.profiler sink ----------------------------------------------
def test_spans_are_host_events_of_a_jax_profiler_capture(timers, tmp_path):
    from jax.profiler import ProfileData

    trainer, _, reader = _tiny_trainer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _train(trainer, reader)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    # line index -> {span name: [(start, end)]}, our spans only
    wanted = set(TRAINER_SPANS + PREFETCH_SPANS)
    lines = []
    for line in host.lines:
        spans = {}
        for ev in line.events:
            if ev.name in wanted:
                spans.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns))
        if spans:
            lines.append(spans)
    seen = set().union(*lines)
    assert seen == wanted, wanted - seen
    trainer_line = next(ln for ln in lines if "forwardBackward" in ln)
    prefetch_line = next(ln for ln in lines if "prefetch.batch" in ln)
    assert trainer_line is not prefetch_line
    assert set(TRAINER_SPANS) <= set(trainer_line)
    assert set(PREFETCH_SPANS) <= set(prefetch_line)
    assert not set(PREFETCH_SPANS) & set(trainer_line)
    # the profiler's own nanoseconds: executor.* nests in forwardBackward
    outer = trainer_line["forwardBackward"]
    assert len(outer) == N_BATCHES
    for part in ("executor.prepare", "executor.call", "executor.commit"):
        assert len(trainer_line[part]) == N_BATCHES
        for s, e in trainer_line[part]:
            assert any(a <= s and e <= b for a, b in outer), (part, s, e)


def test_obs_trace_spans_are_annotations_too(monkeypatch):
    """obs.trace.span()/_begin/_end (serving, the checkpoint writer) go
    through the same helper as the timer."""
    entered, exited = [], []

    class Fake:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            exited.append(self.name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Fake)
    with obs_trace.tracing() as tr:
        with obs_trace.span("outer", cat="t"):
            obs_trace._begin("inner", "t")
            obs_trace._end()
        with profiler.StatSet().timer("timed"):
            pass
    assert entered == ["outer", "inner", "timed"]
    assert exited == ["inner", "outer", "timed"]
    assert [e[1] for b in tr._bufs for e in b.events] == \
        ["inner", "outer", "timed"]


@pytest.mark.parametrize("window", [False, True], ids=["run", "run_window"])
def test_executor_spans_once_a_call_in_order_plan_built_or_reused(window):
    """The per-layer readers sum `executor.prepare` / `.call` / `.commit`
    by name: each opens and closes once a call, in that order, whether the
    call built its step plan or found it."""
    trainer, prog, reader = _tiny_trainer()
    exe, feed = trainer.exe, next(reader())
    if window:
        feed = {k: np.stack([v, v]) for k, v in feed.items()}

    def call():
        run = exe.run_window if window else exe.run
        run(prog, feed=feed, fetch_list=[trainer.cost], scope=trainer.scope)

    for outcome in ("plans_built", "plans_reused", "plans_reused"):
        before = exe.cache_stats[outcome]
        with obs_trace.tracing() as tr:
            call()
        assert exe.cache_stats[outcome] == before + 1
        closed = [e[1] for b in tr._bufs for e in b.events]
        # the call that compiles holds the build (ISSUE 55), no other does
        built = ["executor.build"] if outcome == "plans_built" else []
        assert [n for n in closed if n.startswith("executor.")] == \
            ["executor.prepare"] + built + ["executor.call",
                                            "executor.commit"], closed


# -- (c) off: zero cost -----------------------------------------------------
def test_off_path_is_one_shared_noop(monkeypatch):
    assert not FLAGS.enable_timers and not obs_trace.armed()
    # the builds are behind it: their spans are `always` (ISSUE 55), and
    # on no step but the first of a shape
    trainer, _, reader = _tiny_trainer()
    _train(trainer, reader)
    built = len(trainer.exe.builds)

    def boom(*a, **kw):
        raise AssertionError("a TraceAnnotation on the off path")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    # profiler.py's own view of the clock; nobody else's
    monkeypatch.setattr(profiler, "time",
                        types.SimpleNamespace(perf_counter=boom))
    stats = profiler.global_stat_set()
    stats.reset()
    a, b = profiler.timer("forwardBackward"), profiler.timer("hostSync")
    assert a is b is obs_trace._NULL
    with a:
        pass
    _train(trainer, reader)   # a whole pass constructs none
    assert len(trainer.exe.builds) == built
    assert stats.stats == {}


def test_either_switch_turns_the_primitive_on():
    ss = profiler.StatSet()
    with ss.timer("forced", always=True):
        pass
    assert ss.stats["forced"].count == 1
    with obs_trace.tracing() as tr:
        with ss.timer("traced"):
            pass
    assert "traced" not in ss.stats   # armed alone feeds no Stat
    assert [e[1] for b in tr._bufs for e in b.events] == ["traced"]


# -- (d) the third fence ----------------------------------------------------
def test_lazy_scalar_records_lazy_read_on_first_read_only(timers):
    syncs = []
    lazy = _LazyScalar(jnp.arange(3.0), lambda: syncs.append(1), index=2)
    assert "lazyRead" not in timers.stats
    assert float(lazy) == 2.0 and lazy + 1 == 3.0 and f"{lazy:.1f}" == "2.0"
    assert timers.stats["lazyRead"].count == 1 and len(syncs) == 1


def test_lazy_read_lands_on_the_reading_thread():
    lazy = _LazyScalar(jnp.ones(()))
    with obs_trace.tracing() as tr:
        t = threading.Thread(target=lazy.materialize, name="reader")
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    (buf,) = [b for b in tr._bufs if b.events]
    assert buf.name == "reader" and buf.events[0][1] == "lazyRead"


# -- (e) device ops carry their op's name -----------------------------------
def test_compiled_step_carries_op_scopes_forward_and_transposed():
    trainer, prog, reader = _tiny_trainer()
    feed = {k: jnp.asarray(v) for k, v in next(reader()).items()}
    trainer.exe.run(prog, feed=feed, fetch_list=[trainer.cost],
                    scope=trainer.scope)
    (fn,) = [f for p, f in trainer.exe._cache.values() if p is prog]
    donated, kept = trainer.exe._split_state(prog, {
        v.name: trainer.scope.get(v.name) for v in prog.persistables()
        if trainer.scope.has(v.name)})
    text = fn.lower(donated, kept, feed, jnp.uint32(0)).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    muls = [op.outputs["Out"][0] for op in prog.blocks[0].ops
            if op.type == "mul"]
    assert len(muls) == 2
    for out in muls:
        # the forward is in the program once, as the forward half of the
        # differentiated op: no plain copy of it beside that
        assert any(f"/jvp(mul.{out})/" in n for n in op_names), (out, op_names)
        assert not any(f"/mul.{out}/" in n for n in op_names), (out, op_names)
        assert any(f"transpose(jvp(mul.{out}))" in n for n in op_names), \
            (out, op_names)
    assert any(re.search(r"/adam\.fc_\d+\.w_\d+/", n) for n in op_names)


# -- (f) chipbench's readers over the spans ---------------------------------
READERS = {
    "loop.feed_wait_ms_per_step": ("prefetchWait", "prepareBatchData"),
    "loop.dispatch_ms_per_step": ("forwardBackward",),
    "loop.accum_ms_per_step": ("accumUpdate",),
    "loop.sync_ms_per_step": ("hostSync", "lazyRead"),
    "step.host_prepare_ms": ("executor.prepare",),
    "step.host_call_ms": ("executor.call",),
    "step.host_commit_ms": ("executor.commit",),
    "feed.produce_ms_per_step": ("prefetch.read", "prefetch.batch"),
}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_layer_metric_reader_sums_its_spans_over_steps(metric):
    spec = importlib.util.spec_from_file_location(
        "reader_" + re.sub(r"\W", "_", metric),
        os.path.join(ROOT, "chipbench", "layer_metrics", metric + ".py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    spans = READERS[metric]
    assert tuple(reader.SPANS) == spans
    # seconds in, ms a step out; spans of other metrics are not counted
    timers = {s: 0.25 * (i + 1) for i, s in enumerate(spans)}
    timers["checkpointSnapshot"] = 100.0
    want = 1e3 * sum(0.25 * (i + 1) for i in range(len(spans))) / 5
    assert reader.compute({"steps": 5, "timers_s": timers}) == \
        pytest.approx(want)
    # one of two spans recorded (a mesh executor has no prefetcher)
    assert reader.compute({"steps": 5, "timers_s": {spans[-1]: 0.5}}) == \
        pytest.approx(100.0)
    # a program without the spans (the parent commit): nothing to read
    for run in ({"steps": 5, "timers_s": {"checkpointSnapshot": 1.0}},
                {"steps": 5, "timers_s": {}}, {"steps": 5}):
        assert reader.compute(run) is None
