"""A step program's build as a record (ISSUE 55, `core/build.py`): its
phases and its cause, in the executor's list, the StatSet, the obs.trace
ring and the registry's two families; and nothing of it on a call that
finds its function."""

import jax
import jax.monitoring
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import profiler
from paddle_tpu.core import build as builds
from paddle_tpu.flags import FLAGS
from paddle_tpu.obs import metrics as obs_metrics
from paddle_tpu.obs import promparse
from paddle_tpu.obs import trace as obs_trace

PHASES = {"trace", "lower", "compile", "rest"}
SPANS = ["executor.build", "build.trace", "build.lower", "build.compile"]


class _Model:
    """fc-tanh-fc regression under SGD: its own programs and scope, started
    from the seed by an executor of its own."""

    def __init__(self):
        self.prog, self.startup = pt.Program(), pt.Program()
        self.prog.random_seed = self.startup.random_seed = 5
        with pt.program_guard(self.prog, self.startup):
            x = pt.layers.data("x", shape=[6])
            y = pt.layers.data("y", shape=[1])
            hid = pt.layers.fc(x, size=8, act="tanh")
            self.pred = pt.layers.fc(hid, size=1)
            self.loss = pt.layers.mean(
                pt.layers.square_error_cost(self.pred, y))
            pt.optimizer.SGD(learning_rate=0.05).minimize(self.loss)
            # a persistable no op reads and the startup program does not make
            self.prog.global_block().create_var(
                name="late", shape=[1], dtype="float32", persistable=True)
        self.scope = pt.Scope()
        self.exe = pt.Executor()
        self.exe.run(self.startup, scope=self.scope)

    @staticmethod
    def feed(batch=8):
        rng = np.random.RandomState(batch)
        xv = rng.randn(batch, 6).astype(np.float32)
        return {"x": xv, "y": xv.sum(1, keepdims=True).astype(np.float32)}

    def step(self, batch=8, fetch=None):
        return self.exe.run(self.prog, feed=self.feed(batch), scope=self.scope,
                            fetch_list=fetch or [self.loss])


def _series(family):
    """{labels as a sorted tuple: value} of one family of the registry."""
    fams = promparse.parse_text(obs_metrics.registry().render())
    return {tuple(sorted(labels.items())): value
            for _, labels, value in fams[family].samples} \
        if family in fams else {}


# -- (i) one record a function, its phases inside its span ------------------
def test_two_runs_of_one_feed_are_one_build_whose_phases_sum_to_its_span():
    m = _Model()
    startup, = m.exe.builds
    assert (startup.program, startup.kind, startup.cause) == \
        ("startup.1", "startup", "first")
    stats = profiler.global_stat_set()
    before = {n: stats.get(n).total for n in SPANS}
    m.step()
    m.step()
    assert [b.program for b in m.exe.builds] == ["startup.1", "step.1"]
    b = m.exe.builds[-1]
    assert (b.kind, b.cause, b.fn_name) == ("step", "first", "raw")
    assert set(b.phases) == PHASES and b.cache in ("hit", "miss", "off")
    assert all(s >= 0 for s in b.phases.values()), b.phases
    assert b.phases["compile"] > 0 and b.phases["trace"] > 0
    timed = sum(s for p, s in b.phases.items() if p != "rest")
    assert timed <= b.seconds
    assert b.phases["rest"] == pytest.approx(b.seconds - timed, abs=1e-9)
    # the StatSet got the same seconds, timers off: the spans are `always`
    assert not FLAGS.enable_timers
    grew = {n: stats.get(n).total - before[n] for n in SPANS}
    assert grew["executor.build"] >= b.seconds
    assert grew["executor.build"] == pytest.approx(b.seconds, abs=5e-3)
    for phase in ("trace", "lower", "compile"):
        assert grew["build." + phase] == pytest.approx(b.phases[phase])


# -- (ii) the cause ---------------------------------------------------------
def _feed_shape(m):
    m.step(batch=4)


def _scope_name(m):
    m.scope.set("a_name_the_program_does_not_know", np.zeros(1, np.float32))
    m.step()   # the plan is made again, over the same names: no build
    assert len(m.exe.builds) == 2
    m.scope.set("late", np.zeros(1, np.float32))
    m.step()


def _fetch_list(m):
    m.step(fetch=[m.loss, m.pred])


def _program_version(m):
    with pt.program_guard(m.prog, m.startup):
        pt.layers.scale(m.loss, scale=2.0)
    m.step()


@pytest.mark.parametrize("change, cause", [
    (_feed_shape, "feed_signature"), (_scope_name, "scope_names"),
    (_fetch_list, "fetch_list"), (_program_version, "program_version")])
def test_a_second_build_says_what_differed(change, cause):
    m = _Model()
    m.step()
    counts = _series("pt_executor_builds_total")
    key = (("cause", cause), ("kind", "step"))
    change(m)
    assert [(b.program, b.cause) for b in m.exe.builds] == [
        ("startup.1", "first"), ("step.1", "first"), ("step.2", cause)]
    assert _series("pt_executor_builds_total")[key] == counts.get(key, 0) + 1


def test_cause_is_the_first_part_that_differs():
    now = ("names", "feed", "fetch", 3, "key")
    assert builds.cause(None, now) == "first"
    for i, name in enumerate(builds.CAUSES):
        before = tuple("other" if j >= i else v for j, v in enumerate(now))
        assert builds.cause(before, now) == name


def test_a_window_and_a_mesh_build_too():
    m = _Model()
    feed = {n: np.stack([v, v]) for n, v in m.feed().items()}
    for _ in range(2):
        m.exe.run_window(m.prog, feed=feed, fetch_list=[m.loss],
                         scope=m.scope)
    b = m.exe.builds[-1]
    assert (b.program, b.kind, b.cause, b.fn_name) == \
        ("window.1", "window", "first", "win")
    assert set(b.phases) == PHASES and b.phases["compile"] > 0

    from paddle_tpu.parallel import ParallelExecutor
    from paddle_tpu.parallel.mesh import mesh_from_spec

    par = ParallelExecutor(mesh_from_spec("dp2"))
    scope = pt.Scope()
    par.run_startup(m.startup, scope=scope)
    for _ in range(2):
        par.run(m.prog, feed=m.feed(), fetch_list=[m.loss], scope=scope)
    assert [(b.program, b.cause) for b in par.builds
            if b.cause != "jit_arguments"] == [("step.1", "first")]
    assert par.builds[0].phases["compile"] > 0


def test_a_build_jit_makes_on_its_own_is_a_record():
    """The trainer's second step: state that came from the startup program
    uncommitted, then from a step committed. `jax.jit` lowers and compiles
    the same function again; no miss of the executor's sees it."""
    m = _Model()
    dev = jax.devices()[0]
    committed = {n: jax.device_put(v, dev) for n, v in m.feed().items()}
    for _ in range(3):
        m.exe.run(m.prog, feed=committed, fetch_list=[m.loss], scope=m.scope)
    assert [(b.program, b.kind, b.cause) for b in m.exe.builds] == [
        ("startup.1", "startup", "first"), ("step.1", "step", "first"),
        ("step.2", "step", "jit_arguments")]
    own = m.exe.builds[-1]
    assert own.phases["lower"] > 0 and own.phases["compile"] > 0
    assert sum(own.phases.values()) == pytest.approx(own.seconds)
    assert m.exe.cache_stats["misses"] == 2   # startup, step: not three


# -- (iii) the registry's compile seconds are a listener's ------------------
def test_compile_seconds_over_all_programs_equal_a_listener_outside():
    heard = []

    def listener(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            heard.append(secs)

    def compile_seconds():
        return sum(v for k, v in _series("pt_executor_build_seconds").items()
                   if ("phase", "compile") in k)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        before = compile_seconds()
        totals = builds.compile_totals()
        m = _Model()
        m.step()
        m.step(batch=4)
        jax.jit(lambda a: a * 3 + 1)(np.arange(5.0))   # nobody's build
        after = compile_seconds()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert len(heard) >= 4 and sum(heard) > 0
    assert after - before == pytest.approx(sum(heard), rel=0.01)
    assert builds.compile_totals()["compile_s"] - totals["compile_s"] == \
        pytest.approx(sum(heard), rel=0.01)
    other = _series("pt_executor_build_seconds")[
        (("kind", "other"), ("phase", "compile"), ("program", "other"))]
    assert other > 0


# -- (iv) the registry's rendering, and the harness's reading of it ---------
def test_registry_renders_both_families_and_the_harness_reads_the_gauge():
    import importlib.util
    import os

    m = _Model()
    m.step()
    text = obs_metrics.registry().render()
    assert "# TYPE pt_executor_build_seconds gauge" in text
    assert "# TYPE pt_executor_builds_total counter" in text
    assert text.count("# TYPE pt_executor_build_seconds ") == 1
    assert promparse.parse_text(text)["pt_executor_builds_total"].type == \
        "counter"
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "drivers", "train.py")
    spec = importlib.util.spec_from_file_location("chipbench_train", path)
    train = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(train)
    snap = train.registry_snapshot(text)
    kind, seconds = snap[
        'pt_executor_build_seconds{kind="step",phase="compile",'
        'program="step.1"}']
    assert kind == "gauge" and seconds > 0
    kind, n = snap['pt_executor_builds_total{cause="first",kind="step"}']
    assert kind == "counter" and n >= 1
    # a window that builds nothing: the counter's growth is 0, the gauge
    # still says what the builds before it took
    later = train.registry_snapshot(obs_metrics.registry().render())
    delta = train.registry_delta(snap, later)
    assert delta['pt_executor_builds_total{cause="first",kind="step"}'] == 0
    assert delta['pt_executor_build_seconds{kind="step",phase="compile",'
                 'program="step.1"}'] == seconds


# -- (v) the obs.trace ring -------------------------------------------------
def test_chrome_trace_holds_the_build_inside_the_first_call_only():
    m = _Model()
    with obs_trace.tracing() as tr:
        obs_trace.set_context(step=1)
        m.step()
        obs_trace.set_context(step=2)
        m.step()
        doc = tr.to_chrome()
    assert not obs_trace.validate_chrome_trace(doc)
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    first, second = [e for e in spans if e["name"] == "executor.call"]
    build, = [e for e in spans if e["name"] == "executor.build"]
    children = [e for e in spans if e["name"].startswith("build.")]
    assert {e["name"] for e in children} >= {
        "build.trace", "build.lower", "build.compile"}

    def inside(inner, outer, slack=50.0):   # microseconds
        return (outer["ts"] - slack <= inner["ts"] and
                inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + slack)

    assert inside(build, first) and not inside(build, second)
    assert all(inside(c, build) for c in children)
    assert sum(c["dur"] for c in children) <= build["dur"] + 50.0
    assert build["args"] == {"step": 1, "program": "step.1", "kind": "step",
                             "cause": "first", "cache": build["args"]["cache"]}
    compiled = next(c for c in children if c["name"] == "build.compile")
    assert compiled["args"]["program"] == "step.1"
    assert compiled["args"]["cache"] in ("hit", "miss", "off")


# -- (vi) the steady path ---------------------------------------------------
def test_further_calls_leave_no_span_no_series_and_the_record_alone():
    m = _Model()
    m.step()
    record = m.exe.builds[-1]
    seen = (dict(record.phases), record.seconds, len(m.exe.builds))
    series = _series("pt_executor_build_seconds")
    counts = _series("pt_executor_builds_total")
    stats = profiler.global_stat_set()
    spans = {n: stats.get(n).count for n in SPANS}
    with obs_trace.tracing() as tr:
        for _ in range(5):
            m.step()
        doc = tr.to_chrome()
    names = {e["name"] for e in doc["traceEvents"]}
    assert "executor.call" in names
    assert not {n for n in names if n.startswith("build.")
                or n == "executor.build"}
    assert (dict(record.phases), record.seconds, len(m.exe.builds)) == seen
    assert _series("pt_executor_build_seconds") == series
    assert _series("pt_executor_builds_total") == counts
    assert {n: stats.get(n).count for n in SPANS} == spans


# -- (vii) the provenance phase ---------------------------------------------
@pytest.mark.parametrize("timers", [False, True])
def test_provenance_phase_follows_enable_timers(timers):
    saved = FLAGS.enable_timers
    FLAGS.enable_timers = timers
    try:
        m = _Model()
        m.step()
    finally:
        FLAGS.enable_timers = saved
    for b in m.exe.builds:
        assert ("provenance" in b.phases) == timers, b.phases
        if timers:
            assert b.phases["provenance"] > 0
            assert b.args["module"].startswith("jit_raw.")
            assert b.args["module"] in {
                t["program"] for t in m.exe._provenance}
            assert sum(b.phases.values()) == pytest.approx(b.seconds)
        else:
            assert "module" not in b.args
