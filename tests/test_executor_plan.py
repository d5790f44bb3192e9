"""The Executor's step plan (ISSUE 54, core/executor.py `_StepPlan`).

What `Executor.run` / `run_window` derive from the program, the names the
scope holds, the feed's signature and the fetch list is computed once and
reused while the scope's layout and the program's version stand. The rule
under test: a reused plan changes no value. Every scenario is run twice,
once on executors that live through it (their plans are reused) and once
with a new `Executor` for every call (each call lists, sorts, splits and
checks anew, as every call did before the plan); the two must agree to
the bit, and the first's `cache_stats` must say which calls built a plan.
"""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu import optimizer as opt
from paddle_tpu.core.executor import Program, Scope, rebound_persistables
from paddle_tpu.obs import metrics as obs_metrics


class _Model:
    """Two fc towers of one shape (`wa`, `wb`: rebound, so donated) beside
    `frozen`, a parameter the program only reads (kept), under SGD; its
    own programs and its own scope, started from the seed."""

    def __init__(self):
        self.prog, self.startup = pt.Program(), pt.Program()
        self.prog.random_seed = self.startup.random_seed = 3
        with pt.program_guard(self.prog, self.startup):
            x = pt.layers.data("x", shape=[6])
            y = pt.layers.data("y", shape=[1])
            a = pt.layers.fc(x, size=6, param_attr=pt.ParamAttr(name="wa"),
                             bias_attr=False)
            b = pt.layers.fc(x, size=6, param_attr=pt.ParamAttr(name="wb"),
                             bias_attr=False)
            f = pt.layers.fc(
                x, size=6, bias_attr=False,
                param_attr=pt.ParamAttr(name="frozen", trainable=False))
            h = pt.layers.elementwise_add(pt.layers.elementwise_add(a, b), f)
            self.pred = pt.layers.fc(h, size=1, bias_attr=False)
            self.loss = pt.layers.mean(
                pt.layers.square_error_cost(self.pred, y))
            opt.SGD(learning_rate=0.05).minimize(self.loss)
        self.scope = self.started()
        rebound = rebound_persistables(self.prog)
        assert {"wa", "wb"} <= rebound and "frozen" not in rebound

    def started(self) -> Scope:
        scope = pt.Scope()
        pt.Executor().run(self.startup, scope=scope)
        return scope

    @staticmethod
    def feed(step=0, batch=8):
        rng = np.random.RandomState(step)
        xv = rng.randn(batch, 6).astype(np.float32)
        return {"x": xv, "y": xv.sum(1, keepdims=True).astype(np.float32)}

    def window(self, first, k=2):
        feeds = [self.feed(first + i) for i in range(k)]
        return {n: np.stack([f[n] for f in feeds]) for n in feeds[0]}

    def state(self, scope=None):
        scope = scope or self.scope
        return [np.array(scope.get(n)) for n in sorted(scope.keys())]


class _Executors:
    """`exes(slot)`: the scenario's executor number `slot`, or, when
    `fresh`, a new one at every call."""

    def __init__(self, fresh):
        self.fresh, self.held = fresh, {}

    def __call__(self, slot=0):
        if self.fresh:
            return pt.Executor()
        return self.held.setdefault(slot, pt.Executor())

    def plans(self):
        return {slot: (e.cache_stats["plans_built"],
                       e.cache_stats["plans_reused"])
                for slot, e in self.held.items()}


# Each scenario: (model, exes) -> the values it saw, as a flat list of
# arrays. Beside it, {slot: (plans built, plans reused)} of the executors
# that lived through it.


def unchanged_scope(m, exes):
    return [exes().run(m.prog, feed=m.feed(i), fetch_list=[m.loss],
                       scope=m.scope)[0] for i in range(4)] + m.state()


def a_name_added_to_the_scope(m, exes):
    """`late` is a persistable the program declares and the scope gains
    between two runs: from then on it is passed too."""
    m.prog.global_block().create_var(
        name="late", shape=[2], dtype=np.float32, persistable=True)
    out = []
    for i in range(4):
        if i == 2:
            m.scope.set("late", jnp.ones(2))
        out += exes().run(m.prog, feed=m.feed(i), fetch_list=[m.loss],
                          scope=m.scope)
    assert exes.fresh or exes().cache_stats["misses"] == 2
    return out + m.state()


def the_programs_version_moved(m, exes):
    out = []
    for i in range(4):
        if i == 2:
            version = m.prog.version
            with pt.program_guard(m.prog, m.startup):
                doubled = pt.layers.scale(m.loss, scale=2.0)
            assert m.prog.version > version
        out += exes().run(m.prog, feed=m.feed(i), fetch_list=[m.loss],
                          scope=m.scope)
    out += exes().run(m.prog, feed=m.feed(9), fetch_list=[doubled],
                      scope=m.scope)
    return out + m.state()


def a_foreign_write_of_a_donated_name(m, exes):
    """A loaded checkpoint: the value written between two runs is the one
    the next step reads."""
    out = []
    for i in range(4):
        if i == 2:
            m.scope.set("wa", jnp.full((6, 6), 0.25, jnp.float32))
        out += exes().run(m.prog, feed=m.feed(i), fetch_list=[m.loss],
                          scope=m.scope)
    unwritten = _Model()
    for i in range(4):
        pt.Executor().run(unwritten.prog, feed=m.feed(i),
                          fetch_list=[unwritten.loss], scope=unwritten.scope)
    assert not np.array_equal(np.array(m.scope.get("wa")),
                              np.array(unwritten.scope.get("wa")))
    return out + m.state()


def a_host_value_among_the_kept(m, exes):
    """Placed once, and left in the scope: the later runs pass the very
    array the first placed."""
    out, placed = [], []
    for i in range(4):
        if i == 1:
            m.scope.set("frozen", np.full((6, 6), 0.5, np.float32))
        out += exes().run(m.prog, feed=m.feed(i), fetch_list=[m.loss],
                          scope=m.scope)
        placed.append(m.scope.get("frozen"))
    assert all(isinstance(a, jax.Array) for a in placed)
    assert placed[1] is placed[2] is placed[3] and placed[0] is not placed[1]
    return out + m.state()


def a_second_name_of_one_array(m, exes):
    """`scope.set(b, scope.get(a))` between two runs: PJRT refuses a buffer
    donated twice, or donated and read, in one call. `wb` gets its copy
    once; `frozen` keeps reading the array it was given."""
    out = []
    for i in range(4):
        if i == 1:
            shared = m.scope.get("wa")
            m.scope.set("wb", shared)
            m.scope.set("frozen", shared)
        out += exes().run(m.prog, feed=m.feed(i), fetch_list=[m.loss],
                          scope=m.scope)
        if i == 1:
            # the step took a copy for one of the names and left the other
            # reader its array: nothing it still reads is dead
            assert m.scope.get("frozen") is shared
            assert not shared.is_deleted()
    assert m.scope.get("wa") is not m.scope.get("wb")
    return out + m.state()


def another_fetch_list(m, exes):
    out = []
    for i in range(4):
        fetch = [m.loss, m.pred] if i % 2 else [m.loss]
        out += exes().run(m.prog, feed=m.feed(i), fetch_list=fetch,
                          scope=m.scope)
    return out + m.state()


def another_feed_shape(m, exes):
    return [exes().run(m.prog, feed=m.feed(i, batch=4 if i % 2 else 8),
                       fetch_list=[m.loss], scope=m.scope)[0]
            for i in range(4)] + m.state()


def two_scopes_on_one_executor(m, exes):
    other = m.started()
    other.set("wa", jnp.full((6, 6), 0.25, jnp.float32))
    out = []
    for i in range(4):
        out += exes().run(m.prog, feed=m.feed(i), fetch_list=[m.loss],
                          scope=other if i % 2 else m.scope)
    assert not np.array_equal(np.array(other.get("wa")),
                              np.array(m.scope.get("wa")))
    return out + m.state() + m.state(other)


def two_executors_on_one_scope(m, exes):
    return [exes(i % 2).run(m.prog, feed=m.feed(i), fetch_list=[m.loss],
                            scope=m.scope)[0] for i in range(4)] + m.state()


def run_then_run_window_on_one_scope(m, exes):
    out = []
    for i in range(0, 6, 3):
        out += exes().run(m.prog, feed=m.feed(i), fetch_list=[m.loss],
                          scope=m.scope)
        ys, _ = exes().run_window(m.prog, feed=m.window(i + 1),
                                  fetch_list=[m.loss], scope=m.scope)
        out.append(np.asarray(ys[0]))
    # the window's two steps are the step's, taken one by one
    steps = _Model()
    want = [pt.Executor().run(steps.prog, feed=m.feed(i),
                              fetch_list=[steps.loss], scope=steps.scope)[0]
            for i in range(6)]
    got = np.concatenate([np.ravel(v) for v in out])
    np.testing.assert_allclose(got, np.ravel(want), rtol=1e-6)
    return out + m.state()


SCENARIOS = [
    (unchanged_scope, {0: (1, 3)}),
    (a_name_added_to_the_scope, {0: (2, 2)}),
    (the_programs_version_moved, {0: (3, 2)}),
    (a_foreign_write_of_a_donated_name, {0: (1, 3)}),
    (a_host_value_among_the_kept, {0: (1, 3)}),
    (a_second_name_of_one_array, {0: (1, 3)}),
    (another_fetch_list, {0: (2, 2)}),
    (another_feed_shape, {0: (2, 2)}),
    (two_scopes_on_one_executor, {0: (2, 2)}),
    (two_executors_on_one_scope, {0: (1, 1), 1: (1, 1)}),
    (run_then_run_window_on_one_scope, {0: (2, 2)}),
]


@pytest.mark.parametrize("scenario,plans", SCENARIOS,
                         ids=[s.__name__ for s, _ in SCENARIOS])
def test_a_reused_plan_changes_no_value(scenario, plans):
    planned = _Executors(fresh=False)
    got = scenario(_Model(), planned)
    want = scenario(_Model(), _Executors(fresh=True))
    assert planned.plans() == plans
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"value {i}")
    for exe in planned.held.values():
        st = exe.cache_stats
        assert st["hits"] + st["misses"] == \
            st["plans_built"] + st["plans_reused"]


def test_a_plan_holds_names_and_no_array():
    """The scope's reference to a donated buffer stays the last one, and an
    executor keeps neither a scope nor what it held alive."""
    m, exe = _Model(), pt.Executor()
    for i in range(2):
        exe.run(m.prog, feed=m.feed(i), fetch_list=[m.loss], scope=m.scope)
    (plans,) = exe._plans.values()
    (plan,) = plans.values()
    assert set(plan.donated) >= {"wa", "wb"}
    assert list(plan.donated) == sorted(plan.donated)
    assert "frozen" in plan.kept and list(plan.kept) == sorted(plan.kept)
    for slot in plan.__slots__:
        leaves = jax.tree_util.tree_leaves(getattr(plan, slot))
        assert not any(isinstance(a, (jax.Array, np.ndarray)) for a in leaves)
    array = weakref.ref(m.scope.get("wa"))
    m.scope.vars.clear()
    gc.collect()
    assert array() is None
    scope = weakref.ref(m.scope)
    del m
    gc.collect()
    assert scope() is None and not len(exe._plans)


def test_emptying_the_scope_behind_its_back_is_seen():
    """`scope.vars.clear()` moves no counter (chipbench does it, after its
    window): the plan must not hand the step names the scope lost."""
    m, exe = _Model(), pt.Executor()
    first = exe.run(m.prog, feed=m.feed(), fetch_list=[m.loss], scope=m.scope)
    m.scope.vars.clear()
    exe.run(m.startup, scope=m.scope)
    again = exe.run(m.prog, feed=m.feed(), fetch_list=[m.loss], scope=m.scope)
    np.testing.assert_array_equal(again[0], first[0])
    assert exe.cache_stats["plans_built"] == 3


def test_the_plan_counter_reaches_the_registry():
    def read():
        text = obs_metrics.registry().render()
        return {o: float(line.rpartition(" ")[2])
                for line in text.splitlines()
                for o in ("built", "reused")
                if line.startswith(
                    f'pt_executor_plans_total{{outcome="{o}"}} ')}

    m, exe = _Model(), pt.Executor()
    before = read()
    assert set(before) == {"built", "reused"}   # there from the first one on
    for i in range(3):
        exe.run(m.prog, feed=m.feed(i), fetch_list=[m.loss], scope=m.scope)
    after = read()
    assert after["built"] - before["built"] == 1
    assert after["reused"] - before["reused"] == 2
    assert "# TYPE pt_executor_plans_total counter" in \
        obs_metrics.registry().render()


# ------------------------------------------- the bookkeeping stays planned --


def _wide_model(layers=5):
    """A small transformer under Adam: several hundred persistables."""
    prog, startup = pt.Program(), pt.Program()
    prog.random_seed = startup.random_seed = 5
    with pt.program_guard(prog, startup):
        toks = pt.layers.data("toks", shape=[8], dtype=np.int32)
        labels = pt.layers.data("labels", shape=[8, 1], dtype=np.int32)
        logits = models.transformer_lm(
            toks, vocab_size=32, dim=8, num_heads=2, num_layers=layers,
            max_len=8)
        loss = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, labels))
        opt.Adam(learning_rate=1e-3).minimize(loss)
    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope)
    feed = {"toks": np.zeros((2, 8), np.int32),
            "labels": np.ones((2, 8, 1), np.int32)}
    return prog, scope, loss, feed


@pytest.mark.parametrize("window", [False, True], ids=["run", "run_window"])
def test_later_calls_on_an_unchanged_scope_list_nothing(monkeypatch, window):
    prog, scope, loss, feed = _wide_model()
    held = sum(scope.has(v.name) for v in prog.persistables())
    assert held >= 300, held
    calls = {"persistables": 0, "has": 0}
    persistables, has = Program.persistables, Scope.has

    def counted_persistables(self):
        calls["persistables"] += 1
        return persistables(self)

    def counted_has(self, name):
        calls["has"] += 1
        return has(self, name)

    monkeypatch.setattr(Program, "persistables", counted_persistables)
    monkeypatch.setattr(Scope, "has", counted_has)
    exe = pt.Executor()
    if window:
        feed = {k: np.stack([v, v]) for k, v in feed.items()}

    def call():
        if window:
            ys, _ = exe.run_window(prog, feed=feed, fetch_list=[loss],
                                   scope=scope)
            return np.asarray(ys[0])[-1]
        return exe.run(prog, feed=feed, fetch_list=[loss], scope=scope)[0]

    first = call()
    assert calls["persistables"] >= 1 and calls["has"] >= held
    calls.update(persistables=0, has=0)
    later = [call() for _ in range(3)]
    assert calls == {"persistables": 0, "has": 0}
    assert exe.cache_stats["plans_built"] == 1
    assert exe.cache_stats["plans_reused"] == 3
    assert later[-1] < first
