"""Donation of what a step overwrites (ISSUE 25, core/executor.py).

The rule under test: `Executor.run` / `run_window` donate the buffers of
the persistables a program REBINDS (`rebound_persistables`: parameters,
optimizer state, batch-norm statistics, `@AVG@` sums) and never the ones
it only reads; a program that rebinds nothing (inference,
`clone(for_test=True)`) donates nothing. The set is read off the Program
(declared outputs + `register_op(writes=...)`) and checked at trace time.
"""

import logging
import threading

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer as opt
from paddle_tpu.core import registry
from paddle_tpu.core.executor import Executor, rebound_persistables
from paddle_tpu.obs import metrics as obs_metrics

OPTIMIZERS = {
    "sgd": lambda: opt.SGD(learning_rate=0.1),
    "momentum": lambda: opt.Momentum(learning_rate=0.1, momentum=0.9),
    "adagrad": lambda: opt.Adagrad(learning_rate=0.1),
    "adadelta": lambda: opt.Adadelta(learning_rate=0.1),
    "rmsprop": lambda: opt.RMSProp(learning_rate=0.01),
    "decayed_adagrad": lambda: opt.DecayedAdagrad(learning_rate=0.1),
    "adam": lambda: opt.Adam(learning_rate=0.01),
    "adamax": lambda: opt.Adamax(learning_rate=0.01),
    "ftrl": lambda: opt.Ftrl(learning_rate=0.1),
    "model_average": lambda: opt.Adam(learning_rate=0.01),
}


def _build(optimizer="adam", batch_norm=True, seed=11):
    """fc -> batch_norm -> fc regressor; returns (loss, test_program,
    model_average or None). The default programs are reset."""
    pt.reset()
    pt.default_main_program().random_seed = seed
    pt.default_startup_program().random_seed = seed
    x = pt.layers.data("x", shape=[6])
    y = pt.layers.data("y", shape=[1])
    h = pt.layers.fc(x, size=8, act="relu")
    if batch_norm:
        h = pt.layers.batch_norm(h)
    pred = pt.layers.fc(h, size=1)
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    test_program = pt.default_main_program().clone(for_test=True)
    OPTIMIZERS[optimizer]().minimize(loss)
    avg = None
    if optimizer == "model_average":
        avg = opt.ModelAverage(min_average_window=2, max_average_window=4)
    return loss, test_program, avg


def _feed(step=0, batch=8):
    rng = np.random.RandomState(step)
    xv = rng.randn(batch, 6).astype(np.float32)
    return {"x": xv, "y": xv.sum(1, keepdims=True).astype(np.float32)}


def _state(scope=None):
    scope = scope or pt.global_scope()
    return {n: scope.get(n) for n in scope.keys()}


def _host(scope=None):
    return {n: np.array(v) for n, v in _state(scope).items()}


def _started(optimizer="adam", **kw):
    loss, test_program, avg = _build(optimizer, **kw)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    return exe, loss, test_program, avg


# ------------------------------------------------- the rule, per optimizer --


@pytest.mark.parametrize("name", sorted(OPTIMIZERS))
def test_step_donates_exactly_what_it_rebinds(name):
    exe, loss, _, _ = _started(name)
    prog = pt.default_main_program()
    rebound = rebound_persistables(prog)
    before = _state()
    assert rebound and set(before) - rebound, "need both kinds of state"
    exe.run(feed=_feed(), fetch_list=[loss])
    for n, a in before.items():
        assert a.is_deleted() == (n in rebound), n
    for n in before:
        assert not pt.global_scope().get(n).is_deleted(), n
    st = exe.donation_stats
    assert st["donated_buffers"] == len(rebound)
    assert st["donated_buffers"] + st["kept_buffers"] == len(before)
    assert st["donated_bytes"] + st["kept_bytes"] == sum(
        a.size * a.dtype.itemsize for a in pt.global_scope().vars.values())
    assert st["mismatches"] == 0


@pytest.mark.parametrize("name", sorted(OPTIMIZERS))
def test_ten_steps_bit_identical_to_the_undonated_step(name):
    exe, loss, _, _ = _started(name)
    prog = pt.default_main_program()
    start = _host()
    costs = [exe.run(feed=_feed(i), fetch_list=[loss])[0] for i in range(10)]
    got = _host()

    # the same traced step under a plain jax.jit: nothing donated
    persist = sorted(start)
    plain = jax.jit(Executor()._raw_step(prog, [loss.name]))
    donated, kept = Executor._split_state(prog, start)
    seed = np.uint32(prog.random_seed)
    want_costs = []
    for i in range(10):
        (cost,), donated, extras = plain(
            donated, kept, {k: jax.numpy.asarray(v)
                            for k, v in _feed(i).items()}, seed)
        assert not extras
        want_costs.append(np.asarray(cost))
    np.testing.assert_array_equal(np.array(costs), np.array(want_costs))
    for n in persist:
        want = donated[n] if n in donated else kept[n]
        np.testing.assert_array_equal(got[n], np.asarray(want), err_msg=n)
    assert any(not np.array_equal(got[n], start[n]) for n in donated)


def test_donating_training_loop_learns():
    """Moved from tests/test_amp.py (`Executor(donate_state=True)`, an
    argument that is gone: every training step donates)."""
    exe, loss, _, _ = _started("sgd", batch_norm=False)
    first = last = None
    for step in range(10):
        (l,) = exe.run(feed=_feed(step % 3), fetch_list=[loss])
        first = l if first is None else first
        last = l
    assert np.isfinite(last) and last < first
    prog = pt.default_main_program()
    w = np.asarray(pt.global_scope().get(prog.parameters()[0].name))
    assert np.all(np.isfinite(w))


def test_no_donate_state_argument_is_left():
    from paddle_tpu.pipeline import PipelineExecutor

    for cls, kw in ((pt.Executor, {}), (PipelineExecutor, {})):
        with pytest.raises(TypeError):
            cls(donate_state=True, **kw)
        assert not hasattr(cls(**kw), "donate_state")


# ------------------------------------------ programs that rebind nothing --


def test_inference_programs_donate_nothing():
    exe, loss, test_program, _ = _started("adam")
    exe.run(feed=_feed(), fetch_list=[loss])
    donated_by_training = exe.donation_stats["donated_buffers"]
    assert rebound_persistables(test_program) == frozenset()
    before = _state()
    for i in range(3):
        exe.run(test_program, feed=_feed(i), fetch_list=[loss])
    assert all(a is pt.global_scope().get(n) and not a.is_deleted()
               for n, a in before.items())
    st = exe.donation_stats
    assert st["donated_buffers"] == donated_by_training
    assert st["kept_buffers"] >= len(rebound_persistables(
        pt.default_main_program()) & set(
            v.name for v in test_program.persistables()))


def test_saved_inference_model_donates_nothing(tmp_path):
    exe, loss, _, _ = _started("sgd", batch_norm=False)
    exe.run(feed=_feed(), fetch_list=[loss])
    prog = pt.default_main_program()
    pred = prog.global_block().var(
        next(op for op in prog.global_block().ops
             if op.type == "square_error_cost").inputs["X"][0])
    pt.io.save_inference_model(str(tmp_path), ["x"], [pred])
    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(str(tmp_path), model_name="donation")
    eng.predict({"x": np.zeros((3, 6), np.float32)})
    held = _state(eng.scope)  # on the device since the first predict
    assert all(isinstance(a, jax.Array) for a in held.values())
    errors = []

    def client(seed):
        try:
            rng = np.random.RandomState(seed)
            for _ in range(50):
                eng.predict({"x": rng.randn(3, 6).astype(np.float32)})
        except Exception as e:  # pragma: no cover - the failure mode
            errors.append(e)

    threads = [threading.Thread(target=client, args=(s,)) for s in (1, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert all(a is eng.scope.get(n) and not a.is_deleted()
               for n, a in held.items())
    st = eng.stats()["executor_donation"]
    assert st["donated_buffers"] == 0 and st["kept_buffers"] > 0


# --------------------------------------------------- the two hazards --


def _two_towers():
    """Two fc towers of one shape, so that their state can share an
    array; `frozen` is a parameter the program only reads."""
    pt.reset()
    pt.default_main_program().random_seed = 3
    pt.default_startup_program().random_seed = 3
    x = pt.layers.data("x", shape=[6])
    y = pt.layers.data("y", shape=[1])
    a = pt.layers.fc(x, size=6, param_attr=pt.ParamAttr(name="wa"),
                     bias_attr=False)
    b = pt.layers.fc(x, size=6, param_attr=pt.ParamAttr(name="wb"),
                     bias_attr=False)
    f = pt.layers.fc(x, size=6, bias_attr=False,
                     param_attr=pt.ParamAttr(name="frozen", trainable=False))
    h = pt.layers.elementwise_add(pt.layers.elementwise_add(a, b), f)
    pred = pt.layers.fc(h, size=1, bias_attr=False)
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    opt.SGD(learning_rate=0.05).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    return exe, loss


def _towers_run(share: bool):
    """Three steps with wa, wb and frozen holding one value: in one
    array (share) or in an array each."""
    exe, loss = _two_towers()
    scope = pt.global_scope()
    value = np.array(scope.get("wa"))
    shared = jax.numpy.asarray(value)
    for n in ("wa", "wb", "frozen"):
        scope.set(n, shared if share else jax.numpy.array(value))
    costs = [exe.run(feed=_feed(i), fetch_list=[loss])[0] for i in range(3)]
    return costs, _host(), shared


def test_one_array_under_two_names():
    """PJRT refuses a buffer donated twice, or donated and read, in one
    call; and a name left bound to a donated array would read a dead
    one. wa and wb are rebound, frozen is only read."""
    _two_towers()
    rebound = rebound_persistables(pt.default_main_program())
    assert {"wa", "wb"} <= rebound and "frozen" not in rebound
    want_costs, want_state, _ = _towers_run(share=False)
    costs, state, shared = _towers_run(share=True)
    np.testing.assert_array_equal(np.array(costs), np.array(want_costs))
    for n, v in state.items():
        np.testing.assert_array_equal(v, want_state[n], err_msg=n)
    # `frozen` still reads the shared array, which nothing consumed
    assert pt.global_scope().get("frozen") is shared
    assert not shared.is_deleted()
    assert not np.array_equal(state["wa"], state["frozen"])


def test_host_values_in_the_scope():
    """`load_checkpoint` and `ModelAverage.apply` leave numpy arrays in
    the scope: jit donates the temporary, the host array is untouched."""
    exe, loss, _, _ = _started("adam")
    want = [exe.run(feed=_feed(i), fetch_list=[loss])[0] for i in range(3)]

    exe, loss, _, _ = _started("adam")
    host = _host()
    frozen = {n: v.copy() for n, v in host.items()}
    for n, v in host.items():
        pt.global_scope().set(n, v)
    got = [exe.run(feed=_feed(i), fetch_list=[loss])[0] for i in range(3)]
    np.testing.assert_array_equal(np.array(got), np.array(want))
    for n, v in host.items():
        np.testing.assert_array_equal(v, frozen[n], err_msg=n)
    assert all(isinstance(v, jax.Array) or n not in rebound_persistables(
        pt.default_main_program()) for n, v in _state().items())


def test_a_fetched_persistable_survives_the_next_step():
    exe, loss, _, _ = _started("sgd", batch_norm=False)
    w = pt.default_main_program().parameters()[0]
    (kept_w,) = exe.run(feed=_feed(0), fetch_list=[w], as_numpy=False)
    value = np.array(kept_w)
    exe.run(feed=_feed(1), fetch_list=[loss])
    assert not kept_w.is_deleted()
    np.testing.assert_array_equal(np.asarray(kept_w), value)


# ----------------------------------------- the check made at trace time --


@pytest.fixture
def sneaky_op():
    """An op whose kernel rebinds its input and does not say so."""
    def kernel(ctx):
        ctx.env[ctx.op.inputs["X"][0]] = ctx.input("X") + 1.0

    registry._KERNELS["sneaky_increment"] = kernel
    yield "sneaky_increment"
    del registry._KERNELS["sneaky_increment"]


def _sneaky_program(op_type):
    exe, loss, _, _ = _started("sgd", batch_norm=False)
    prog, startup = pt.default_main_program(), pt.default_startup_program()
    gb = prog.global_block()
    counter = gb.create_var("sneaky.counter", (), np.float32,
                            persistable=True)
    pt.initializer.ConstantInitializer(0.0)(counter, startup)
    gb.append_op(type=op_type, inputs={"X": [counter]}, outputs={})
    exe.run(startup)
    return exe, loss


def test_an_undeclared_rebind_is_counted_and_stays_right(sneaky_op, caplog):
    exe, loss = _sneaky_program(sneaky_op)
    assert "sneaky.counter" not in rebound_persistables(
        pt.default_main_program())
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.executor"):
        for i in range(3):
            exe.run(feed=_feed(i), fetch_list=[loss])
    assert float(pt.global_scope().get("sneaky.counter")) == 3.0
    assert exe.donation_stats["mismatches"] == 1
    warned = [r for r in caplog.records if "sneaky.counter" in r.getMessage()]
    assert len(warned) == 1  # at the trace, not at every step


def test_an_undeclared_rebind_in_a_window_stays_right(sneaky_op):
    exe, loss = _sneaky_program(sneaky_op)
    feeds = [_feed(i) for i in range(4)]
    window = {k: np.stack([f[k] for f in feeds]) for k in feeds[0]}
    exe.run_window(feed=window, fetch_list=[loss])
    assert float(pt.global_scope().get("sneaky.counter")) == 4.0
    exe.run_window(feed=window, fetch_list=[loss])
    assert float(pt.global_scope().get("sneaky.counter")) == 8.0


def test_rebound_set_follows_the_program_version():
    _build("sgd", batch_norm=False)
    prog = pt.default_main_program()
    first = rebound_persistables(prog)
    assert rebound_persistables(prog) is first  # cached
    gb = prog.global_block()
    extra = gb.create_var("extra.stat", (), np.float32, persistable=True)
    gb.append_op(type="scale", inputs={"X": [extra]},
                 outputs={"Out": [extra]}, attrs={"scale": 2.0})
    assert rebound_persistables(prog) == first | {"extra.stat"}


# ------------------------------------------------------- the other paths --


def test_run_window_donates_state_and_accumulator():
    exe, loss, _, _ = _started("adam")
    rebound = rebound_persistables(pt.default_main_program())
    want = [exe.run(feed=_feed(i), fetch_list=[loss])[0] for i in range(4)]
    want_state = _host()

    exe, loss, _, _ = _started("adam")
    before = _state()
    feeds = [_feed(i) for i in range(4)]
    window = {k: np.stack([f[k] for f in feeds]) for k in feeds[0]}
    z, zf = jax.numpy.zeros((), np.int32), jax.numpy.zeros((), np.float32)
    acc = (z, zf, [], z)  # one zero under two leaves, as a fresh pass has
    ys, acc_out = exe.run_window(feed=window, fetch_list=[loss],
                                 acc_state=acc)
    np.testing.assert_array_equal(np.asarray(ys[0]), np.array(want))
    for n, v in _host().items():
        np.testing.assert_array_equal(v, want_state[n], err_msg=n)
    for n, a in before.items():
        assert a.is_deleted() == (n in rebound), n
    assert int(acc_out[0]) == 4
    assert float(acc_out[1]) == pytest.approx(float(np.sum(want)), rel=1e-6)
    st = exe.donation_stats
    assert st["donated_buffers"] == len(rebound) and st["mismatches"] == 0
    # the carry goes round again: the second window consumes the first's
    mid = _state()
    exe.run_window(feed=window, fetch_list=[loss], acc_state=acc_out)
    assert all(a.is_deleted() == (n in rebound) for n, a in mid.items())
    assert acc_out[0].is_deleted()


def test_parallel_executor_donates_on_the_mesh():
    from paddle_tpu import parallel as pp

    exe, loss, _, _ = _started("adam", batch_norm=False)
    want = [exe.run(feed=_feed(i, batch=16), fetch_list=[loss])[0]
            for i in range(3)]

    loss, _, _ = _build("adam", batch_norm=False)
    prog = pt.default_main_program()
    rebound = rebound_persistables(prog)
    pexe = pp.ParallelExecutor(pp.make_mesh())
    pexe.run(pt.default_startup_program())
    got = [pexe.run(feed=_feed(0, batch=16), fetch_list=[loss])[0]]
    # the first step resharded single-device startup values; from the
    # second on the step consumes the mesh-placed arrays it made
    before = _state()
    n_dev = len(jax.devices())
    assert all(len(a.sharding.device_set) == n_dev for a in before.values())
    got += [pexe.run(feed=_feed(i, batch=16), fetch_list=[loss])[0]
            for i in (1, 2)]
    for n, a in before.items():
        assert a.is_deleted() == (n in rebound), n
    np.testing.assert_allclose(np.array(got), np.array(want), rtol=1e-5)
    st = pexe.donation_stats
    assert st["donated_buffers"] == len(rebound) and st["mismatches"] == 0
    assert st["kept_buffers"] == len(before) - len(rebound)


def test_pipeline_executor_donates():
    from paddle_tpu.pipeline import PipelineExecutor

    loss, _, _ = _build("adam", batch_norm=False)
    rebound = rebound_persistables(pt.default_main_program())
    exe = PipelineExecutor(num_stages=2, num_microbatches=2)
    exe.run(pt.default_startup_program())
    before = _state()
    (c0,) = exe.run(feed=_feed(0), fetch_list=[loss])
    (c1,) = exe.run(feed=_feed(0), fetch_list=[loss])
    assert np.isfinite(c1) and c1 < c0
    for n, a in before.items():
        assert a.is_deleted() == (n in rebound), n
    st = exe.donation_stats
    assert st["donated_buffers"] == len(rebound) and st["mismatches"] == 0


def test_model_average_apply_test_restore_train():
    """`ModelAverage` holds the live parameters across `apply` ..
    `restore`; only evaluation programs, which donate nothing, run in
    between, so the held arrays are alive at `restore`."""
    exe, loss, test_program, avg = _started("model_average")
    for i in range(6):  # the window of 2 holds two values at even steps
        exe.run(feed=_feed(i), fetch_list=[loss])
    live = {p.name: pt.global_scope().get(p.name)
            for p in pt.default_main_program().parameters()}
    live_host = {n: np.array(v) for n, v in live.items()}
    (plain,) = exe.run(test_program, feed=_feed(9), fetch_list=[loss])

    avg.apply(exe)
    (averaged,) = exe.run(test_program, feed=_feed(9), fetch_list=[loss])
    assert averaged != plain
    assert all(not a.is_deleted() for a in live.values())
    avg.restore(exe)
    for n, a in live.items():
        assert pt.global_scope().get(n) is a
    (again,) = exe.run(test_program, feed=_feed(9), fetch_list=[loss])
    assert again == plain

    (cost,) = exe.run(feed=_feed(5), fetch_list=[loss])
    assert np.isfinite(cost)
    assert all(a.is_deleted() for a in live.values())
    for n, v in live_host.items():
        assert not np.array_equal(np.asarray(pt.global_scope().get(n)), v)


def _gauge(name):
    line = next(ln for ln in obs_metrics.registry().render().splitlines()
                if ln.startswith(name + " "))
    return float(line.split()[1])


def test_registry_gauges_and_trainer_loop():
    """The counter beside `cache_stats` reaches /metrics (summed over the
    live executors, so read as a difference), and a Trainer pass
    (pipelined loop, `Trainer.test` after it) runs on the donating step."""
    from paddle_tpu.trainer import Trainer

    loss, _, _ = _build("adam")
    rebound = rebound_persistables(pt.default_main_program())
    trainer = Trainer(cost=loss)
    trainer.init()
    names = ("pt_executor_donated_buffers", "pt_executor_donated_bytes",
             "pt_executor_kept_buffers", "pt_executor_donation_mismatches")
    import gc

    gc.collect()  # an earlier test's executor must not die between the reads
    before = {n: _gauge(n) for n in names}  # declared before any step

    def reader():
        for i in range(6):
            f = _feed(i)
            yield [(f["x"][j], f["y"][j]) for j in range(8)]

    order = [pt.default_main_program().global_block().var(n)
             for n in ("x", "y")]
    trainer.train(reader, num_passes=2, feed_order=order)
    result = trainer.test(reader, feed_order=order)
    assert np.isfinite(result["cost"])
    moved = {n: _gauge(n) - before[n] for n in names}
    assert moved["pt_executor_donated_buffers"] == len(rebound)
    assert moved["pt_executor_donated_bytes"] == \
        trainer.exe.donation_stats["donated_bytes"] > 0
    assert moved["pt_executor_donation_mismatches"] == 0
