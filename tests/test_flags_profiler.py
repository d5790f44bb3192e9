"""Flags, stat timers, NaN guard, op-path diagnostics tests.

Reference analogues: utils/Flags.cpp gflags registry; utils/Stat.h
REGISTER_TIMER; fluid executor.cc:60-72 FLAGS_check_nan_inf;
utils/CustomStackTrace.h layer-path crash dumps.
"""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import profiler
from paddle_tpu.flags import FLAGS, define_flag, parse_flags


def test_flag_define_parse_and_env(monkeypatch):
    define_flag("test_flag_xyz", 3, "a test flag")
    assert FLAGS.test_flag_xyz == 3
    rest = parse_flags(["--test_flag_xyz=7", "positional", "--unknown=1"])
    assert FLAGS.test_flag_xyz == 7
    assert rest == ["positional", "--unknown=1"]
    FLAGS.test_flag_xyz = "9"  # coerced to the default's type
    assert FLAGS.test_flag_xyz == 9
    monkeypatch.setenv("PT_FLAGS_TEST_ENV_FLAG", "true")
    define_flag("test_env_flag", False)
    assert FLAGS.test_env_flag is True
    with pytest.raises(AttributeError):
        FLAGS.never_defined


def test_every_flag_is_read_by_the_package():
    """A flag nothing reads is an option that selects nothing."""
    import os
    import re

    import paddle_tpu.flags as flags_mod

    root = os.path.dirname(flags_mod.__file__)
    source = []
    for folder, _, files in os.walk(root):
        for name in files:
            path = os.path.join(folder, name)
            if name.endswith(".py") and path != flags_mod.__file__:
                with open(path) as f:
                    source.append(f.read())
    source = "\n".join(source)
    # read as an attribute, or by name through the kernels' backend gate
    unread = sorted(
        name for name in flags_mod._REGISTRY
        if not name.startswith("test_")  # defined by the tests above
        and not re.search(rf"FLAGS\.{name}\b", source)
        and f'backend_ok("{name}")' not in source)
    assert unread == []


def test_parse_bool_flag_bare():
    """gflags semantics: bare --bool_flag sets True, never eats the next arg."""
    define_flag("test_bool_pf", False)
    rest = parse_flags(["--test_bool_pf", "train.py"])
    assert FLAGS.test_bool_pf is True
    assert rest == ["train.py"]
    define_flag("test_int_pf", 1)
    rest = parse_flags(["--test-int-pf", "5", "x"])  # hyphens normalize
    assert FLAGS.test_int_pf == 5 and rest == ["x"]


def test_parse_never_consumes_flag_as_value():
    """--int_flag --other: the next token is itself a flag, so it must not
    be eaten as the value (and no bare-ValueError crash)."""
    define_flag("test_int_nv", 2)
    define_flag("test_bool_nv", False)
    rest = parse_flags(["--test_int_nv", "--test_bool_nv"])
    assert FLAGS.test_int_nv == 2  # unvalued: left alone
    assert FLAGS.test_bool_nv is True
    assert rest == ["--test_int_nv"]


def test_parse_bad_value_names_flag():
    define_flag("test_int_bv", 2)
    with pytest.raises(ValueError, match="test_int_bv"):
        parse_flags(["--test_int_bv=notanint"])
    with pytest.raises(ValueError, match="test_int_bv"):
        parse_flags(["--test_int_bv", "notanint"])


def test_init_atomic_on_bad_value():
    """A failing coercion mid-kwargs applies nothing (docstring claim)."""
    before = FLAGS.log_period
    with pytest.raises((TypeError, ValueError)):
        pt.init(log_period=99, stats_period="xyz")  # int("xyz") fails
    assert FLAGS.log_period == before


def test_stat_timers():
    ss = profiler.StatSet()
    for _ in range(3):
        with ss.timer("step", always=True):
            pass
    with ss.timer("gated_off"):  # FLAGS.enable_timers is False
        pass
    assert ss.stats["step"].count == 3
    assert "gated_off" not in ss.stats
    table = ss.print_all_status()
    assert "step" in table and "count" in table


def test_parameter_stats():
    x = pt.layers.data("x", shape=[4])
    y = pt.layers.fc(x, size=2)
    loss = pt.layers.mean(y)
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    exe.run(feed={"x": np.ones((2, 4), np.float32)}, fetch_list=[loss])
    stats = profiler.parameter_stats()
    assert stats
    for st in stats.values():
        assert np.isfinite(st["mean"]) and np.isfinite(st["abs_max"])


def test_trainer_param_stats_include_grads(monkeypatch, capsys):
    """show_param_stats_period prints grad stats (grads are fetched from

    the step, since grad vars are jit temporaries)."""
    monkeypatch.setattr(FLAGS, "show_param_stats_period", 1)
    x = pt.layers.data("x", shape=[4])
    y = pt.layers.data("y", shape=[1])
    pred = pt.layers.fc(x, size=1)
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    trainer = pt.Trainer(cost=loss)

    def reader():
        yield {"x": np.ones((4, 4), np.float32), "y": np.ones((4, 1), np.float32)}

    trainer.train(reader, num_passes=1)
    out = capsys.readouterr().out
    assert "grad_abs_max" in out and "mean" in out


def test_trainer_param_stats_with_frozen_param(monkeypatch, capsys):
    """A parameter outside minimize()'s slice has no grad var; stats steps

    must not try to fetch one."""
    monkeypatch.setattr(FLAGS, "show_param_stats_period", 1)
    x = pt.layers.data("x", shape=[4])
    y = pt.layers.data("y", shape=[1])
    pred = pt.layers.fc(x, size=1)
    frozen = pt.layers.fc(x, size=1)  # built but not part of the loss
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    trainer = pt.Trainer(cost=loss)

    def reader():
        yield {"x": np.ones((4, 4), np.float32), "y": np.ones((4, 1), np.float32)}

    trainer.train(reader, num_passes=1)  # must not raise
    assert "grad_abs_max" in capsys.readouterr().out


def test_profiler_exception_passthrough():
    """An exception inside profiler() propagates unchanged."""
    with pytest.raises(RuntimeError, match="boom"):
        with profiler.profiler("/tmp/pt_prof_test"):
            raise RuntimeError("boom")


def test_check_nan_inf_catches(monkeypatch):
    x = pt.layers.data("x", shape=[2])
    y = pt.layers.scale(x, scale=1.0)
    exe = pt.Executor()
    monkeypatch.setattr(FLAGS, "check_nan_inf", True)
    # finite feed passes
    exe.run(feed={"x": np.ones((1, 2), np.float32)}, fetch_list=[y])
    with pytest.raises(FloatingPointError, match="non-finite"):
        exe.run(
            feed={"x": np.array([[np.nan, 1.0]], np.float32)}, fetch_list=[y]
        )


def test_op_path_in_trace_errors():
    """A kernel failure names the op and its outputs (CustomStackTrace)."""
    x = pt.layers.data("x", shape=[4])
    y = pt.layers.fc(x, size=2)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    with pytest.raises(Exception, match="while executing op #.*mul"):
        # feed with the wrong inner dim: the mul kernel raises at trace time
        exe.run(feed={"x": np.ones((2, 5), np.float32)}, fetch_list=[y])


def test_profiler_context_smoke(tmp_path):
    with profiler.profiler(str(tmp_path)):
        import jax.numpy as jnp

        (jnp.ones((4,)) * 2).block_until_ready()


def test_init_api_and_ploter(tmp_path, monkeypatch):
    """v2 paddle.init parity + plot.Ploter parity."""
    monkeypatch.setattr(FLAGS, "log_period", FLAGS.log_period)  # restore after
    monkeypatch.setattr(FLAGS, "seed", FLAGS.seed)
    pt.init(seed=42, log_period=7)
    assert FLAGS.log_period == 7 and FLAGS.seed == 42
    assert pt.default_main_program().random_seed == 42
    # atomic: an unknown flag applies nothing
    with pytest.raises(AttributeError):
        pt.init(enable_timers=True, not_a_flag=1)
    assert FLAGS.enable_timers is False

    from paddle_tpu.plot import Ploter

    p = Ploter("train_cost", "test_cost")
    p.append("train_cost", 0, 1.5)
    p.append("train_cost", 1, 1.2)
    p.append("test_cost", 1, 1.3)
    out = p.plot(str(tmp_path / "curve.png"))
    assert out == str(tmp_path / "curve.png")  # path in both branches
    with pytest.raises(KeyError):
        p.append("nope", 0, 0.0)
    p.reset()
    assert not p.data["train_cost"]
