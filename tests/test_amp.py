"""Mixed-precision (bf16) tests.

Reference analogue: paddle/math/float16.h + fp16 GEMM paths; here bf16 on
the MXU with f32 master weights (paddle_tpu/amp.py).
"""

import numpy as np
import pytest

import paddle_tpu as pt


def _build_mlp(amp):
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = pt.layers.data("x", shape=[16])
        label = pt.layers.data("label", shape=[1], dtype=np.int32)
        h = pt.layers.fc(x, size=32, act="relu")
        logits = pt.layers.fc(h, size=4)
        loss = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, label)
        )
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    if amp:
        prog.set_amp("bfloat16")
    return prog, startup, loss


def _feed(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "x": rng.randn(8, 16).astype(np.float32),
        "label": rng.randint(0, 4, (8, 1)).astype(np.int32),
    }


def test_amp_matches_fp32_loosely():
    losses = {}
    for amp in (False, True):
        pt.reset()
        prog, startup, loss = _build_mlp(amp)
        prog.random_seed = 7
        startup.random_seed = 7
        exe = pt.Executor()
        exe.run(startup)
        for step in range(5):
            (l,) = exe.run(prog, feed=_feed(step), fetch_list=[loss])
        losses[amp] = float(l)
        # master weights stay f32 under amp
        w = pt.global_scope().get(prog.parameters()[0].name)
        assert np.dtype(w.dtype) == np.float32
    assert np.isfinite(losses[True])
    # bf16 has ~3 decimal digits; losses should agree to ~1e-2 relative
    assert losses[True] == pytest.approx(losses[False], rel=5e-2, abs=5e-2)


def test_amp_conv_runs():
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        img = pt.layers.data("img", shape=[3, 8, 8])
        y = pt.layers.conv2d(img, num_filters=4, filter_size=3, act="relu")
        out = pt.layers.mean(y)
    prog.set_amp("bfloat16")
    exe = pt.Executor()
    exe.run(startup)
    (v,) = exe.run(
        prog,
        feed={"img": np.random.RandomState(0).randn(2, 3, 8, 8).astype(np.float32)},
        fetch_list=[out],
    )
    assert np.isfinite(v)
    assert v.dtype == np.float32


def test_amp_guard_affects_execution():
    """amp_guard wraps the *run* calls: inside the guard a matmul computes

    in bf16 (2**-10 rounds away from the 8-bit mantissa), outside in f32."""
    x = pt.layers.data("x", shape=[1, 1], append_batch_size=False)
    y = pt.layers.data("y", shape=[1, 1], append_batch_size=False)
    out = pt.layers.matmul(x, y)
    exe = pt.Executor()
    feed = {
        "x": np.array([[1.0 + 2.0**-10]], np.float32),
        "y": np.array([[1.0]], np.float32),
    }
    prog = pt.default_main_program()
    assert prog.amp_dtype is None
    with pt.amp_guard("bfloat16"):
        assert prog.amp_dtype == "bfloat16"
        (inside,) = exe.run(prog, feed=feed, fetch_list=[out])
    assert prog.amp_dtype is None
    (outside,) = exe.run(prog, feed=feed, fetch_list=[out])
    assert float(inside[0, 0]) == 1.0  # bf16 dropped the 2**-10
    assert float(outside[0, 0]) == np.float32(1.0 + 2.0**-10)
