"""Contracts of the chip bring-up (ISSUE 21): a compile cache placed
from outside, an artifact that keeps its compute dtype, and a chip smoke
that refuses to run without a TPU. (No silent CPU fallback in what the
benchmark prints is `chipbench/selftest.py`'s to assert.)"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra=None, drop=()):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    for k in drop:
        env.pop(k, None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_directory(tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set in code;
    unset, the cache lands at the fixed path in the checkout. In a child:
    this process's jax config must stay as conftest left it."""
    code = ("import jax; from paddle_tpu import compile_cache; "
            "print(compile_cache.enable()); "
            "print(jax.config.jax_compilation_cache_dir)")
    want = str(tmp_path / "cc") if placed else os.path.join(REPO, ".jax_cache")
    r = _run(["-c", code],
             {"JAX_COMPILATION_CACHE_DIR": want} if placed else None,
             drop=() if placed else ("JAX_COMPILATION_CACHE_DIR",))
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [want, want]


def test_saved_artifact_keeps_its_compute_dtype(tmp_path):
    x = pt.layers.data("x", shape=[8])
    y = pt.layers.fc(x, size=4)
    pt.default_main_program().set_amp("bfloat16")
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    xv = np.random.RandomState(0).randn(2, 8).astype(np.float32)
    (want,) = exe.run(feed={"x": xv}, fetch_list=[y])
    pt.io.save_inference_model(str(tmp_path), ["x"], [y])
    scope = pt.Scope()
    prog, feeds, fetches = pt.io.load_inference_model(str(tmp_path),
                                                      scope=scope)
    assert prog.amp_dtype == "bfloat16"
    (got,) = exe.run(prog, feed={"x": xv}, fetch_list=fetches, scope=scope)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    # and an f32 program's serialized form is what it always was
    pt.reset()
    pt.layers.fc(pt.layers.data("x", shape=[8]), size=4)
    assert "amp_dtype" not in pt.default_main_program().to_dict()


def test_chip_smoke_refuses_to_run_without_a_tpu():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "train_lstm" not in r.stdout  # it stopped before any phase


def test_chip_smoke_needs_the_repo_beside_it(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode != 0 and r.stdout.strip() == ""
