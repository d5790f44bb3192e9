"""CLI tests (`python -m paddle_tpu ...`).

Reference analogue: the `paddle train` shell command
(scripts/submit_local.sh.in:177-180) driving TrainerMain with a config
file — here the config is a Python module defining get_model().
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIG = """
import numpy as np
import paddle_tpu as pt

def get_model():
    x = pt.layers.data("x", shape=[4])
    y = pt.layers.data("y", shape=[1])
    pred = pt.layers.fc(x, size=1)
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    rng = np.random.RandomState(0)
    w = rng.randn(4, 1).astype(np.float32)

    def reader():
        for _ in range(8):
            xs = rng.randn(16, 4).astype(np.float32)
            yield {"x": xs, "y": xs @ w}

    return {"cost": loss, "reader": reader, "num_passes": 3}
"""


def _run(args, cwd):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "paddle_tpu"] + args,
        capture_output=True, text=True, cwd=cwd, env=env, timeout=240,
    )


def test_cli_train(tmp_path):
    cfg = tmp_path / "model.py"
    cfg.write_text(CONFIG)
    r = _run(["train", "--config", str(cfg), "--save_dir", ""], str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final:" in r.stdout
    # cost decreased over the run
    assert "Pass 2 done" in r.stdout


def test_cli_flags_and_version(tmp_path):
    r = _run(["flags"], str(tmp_path))
    assert r.returncode == 0 and "--check_nan_inf" in r.stdout
    r = _run(["version"], str(tmp_path))
    assert r.returncode == 0 and r.stdout.strip()


def test_cli_unknown_command(tmp_path):
    r = _run(["frobnicate"], str(tmp_path))
    assert r.returncode != 0


def test_cli_train_rejects_unknown_flag(tmp_path):
    """gflags parity: a typo'd flag must error, not silently train with
    defaults."""
    cfg = tmp_path / "model.py"
    cfg.write_text(CONFIG)
    r = _run(["train", "--config", str(cfg), "--log_perod=10"],
             str(tmp_path))
    assert r.returncode != 0
    assert "unknown flag" in (r.stderr + r.stdout)
    assert "log_perod" in (r.stderr + r.stdout)


def test_cli_train_eq_form_options(tmp_path):
    """--num_passes=N / --save_dir=D forms must work (and save_dir must
    reach the checkpoint config)."""
    cfg = tmp_path / "model.py"
    cfg.write_text(CONFIG)
    ckpt = tmp_path / "ck"
    r = _run(["train", f"--config={cfg}", "--num_passes=2",
              f"--save_dir={ckpt}"], str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Pass 1 done" in r.stdout and "Pass 2 done" not in r.stdout
    assert ckpt.exists()  # checkpoints actually written


def test_cli_train_flag_missing_value_and_bad_value(tmp_path):
    cfg = tmp_path / "model.py"
    cfg.write_text(CONFIG)
    r = _run(["train", "--config", str(cfg), "--log_period"], str(tmp_path))
    assert r.returncode != 0
    assert "requires a value" in (r.stderr + r.stdout)
    r = _run(["train", "--config", str(cfg), "--log_period=abc"],
             str(tmp_path))
    assert r.returncode != 0
    out = r.stderr + r.stdout
    assert "invalid value" in out and "Traceback" not in out


INFER_CONFIG = CONFIG + """

def get_inference():
    import paddle_tpu as pt
    pt.reset()
    x = pt.layers.data("x", shape=[4])
    pred = pt.layers.fc(x, size=1)
    return ["x"], [pred]
"""


def test_cli_train_checkpoint_merge_infer_roundtrip(tmp_path):
    """Full deploy flow: train with checkpoints -> merge_model -> load the

    inference model and predict (MergeModel.cpp + capi flow parity)."""
    cfg = tmp_path / "model.py"
    cfg.write_text(INFER_CONFIG)
    ckpt = tmp_path / "ckpt"
    r = _run(["train", "--config", str(cfg), "--save_dir", str(ckpt)],
             str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    out = tmp_path / "deploy"
    r = _run(["merge_model", "--config", str(cfg), "--model_dir", str(ckpt),
              "--out", str(out)], str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    # load and run the merged model in-process
    import paddle_tpu as pt

    pt.reset()
    prog, feed_names, fetch_names = pt.io.load_inference_model(str(out))
    exe = pt.Executor()
    (pred,) = exe.run(prog,
                      feed={feed_names[0]: np.ones((2, 4), np.float32)},
                      fetch_list=fetch_names)
    assert pred.shape == (2, 1) and np.all(np.isfinite(pred))


def test_cli_serve_end_to_end(tmp_path):
    """`serve` boots the batching HTTP server over a saved inference
    model: /healthz answers, /predict matches the in-process engine,
    /metrics exposes the cache counters."""
    import json
    import subprocess as sp
    import threading
    import urllib.request

    import paddle_tpu as pt

    pt.reset()
    x = pt.layers.data("x", shape=[4])
    pred = pt.layers.fc(x, size=2)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    model_dir = str(tmp_path / "model")
    pt.io.save_inference_model(model_dir, ["x"], [pred])
    xv = np.ones((3, 4), np.float32)
    want = pt.serving.ServingEngine(model_dir).predict(
        {"x": xv}, bucketed=False)[0]

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = sp.Popen(
        [sys.executable, "-m", "paddle_tpu", "serve", "--model_dir",
         model_dir, "--port", "0", "--max_batch_size", "8"],
        stdout=sp.PIPE, stderr=sp.PIPE, text=True, env=env)
    lines = []
    reader = threading.Thread(
        target=lambda: [lines.append(ln) for ln in proc.stdout],
        daemon=True)
    reader.start()
    try:
        deadline = __import__("time").monotonic() + 120
        port = None
        while __import__("time").monotonic() < deadline:
            for ln in list(lines):
                if ln.startswith("serving "):
                    port = int(ln.rsplit(":", 1)[1])
                    break
            if port or proc.poll() is not None:
                break
            __import__("time").sleep(0.2)
        assert port, (lines, proc.stderr.read() if proc.poll() is not None
                      else "server did not announce a port")
        url = f"http://127.0.0.1:{port}"
        with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
            assert json.load(r)["status"] == "ok"
        body = json.dumps({"inputs": {"x": xv.tolist()}}).encode()
        req = urllib.request.Request(
            url + "/predict", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json.load(r)
        (vals,) = out["outputs"].values()
        np.testing.assert_allclose(np.asarray(vals, np.float32), want,
                                   rtol=1e-5, atol=1e-6)
        with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
            assert "ptserving_compile_cache" in r.read().decode()
    finally:
        proc.terminate()
        proc.wait(timeout=30)


def test_cli_quant_end_to_end(tmp_path):
    """`quant` converts a saved fp32 artifact to int8: loud report on
    stdout, converted artifact carries the quant sidecar, serves the
    same shapes, and re-quantizing an already-quantized dir errors."""
    import json

    import paddle_tpu as pt

    pt.reset()
    pt.default_startup_program().random_seed = 2
    x = pt.layers.data("x", shape=[8])
    h = pt.layers.fc(x, size=16, act="relu")
    pred = pt.layers.fc(h, size=4)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    model_dir = str(tmp_path / "fp32")
    pt.io.save_inference_model(model_dir, ["x"], [pred])

    out_dir = str(tmp_path / "int8")
    r = _run(["quant", "--model_dir", model_dir, "--out", out_dir,
              "--samples", "4"], str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "quantized 2 matmul sites to int8" in r.stdout
    assert "accuracy check" in r.stdout
    assert f"quantized model written to {out_dir}" in r.stdout
    with open(os.path.join(out_dir, "meta.json")) as f:
        meta = json.load(f)
    assert meta["quant"]["mode"] == "int8"
    assert meta["quant"]["sites"] == 2
    assert meta["quant"]["calibration_samples"] == 4
    assert meta["quant"]["program_fingerprint"]
    assert meta["quant"]["scales_digest"]
    # converted artifact serves in-process (sidecar validates at load)
    eng = pt.serving.ServingEngine(out_dir, quantize="int8")
    out = eng.predict({"x": np.ones((2, 8), np.float32)})
    assert np.asarray(out[0]).shape == (2, 4)
    # double-quantization is an operator error
    r2 = _run(["quant", "--model_dir", out_dir,
               "--out", str(tmp_path / "int8x2")], str(tmp_path))
    assert r2.returncode != 0
    assert "already quantized" in (r2.stderr + r2.stdout)


def test_cli_quant_requires_dirs(tmp_path):
    r = _run(["quant", "--samples", "4"], str(tmp_path))
    assert r.returncode != 0
    assert "--model_dir" in (r.stderr + r.stdout)


def test_cli_tune_dry_run(tmp_path):
    """`tune --dry-run` lists legal candidates for at least two kernel
    families on any backend (no timing, no TPU)."""
    r = _run(["tune", "--kernel", "bahdanau",
              "--shape", "B=256,S=60,A=512,C=512", "--dtype", "bf16",
              "--dry-run"], str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "kernel bahdanau_attention" in r.stdout
    assert "bblk=8   (analytic default)" in r.stdout
    r = _run(["tune", "--kernel", "flash", "--shape", "Tq=1024,Tk=1024",
              "--dry-run"], str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "kernel flash_attention" in r.stdout
    assert "block_k=512,block_q=512   (analytic default)" in r.stdout


def test_cli_tune_refuses_to_time_on_cpu(tmp_path):
    """Without --dry-run, timing on a CPU backend must refuse loudly: a
    timing off the TPU says nothing of the chip."""
    r = _run(["tune", "--kernel", "bahdanau",
              "--shape", "B=16,S=10,A=128,C=128"], str(tmp_path))
    assert r.returncode != 0
    assert "refusing to time" in (r.stderr + r.stdout)


def test_cli_tune_config_sweep_dry_run(tmp_path):
    """`tune --config model.py --dry-run` scans the model program for
    tunable kernel sites."""
    cfg = tmp_path / "attn_model.py"
    cfg.write_text("""
import numpy as np
import paddle_tpu as pt

def get_model():
    q = pt.layers.data("q", shape=[1024, 256])
    out = pt.layers.multi_head_attention(q, num_heads=2)
    loss = pt.layers.mean(out)
    def reader():
        yield {"q": np.zeros((2, 1024, 256), np.float32)}
    return {"cost": loss, "reader": reader}
""")
    r = _run(["tune", "--config", str(cfg), "--dry-run"], str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "kernel flash_attention" in r.stdout
    assert "Tk=1024,Tq=1024" in r.stdout


def test_cli_tune_table_verbs_are_gone(tmp_path, monkeypatch):
    """There is no tuned table to export, import or merge, and a sweep
    takes no --cache, --search or --budget: each exits non-zero, the
    verbs naming what `tune` does now, and nothing is written."""
    from paddle_tpu import cli

    r = _run(["tune", "export", "--out", "t.json"], str(tmp_path))
    assert r.returncode != 0
    out = r.stderr + r.stdout
    assert "tune --kernel" in out and "Traceback" not in out, out
    monkeypatch.chdir(tmp_path)
    for verb in (["import", "t.json"], ["merge", "--out", "t.json", "a"]):
        with pytest.raises(SystemExit, match="tune --kernel"):
            cli._cmd_tune(verb)
    for opt in (["--cache", "t.json"], ["--search", "guided"],
                ["--budget", "0.4"]):
        with pytest.raises(SystemExit, match=f"unknown option {opt[0]}"):
            cli._cmd_tune(["--kernel", "flash", "--shape",
                           "Tq=1024,Tk=1024", "--dry-run", *opt])
    assert not list(tmp_path.iterdir())
