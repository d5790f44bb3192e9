"""Chip smoke: the trainer and the server, once, on the attached TPU.

    python chip_smoke.py                 one chip: train LSTM, train
                                         transformer, serve ResNet-50
    python chip_smoke.py --four-chips    only the dp4 LSTM run and its
                                         one-chip reference
    python chip_smoke.py --rehearse-cpu  [--four-chips]
                                         the same control flow at tiny
                                         sizes on the CPU backend; every
                                         line says "rehearsal": true and
                                         none can name the platform tpu

One process per chip: this parent never imports JAX. Each phase is a
child that asserts the platform first, does its work through the
ordinary entry points (`paddle_tpu.cli.main(["train", ...])`,
`python -m paddle_tpu serve`), prints one JSON line naming the device,
and exits before the next child starts. The first failing phase fails
the script; the last stdout line is the verdict and nothing else.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(ROOT, "configs")
DEADLINE_S = 1150  # whole-script budget (the contract allows 1200)
KERNEL = 'custom_call_target="tpu_custom_call"'
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")

# sizes the CPU rehearsal passes to the config modules' get_model();
# the chip run passes nothing and gets the published widths
REHEARSAL_SIZES = {
    "lstm_benchmark": dict(hidden=128, batch=8, seqlen=12, vocab=200,
                           emb_dim=16, steps=12),
    "transformer_lm": dict(dim=64, heads=2, layers=2, seqlen=128,
                           vocab=256, batch=2, steps=6),
}
FOUR_CHIP_BATCH = 512
FOUR_CHIP_STEPS = 8
# dp4 vs one chip, same seed and data. Losses, every step: |dp4 - one|
# over max(1, |one|) — the loss falls from ln 2 to ~1e-6 inside these
# steps, so absolute where it is small. Then each parameter's first-step
# gradient: max |g_dp4 - g_one| over max |g_one| — dp4 sums four bf16
# partial sums where one chip sums once; a lost or doubled reduction
# reads 0.75 or 3.0
FOUR_CHIP_LOSS_TOL = 2e-2
FOUR_CHIP_GRAD_TOL = 0.1
# served vs in-process ResNet-50 logits, both bf16 on the same backend:
# max |diff| over max |oracle|
SERVE_REL_TOL = 2e-2


_TAG = "chip_smoke"  # the rehearsal re-tags every line it prints


def log(msg: str) -> None:
    print(f"[{_TAG} {time.strftime('%H:%M:%S')}] {msg}", flush=True)


# --------------------------------------------------------------- children ---
def _device(rehearse: bool) -> dict:
    """First thing every child does: name the device, or die."""
    import jax

    d = jax.devices()[0]
    dev = {"platform": d.platform, "kind": d.device_kind,
           "count": len(jax.devices())}
    if rehearse:
        assert d.platform == "cpu", f"rehearsal must stay on the CPU: {dev}"
    elif d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU — JAX reports {dev}")
    return dev


def _emit(phase: str, dev: dict, rehearse: bool, **fields) -> dict:
    rec = {"phase": phase, "platform": dev["platform"],
           "device_kind": dev["kind"], "device_count": dev["count"]}
    if rehearse:
        rec["rehearsal"] = True
    rec.update(fields)
    print(json.dumps(rec), flush=True)
    return rec


def _compile_fields() -> dict:
    """Seconds JAX spent in backend compiles (or, on a persistent-cache
    hit, in reading the executable back) plus the hit/miss counts, in this
    process so far: the program's own record (`core/build.py`'s listener,
    the one the repo has beside the yardstick's)."""
    from paddle_tpu.core import build

    t = build.compile_totals()
    return {"compile_s": round(t["compile_s"], 2),
            "cache_hits": t["cache_hits"], "cache_misses": t["cache_misses"]}


def _config_for(name: str, work: str, sizes: dict) -> str:
    """The committed config file itself, or — when sizes are overridden —
    a wrapper in the work dir that calls its get_model with them."""
    path = os.path.join(CONFIGS, name + ".py")
    if not sizes:
        return path
    wrapper = os.path.join(work, name + "_sized.py")
    with open(wrapper, "w") as f:
        f.write("import runpy\n"
                f"_ns = runpy.run_path({path!r})\n"
                f"def get_model():\n    return _ns['get_model'](**{sizes!r})\n")
    return wrapper


def _default_sizes(name: str) -> dict:
    import inspect
    import runpy

    sig = inspect.signature(
        runpy.run_path(os.path.join(CONFIGS, name + ".py"))["get_model"])
    return {k: p.default for k, p in sig.parameters.items()}


def _cli_train(config: str, extra=()) -> tuple:
    """`paddle_tpu train --config ...` in this process; returns the
    per-step costs it logged and the wall time of each log line."""
    import contextlib
    import io
    import re

    from paddle_tpu import cli

    class Tee(io.TextIOBase):
        def __init__(self):
            self.lines, self.stamps, self._buf = [], [], ""

        def write(self, s):
            sys.__stdout__.write(s)
            self._buf += s
            while "\n" in self._buf:
                line, self._buf = self._buf.split("\n", 1)
                self.lines.append(line)
                self.stamps.append(time.perf_counter())
            return len(s)

    tee = Tee()
    with contextlib.redirect_stdout(tee):
        rc = cli.main(["train", "--config", config, "--log_period", "1",
                       *extra])
    assert rc == 0, f"train exited {rc}"
    costs, stamps = [], []
    for line, t in zip(tee.lines, tee.stamps):
        m = re.match(r"pass \d+ batch \d+ cost=(\S+)", line)
        if m:
            costs.append(float(m.group(1)))
            stamps.append(t)
    return costs, stamps


def _check_losses(costs, want_steps: int) -> dict:
    import numpy as np

    assert len(costs) == want_steps, (len(costs), want_steps)
    assert np.all(np.isfinite(costs)), f"non-finite loss: {costs}"
    k = max(1, len(costs) // 4)
    first, last = float(np.mean(costs[:k])), float(np.mean(costs[-k:]))
    assert last < first, f"loss did not fall: {costs}"
    return {"steps": len(costs), "loss_first": round(first, 4),
            "loss_last": round(last, 4)}


def _step_text(exe, feed) -> str:
    """Compiled text of the train step the CLI just ran: the executor's
    own build of the default program over the scope it left behind, fed
    as the Trainer feeds it (committed to the device where the executor
    takes prefetched input). Same Program, same executor class, same
    process and flags — but a second compile: on the chip its cache key
    differs from the dispatched step's (on the CPU it does not), so the
    callers report cache hits and do not require one."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt

    prog, scope = pt.default_main_program(), pt.global_scope()
    cost = next(op for op in prog.global_block().ops
                if op.type == "autodiff").inputs["Loss"][0]
    persist = sorted(v.name for v in prog.persistables() if scope.has(v.name))
    fn = exe._compile(prog, feed, [cost], persist)
    donated, kept = exe._split_state(prog, {n: scope.get(n) for n in persist})
    if exe.prefetch_by_default:
        feed = jax.device_put(feed, exe.place.device)
    with exe._device_context(), exe._trace_context():
        return fn.lower(donated, kept, feed,
                        jnp.uint32(0)).compile().as_text()


def _kernel_fields(text: str, at_least: int, relower_hits: int) -> dict:
    """A Pallas kernel that compiled for the chip is a tpu_custom_call in
    the step's text; interpret mode and the XLA fallbacks leave none
    (which is all the CPU rehearsal can see, so it asks for 0)."""
    n = text.count(KERNEL)
    assert n >= at_least, (
        f"expected >= {at_least} compiled Pallas kernels in the train "
        f"step, found {n}: the fused path was not taken")
    return {"tpu_custom_calls": n, "relower_cache_hits": relower_hits}


def _lstm_feed(sz: dict) -> dict:
    import numpy as np

    from paddle_tpu.core.lod import LoDArray

    seqs = [np.zeros(sz["seqlen"], np.int32)] * sz["batch"]
    return {"words": LoDArray.from_sequences(seqs, bucket=256,
                                             max_seqs=sz["batch"]),
            "label": np.zeros((sz["batch"], 1), np.int32)}


def _transformer_feed(sz: dict) -> dict:
    import numpy as np

    B, T = sz["batch"], sz["seqlen"]
    return {"toks": np.zeros((B, T), np.int32),
            "labels": np.zeros((B, T, 1), np.int32)}


def phase_probe(args, work):
    """Name the device; then the one thing a checkout has to build:
    native/build/ is git-ignored, so the .so must come from the committed
    sources on this machine, and round-trip a record file."""
    dev = _device(args.rehearse_cpu)
    from paddle_tpu import native

    path = os.path.join(work, "smoke.recordio")
    with native.RecordIOWriter(path) as w:
        for i in range(5):
            w.write(b"record-%d" % i)
    with native.RecordIOReader(path) as r:
        got = list(r)
    assert got == [b"record-%d" % i for i in range(5)], got
    _emit("probe", dev, args.rehearse_cpu, native_records=len(got))


def _train_phase(args, work, phase, config, make_feed, min_kernels):
    """Train `configs/<config>.py` through the CLI, then check the loss
    and that the step it ran holds the compiled kernels."""
    dev = _device(args.rehearse_cpu)
    import paddle_tpu as pt

    over = REHEARSAL_SIZES[config] if args.rehearse_cpu else {}
    sz = {**_default_sizes(config), **over}
    costs, stamps = _cli_train(_config_for(config, work, over))
    out = _check_losses(costs, sz["steps"])
    # steady rate over the steps after the compile step (each logged
    # cost is a host read, so this includes one d2h fence per step)
    n = len(stamps) - 2
    tok_s = n * sz["batch"] * sz["seqlen"] / (stamps[-1] - stamps[1])
    log(f"{phase}: {out['steps']} steps, {tok_s:.0f} tok/s "
        f"(per-step host read included)")
    train = _compile_fields()
    text = _step_text(pt.Executor(), make_feed(sz))
    out.update(_kernel_fields(
        text, 0 if args.rehearse_cpu else min_kernels(sz),
        _compile_fields()["cache_hits"] - train["cache_hits"]))
    _emit(phase, dev, args.rehearse_cpu, tok_per_s=round(tok_s, 1),
          **out, **train)


def phase_train_lstm(args, work):
    # two layers, each a fused forward and a fused backward kernel
    _train_phase(args, work, "train_lstm", "lstm_benchmark", _lstm_feed,
                 lambda sz: 4)


def phase_train_transformer(args, work):
    # flash forward + dq + dkv kernels per layer
    _train_phase(args, work, "train_transformer", "transformer_lm",
                 _transformer_feed, lambda sz: 3 * sz["layers"])


def _serve_sizes(rehearse: bool) -> dict:
    return (dict(image=64, max_batch=4) if rehearse
            else dict(image=224, max_batch=8))


def phase_serve_prepare(args, work):
    """Save the artifact and the oracle answers, then EXIT: this process
    holds the chip, and the server child needs it next."""
    dev = _device(args.rehearse_cpu)
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import models

    sz = _serve_sizes(args.rehearse_cpu)
    prog, startup = pt.Program(), pt.Program()
    startup.random_seed = 7
    with pt.program_guard(prog, startup):
        img = pt.layers.data("img", shape=[sz["image"], sz["image"], 3])
        logits = models.resnet_imagenet(img, class_dim=1000, is_test=True,
                                        data_format="NHWC")
    prog.set_amp("bfloat16")
    exe = pt.Executor()
    exe.run(startup)
    model_dir = os.path.join(work, "resnet50")
    pt.io.save_inference_model(model_dir, ["img"], [logits],
                               main_program=prog)
    iprog, feed_names, fetch_names = pt.io.load_inference_model(model_dir)
    assert iprog.amp_dtype == "bfloat16", "artifact lost its compute dtype"
    rng = np.random.RandomState(7)
    oracle = {}
    for b in (1, sz["max_batch"]):
        # multiples of 1/64: exact in f32 and short in the request's JSON
        x = (rng.randint(-128, 128, (b, sz["image"], sz["image"], 3))
             / 64.0).astype(np.float32)
        (y,) = exe.run(iprog, feed={feed_names[0]: x},
                       fetch_list=fetch_names)
        assert y.shape == (b, 1000) and np.all(np.isfinite(y)), y.shape
        oracle[f"x{b}"], oracle[f"y{b}"] = x, np.asarray(y, np.float32)
    np.savez(os.path.join(work, "oracle.npz"), **oracle)
    _emit("serve_prepare", dev, args.rehearse_cpu, model_dir=model_dir,
          batches=[1, sz["max_batch"]], **_compile_fields())


def _first_step_grads(config: str, make_executor) -> dict:
    """Gradients of every trained parameter on the first batch, from
    freshly seeded parameters, through `make_executor()`."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import cli
    from paddle_tpu.core.program import grad_var_name
    from paddle_tpu.data.feeder import DataFeeder

    pt.reset()
    model = cli._load_config(config)
    prog = pt.default_main_program()
    exe = make_executor()
    exe.run(pt.default_startup_program())
    params = list(next(op for op in prog.global_block().ops
                       if op.type == "autodiff").attrs["params"])
    feed = DataFeeder(model["feed_order"]).feed(next(iter(model["reader"]())))
    grads = exe.run(prog, feed=feed,
                    fetch_list=[grad_var_name(p) for p in params])
    return {p: np.asarray(g, np.float32) for p, g in zip(params, grads)}


def phase_four_chips(args, work):
    """LSTM benchmark config, global batch 512: `train --mesh dp4` on four
    devices, then the same program, seed and data on one, in this one
    process (it owns all four chips); then both executors' first-step
    gradients side by side."""
    dev = _device(args.rehearse_cpu)
    assert dev["count"] >= 4, f"--four-chips needs 4 devices: {dev}"
    import jax
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.parallel import ParallelExecutor
    from paddle_tpu.parallel.mesh import mesh_from_spec

    over = dict(REHEARSAL_SIZES["lstm_benchmark"], batch=32) \
        if args.rehearse_cpu else dict(batch=FOUR_CHIP_BATCH)
    over["steps"] = FOUR_CHIP_STEPS
    sz = {**_default_sizes("lstm_benchmark"), **over}
    cfg = _config_for("lstm_benchmark", work, over)

    dp4, _ = _cli_train(cfg, ["--mesh", "dp4"])
    out, hits0 = {}, _compile_fields()["cache_hits"]
    # what the dp4 run left behind and what it compiled
    exe = ParallelExecutor(mesh_from_spec("dp4"))
    scope = pt.global_scope()
    for v in pt.default_main_program().parameters():
        a = scope.get(v.name)
        devs = {s.device for s in a.addressable_shards}
        assert len(devs) == 4, f"param {v.name} lives on {len(devs)} device(s)"
    feed = _lstm_feed(sz)
    placed = jax.device_put(feed["label"], exe._feed_sharding(feed["label"]))
    shards = placed.addressable_shards
    assert len({s.device for s in shards}) == 4 and all(
        s.data.shape == (sz["batch"] // 4, 1) for s in shards), \
        [(s.device, s.data.shape) for s in shards]
    text = _step_text(exe, feed)
    out.update(_kernel_fields(text, 0 if args.rehearse_cpu else 4,
                              _compile_fields()["cache_hits"] - hits0))
    if not args.rehearse_cpu:
        # inside the shard_map the kernel sees the per-chip batch
        local = f"[{sz['seqlen']},{sz['batch'] // 4},{4 * sz['hidden']}]"
        calls = [ln for ln in text.splitlines() if KERNEL in ln]
        assert any(local in ln for ln in calls), (
            f"no fused kernel at the per-shard shape {local}")
        assert not any(f"[{sz['seqlen']},{sz['batch']}," in ln
                       for ln in calls), "a fused kernel ran unsharded"
    out["collectives"] = {c: text.count(f" {c}(") + text.count(
        f" {c}-start(") for c in COLLECTIVES}
    log(f"four_chips: collectives in the dp4 step: {out['collectives']}")

    pt.reset()
    one, _ = _cli_train(cfg)
    assert len(dp4) == len(one) == sz["steps"], (len(dp4), len(one))
    assert np.all(np.isfinite(dp4)) and np.all(np.isfinite(one))
    log(f"four_chips: loss dp4 {dp4}")
    log(f"four_chips: loss one {one}")
    # end to end: same seed and data, so the losses agree step by step
    diff = [abs(a - b) / max(1.0, abs(b)) for a, b in zip(dp4, one)]
    assert max(diff) <= FOUR_CHIP_LOSS_TOL, (
        f"dp4 and one-chip losses disagree beyond {FOUR_CHIP_LOSS_TOL}: "
        f"{diff}")
    # and at the source: what data parallelism owes is the same GRADIENT.
    # Adam divides a wrong scale back out of the update, so a loss can
    # hide what this cannot (it is how the fused kernels' doubled psum
    # was found: 4x on the LSTM weights, the losses one step apart).
    g4 = _first_step_grads(cfg, lambda: ParallelExecutor(
        mesh_from_spec("dp4")))
    g1 = _first_step_grads(cfg, pt.Executor)
    assert g4.keys() == g1.keys() and len(g1) >= 6, sorted(g1)
    worst = {n: float(np.max(np.abs(g4[n] - g1[n]))
                      / max(float(np.max(np.abs(g1[n]))), 1e-30))
             for n in g1}
    name = max(worst, key=worst.get)
    log(f"four_chips: gradient max-relative difference per tensor {worst}")
    assert worst[name] <= FOUR_CHIP_GRAD_TOL, (
        f"dp4 gradient of {name} is off the one-chip gradient by "
        f"{worst[name]} of its largest element")
    _emit("four_chips", dev, args.rehearse_cpu, steps=sz["steps"],
          global_batch=sz["batch"], loss_dp4=dp4, loss_one_chip=one,
          loss_max_diff=round(max(diff), 7), loss_tol=FOUR_CHIP_LOSS_TOL,
          grad_tensors=len(worst), grad_worst_tensor=name,
          grad_max_rel_diff=round(worst[name], 5),
          grad_tol=FOUR_CHIP_GRAD_TOL, devices_holding_shards=4,
          **out, **_compile_fields())


PHASES = {
    "probe": phase_probe, "train_lstm": phase_train_lstm,
    "train_transformer": phase_train_transformer,
    "serve_prepare": phase_serve_prepare, "four_chips": phase_four_chips,
}


# ----------------------------------------------------------------- parent ---
def _child_env(args) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
        if args.four_chips:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                " --xla_force_host_platform_device_count=4")
    return env


def _echo(text: str) -> None:
    """Relay a child's output; the rehearsal tags every relayed line."""
    for line in text.splitlines():
        print(line if _TAG == "chip_smoke" else f"[{_TAG}] {line}")
    sys.stdout.flush()


def _run_phase(name, args, work, env, t_end) -> dict:
    """One child, one phase. Returns its JSON line; raises on failure."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
           "--work", work]
    cmd += ["--rehearse-cpu"] if args.rehearse_cpu else []
    cmd += ["--four-chips"] if args.four_chips else []
    log(f"phase {name} ...")
    t0 = time.perf_counter()
    p = subprocess.run(cmd, env=env, stdout=subprocess.PIPE, text=True,
                       timeout=max(1.0, t_end - time.monotonic()))
    _echo(p.stdout)
    if p.returncode != 0:
        raise RuntimeError(f"phase {name} exited {p.returncode}")
    rec = json.loads(p.stdout.strip().splitlines()[-1])
    rec["wall_s"] = round(time.perf_counter() - t0, 1)
    return rec


def _http(url, body=None, timeout=120.0):
    import urllib.request

    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read()


def _serve_phase(args, work, env, t_end, prepared: dict) -> dict:
    """The ordinary single-process server as a child; this parent posts
    the requests and compares with numpy only."""
    import numpy as np

    oracle = np.load(os.path.join(work, "oracle.npz"))
    sz = _serve_sizes(args.rehearse_cpu)
    log("phase serve ...")
    t0 = time.perf_counter()
    srv = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu", "serve", "--model_dir",
         prepared["model_dir"], "--port", "0", "--max_batch_size",
         str(sz["max_batch"])],
        env=env, stdout=subprocess.PIPE, text=True)
    try:
        url = None
        for line in srv.stdout:
            _echo(line)
            if line.startswith("serving ") and " on http://" in line:
                url = line.rsplit(" on ", 1)[1].strip()
                break
            if time.monotonic() > t_end:
                break
        assert url, "server never came up"
        ready_s = time.perf_counter() - t0
        worst, lat_ms = 0.0, {}
        for b in (1, sz["max_batch"], 1):
            x, want = oracle[f"x{b}"], oracle[f"y{b}"]
            t1 = time.perf_counter()
            status, raw = _http(url + "/predict", {
                "inputs": {"img": x.astype(np.float64).tolist()},
                "timeout_ms": 60000})
            lat_ms[f"b{b}"] = round((time.perf_counter() - t1) * 1e3, 1)
            assert status == 200, status
            (got,) = json.loads(raw)["outputs"].values()
            got = np.asarray(got, np.float32)
            assert got.shape == want.shape and np.all(np.isfinite(got))
            rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
            assert rel <= SERVE_REL_TOL, (
                f"batch {b}: served answer off the oracle by {rel}")
            worst = max(worst, rel)
        status, raw = _http(url + "/healthz")
        health = json.loads(raw)
        assert status == 200 and health["status"] == "ok", health
        status, raw = _http(url + "/metrics")
        assert status == 200 and b"ptserving_" in raw
        srv.send_signal(signal.SIGTERM)
        tail = srv.stdout.read()
        _echo(tail)
        rc = srv.wait(timeout=90)
        assert rc == 0, f"server exited {rc} after SIGTERM"
        assert "drained; exiting" in tail, "no drain on SIGTERM"
    finally:
        if srv.poll() is None:
            srv.kill()
            srv.wait()
    dev = {"platform": prepared["platform"],
           "kind": prepared["device_kind"],
           "count": prepared["device_count"]}
    return _emit("serve", dev, args.rehearse_cpu,
                 ready_s=round(ready_s, 1), max_rel_diff=round(worst, 6),
                 rel_tol=SERVE_REL_TOL, request_ms=lat_ms, sigterm_rc=0,
                 wall_s=round(time.perf_counter() - t0, 1))


def _cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")


def parent(args) -> int:
    import tempfile

    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print("chip_smoke: paddle_tpu/ is not beside this script",
              file=sys.stderr)
        return 2
    t_end = time.monotonic() + DEADLINE_S
    env = _child_env(args)
    # what the previous smoke against this cache directory paid, to print
    # beside this run's compile seconds
    side = os.path.join(_cache_dir(), "chip_smoke_compile_seconds.json")
    mode = "four_chips" if args.four_chips else "one_chip"
    try:
        with open(side) as f:
            history = json.load(f)
    except (OSError, ValueError):
        history = {}
    prev = history.get(mode, {})
    names = ["probe", "four_chips"] if args.four_chips else [
        "probe", "train_lstm", "train_transformer", "serve_prepare"]
    recs = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        try:
            for name in names:
                recs[name] = _run_phase(name, args, work, env, t_end)
            if not args.four_chips:
                recs["serve"] = _serve_phase(args, work, env, t_end,
                                             recs["serve_prepare"])
        except Exception as e:  # noqa: BLE001 — any failure fails the smoke
            log(f"FAILED: {type(e).__name__}: {e}")
            return 1
    now = {n: r.get("compile_s", r.get("ready_s"))
           for n, r in recs.items() if "compile_s" in r or "ready_s" in r}
    for n, s in now.items():
        was = prev.get(n)
        log(f"compile seconds {n}: {s}"
            + (f" (previous run on this cache: {was})" if was is not None
               else " (no previous run on this cache)"))
    if not args.rehearse_cpu:
        try:
            os.makedirs(os.path.dirname(side), exist_ok=True)
            history[mode] = now
            with open(side, "w") as f:
                json.dump(history, f)
        except OSError as e:
            log(f"could not record compile seconds: {e}")
    probe = recs["probe"]
    verdict = {"ok": True, "device": {
        "platform": probe["platform"], "kind": probe["device_kind"],
        "count": probe["device_count"]}}
    if args.rehearse_cpu:
        verdict["rehearsal"] = True
    print(json.dumps(verdict), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    ap.add_argument("--work", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.rehearse_cpu:
        global _TAG
        _TAG = "chip_smoke REHEARSAL-ON-CPU"
    if args.phase:
        PHASES[args.phase](args, args.work)
        return 0
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())
