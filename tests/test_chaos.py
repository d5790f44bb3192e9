"""Chaos harness: crash/corrupt/resume end-to-end (ISSUE 4 acceptance).

The headline case SIGKILLs a real training subprocess mid-pass (via the
deterministic `executor.step` kill fault — the process dies with the
SIGKILL status 137 and zero chance to clean up), corrupts the newest
checkpoint it left behind, resumes, and asserts the run completes with
parameters BIT-IDENTICAL to an uninterrupted run: the recovery path is
correct, not approximately correct.

Subprocess cases cost a few seconds of jax import each; the SIGTERM
preemption e2e is additionally marked `slow` (tier-1 covers the same
machinery in-process, test_resilience.py). The sharded chaos case runs
in-process on the 8-device CPU mesh.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import io as pio


TRAIN_SCRIPT = """
import sys
import time
import numpy as np
import paddle_tpu as pt

ckpt_dir, num_passes, out = sys.argv[1], int(sys.argv[2]), sys.argv[3]
# optional per-batch stall so a test can land a signal mid-training
sleep_s = float(sys.argv[4]) if len(sys.argv) > 4 else 0.0

x = pt.layers.data("x", shape=[4])
y = pt.layers.data("y", shape=[1])
pred = pt.layers.fc(x, size=1, param_attr=pt.ParamAttr(name="w"),
                    bias_attr=False)
loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
pt.optimizer.SGD(learning_rate=0.05).minimize(loss)
pt.init(seed=7)

def reader():
    for i in range(8):
        if sleep_s:
            time.sleep(sleep_s)
        rng = np.random.RandomState(100 + i)
        xs = rng.randn(8, 4).astype(np.float32)
        yield {"x": xs, "y": xs.sum(1, keepdims=True).astype(np.float32)}

cc = pt.CheckpointConfig(ckpt_dir, epoch_interval=0, step_interval=2,
                         max_num_checkpoints=100)
t = pt.Trainer(loss, checkpoint_config=cc)
try:
    t.train(reader, num_passes=num_passes)
except pt.resilience.PreemptedError as e:
    # what the CLI train command does: EX_TEMPFAIL for the scheduler
    print("PREEMPTED:", e, flush=True)
    sys.exit(pt.resilience.PREEMPT_EXIT_CODE)
np.savez(out, w=np.asarray(pt.global_scope().get("w")),
         step=np.int64(t.step))
print("DONE step", t.step, flush=True)
"""


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chaos_env(fault_spec=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # `python script.py` puts the SCRIPT's dir on sys.path, not our cwd
    env["PYTHONPATH"] = REPO_ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.pop("PT_FLAGS_FAULT_SPEC", None)
    if fault_spec:
        env["PT_FLAGS_FAULT_SPEC"] = fault_spec
    return env


def _run_script(script_path, args, fault_spec=None, timeout=180):
    return subprocess.run(
        [sys.executable, script_path, *map(str, args)],
        env=_chaos_env(fault_spec), capture_output=True, text=True,
        timeout=timeout)


@pytest.fixture
def train_script(tmp_path):
    p = tmp_path / "train_job.py"
    p.write_text(TRAIN_SCRIPT)
    return str(p)


@pytest.mark.chaos
def test_sigkill_midpass_corrupt_newest_resume_bitexact(
        train_script, tmp_path):
    """The acceptance e2e: kill -9 mid-pass, rot the newest checkpoint,
    resume → final params identical to a never-interrupted run."""
    # 1) uninterrupted reference run (3 passes × 8 batches = 24 steps)
    ref_out = str(tmp_path / "ref.npz")
    r = _run_script(train_script, [str(tmp_path / "ck_ref"), 3, ref_out])
    assert r.returncode == 0, r.stderr

    # 2) the victim: an uncatchable kill at the 11th step (mid-pass 1)
    d = str(tmp_path / "ck")
    r = _run_script(train_script, [d, 3, str(tmp_path / "never.npz")],
                    fault_spec="executor.step:hit=11:action=kill")
    assert r.returncode == 137, (r.returncode, r.stderr)  # SIGKILL status
    assert not os.path.exists(str(tmp_path / "never.npz"))
    newest = pio.get_latest_checkpoint_serial(d)
    assert newest >= 1, "the victim checkpointed before dying"

    # 3) bit-rot the newest checkpoint (meta marker stays present)
    p = os.path.join(d, f"checkpoint_{newest}", pio.PARAMS_FILE)
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)

    # 4) resume: must quarantine the rotten serial, restore the previous
    # one, and train to completion
    res_out = str(tmp_path / "res.npz")
    r = _run_script(train_script, [d, 3, res_out])
    assert r.returncode == 0, r.stderr
    assert os.path.isdir(os.path.join(d, f"checkpoint_{newest}.corrupt"))

    ref, res = np.load(ref_out), np.load(res_out)
    assert int(ref["step"]) == int(res["step"]) == 24
    np.testing.assert_array_equal(ref["w"], res["w"])


@pytest.mark.chaos
@pytest.mark.slow
def test_sigterm_preemption_resume_e2e(train_script, tmp_path):
    """Graceful preemption: SIGTERM → finish batch → emergency
    checkpoint → exit 75 (EX_TEMPFAIL); a rerun resumes and finishes
    with params identical to an uninterrupted run."""
    ref_out = str(tmp_path / "ref.npz")
    r = _run_script(train_script, [str(tmp_path / "ck_ref"), 3, ref_out])
    assert r.returncode == 0, r.stderr

    d = str(tmp_path / "ck")
    # 0.2s per batch keeps the victim inside train() long enough for
    # the signal to land mid-pass deterministically
    proc = subprocess.Popen(
        [sys.executable, train_script, d, "30",
         str(tmp_path / "never.npz"), "0.2"],
        env=_chaos_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    # preempt once training has demonstrably started (first cadence save)
    deadline = time.monotonic() + 120
    while (pio.get_latest_checkpoint_serial(d) < 0
           and time.monotonic() < deadline):
        time.sleep(0.1)
        if proc.poll() is not None:
            break
    assert pio.get_latest_checkpoint_serial(d) >= 0, proc.communicate()[1]
    proc.send_signal(signal.SIGTERM)
    out, err = proc.communicate(timeout=120)
    from paddle_tpu.resilience import PREEMPT_EXIT_CODE

    assert proc.returncode == PREEMPT_EXIT_CODE, (proc.returncode, err)
    assert "PREEMPTED" in out
    # the emergency checkpoint carries the exact mid-pass position
    args = json.load(open(os.path.join(
        d, f"checkpoint_{pio.get_latest_checkpoint_serial(d)}",
        pio.META_FILE)))["trainer_args"]
    assert args["step"] >= 1 and args.get("mid_pass")

    res_out = str(tmp_path / "res.npz")
    r = _run_script(train_script, [d, 3, res_out])
    assert r.returncode == 0, r.stderr
    ref, res = np.load(ref_out), np.load(res_out)
    assert int(res["step"]) == 24
    np.testing.assert_array_equal(ref["w"], res["w"])


# ------------------------------------- background checkpointing (in-proc)


@pytest.mark.chaos
def test_background_checkpoint_sigterm_drains_cleanly(tmp_path):
    """ISSUE 5: checkpoints commit on a background writer thread; a
    SIGTERM preemption must drain it — the emergency checkpoint (and
    every cadence one before it) is fully committed, hash-verified, with
    no torn tmp files, BEFORE PreemptedError reaches the caller."""
    import threading

    # threads that earlier tests of this worker process left alive are not
    # this run's: under `--dist loadfile` which files share a worker moves
    # with every test added anywhere, and the count read 23 once (PR 41)
    threads_before = threading.active_count()
    d = str(tmp_path / "ck")
    pt.reset()
    x = pt.layers.data("x", shape=[4])
    y = pt.layers.data("y", shape=[1])
    pred = pt.layers.fc(x, size=1, param_attr=pt.ParamAttr(name="w"),
                        bias_attr=False)
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.SGD(learning_rate=0.05).minimize(loss)

    def reader():
        for i in range(10):
            rng = np.random.RandomState(i)
            xs = rng.randn(8, 4).astype(np.float32)
            yield {"x": xs, "y": xs.sum(1, keepdims=True)}

    cc = pt.CheckpointConfig(d, epoch_interval=0, step_interval=1,
                             max_num_checkpoints=100)
    assert cc.background  # the async commit path is the default
    t = pt.Trainer(loss, checkpoint_config=cc)

    def preempt_at_4(e):
        if isinstance(e, pt.EndIteration) and e.step == 4:
            os.kill(os.getpid(), signal.SIGTERM)

    with pytest.raises(pt.resilience.PreemptedError, match="SIGTERM"):
        # coarse sync cadence: checkpoints + preemption must not depend
        # on the per-step fences of the legacy loop
        t.train(reader, num_passes=3, event_handler=preempt_at_4,
                log_interval=8)
    # writer idle and its thread quiesced — nothing is still writing
    assert t._ckpt_writer._idle.is_set()
    # every serial is complete and hash-valid, incl. the emergency one
    latest = pio.get_latest_checkpoint_serial(d)
    assert latest >= 1
    for name in os.listdir(d):
        sd = os.path.join(d, name)
        if os.path.isdir(sd):
            pio.verify_checkpoint(sd)
        assert not name.endswith(".tmp"), "torn background write left over"
    for name in os.listdir(os.path.join(d, f"checkpoint_{latest}")):
        assert not name.endswith(".tmp")
    # the emergency checkpoint carries the mid-pass resume position
    args = json.load(open(os.path.join(
        d, f"checkpoint_{latest}", pio.META_FILE)))["trainer_args"]
    assert args["step"] == 4 and args.get("mid_pass")
    # and a resume picks it up exactly (no threads from the dead run)
    assert threading.active_count() - threads_before < 20
    pt.reset_global_scope()
    t2 = pt.Trainer(loss, checkpoint_config=cc)
    t2.init()
    assert t2.step == 4 and t2._resume_batch == 4


@pytest.mark.chaos
def test_background_checkpoint_write_failure_surfaces(tmp_path):
    """An injected ckpt.write failure on the writer thread must fail the
    training run (at the next submit/drain), not vanish into a daemon."""
    from paddle_tpu.resilience import faults

    d = str(tmp_path / "ck")
    pt.reset()
    faults.arm("ckpt.write", hit=1, action="raise")
    try:
        x = pt.layers.data("x", shape=[4])
        pred = pt.layers.fc(x, size=1)
        loss = pt.layers.mean(pred)
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)

        def reader():
            for i in range(6):
                yield {"x": np.ones((4, 4), np.float32)}

        cc = pt.CheckpointConfig(d, epoch_interval=0, step_interval=2)
        t = pt.Trainer(loss, checkpoint_config=cc)
        with pytest.raises(RuntimeError, match="background checkpoint"):
            t.train(reader, num_passes=1, log_interval=8)
    finally:
        faults.disarm()


# ------------------------------------------------- sharded chaos (in-proc)


@pytest.mark.chaos
def test_sharded_corrupt_shard_falls_back_and_quarantines(tmp_path):
    """Satellite: corrupt one shards_p*.npz of the newest sharded
    serial — load must fall back to the previous serial and quarantine
    the bad one."""
    import jax
    from jax.sharding import PartitionSpec

    from paddle_tpu import parallel as pp

    assert len(jax.devices()) == 8
    mesh = pp.make_mesh((4, 2), ("dp", "mp"))
    pt.reset()
    x = pt.layers.data("x", shape=[16])
    y = pt.layers.data("y", shape=[1])
    h = pt.layers.fc(x, size=64, act="relu",
                     param_attr=pt.ParamAttr(name="w1"), bias_attr=False)
    pred = pt.layers.fc(h, size=1, param_attr=pt.ParamAttr(name="w2"),
                        bias_attr=False)
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.Adam(learning_rate=0.05).minimize(loss)
    prog = pt.default_main_program()
    prog.global_block().var("w1").sharding = PartitionSpec(None, "mp")
    prog.random_seed = 3
    pt.default_startup_program().random_seed = 3
    exe = pp.ParallelExecutor(mesh, shard_optimizer_state=True)
    pt.Executor().run(pt.default_startup_program())

    def feed(step):
        rng = np.random.RandomState(step)
        return {"x": rng.randn(16, 16).astype(np.float32),
                "y": rng.randn(16, 1).astype(np.float32)}

    d = str(tmp_path / "ck")
    exe.run(prog, feed=feed(0), fetch_list=[loss])
    pio.save_checkpoint(d, {"step": 1}, prog, sharded=True)
    w1_at_1 = np.asarray(pt.global_scope().get("w1")).copy()
    exe.run(prog, feed=feed(1), fetch_list=[loss])
    pio.save_checkpoint(d, {"step": 2}, prog, sharded=True)

    shard = os.path.join(d, "checkpoint_1", "shards_p0.npz")
    assert os.path.exists(shard)
    with open(shard, "r+b") as f:
        f.truncate(os.path.getsize(shard) // 2)

    pt.reset_global_scope()
    with pytest.warns(UserWarning, match="quarantined"):
        args = pio.load_checkpoint(d, prog)
    assert args["step"] == 1
    assert os.path.isdir(os.path.join(d, "checkpoint_1.corrupt"))
    np.testing.assert_array_equal(
        np.asarray(pt.global_scope().get("w1")), w1_at_1)


@pytest.mark.chaos
def test_sharded_injected_shard_corruption(tmp_path):
    """ckpt.write corrupt fires on the SHARD write path too."""
    import jax

    from paddle_tpu import parallel as pp
    from paddle_tpu.resilience import faults

    assert len(jax.devices()) == 8
    pp.make_mesh((4, 2), ("dp", "mp"))
    pt.reset()
    x = pt.layers.data("x", shape=[4])
    pred = pt.layers.fc(x, size=1, param_attr=pt.ParamAttr(name="w"),
                        bias_attr=False)
    loss = pt.layers.mean(pred)
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    prog = pt.default_main_program()
    pt.Executor().run(pt.default_startup_program())

    d = str(tmp_path / "ck")
    pio.save_checkpoint(d, {"step": 1}, prog, sharded=True)
    faults.arm("ckpt.write", hit=1, action="corrupt")
    pio.save_checkpoint(d, {"step": 2}, prog, sharded=True)
    faults.disarm()
    assert faults.stats()["ckpt.write"]["fired"] == 1
    with pytest.warns(UserWarning, match="quarantined"):
        assert pio.load_checkpoint(d, prog)["step"] == 1


# ------------------------------------ elastic restart on a new mesh shape


@pytest.mark.chaos
def test_sigterm_sharded_restart_on_different_mesh_bitwise(tmp_path):
    """ISSUE 14 acceptance: SIGTERM lands mid-pass in a dp-sharded
    (ZeRO optimizer state) run whose checkpoints commit sharded on the
    background writer; the restart happens on a DIFFERENT mesh shape
    (dp8 -> dp4x2) and must end with parameters BIT-IDENTICAL to an
    uninterrupted reference that checkpoints and switches mesh at the
    same step — the emergency path and the elastic reshard are both
    exact, not approximately correct."""
    import jax

    from paddle_tpu import parallel as pp

    assert len(jax.devices()) == 8

    def build():
        pt.reset()
        pt.default_main_program().random_seed = 13
        pt.default_startup_program().random_seed = 13
        x = pt.layers.data("x", shape=[8])
        y = pt.layers.data("y", shape=[1])
        h = pt.layers.fc(x, size=16, act="relu")
        pred = pt.layers.fc(h, size=1)
        loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.Adam(learning_rate=0.05).minimize(loss)
        return loss

    def batches(lo, hi):
        def reader():
            for i in range(lo, hi):
                rng = np.random.RandomState(100 + i)
                xs = rng.randn(8, 8).astype(np.float32)
                yield {"x": xs, "y": xs.sum(1, keepdims=True)}
        return reader

    def exe_on(spec):
        return pp.ParallelExecutor(pp.mesh_from_spec(spec),
                                   shard_optimizer_state=True)

    def host_params():
        return {n: np.asarray(pt.global_scope().get(n))
                for n in sorted(pt.global_scope().keys())
                if not n.startswith("@")}

    # --- interrupted arm: dp8, SIGTERM after batch 2, emergency
    # sharded checkpoint on the background writer ----------------------
    d = str(tmp_path / "ck")
    loss = build()
    cc = pt.CheckpointConfig(d, epoch_interval=0, sharded=True)
    assert cc.background
    t = pt.Trainer(loss, checkpoint_config=cc, executor=exe_on("dp8"))

    def preempt_at_3(e):
        if isinstance(e, pt.EndIteration) and e.step == 3:
            os.kill(os.getpid(), signal.SIGTERM)

    with pytest.raises(pt.resilience.PreemptedError, match="SIGTERM"):
        t.train(batches(0, 6), num_passes=1, event_handler=preempt_at_3,
                log_interval=1)
    assert t._ckpt_writer._idle.is_set()  # emergency commit fully drained
    args = json.load(open(os.path.join(
        d, f"checkpoint_{pio.get_latest_checkpoint_serial(d)}",
        pio.META_FILE)))["trainer_args"]
    assert args["step"] == 3 and args["mid_pass"]

    # restart on dp4x2: resumes pass 0 at batch 3, finishes the pass
    loss = build()
    t2 = pt.Trainer(loss, checkpoint_config=pt.CheckpointConfig(
        d, epoch_interval=0, sharded=True), executor=exe_on("dp4,mp2"))
    t2.train(batches(0, 6), num_passes=1, log_interval=1)
    assert t2.step == 6
    interrupted = host_params()

    # --- reference arm: same schedule, no SIGTERM — 3 batches on dp8,
    # checkpoint, then batches 3..5 on dp4x2 ---------------------------
    d_ref = str(tmp_path / "ck_ref")
    loss = build()
    tr1 = pt.Trainer(loss, executor=exe_on("dp8"))
    tr1.train(batches(0, 3), num_passes=1, log_interval=1)
    pio.save_checkpoint(d_ref, {"step": 3}, pt.default_main_program(),
                        sharded=True)
    loss = build()
    tr2 = pt.Trainer(loss, checkpoint_config=pt.CheckpointConfig(
        d_ref, epoch_interval=0, sharded=True), executor=exe_on("dp4,mp2"))
    tr2.train(batches(3, 6), num_passes=1, log_interval=1)
    ref = host_params()

    assert set(interrupted) == set(ref)
    bad = [n for n in ref
           if not np.array_equal(ref[n], interrupted[n])]
    assert not bad, f"elastic restart diverged from reference: {bad[:6]}"
