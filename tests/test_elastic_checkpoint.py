"""Elastic sharded checkpoints (ISSUE 14, paddle_tpu/pipeline/elastic).

Three contracts:
  * background sharded commit blocks only on an IN-FLIGHT previous
    commit — the capture is one device-side copy of the state (no d2h,
    no host sync), and the values committed are the values at submit
    time even though the training steps that follow donate the live
    scope's buffers;
  * resume-with-resharding — a dp8-saved checkpoint restores
    bit-identically onto a dp4x2 mesh and onto a 4-device mesh (the
    sharded format stores GLOBAL arrays, placement is re-derived);
  * a torn single shard costs one checkpoint interval, never the
    restore — typed corruption, quarantine, newest-VALID fallback.
"""

import json
import os
import time
import zipfile

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec

import paddle_tpu as pt
from paddle_tpu import io as pio
from paddle_tpu import parallel as pp
from paddle_tpu.obs.metrics import registry
from paddle_tpu.pipeline import elastic
from paddle_tpu.trainer import _CheckpointWriter


def _build(seed=5):
    pt.default_main_program().random_seed = seed
    pt.default_startup_program().random_seed = seed
    x = pt.layers.data("x", shape=[16])
    y = pt.layers.data("y", shape=[1])
    h = pt.layers.fc(x, size=32, act="relu",
                     param_attr=pt.ParamAttr(name="w1"), bias_attr=False)
    pred = pt.layers.fc(h, size=1, param_attr=pt.ParamAttr(name="w2"),
                        bias_attr=False)
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.Adam(learning_rate=0.05).minimize(loss)
    return loss


def _feed(step):
    rng = np.random.RandomState(step)
    return {"x": rng.randn(16, 16).astype(np.float32),
            "y": rng.randn(16, 1).astype(np.float32)}


def _host_params():
    return {n: np.asarray(pt.global_scope().get(n))
            for n in sorted(pt.global_scope().keys())
            if not n.startswith("@")}


# ------------------------------------------------- background commit --


def test_submit_blocks_only_on_inflight_commit(tmp_path, monkeypatch):
    """The acceptance assertion: with a slow commit in flight, a fresh
    submit returns immediately (a device-side copy, no d2h, no disk);
    the NEXT submit drains the in-flight one first (double buffer)."""
    loss = _build()
    exe = pt.Executor()
    exe.run_startup(pt.default_startup_program())
    exe.run(feed=_feed(0), fetch_list=[loss])

    real_save = pio.save_checkpoint
    delay = 0.4

    def slow_save(*a, **kw):
        time.sleep(delay)
        return real_save(*a, **kw)

    monkeypatch.setattr(pio, "save_checkpoint", slow_save)
    writer = _CheckpointWriter()
    d = str(tmp_path / "ck")
    prog = pt.default_main_program()

    t0 = time.monotonic()
    elastic.submit_sharded_save(writer, d, trainer_args={"step": 1},
                                main_program=prog)
    first_submit = time.monotonic() - t0
    assert first_submit < delay / 2, (
        f"submit spent {first_submit:.3f}s — it must not wait for the "
        "commit it just enqueued")

    t0 = time.monotonic()
    elastic.submit_sharded_save(writer, d, trainer_args={"step": 2},
                                main_program=prog)
    second_submit = time.monotonic() - t0
    assert second_submit >= delay / 2, (
        "second submit returned before the in-flight commit drained — "
        "unbounded snapshot queue")
    writer.drain()
    assert writer.commits == 2 and writer.failures == 0
    assert pio.get_latest_checkpoint_serial(d) == 1


def test_snapshot_isolated_from_continued_training(tmp_path):
    """Values committed are the values AT SUBMIT TIME: training (or an
    outright overwrite) after submit must not leak into the commit."""
    loss = _build()
    exe = pt.Executor()
    exe.run_startup(pt.default_startup_program())
    exe.run(feed=_feed(0), fetch_list=[loss])
    at_submit = _host_params()

    writer = _CheckpointWriter()
    d = str(tmp_path / "ck")
    elastic.submit_sharded_save(writer, d, trainer_args={"step": 1},
                                main_program=pt.default_main_program())
    # mutate the live scope while the commit may still be in flight
    pt.global_scope().set("w1", np.zeros_like(at_submit["w1"]))
    writer.drain()

    pt.reset_global_scope()
    args = pio.load_checkpoint(d, pt.default_main_program())
    assert args == {"step": 1}
    got = _host_params()
    for n, v in at_submit.items():
        np.testing.assert_array_equal(v, got[n], err_msg=n)


def test_snapshot_survives_the_steps_dispatched_after_it(tmp_path,
                                                         monkeypatch):
    """A training step donates the buffers it rebinds, so the arrays the
    scope held at submit are dead one step later: the snapshot owns a
    copy, and a commit whose d2h starts after five more steps have been
    dispatched still writes step k's values."""
    loss = _build()
    exe = pt.Executor()
    exe.run_startup(pt.default_startup_program())
    for step in range(3):
        exe.run(feed=_feed(step), fetch_list=[loss])
    # copies: on the CPU `np.asarray` is a view that pins its buffer,
    # and a pinned buffer is (safely) not donated
    at_submit = {n: np.array(v) for n, v in _host_params().items()}
    live = {n: pt.global_scope().get(n) for n in at_submit}

    real_save = pio.save_checkpoint
    started = []

    def late_save(*a, **kw):
        time.sleep(0.3)  # the five steps below are dispatched meanwhile
        started.append(time.monotonic())
        return real_save(*a, **kw)

    monkeypatch.setattr(pio, "save_checkpoint", late_save)
    writer = _CheckpointWriter()
    d = str(tmp_path / "ck")
    elastic.submit_sharded_save(writer, d, trainer_args={"step": 3},
                                main_program=pt.default_main_program())
    for step in range(3, 8):
        exe.run(feed=_feed(step), fetch_list=[loss], as_numpy=False)
    dispatched = time.monotonic()
    # every array the program rebinds is gone from under a reference
    # snapshot; only the learning rate is still the object it was
    dead = {n for n, a in live.items() if a.is_deleted()}
    assert {"w1", "w2"} <= dead and len(dead) == len(live) - 1
    writer.drain()
    assert writer.commits == 1 and writer.failures == 0
    assert started and started[0] > dispatched - 0.25
    after_training = _host_params()

    pt.reset_global_scope()
    assert pio.load_checkpoint(d, pt.default_main_program()) == {"step": 3}
    got = _host_params()
    for n, v in at_submit.items():
        np.testing.assert_array_equal(v, got[n], err_msg=n)
    assert not np.array_equal(after_training["w1"], at_submit["w1"])


def test_submit_refuses_multiprocess(monkeypatch):
    _build()
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    with pytest.raises(NotImplementedError, match="single-process"):
        elastic.submit_sharded_save(_CheckpointWriter(), "/tmp/nope")


# ---------------------------------------------------------- resharding --


def _train_on_mesh(mesh, steps):
    pt.reset()
    loss = _build()
    gb = pt.default_main_program().global_block()
    gb.var("w1").sharding = PartitionSpec(None, "mp") \
        if "mp" in mesh.axis_names else PartitionSpec()
    exe = pp.ParallelExecutor(mesh, shard_optimizer_state=True)
    pt.Executor().run(pt.default_startup_program())
    for s in range(steps):
        exe.run(pt.default_main_program(), feed=_feed(s),
                fetch_list=[loss])
    return loss


@pytest.mark.parametrize("target_spec", ["dp4,mp2", "dp4"])
def test_dp8_checkpoint_resumes_resharded_bitwise(tmp_path, target_spec):
    """dp8-saved params restore BIT-identically onto a dp4x2 mesh and
    onto a 4-device mesh (different device count via mesh prefix)."""
    assert len(jax.devices()) == 8
    mesh8 = pp.make_mesh((8,), ("dp",))
    _train_on_mesh(mesh8, 2)
    saved = _host_params()
    d = str(tmp_path / "ck")
    pio.save_checkpoint(d, {"step": 2}, pt.default_main_program(),
                        sharded=True)

    pt.reset_global_scope()
    target = pp.mesh_from_spec(target_spec)
    args = elastic.load_checkpoint_resharded(
        d, pt.default_main_program(), mesh=target)
    assert args == {"step": 2}
    got = _host_params()
    assert set(got) == set(saved)
    for n, v in saved.items():
        np.testing.assert_array_equal(v, got[n], err_msg=n)
    # and the restored state actually lives on the target mesh
    w1 = pt.global_scope().get("w1")
    assert set(w1.sharding.mesh.axis_names) == set(target.axis_names)


def test_world_change_counts_reshard(tmp_path):
    """sharded_meta.json records the saving world; loading under a
    different one increments pt_ckpt_reshard_total."""
    loss = _build()
    pt.Executor().run(pt.default_startup_program())
    pt.Executor().run(feed=_feed(0), fetch_list=[loss])
    d = str(tmp_path / "ck")
    pio.save_checkpoint(d, {"step": 1}, pt.default_main_program(),
                        sharded=True)
    sd = os.path.join(d, "checkpoint_0")
    meta_path = os.path.join(sd, pio.SHARDED_META)
    with open(meta_path) as f:
        meta = json.load(f)
    assert meta["world"]["device_count"] == jax.device_count()

    before = registry().counter_value(elastic.RESHARD_COUNTER) or 0.0
    pio.load_sharded_checkpoint(sd, pt.default_main_program())
    assert registry().counter_value(elastic.RESHARD_COUNTER) == before

    # rewrite the recorded world: now it's an elastic restore.
    # (sha256 integrity covers payload files, not the manifest itself,
    # so the edit keeps the serial loadable — mirror any hash update
    # here if that ever changes.)
    meta["world"]["device_count"] = 9999
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    pio.load_sharded_checkpoint(sd, pt.default_main_program())
    assert registry().counter_value(elastic.RESHARD_COUNTER) == before + 1


# ------------------------------------------------- torn-shard fallback --


def _two_serials(tmp_path):
    loss = _build()
    exe = pt.Executor()
    exe.run_startup(pt.default_startup_program())
    d = str(tmp_path / "ck")
    prog = pt.default_main_program()
    exe.run(feed=_feed(0), fetch_list=[loss])
    pio.save_checkpoint(d, {"step": 1}, prog, sharded=True)
    good = _host_params()
    exe.run(feed=_feed(1), fetch_list=[loss])
    pio.save_checkpoint(d, {"step": 2}, prog, sharded=True)
    return d, good


def test_torn_shard_quarantines_and_falls_back(tmp_path):
    d, good = _two_serials(tmp_path)
    shard = os.path.join(d, "checkpoint_1", "shards_p0.npz")
    with open(shard, "r+b") as f:  # tear the newest serial's one shard
        f.truncate(max(os.path.getsize(shard) // 2, 8))

    # read-only probe: newest COMPLETE is 1, newest VALID is 0
    assert pio.get_latest_checkpoint_serial(d) == 1
    assert pio.get_latest_checkpoint_serial(d, verify=True) == 0
    assert os.path.exists(os.path.join(d, "checkpoint_1"))  # no side effect

    with pytest.warns(UserWarning, match="quarantined"):
        args = pio.load_checkpoint(d, pt.default_main_program())
    assert args == {"step": 1}
    assert not os.path.exists(os.path.join(d, "checkpoint_1"))
    assert os.path.exists(os.path.join(d, "checkpoint_1.corrupt"))
    got = _host_params()
    for n, v in good.items():
        np.testing.assert_array_equal(v, got[n], err_msg=n)


def test_verify_detects_flipped_payload_byte(tmp_path):
    d, _ = _two_serials(tmp_path)
    shard = os.path.join(d, "checkpoint_1", "shards_p0.npz")
    with open(shard, "r+b") as f:
        f.seek(os.path.getsize(shard) // 2)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(pio.CheckpointCorruptError, match="sha256"):
        pio.verify_checkpoint(os.path.join(d, "checkpoint_1"))
    assert pio.get_latest_checkpoint_serial(d, verify=True) == 0


def test_missing_shard_member_is_typed(tmp_path):
    """A stale/truncated shard file that still opens as a zip raises the
    TYPED CheckpointCorruptError (so load_checkpoint can fall back), not
    a bare KeyError."""
    d, _ = _two_serials(tmp_path)
    sd = os.path.join(d, "checkpoint_1")
    shard = os.path.join(sd, "shards_p0.npz")
    # rebuild the archive with one member dropped
    with zipfile.ZipFile(shard) as z:
        names = z.namelist()
        keep = {n: z.read(n) for n in names[:-1]}
    with zipfile.ZipFile(shard, "w") as z:
        for n, blob in keep.items():
            z.writestr(n, blob)
    with pytest.raises(pio.CheckpointCorruptError,
                       match="missing member|uncovered"):
        pio.load_sharded_checkpoint(sd, pt.default_main_program())
