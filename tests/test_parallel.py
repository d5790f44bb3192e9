"""Parallelism tests on the simulated 8-device CPU mesh.

Reference analogue: in-process distributed tests (SURVEY.md §4.5 —
test_ParameterServer2.cpp runs servers+client in one process; nccl_op
tests run multi-GPU in one process). Here an 8-virtual-device mesh
exercises dp sharding, sharded embeddings (mp), and explicit collectives.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

import paddle_tpu as pt
from paddle_tpu import parallel as pp


@pytest.fixture
def mesh8():
    assert len(jax.devices()) == 8, "conftest must force 8 virtual devices"
    return pp.make_mesh((8,), ("dp",))


@pytest.fixture
def mesh42():
    return pp.make_mesh((4, 2), ("dp", "mp"))


def test_data_parallel_matches_single_device(mesh8):
    """Same program, same data: ParallelExecutor over 8 devices must equal

    the single-device Executor numerically (the reference's CPU-vs-GPU /
    single-vs-multi equivalence pattern, test_CompareTwoNets.cpp)."""
    def build():
        x = pt.layers.data("x", shape=[8])
        y = pt.layers.data("y", shape=[1])
        h = pt.layers.fc(x, size=16, act="relu",
                         param_attr=pt.ParamAttr(name="w1"),
                         bias_attr=pt.ParamAttr(name="b1"))
        pred = pt.layers.fc(h, size=1, param_attr=pt.ParamAttr(name="w2"),
                            bias_attr=pt.ParamAttr(name="b2"))
        loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.SGD(learning_rate=0.05).minimize(loss)
        return loss

    rng = np.random.RandomState(0)
    xv = rng.randn(32, 8).astype(np.float32)
    yv = rng.randn(32, 1).astype(np.float32)

    # single device
    pt.reset()
    loss = build()
    prog_s = pt.default_main_program()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    snap = {k: np.asarray(pt.global_scope().get(k)).copy()
            for k in pt.global_scope().keys()}
    for _ in range(3):
        (ls,) = exe.run(prog_s, feed={"x": xv, "y": yv}, fetch_list=[loss])
    w_single = np.asarray(pt.global_scope().get("w1")).copy()

    # 8-device dp, identical init
    pt.reset()
    loss = build()
    prog_p = pt.default_main_program()
    for k, v in snap.items():
        pt.global_scope().set(k, v)
    pexe = pp.ParallelExecutor(mesh8)
    for _ in range(3):
        (lp,) = pexe.run(prog_p, feed={"x": xv, "y": yv}, fetch_list=[loss])
    w_par = np.asarray(pt.global_scope().get("w1"))

    np.testing.assert_allclose(ls, lp, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(w_single, w_par, rtol=1e-5, atol=1e-6)


def test_sharded_embedding_trains(mesh42):
    ids = pt.layers.data("ids", shape=[1], dtype=np.int32)
    label = pt.layers.data("label", shape=[1])
    emb = pp.sharded_embedding(ids, size=[64, 16], mesh_axis="mp",
                               param_attr=pt.ParamAttr(name="emb_w"))
    emb2 = pt.layers.reshape(emb, (-1, 16))
    pred = pt.layers.fc(emb2, size=1)
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, label))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)

    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    pexe = pp.ParallelExecutor(mesh42)
    rng = np.random.RandomState(0)
    ids_v = rng.randint(0, 64, size=(16, 1)).astype(np.int32)
    y_v = rng.randn(16, 1).astype(np.float32)
    losses = [
        float(pexe.run(feed={"ids": ids_v, "label": y_v}, fetch_list=[loss])[0])
        for _ in range(10)
    ]
    assert losses[-1] < losses[0]
    # table sharding survived the update loop
    w = pt.global_scope().get("emb_w")
    spec = w.sharding.spec if hasattr(w.sharding, "spec") else None
    assert spec == PartitionSpec("mp", None), spec


def test_ragged_feed_data_parallel(mesh8):
    """LSTM over a dp-sharded ragged batch runs and matches 1-device."""
    x = pt.layers.data("x", shape=[-1, 8], lod_level=1, append_batch_size=False)
    h = pt.layers.dynamic_lstm(x, size=8, max_len=8,
                               param_attr=pt.ParamAttr(name="lw"))
    pooled = pt.layers.sequence_pool(h, "last")
    out = pt.layers.mean(pooled)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    from paddle_tpu.core.lod import LoDArray

    rng = np.random.RandomState(0)
    seqs = [rng.randn(5, 8).astype(np.float32) for _ in range(8)]
    lod = LoDArray.from_sequences(seqs, capacity=64, max_seqs=8)
    (ref,) = exe.run(feed={"x": lod}, fetch_list=[out])
    pexe = pp.ParallelExecutor(mesh8)
    (par,) = pexe.run(feed={"x": lod}, fetch_list=[out])
    np.testing.assert_allclose(ref, par, rtol=1e-5, atol=1e-6)


def test_collectives_shard_map(mesh8):
    """psum / ring allreduce equivalence under shard_map."""
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)

    def f_psum(x):
        return pp.all_reduce(x, "dp")

    def f_ring(x):
        return pp.ring_all_reduce(x, "dp")

    s = PartitionSpec("dp", None)
    out1 = pp.shard_map_fn(f_psum, mesh8, (s,), s)(x)
    out2 = pp.shard_map_fn(f_ring, mesh8, (s,), s)(x)
    expect = np.tile(np.asarray(x).reshape(8, 1, 8).sum(axis=0), (8, 1))
    np.testing.assert_allclose(np.asarray(out1), expect.reshape(8, 8))
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out1), rtol=1e-5)


def test_reduce_scatter_allgather_roundtrip(mesh8):
    x = jnp.ones((64, 16), jnp.float32)  # per-shard [8, 16]

    def f(x):
        rs = pp.reduce_scatter(x, "dp", axis=0)  # -> [1, 16] per shard
        return pp.all_gather(rs, "dp", axis=0)  # -> [8, 16] per shard

    s = PartitionSpec("dp", None)
    out = pp.shard_map_fn(f, mesh8, (s,), s)(x)
    np.testing.assert_allclose(np.asarray(out), 8.0 * np.ones((64, 16)))


def test_sharded_optimizer_state_matches_replicated(mesh8):
    """ZeRO-1 via GSPMD (SURVEY.md §5.8): sharding Adam moments over dp

    must not change the training trajectory, and the state arrays must
    actually live sharded on the mesh."""
    def build():
        x = pt.layers.data("x", shape=[8])
        y = pt.layers.data("y", shape=[1])
        h = pt.layers.fc(x, size=16, act="relu",
                         param_attr=pt.ParamAttr(name="zw1"))
        pred = pt.layers.fc(h, size=1, param_attr=pt.ParamAttr(name="zw2"))
        loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.Adam(learning_rate=0.01).minimize(loss)
        return loss

    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(16, 8).astype(np.float32),
            "y": rng.randn(16, 1).astype(np.float32)}

    losses = {}
    for shard in (False, True):
        pt.reset()
        pt.default_startup_program().random_seed = 5
        loss = build()
        exe = pp.ParallelExecutor(mesh8, shard_optimizer_state=shard)
        base = pt.Executor()
        base.run(pt.default_startup_program())
        ls = []
        for _ in range(5):
            (l,) = exe.run(feed=feed, fetch_list=[loss])
            ls.append(float(l))
        losses[shard] = ls
        if shard:
            state_names = [
                v.name for v in pt.default_main_program().persistables()
                if getattr(v, "is_optimizer_state", False)
                and v.shape and v.shape[0] != -1 and v.shape[0] % 8 == 0
            ]
            assert state_names, "no shardable optimizer state found"
            m = pt.global_scope().get(state_names[0])
            spec = m.sharding.spec
            assert spec and spec[0] == "dp", (state_names[0], spec)
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)
