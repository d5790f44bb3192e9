"""paddle_tpu.serving: shape-bucketed batching inference server.

The contract under test (ISSUE 1 acceptance): ≥100 mixed-shape
requests compile at most len(buckets) XLA programs with ≥90% cache
hits after warmup, and every bucketed response is numerically
identical to the single-request exact-shape path (padding is sliced
away bit-for-bit). Plus the batcher's coalescing / load-shed /
deadline behavior and the HTTP front-end's endpoints.

One numerics note: padding within a request is bit-exact (asserted
with array_equal below), but rows COALESCED from different requests
run at a different total batch than they would alone, and XLA may
re-associate reductions across program shapes — the coalescing tests
therefore pin to float tolerance, not bits (see PERF.md "Serving").
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.serving import (
    BucketPolicy,
    DeadlineError,
    MicroBatcher,
    ModelRegistry,
    ServingEngine,
    ShedError,
    make_server,
)

# ---------------------------------------------------------------- fixtures --


def _train_dense_model(dirname: str) -> None:
    """Tiny 2-layer MLP regressor, saved as an inference model."""
    pt.reset()
    pt.default_startup_program().random_seed = 3
    x = pt.layers.data("x", shape=[4])
    y = pt.layers.data("y", shape=[1])
    h = pt.layers.fc(x, size=8, act="relu")
    pred = pt.layers.fc(h, size=1)
    cost = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.SGD(learning_rate=0.05).minimize(cost)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    rng = np.random.RandomState(0)
    for _ in range(10):
        xv = rng.randn(16, 4).astype(np.float32)
        exe.run(feed={"x": xv, "y": xv.sum(1, keepdims=True)},
                fetch_list=[cost])
    pt.io.save_inference_model(dirname, ["x"], [pred])


def _build_seq_model(dirname: str) -> None:
    """Position-wise model over [B, T, 6] (fc applied per position):
    zero-padded sequence positions cannot leak into real positions, the
    serving contract for seq-bucketed models."""
    pt.reset()
    pt.default_startup_program().random_seed = 3
    x = pt.layers.data("x", shape=[8, 6])  # declared T=8; runtime T varies
    h = pt.layers.fc(x, size=5, act="tanh", num_flatten_dims=2)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    pt.io.save_inference_model(dirname, ["x"], [h])


@pytest.fixture(scope="module")
def dense_model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("serve_dense"))
    _train_dense_model(d)
    return d


@pytest.fixture(scope="module")
def seq_model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("serve_seq"))
    _build_seq_model(d)
    return d


# ---------------------------------------------------------------- engine ----


def test_bucketed_bitmatch_and_bounded_compiles(dense_model_dir):
    """The acceptance criterion: 100 mixed-batch requests → at most
    len(batch_buckets) programs, ≥90% hit rate, every response
    bit-identical to the exact-shape single-request path."""
    eng = ServingEngine(dense_model_dir,
                        policy=BucketPolicy(max_batch_size=16),
                        model_name="acc")
    oracle = ServingEngine(dense_model_dir, model_name="acc_oracle")
    assert eng.policy.batch_buckets == (1, 2, 4, 8, 16)
    rng = np.random.RandomState(1)
    for n in rng.randint(1, 17, size=100):
        xv = rng.randn(n, 4).astype(np.float32)
        got = eng.predict({"x": xv})[0]
        want = oracle.predict({"x": xv}, bucketed=False)[0]
        assert got.shape == (n, 1)
        np.testing.assert_array_equal(got, want)
    s = eng.stats()
    assert s["compiled_programs"] <= len(eng.policy.batch_buckets), s
    assert s["hit_rate"] >= 0.9, s
    assert s["cache_hits"] + s["cache_misses"] == 100
    # engine bucket accounting and executor jit accounting must agree
    assert s["executor_cache"]["misses"] == s["compiled_programs"]


def test_warmup_precompiles_every_bucket(dense_model_dir):
    eng = ServingEngine(dense_model_dir,
                        policy=BucketPolicy(max_batch_size=8),
                        model_name="warm")
    n = eng.warmup()
    assert n == len(eng.policy.batch_buckets) == eng.compiled_programs()
    before = eng.exe.cache_stats["misses"]
    rng = np.random.RandomState(2)
    for k in rng.randint(1, 9, size=20):
        eng.predict({"x": rng.randn(k, 4).astype(np.float32)})
    # zero compiles after warmup: traffic is 100% cache hits
    assert eng.exe.cache_stats["misses"] == before


def test_uniform_dispatch_sync_counters(dense_model_dir):
    """The engine exposes Trainer-parity dispatch/sync counters
    (dispatches_total / syncs_total, ISSUE 6): warmup's pre-compiles
    count as dispatches, every predict is one dispatch + one d2h fence,
    and /stats and the Prometheus render carry the same numbers the
    trainer A/B tests assert on."""
    eng = ServingEngine(dense_model_dir,
                        policy=BucketPolicy(max_batch_size=8),
                        model_name="counters")
    warm = eng.warmup()
    assert eng.dispatches_total == eng.syncs_total == warm
    rng = np.random.RandomState(3)
    for k in (1, 3, 8):
        eng.predict({"x": rng.randn(k, 4).astype(np.float32)})
    s = eng.stats()
    assert s["dispatches_total"] == warm + 3
    assert s["syncs_total"] == warm + 3
    rendered = eng.metrics.render()
    assert "dispatches_total" in rendered and "syncs_total" in rendered


def test_seq_len_buckets(seq_model_dir):
    """Varying [B, T] traffic lands on the (batch × seq) bucket grid;
    padded positions are sliced away. A request whose shape IS a bucket
    runs the same program as the exact-shape path and must bit-match it.
    Any other request runs a DIFFERENT compiled program (the padded
    bucket's), and XLA:CPU may order its reductions differently there
    (observed 1.8e-7 on O(1) outputs with jaxlib 0.9.0), so those are
    held to 4 ulp of the largest output, not to bit identity."""
    pol = BucketPolicy(max_batch_size=4, seq_len_buckets=(4, 8))
    eng = ServingEngine(seq_model_dir, policy=pol, model_name="seq")
    oracle = ServingEngine(seq_model_dir, model_name="seq_oracle")
    rng = np.random.RandomState(3)
    exact = 0
    for _ in range(40):
        n = int(rng.randint(1, 5))
        t = int(rng.randint(2, 9))
        xv = rng.randn(n, t, 6).astype(np.float32)
        got = eng.predict({"x": xv})[0]
        want = oracle.predict({"x": xv}, bucketed=False)[0]
        assert got.shape == (n, t, 5)
        if n in pol.batch_buckets and t in pol.seq_len_buckets:
            exact += 1
            np.testing.assert_array_equal(got, want)
        else:
            atol = 4 * np.finfo(np.float32).eps * max(
                1.0, float(np.abs(want).max()))
            np.testing.assert_allclose(got, want, rtol=0, atol=atol)
    assert exact > 0  # the bit-exact arm was exercised
    assert eng.compiled_programs() <= pol.max_programs(), eng.stats()


def test_oversized_batch_rejected(dense_model_dir):
    eng = ServingEngine(dense_model_dir,
                        policy=BucketPolicy(max_batch_size=4),
                        model_name="cap")
    with pytest.raises(ValueError, match="exceeds the largest"):
        eng.predict({"x": np.zeros((5, 4), np.float32)})


def test_predictor_delegates_to_engine(dense_model_dir):
    """capi Predictor rides the same bucketed cache: sweeping batch
    sizes compiles per-bucket, not per-size, and raw-buffer IO
    round-trips."""
    from paddle_tpu.capi_support import Predictor

    p = Predictor(dense_model_dir)
    oracle = ServingEngine(dense_model_dir, model_name="pred_oracle")
    rng = np.random.RandomState(4)
    for n in (1, 2, 3, 5, 7, 8):
        xv = rng.randn(n, 4).astype(np.float32)
        blob, shape, dt = p.run_raw(
            ["x"], [xv.tobytes()], [list(xv.shape)], ["float32"], 0)
        got = np.frombuffer(blob, np.dtype(dt)).reshape(shape)
        want = oracle.predict({"x": xv}, bucketed=False)[0]
        np.testing.assert_array_equal(got, want)
    # 6 batch sizes -> buckets {1, 2, 4, 8}
    assert p.engine.compiled_programs() <= 4


# --------------------------------------------------------------- batcher ----


def test_batcher_coalesces_queued_requests(dense_model_dir):
    """Requests queued before the worker starts coalesce into ONE
    engine call (deterministic coalescing — no timing races)."""
    eng = ServingEngine(dense_model_dir,
                        policy=BucketPolicy(max_batch_size=16),
                        model_name="coal")
    oracle = ServingEngine(dense_model_dir, model_name="coal_oracle")
    b = MicroBatcher(eng, max_wait_ms=10, max_queue=16)
    rng = np.random.RandomState(5)
    reqs = [rng.randn(1, 4).astype(np.float32) for _ in range(6)]
    futs = [b.submit({"x": r}) for r in reqs]
    b.start()
    results = [f.result(timeout=30) for f in futs]
    b.stop()
    assert eng.cache_hits + eng.cache_misses == 1  # one coalesced call
    assert b._batch_hist.count == 1 and b._batch_hist.sum == 6
    for r, xv in zip(results, reqs):
        want = oracle.predict({"x": xv}, bucketed=False)[0]
        assert r[0].shape == want.shape
        # coalesced rows run at a different batch size than they would
        # alone; XLA may re-associate reductions across program shapes
        np.testing.assert_allclose(r[0], want, rtol=1e-5, atol=1e-6)


def test_batcher_concurrent_clients(dense_model_dir):
    """8 threads × 3 requests each against a running batcher: all
    correct, and coalescing did happen (fewer engine calls than
    requests)."""
    eng = ServingEngine(dense_model_dir,
                        policy=BucketPolicy(max_batch_size=32),
                        model_name="conc")
    eng.warmup()
    calls0 = eng.cache_hits + eng.cache_misses
    oracle = ServingEngine(dense_model_dir, model_name="conc_oracle")
    b = MicroBatcher(eng, max_wait_ms=30, max_queue=64).start()
    rng = np.random.RandomState(6)
    inputs = [rng.randn(2, 4).astype(np.float32) for _ in range(24)]
    outs: dict = {}
    errs = []

    def client(i):
        try:
            for j in range(3):
                k = i * 3 + j
                outs[k] = b.predict({"x": inputs[k]}, timeout_ms=20000)
        except Exception as e:  # pragma: no cover - diagnostic
            errs.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    b.stop()
    assert not errs, errs
    assert len(outs) == 24
    for k, res in outs.items():
        want = oracle.predict({"x": inputs[k]}, bucketed=False)[0]
        np.testing.assert_allclose(res[0], want, rtol=1e-5, atol=1e-6)
    engine_calls = eng.cache_hits + eng.cache_misses - calls0
    assert engine_calls < 24, f"no coalescing: {engine_calls} calls"


def test_queue_full_sheds_instead_of_hanging(dense_model_dir):
    eng = ServingEngine(dense_model_dir, model_name="shed")
    b = MicroBatcher(eng, max_queue=2)  # worker NOT started
    b.submit({"x": np.zeros((1, 4), np.float32)})
    b.submit({"x": np.zeros((1, 4), np.float32)})
    t0 = time.monotonic()
    with pytest.raises(ShedError, match="queue full"):
        b.submit({"x": np.zeros((1, 4), np.float32)})
    assert time.monotonic() - t0 < 1.0  # rejected immediately, no wait
    assert b.metrics.counter_value("shed_total") >= 1
    b.stop()  # queued requests fail with ShedError on shutdown


def test_deadline_exceeded_while_queued(dense_model_dir):
    eng = ServingEngine(dense_model_dir, model_name="dl")
    b = MicroBatcher(eng, max_queue=8)  # worker not started yet
    fut = b.submit({"x": np.zeros((1, 4), np.float32)}, timeout_ms=10)
    time.sleep(0.05)  # let the deadline lapse, then start the worker
    b.start()
    with pytest.raises(DeadlineError):
        fut.result(timeout=30)
    b.stop()


# ----------------------------------------------------------------- server ---


@pytest.fixture()
def http_stack(dense_model_dir):
    reg = ModelRegistry()
    eng, _ = reg.add("default", model_dir=dense_model_dir,
                     policy=BucketPolicy(max_batch_size=16),
                     max_wait_ms=5.0, timeout_ms=20000.0)
    eng.warmup()
    srv = make_server(reg)
    srv.serve_background()
    yield reg, srv, f"http://127.0.0.1:{srv.port}"
    srv.shutdown()
    reg.stop()
    srv.server_close()


def _post(url, payload):
    body = json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.load(r)


def test_http_predict_healthz_metrics(http_stack, dense_model_dir):
    reg, srv, url = http_stack
    with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
        h = json.load(r)
    assert h["status"] == "ok" and h["models"] == ["default"]

    oracle = ServingEngine(dense_model_dir, model_name="http_oracle")
    rng = np.random.RandomState(7)
    for n in (1, 3, 8):
        xv = rng.randn(n, 4).astype(np.float32)
        out = _post(url + "/predict", {"inputs": {"x": xv.tolist()}})
        (vals,) = out["outputs"].values()
        want = oracle.predict({"x": xv}, bucketed=False)[0]
        np.testing.assert_allclose(
            np.asarray(vals, np.float32), want, rtol=1e-5, atol=1e-6)

    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        metrics = r.read().decode()
    # the ISSUE-named surface: cache hit accounting + latency stats
    assert "ptserving_compile_cache_hits_total" in metrics
    assert "ptserving_engine_run_seconds_bucket" in metrics
    assert "ptserving_engine_run_seconds_p99" in metrics
    assert "ptserving_batch_rows" in metrics
    assert "ptserving_queue_depth" in metrics

    with urllib.request.urlopen(url + "/stats", timeout=30) as r:
        stats = json.load(r)
    assert stats["default"]["compiled_programs"] <= 5


def test_http_errors(http_stack):
    reg, srv, url = http_stack
    # unknown model → 404
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(url + "/predict/nope", {"inputs": {"x": [[0, 0, 0, 0]]}})
    assert ei.value.code == 404
    # malformed body → 400
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(url + "/predict", {"not_inputs": 1})
    assert ei.value.code == 400
    # missing feed → 400
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(url + "/predict", {"inputs": {"bogus": [1.0]}})
    assert ei.value.code == 400


def test_http_shed_and_deadline(dense_model_dir):
    """A stuck model (worker never started, queue of 1): the first
    request times out with 504, an overflowing one sheds with 503."""
    reg = ModelRegistry()
    eng = ServingEngine(dense_model_dir, model_name="stuck",
                        metrics=reg.metrics)
    stuck = MicroBatcher(eng, max_queue=1, metrics=reg.metrics)
    reg.add("stuck", engine=eng, batcher=stuck)
    srv = make_server(reg)
    # serve WITHOUT starting batchers (srv thread only)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    url = f"http://127.0.0.1:{srv.port}/predict/stuck"
    codes = {}

    def slow():
        try:
            _post(url, {"inputs": {"x": [[0, 0, 0, 0]]},
                        "timeout_ms": 300})
            codes["a"] = 200
        except urllib.error.HTTPError as e:
            codes["a"] = e.code

    ta = threading.Thread(target=slow)
    ta.start()
    time.sleep(0.1)  # first request now occupies the only queue slot
    try:
        _post(url, {"inputs": {"x": [[0, 0, 0, 0]]}, "timeout_ms": 300})
        codes["b"] = 200
    except urllib.error.HTTPError as e:
        codes["b"] = e.code
    ta.join(timeout=30)
    srv.shutdown()
    srv.server_close()
    assert codes["b"] == 503, codes
    assert codes["a"] == 504, codes


# -------------------------------------------- ISSUE 9: fleet plumbing -------


def test_healthz_reports_load_block(http_stack):
    """/healthz carries the load block a join-shortest-queue router
    scores replicas by: queue depth, slot occupancy, and the uniform
    dispatch/sync counters — no /metrics scrape needed."""
    reg, srv, url = http_stack
    with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
        payload = json.load(r)
    before = payload["load"]
    for k in ("queue_depth", "queue_age_ms", "active_slots", "max_slots",
              "slot_occupancy", "first_token_p99_ms", "dispatches_total",
              "syncs_total", "classes", "models"):
        assert k in before, before
    # per-model breakdown (ISSUE 16 satellite): each served model gets
    # its own queue_depth/age + SLO-class split, and /healthz carries
    # the artifact fingerprint the rollout verify gate checks
    assert set(before["models"]) == {"default"}
    m = before["models"]["default"]
    for k in ("queue_depth", "queue_age_ms", "classes", "slo_class"):
        assert k in m, m
    assert set(before["classes"]) == {"interactive", "batch"}
    assert payload["versions"]["default"]
    _post(url + "/predict", {"inputs": {"x": [[0.1, 0.2, 0.3, 0.4]]}})
    with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
        after = json.load(r)["load"]
    assert after["dispatches_total"] > before["dispatches_total"]
    assert after["syncs_total"] > before["syncs_total"]
    assert after["queue_depth"] == 0  # nothing waiting at rest
    assert after["queue_age_ms"] == 0.0  # empty queue has no age


def test_predict_adopts_request_id_header(http_stack):
    """The router-hop correlation satellite: a forwarded
    X-PT-Request-Id is adopted for the /predict MicroBatcher path and
    echoed on the response; absent the header, the replica mints one."""
    from paddle_tpu.serving import REQUEST_ID_HEADER

    reg, srv, url = http_stack
    body = json.dumps(
        {"inputs": {"x": [[0.1, 0.2, 0.3, 0.4]]}}).encode()
    req = urllib.request.Request(
        url + "/predict", data=body,
        headers={"Content-Type": "application/json",
                 REQUEST_ID_HEADER: "rt-777"})
    with urllib.request.urlopen(req, timeout=60) as r:
        assert r.headers.get(REQUEST_ID_HEADER) == "rt-777"
        json.load(r)
    req = urllib.request.Request(
        url + "/predict", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        minted = r.headers.get(REQUEST_ID_HEADER)
        json.load(r)
    assert minted  # replica minted its own


def test_batcher_submit_adopts_request_id(dense_model_dir):
    """Unit-level: MicroBatcher.submit(request_id=...) threads the id
    into its _Request (the /predict path's correlation key; before
    ISSUE 9 only the generation path carried caller-provided ids)."""
    from paddle_tpu.serving.batcher import _Request

    r = _Request({"x": np.zeros((1, 4), np.float32)}, deadline=1.0,
                 request_id="rt-42")
    assert r.request_id == "rt-42"
    r2 = _Request({"x": np.zeros((1, 4), np.float32)}, deadline=1.0)
    assert r2.request_id and r2.request_id != "rt-42"


# -------------------------------------- ISSUE 9: mesh-sharded inference -----


def _build_sharded_model(dirname: str) -> None:
    """Vocab-sharded embedding (rows striped over `mp`) + fc head:
    the partition spec must survive save→load via the meta.json
    sharding sidecar."""
    from paddle_tpu.parallel import sharded_embedding

    pt.reset()
    pt.default_startup_program().random_seed = 3
    ids = pt.layers.data("ids", shape=[6], dtype="int64")
    emb = sharded_embedding(ids, size=[32, 16])
    h = pt.layers.fc(emb, size=8, act="tanh", num_flatten_dims=2)
    out = pt.layers.fc(h, size=4, num_flatten_dims=2)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    pt.io.save_inference_model(dirname, ["ids"], [out])


def test_sharding_sidecar_roundtrip(tmp_path):
    """save_inference_model records partition specs in meta.json;
    load_inference_model re-attaches them to the restored vars."""
    d = str(tmp_path / "sharded")
    _build_sharded_model(d)
    with open(d + "/meta.json") as f:
        meta = json.load(f)
    assert meta["sharding"]["mesh_axes"] == ["mp"]
    (name, spec), = meta["sharding"]["specs"].items()
    assert spec == ["mp", None]
    prog, feeds, fetches = pt.io.load_inference_model(d, scope=pt.Scope())
    from jax.sharding import PartitionSpec

    v = prog.global_block().var(name)
    assert v.sharding == PartitionSpec("mp", None)


def test_mesh_replica_bit_identical_to_single_device(tmp_path):
    """THE ISSUE 9 sharded-inference acceptance: the same artifact
    served by a mesh replica (dp1,mp2 — embedding table striped over
    2 devices) returns outputs BIT-identical to the single-device
    engine, across batch buckets, including warmup."""
    from paddle_tpu.parallel import mesh_from_spec

    d = str(tmp_path / "sharded")
    _build_sharded_model(d)
    single = ServingEngine(d, policy=BucketPolicy(max_batch_size=4),
                           model_name="one_chip")
    mesh = mesh_from_spec("dp1,mp2")
    meshed = ServingEngine(d, policy=BucketPolicy(max_batch_size=4),
                           model_name="mesh", mesh=mesh)
    assert meshed.warmup() == len(meshed.policy.batch_buckets)
    rng = np.random.RandomState(5)
    for n in (1, 2, 3, 4):
        iv = rng.randint(0, 32, size=(n, 6)).astype(np.int64)
        a = single.predict({"ids": iv})[0]
        b = meshed.predict({"ids": iv})[0]
        assert b.shape == (n, 6, 4)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s = meshed.stats()
    assert s["mesh"]["axes"] == {"dp": 1, "mp": 2}
    assert s["mesh"]["sharded_params"]


def test_mesh_missing_axis_rejected(tmp_path):
    """A serving mesh without the axes the artifact shards over must
    fail loudly at load, not silently serve unsharded."""
    from paddle_tpu.parallel import mesh_from_spec

    d = str(tmp_path / "sharded")
    _build_sharded_model(d)
    with pytest.raises(ValueError, match="mp"):
        ServingEngine(d, model_name="bad",
                      mesh=mesh_from_spec("dp2"))
