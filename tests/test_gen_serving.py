"""Continuous batching for generation serving (ISSUE 7).

The contract under test: a token-level scheduler admits queued
generation requests into a fixed pool of device-resident decode slots,
steps the whole pool as ONE jitted program, retires finished beams
early (compaction), and streams tokens — with per-request results
BIT-IDENTICAL to the batch-mode `beam_search_group` decode (the pool
step and the batch kernel scan share one `beam_step` definition, see
ops/generation_ops.py). Plus: admission never exceeds max_slots,
deadline/shed semantics match the MicroBatcher contract, the
`serving.predict` fault point aborts in-flight requests with 503s and
recovers the slots, /generate streams NDJSON end-to-end, and the
save_inference_model meta sidecar lets warmup pre-compile the pool
without re-tracing the model source.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.resilience import faults
from paddle_tpu.resilience.breaker import CircuitBreaker, CircuitOpenError
from paddle_tpu.serving import (
    BucketPolicy,
    ContinuousScheduler,
    DeadlineError,
    GenerationAborted,
    ModelRegistry,
    ServingEngine,
    ShedError,
    make_server,
)

V, E, H = 12, 8, 16
BOS, EOS = 0, 1
K, T = 3, 6

# ---------------------------------------------------------------- fixtures --


def _build_gen_model(dirname: str, length_normalize: bool = False) -> None:
    """Tiny GRU-ish LM decoder (same shape as test_generation.py),
    saved as an inference model with the generation meta sidecar."""
    pt.reset()
    pt.default_startup_program().random_seed = 3
    h0 = pt.layers.data("h0", shape=[-1, H], append_batch_size=False)
    gen = pt.layers.BeamSearchDecoder(
        beam_size=K, max_len=T, bos_id=BOS, eos_id=EOS,
        length_normalize=length_normalize)
    with gen.step():
        prev = gen.prev_ids()
        h_prev = gen.memory(init=h0)
        emb = pt.layers.embedding(prev, size=[V, E], param_attr="g_emb")
        h = pt.layers.fc(
            pt.layers.concat([emb, h_prev], axis=1), size=H, act="tanh",
            param_attr="g_w", bias_attr=pt.ParamAttr(name="g_b"))
        gen.update_memory(h_prev, h)
        gen.output_logits(pt.layers.fc(
            h, size=V, param_attr="g_wo",
            bias_attr=pt.ParamAttr(name="g_bo")))
    ids, scores, lengths = gen()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    pt.io.save_inference_model(dirname, ["h0"], [ids, scores, lengths])


CH_V, CH_T, CH_K = 20, 12, 2
_CH_BONUS, _CH_BETA = 10.0, 1.0


def _build_chain_model(dirname: str) -> None:
    """Controlled-length decoder (the bench's handcrafted token-chain):
    the request's boot memory is an EOS threshold, so the decode length
    is ~(thr + 11) — ragged-finish tests pick lengths exactly."""
    pt.reset()
    thr = pt.layers.data("thr", shape=[-1, 1], append_batch_size=False)
    gen = pt.layers.BeamSearchDecoder(beam_size=CH_K, max_len=CH_T,
                                      bos_id=BOS, eos_id=EOS)
    with gen.step():
        prev = gen.prev_ids()
        thr_m = gen.memory(init=thr)
        emb = pt.layers.embedding(prev, size=[CH_V, CH_V],
                                  param_attr="c_emb")
        logits = pt.layers.fc(
            pt.layers.concat([emb, thr_m], axis=1), size=CH_V,
            param_attr="c_ctl", bias_attr=False)
        gen.update_memory(thr_m, thr_m)
        gen.output_logits(logits)
    ids, scores, lengths = gen()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    scope = pt.global_scope()
    scope.set("c_emb", np.eye(CH_V, dtype=np.float32))
    w = np.full((CH_V + 1, CH_V), -30.0, np.float32)
    w[:, BOS] = -60.0
    for v in range(2, CH_V - 1):
        for j in range(CH_K):
            w[v, min(v + 1 + j, CH_V - 1)] = _CH_BONUS - j
        w[v, EOS] = _CH_BETA * v
    for j in range(CH_K):
        w[BOS, 2 + j] = _CH_BONUS - j
    w[CH_V - 1, EOS] = _CH_BONUS + 5.0
    w[CH_V, :] = 0.0
    w[CH_V, EOS] = -_CH_BETA
    scope.set("c_ctl", w)
    pt.io.save_inference_model(dirname, ["thr"], [ids, scores, lengths])


def _chain_thr(length: int) -> np.ndarray:
    return np.array([[length - (_CH_BONUS / _CH_BETA + 1.0)]], np.float32)


@pytest.fixture(scope="module")
def gen_model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("gen_model"))
    _build_gen_model(d)
    return d


@pytest.fixture(scope="module")
def gen_ln_model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("gen_ln_model"))
    _build_gen_model(d, length_normalize=True)
    return d


@pytest.fixture(scope="module")
def chain_model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("gen_chain"))
    _build_chain_model(d)
    return d


@pytest.fixture(scope="module")
def dense_model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("gen_dense"))
    pt.reset()
    pt.default_startup_program().random_seed = 3
    x = pt.layers.data("x", shape=[4])
    pred = pt.layers.fc(x, size=2)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    pt.io.save_inference_model(d, ["x"], [pred])
    return d


def _engine(model_dir, name, **sched_kw):
    eng = ServingEngine(model_dir, policy=BucketPolicy(max_batch_size=8),
                        model_name=name)
    sched = eng.scheduler(**sched_kw) if sched_kw else eng.scheduler()
    return eng, sched


# ----------------------------------------------------------------- meta -----


def test_meta_records_generation_state_specs(gen_model_dir):
    """save_inference_model writes the generation sidecar: beam
    geometry + decode-state dtypes/shapes, enough to rebuild slot state
    without re-tracing the model source."""
    with open(gen_model_dir + "/meta.json") as f:
        meta = json.load(f)
    g = meta["generation"]
    assert (g["beam_size"], g["max_len"]) == (K, T)
    assert (g["bos_id"], g["eos_id"]) == (BOS, EOS)
    assert g["state"] == [{"name": "h0", "dtype": "float32",
                           "shape": [H]}]
    assert g["per_example"] == []
    assert set(g["outputs"]) == {"ids", "scores", "lengths"}


def test_feedforward_models_have_no_generation_surface(dense_model_dir):
    with open(dense_model_dir + "/meta.json") as f:
        assert "generation" not in json.load(f)
    eng = ServingEngine(dense_model_dir, model_name="ff")
    assert eng.generation_spec() is None
    with pytest.raises(ValueError, match="not a generation model"):
        eng.scheduler()


# ------------------------------------------------------------- scheduler ----


def test_continuous_bit_identical_to_batch_mode(gen_model_dir):
    """THE acceptance property: per-request beam outputs of the
    continuous scheduler (early-exit compaction, slot pool) are
    bit-identical to the batch-mode beam_search_group decode across
    mixed row counts."""
    eng, sched = _engine(gen_model_dir, "bitident", max_slots=4)
    rng = np.random.RandomState(0)
    try:
        for n in (1, 2, 3, 5):
            feed = {"h0": rng.randn(n, H).astype(np.float32)}
            want_ids, want_sc, want_len = eng.predict(feed)
            got = eng.generate(feed, timeout_ms=60000)
            np.testing.assert_array_equal(got["ids"], want_ids)
            np.testing.assert_array_equal(got["scores"], want_sc)
            np.testing.assert_array_equal(got["lengths"], want_len)
    finally:
        sched.stop()


def test_length_normalized_bit_identical(gen_ln_model_dir):
    """The length_normalize re-sort path of slot finalization matches
    the batch kernel bit-for-bit too."""
    eng, sched = _engine(gen_ln_model_dir, "bitident_ln", max_slots=2)
    rng = np.random.RandomState(1)
    try:
        feed = {"h0": rng.randn(3, H).astype(np.float32)}
        want_ids, want_sc, want_len = eng.predict(feed)
        got = eng.generate(feed, timeout_ms=60000)
        np.testing.assert_array_equal(got["ids"], want_ids)
        np.testing.assert_array_equal(got["scores"], want_sc)
        np.testing.assert_array_equal(got["lengths"], want_len)
    finally:
        sched.stop()


def test_admission_never_exceeds_max_slots(gen_model_dir):
    """Property: with 7 queued single-row requests and max_slots=2, no
    pool step ever runs with more than 2 active slots, every request
    completes, and completions interleave with admissions."""
    eng = ServingEngine(gen_model_dir, model_name="slots")
    sched = ContinuousScheduler(eng, max_slots=2, max_queue=16)
    occupied = []
    orig = sched._step_once

    def spying_step():
        occupied.append(int(sched._active.sum()))
        orig()

    sched._step_once = spying_step
    rng = np.random.RandomState(2)
    feeds = [{"h0": rng.randn(1, H).astype(np.float32)} for _ in range(7)]
    handles = [sched.submit(f, timeout_ms=60000) for f in feeds]
    sched.start()
    try:
        outs = [h.result(timeout=60) for h in handles]
    finally:
        sched.stop()
    assert occupied and max(occupied) <= 2, occupied
    assert sched.admitted_total == sched.retired_total == 7
    for f, o in zip(feeds, outs):
        want = eng.predict(f)
        np.testing.assert_array_equal(o["ids"], want[0])


def test_ragged_finish_order(chain_model_dir):
    """Early-exit compaction: a short request submitted AFTER a long
    one (both resident concurrently) finishes first, and its slot is
    reused — retired_total advances while the long request decodes."""
    eng, sched = _engine(chain_model_dir, "ragged", max_slots=2)
    try:
        done_order = []
        long_h = sched.submit({"thr": _chain_thr(11)}, timeout_ms=60000)
        short_h = sched.submit({"thr": _chain_thr(4)}, timeout_ms=60000)
        ev = threading.Event()

        def wait(tag, h):
            h.result(timeout=60)
            done_order.append(tag)
            ev.set()

        ts = [threading.Thread(target=wait, args=(t, h))
              for t, h in (("long", long_h), ("short", short_h))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert done_order[0] == "short", done_order
        # both results still bit-match batch mode despite the ragged
        # retire order and slot reuse
        for thr, h in ((11, long_h), (4, short_h)):
            want = eng.predict({"thr": _chain_thr(thr)})
            np.testing.assert_array_equal(
                h.result(timeout=1)["ids"], want[0])
        # lengths really were ragged (the short one exited early)
        assert int(eng.predict({"thr": _chain_thr(4)})[2][0, 0]) < \
            int(eng.predict({"thr": _chain_thr(11)})[2][0, 0])
    finally:
        sched.stop()


def test_streaming_token_events(gen_model_dir):
    """submit().events() streams one provisional best-beam token per
    decode step, then the terminal done event with the full outputs."""
    eng, sched = _engine(gen_model_dir, "stream", max_slots=2)
    rng = np.random.RandomState(3)
    try:
        feed = {"h0": rng.randn(1, H).astype(np.float32)}
        events = list(sched.submit(feed, timeout_ms=60000).events(
            timeout=60))
        kinds = [e["event"] for e in events]
        assert kinds[-1] == "done" and set(kinds[:-1]) == {"token"}
        toks = [e for e in events if e["event"] == "token"]
        assert [e["step"] for e in toks] == list(range(len(toks)))
        assert all(e["row"] == 0 for e in toks)
        want = eng.predict(feed)
        np.testing.assert_array_equal(events[-1]["outputs"]["ids"],
                                      want[0])
    finally:
        sched.stop()


# ----------------------------------------------- deadlines, shed, faults ----


def test_queue_full_sheds(gen_model_dir):
    eng = ServingEngine(gen_model_dir, model_name="shed_gen")
    sched = ContinuousScheduler(eng, max_slots=1, max_queue=2)
    # worker NOT started: the queue fills
    f = {"h0": np.zeros((1, H), np.float32)}
    sched.submit(f)
    sched.submit(f)
    with pytest.raises(ShedError, match="queue full"):
        sched.submit(f)
    assert sched.metrics.counter_value("gen_shed_total") >= 1
    sched.stop()


def test_deadline_exceeded_while_queued(gen_model_dir):
    eng = ServingEngine(gen_model_dir, model_name="dl_gen")
    sched = ContinuousScheduler(eng, max_slots=1, max_queue=4)
    h = sched.submit({"h0": np.zeros((1, H), np.float32)}, timeout_ms=10)
    time.sleep(0.05)
    sched.start()
    try:
        with pytest.raises(DeadlineError):
            h.result(timeout=30)
        assert sched.metrics.counter_value(
            "gen_deadline_exceeded_total") >= 1
    finally:
        sched.stop()


def test_deadline_rechecked_after_slot_admission(gen_model_dir):
    """The satellite contract: when admission itself (prefix run — a
    cold compile in real traffic) eats the budget, the request fails
    with DeadlineError BEFORE its first token streams, and the slots
    are recovered."""
    eng = ServingEngine(gen_model_dir, model_name="dl_admit")
    sched = ContinuousScheduler(eng, max_slots=2, max_queue=4)
    orig = sched._run_prefix

    def slow_prefix(req):
        orig(req)
        time.sleep(0.08)  # outlives the deadline after the queue check

    sched._run_prefix = slow_prefix
    h = sched.submit({"h0": np.zeros((1, H), np.float32)}, timeout_ms=60)
    sched.start()
    try:
        with pytest.raises(DeadlineError):
            h.result(timeout=30)
        # no token was ever streamed past the deadline
        ev = next(h.events(timeout=1))
        assert ev["event"] == "error" and ev["kind"] == "DeadlineError"
        deadline = time.monotonic() + 10
        while sched._active.any() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not sched._active.any()  # slots recovered
        # and the pool still serves fresh traffic
        sched._run_prefix = orig
        out = sched.generate({"h0": np.zeros((1, H), np.float32)},
                             timeout_ms=60000)
        assert out["ids"].shape == (1, K, T)
    finally:
        sched.stop()


def test_fault_mid_pool_aborts_inflight_and_recovers(gen_model_dir):
    """Chaos satellite: an injected serving.predict fault during a pool
    step fans GenerationAborted (503, retryable) out to every in-flight
    request, frees the slots, and the next request succeeds."""
    eng, sched = _engine(gen_model_dir, "chaos_gen", max_slots=4)
    rng = np.random.RandomState(4)
    feed = {"h0": rng.randn(2, H).astype(np.float32)}
    try:
        want = eng.predict(feed)  # also warms the engine path
        sched.generate(feed, timeout_ms=60000)  # warm pool, no faults
        faults.reset()
        faults.arm("serving.predict", p=1.0, times=1)
        h1 = sched.submit(feed, timeout_ms=60000)
        h2 = sched.submit(feed, timeout_ms=60000)
        with pytest.raises(GenerationAborted):
            h1.result(timeout=60)
        with pytest.raises(GenerationAborted):
            h2.result(timeout=60)
        assert not sched._active.any()
        # slots recovered: next request decodes bit-identically
        out = sched.generate(feed, timeout_ms=60000)
        np.testing.assert_array_equal(out["ids"], want[0])
    finally:
        faults.reset()
        sched.stop()


def test_generate_trips_shared_breaker(gen_model_dir):
    """/generate and /predict share one per-model CircuitBreaker: pool
    step failures open it, open-circuit submissions fail fast, and a
    half-open probe closes it again."""
    reg = ModelRegistry()
    breaker = CircuitBreaker(failure_threshold=2, reset_timeout_s=0.05)
    eng, _ = reg.add("gen", model_dir=gen_model_dir,
                     policy=BucketPolicy(max_batch_size=8),
                     breaker=breaker, scheduler_kw={"max_slots": 2})
    sched = eng.scheduler()
    feed = {"h0": np.zeros((1, H), np.float32)}
    try:
        sched.generate(feed, timeout_ms=60000)  # warm, breaker closed
        faults.reset()
        faults.arm("serving.predict", p=1.0, times=2)
        for _ in range(2):
            with pytest.raises(GenerationAborted):
                sched.generate(feed, timeout_ms=60000)
        assert breaker.state() == "open"
        assert reg.circuits()["gen"] == "open"
        with pytest.raises(CircuitOpenError):
            sched.submit(feed)
        time.sleep(0.06)  # reset_timeout -> half-open probe admitted
        out = sched.generate(feed, timeout_ms=60000)
        assert out["ids"].shape == (1, K, T)
        assert breaker.state() == "closed"
    finally:
        faults.reset()
        reg.stop()


# ---------------------------------------------------------------- warmup ----


def test_warmup_precompiles_pool_from_meta(gen_model_dir):
    """The meta sidecar lets warmup build the slot pool and compile the
    pool step/admit programs BEFORE any request exists; live traffic
    then compiles nothing."""
    eng = ServingEngine(gen_model_dir,
                        policy=BucketPolicy(max_batch_size=4),
                        model_name="warm_gen")
    eng.warmup()
    sched = eng._scheduler
    assert sched is not None and sched._state is not None
    compiled = sched.compiles
    # pool step + admit + one prefix program per batch bucket
    assert compiled >= 2 + len(eng.policy.batch_buckets)
    out = eng.generate({"h0": np.zeros((2, H), np.float32)},
                       timeout_ms=60000)
    assert out["ids"].shape == (2, K, T)
    assert sched.compiles == compiled  # zero cold compiles under traffic
    assert "generation" in eng.stats()
    sched.stop()


# ----------------------------------------------------------------- http -----


@pytest.fixture()
def http_gen_stack(gen_model_dir, dense_model_dir):
    reg = ModelRegistry()
    eng, _ = reg.add("default", model_dir=gen_model_dir,
                     policy=BucketPolicy(max_batch_size=8),
                     scheduler_kw={"max_slots": 4},
                     timeout_ms=60000.0)
    reg.add("dense", model_dir=dense_model_dir)
    srv = make_server(reg)
    srv.serve_background()
    yield reg, eng, f"http://127.0.0.1:{srv.port}"
    srv.shutdown()
    reg.stop()
    srv.server_close()


def _post(url, payload):
    body = json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=60)


def test_http_generate_e2e(http_gen_stack):
    """Streaming /generate e2e: NDJSON token events then the terminal
    done, bit-identical to both the non-streaming reply and batch-mode
    predict; gen metrics exposed on /metrics and /stats."""
    reg, eng, url = http_gen_stack
    rng = np.random.RandomState(5)
    h0 = rng.randn(2, H).astype(np.float32)
    want = eng.predict({"h0": h0})

    with _post(url + "/generate", {"inputs": {"h0": h0.tolist()},
                                   "timeout_ms": 60000}) as r:
        out = json.load(r)
    np.testing.assert_array_equal(np.asarray(out["outputs"]["ids"]),
                                  want[0])

    with _post(url + "/generate/default",
               {"inputs": {"h0": h0.tolist()}, "stream": True,
                "timeout_ms": 60000}) as r:
        assert r.headers["Content-Type"] == "application/x-ndjson"
        events = [json.loads(line) for line in r]
    kinds = [e["event"] for e in events]
    assert kinds[-1] == "done" and kinds.count("token") >= 2
    np.testing.assert_array_equal(
        np.asarray(events[-1]["outputs"]["ids"]), want[0])
    np.testing.assert_array_equal(
        np.asarray(events[-1]["outputs"]["scores"],
                   np.float32), want[1])

    with urllib.request.urlopen(url + "/stats", timeout=30) as r:
        stats = json.load(r)
    assert stats["default"]["generation"]["retired_total"] >= 4
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        m = r.read().decode()
    for needle in ("gen_slot_occupancy", "gen_first_token_seconds",
                   "gen_token_seconds", "gen_queue_depth",
                   "gen_tokens_total"):
        assert "ptserving_" + needle in m, needle


def test_http_generate_errors(http_gen_stack):
    reg, eng, url = http_gen_stack
    # /generate on a feed-forward model -> 400 with guidance
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(url + "/generate/dense", {"inputs": {"x": [[0, 0, 0, 0]]}})
    assert ei.value.code == 400
    assert "not a generation model" in json.load(ei.value)["error"]
    # unknown model -> 404
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(url + "/generate/nope", {"inputs": {"h0": [[0.0] * H]}})
    assert ei.value.code == 404
    # malformed body -> 400
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(url + "/generate", {"not_inputs": 1})
    assert ei.value.code == 400
