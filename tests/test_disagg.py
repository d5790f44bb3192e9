"""Disaggregated prefill/decode serving (ISSUE 18).

The contract under test: a prefill replica runs ONLY the bucketed
prefix program and emits the request's decode boot state as a
self-describing handoff payload; a decode replica validates and admits
the shipped state through the UNCHANGED `pool_admit` dynamic-update
path — so per-request results are BIT-IDENTICAL to monolithic serving
by construction, at every bucket size. Around that core: the wire
format round-trips (int8 packing bounded by the per-row quant error),
schema-identity mismatches fail at the /admit boundary with a typed
409 naming the rollout fix (never a shape crash in the pool), the
router scores the two replica classes on their own signals, the
dispatcher's failure semantics (same-payload decode failover, ONE
re-prefill on class-wide refusal, then a retryable 503) hold over real
HTTP, one warm pool serves both classes (deficit promotion), the two
phase autoscalers coexist under distinct metric families, the bench
trace mix is digest-stable, and one armed Perfetto capture shows the
prefill → transfer → decode span chain linked by X-PT-Request-Id.
"""

import ast
import json
import struct
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.fleetctl.sim import SimReplica
from paddle_tpu.fleetctl.autoscaler import Autoscaler
from paddle_tpu.fleetctl.traces import (TraceSpec, generate_trace,
                                        trace_digest)
from paddle_tpu.obs import metrics as obs_metrics
from paddle_tpu.obs import promparse
from paddle_tpu.obs import trace as obs_trace
from paddle_tpu.serving import (BucketPolicy, ModelRegistry,
                                ServingEngine, make_server)
from paddle_tpu.serving.disagg import (DisaggDispatcher, DisaggFleet,
                                       HandoffError, HandoffSchemaError,
                                       PhaseFleet, make_phase_autoscalers,
                                       pack_handoff, payload_schema,
                                       unpack_handoff, validate_handoff)
from paddle_tpu.serving.router import (NoReplicaError, Router,
                                       make_router_server)
from paddle_tpu.serving.server import REQUEST_ID_HEADER

V, E, H = 12, 8, 16
BOS, EOS = 0, 1
K, T = 3, 6

# ---------------------------------------------------------------- fixtures --


def _build_gen_model(dirname: str) -> None:
    """Tiny GRU-ish LM decoder (same shape as test_gen_serving.py),
    saved with the generation meta sidecar + schema identity."""
    pt.reset()
    pt.default_startup_program().random_seed = 3
    h0 = pt.layers.data("h0", shape=[-1, H], append_batch_size=False)
    gen = pt.layers.BeamSearchDecoder(beam_size=K, max_len=T,
                                      bos_id=BOS, eos_id=EOS)
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


@pytest.fixture(scope="module")
def gen_model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("disagg_gen"))
    _build_gen_model(d)
    return d


def _engine(model_dir, name, **sched_kw):
    eng = ServingEngine(model_dir, policy=BucketPolicy(max_batch_size=8),
                        model_name=name)
    return eng, eng.scheduler(**sched_kw)


def _schema():
    return {"schema_version": 1, "state_fingerprint": "a" * 16}


def _wait_until(pred, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def _post(url, payload, headers=None, timeout=60):
    body = (payload if isinstance(payload, bytes)
            else json.dumps(payload).encode())
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(url, data=body, headers=hdrs)
    return urllib.request.urlopen(req, timeout=timeout)


# ------------------------------------------------------------ wire format --


def test_pack_unpack_roundtrip_exact():
    rng = np.random.RandomState(0)
    boots = (rng.randn(3, 16).astype(np.float32),
             np.full((3, 1), 7, np.int32))
    pes = (rng.randn(3, 4).astype(np.float32),)
    blob = pack_handoff(boots, pes, _schema(), "default",
                        request_id="r1")
    assert blob.startswith(b"PTHO1")
    header, got_b, got_p = unpack_handoff(blob)
    assert header["model"] == "default"
    assert header["rows"] == 3
    assert header["request_id"] == "r1"
    assert header["quant"] is None
    assert header["state_fingerprint"] == "a" * 16
    for want, got in zip(boots + pes, got_b + got_p):
        assert want.dtype == got.dtype
        np.testing.assert_array_equal(want, got)


def test_int8_packing_cuts_bytes_with_per_row_bounded_error():
    """int8 packing reuses the scheduler's q_rows recipe per ROW:
    absmax/127 scale, so dequant error is bounded by scale/2
    elementwise — and float buffers drop 4x on the wire (int state
    rides raw, byte-exact)."""
    rng = np.random.RandomState(1)
    boots = (rng.randn(4, 64).astype(np.float32) * 3.0,
             np.arange(4, dtype=np.int32).reshape(4, 1))
    raw = pack_handoff(boots, (), _schema(), "m")
    q = pack_handoff(boots, (), _schema(), "m", quant="int8")
    assert len(q) < 0.6 * len(raw)
    header, got_b, _ = unpack_handoff(q)
    assert header["quant"] == "int8"
    deq = got_b[0]
    assert deq.dtype == np.float32
    scale = np.abs(boots[0]).max(axis=1, keepdims=True) / 127.0
    assert np.all(np.abs(deq - boots[0]) <= 0.5 * scale + 1e-6)
    np.testing.assert_array_equal(got_b[1], boots[1])


def test_unpack_rejects_malformed_payloads():
    blob = pack_handoff((np.ones((1, 2), np.float32),), (), _schema(),
                        "m")
    with pytest.raises(HandoffError, match="magic"):
        unpack_handoff(b"nope" + blob)
    with pytest.raises(HandoffError):
        unpack_handoff(blob[:-3])  # truncated buffer
    with pytest.raises(HandoffError, match="trailing"):
        unpack_handoff(blob + b"xx")
    with pytest.raises(HandoffError, match="row"):
        pack_handoff((np.ones((1, 2), np.float32),
                      np.ones((2, 2), np.float32)), (), _schema(), "m")
    with pytest.raises(HandoffError, match="quant"):
        pack_handoff((np.ones((1, 2), np.float32),), (), _schema(),
                     "m", quant="int4")


def test_schema_mismatch_names_the_rollout_command():
    """Satellite 1: a mixed-version fleet fails at admission with a
    TYPED error whose message names the one-command fix."""
    meta = {"schema_version": 1, "state_fingerprint": "a" * 16,
            "state": [], "per_example": []}
    validate_handoff(_schema(), meta)  # matching identity passes
    with pytest.raises(HandoffSchemaError, match="fleetctl rollout"):
        validate_handoff({"schema_version": 1,
                          "state_fingerprint": "b" * 16}, meta)
    with pytest.raises(HandoffSchemaError, match="fleetctl rollout"):
        validate_handoff({"schema_version": 2,
                          "state_fingerprint": "a" * 16}, meta)
    with pytest.raises(HandoffError, match="generation"):
        payload_schema({})


def test_meta_sidecar_carries_schema_identity(gen_model_dir):
    """Satellite 1: save_inference_model stamps the DecodeState schema
    version + state fingerprint into the generation sidecar, and the
    fingerprint is a pure function of the state layout (NOT the
    program fingerprint — a retrained same-geometry artifact must
    hand off mid-rollout)."""
    with open(gen_model_dir + "/meta.json") as f:
        g = json.load(f)["generation"]
    assert g["schema_version"] == pt.io.GENERATION_SCHEMA_VERSION
    assert g["state_fingerprint"] == \
        pt.io.generation_state_fingerprint(g)
    # identity depends only on geometry + state specs, not on the
    # weights: recompute from the layout keys alone
    trimmed = {k: g[k] for k in ("beam_size", "max_len", "bos_id",
                                 "eos_id", "state", "per_example")}
    assert pt.io.generation_state_fingerprint(trimmed) == \
        g["state_fingerprint"]


# ----------------------------------------------- scheduler bit-identity ----


def test_handoff_bit_identical_to_monolithic(gen_model_dir):
    """THE acceptance property: prefill on one engine → serialize →
    unpack → admit on ANOTHER engine is bit-identical to a monolithic
    generate on the admitting engine, across bucket sizes."""
    pf_eng, pf_sched = _engine(gen_model_dir, "pf_bit", max_slots=4)
    de_eng, de_sched = _engine(gen_model_dir, "de_bit", max_slots=4)
    rng = np.random.RandomState(0)
    try:
        for n in (1, 2, 3, 5):
            feed = {"h0": rng.randn(n, H).astype(np.float32)}
            want = de_eng.generate(feed, timeout_ms=60000)
            boots, pes = pf_sched.prefill(feed)
            blob = pack_handoff(
                boots, pes, payload_schema(pf_eng.generation_meta),
                "default")
            header, b2, p2 = unpack_handoff(blob)
            validate_handoff(header, de_eng.generation_meta)
            got = de_sched.submit_handoff(
                b2, p2, timeout_ms=60000).result(timeout=60)
            np.testing.assert_array_equal(got["ids"], want["ids"])
            np.testing.assert_array_equal(got["scores"], want["scores"])
            np.testing.assert_array_equal(got["lengths"],
                                          want["lengths"])
        assert pf_sched.prefills_total == 4
        assert de_sched.handoffs_admitted_total == 4
    finally:
        pf_sched.stop()
        de_sched.stop()


def test_handoff_int8_end_to_end_bounded(gen_model_dir):
    """int8-packed handoffs admit fine; the shipped boot state is
    within the per-row quantization bound of the exact state and the
    decode completes with the right geometry."""
    eng, sched = _engine(gen_model_dir, "int8_ho", max_slots=2)
    try:
        feed = {"h0": np.random.RandomState(2)
                .randn(2, H).astype(np.float32)}
        want = eng.generate(feed, timeout_ms=60000)
        boots, pes = sched.prefill(feed)
        schema = payload_schema(eng.generation_meta)
        blob_q = pack_handoff(boots, pes, schema, "default",
                              quant="int8")
        blob_raw = pack_handoff(boots, pes, schema, "default")
        assert len(blob_q) < len(blob_raw)
        header, b2, p2 = unpack_handoff(blob_q)
        for orig, deq in zip(boots + pes, b2 + p2):
            if np.dtype(orig.dtype).kind == "f":
                n = orig.shape[0]
                sc = (np.abs(np.asarray(orig, np.float32)
                             .reshape(n, -1)).max(axis=1) / 127.0
                      ).reshape((n,) + (1,) * (orig.ndim - 1))
                assert np.all(
                    np.abs(np.asarray(deq, np.float32)
                           - np.asarray(orig, np.float32))
                    <= 0.5 * sc + 1e-6)
            else:
                np.testing.assert_array_equal(orig, deq)
        got = sched.submit_handoff(
            b2, p2, timeout_ms=60000).result(timeout=60)
        assert got["ids"].shape == want["ids"].shape
        assert np.all(got["lengths"] >= 1)
    finally:
        sched.stop()


# --------------------------------------------------------- http replica ----


@pytest.fixture()
def disagg_http_stack(gen_model_dir):
    """Two single-model serving stacks of the SAME artifact: one plays
    the prefill replica, one the decode replica."""
    stacks = []
    for _ in range(2):
        reg = ModelRegistry()
        reg.add("default", model_dir=gen_model_dir,
                policy=BucketPolicy(max_batch_size=8),
                scheduler_kw={"max_slots": 4}, timeout_ms=60000.0)
        srv = make_server(reg)
        srv.serve_background()
        stacks.append((reg, srv, f"http://127.0.0.1:{srv.port}"))
    yield stacks
    for reg, srv, _ in stacks:
        srv.shutdown()
        reg.stop()
        srv.server_close()


def test_http_prefill_admit_bit_identical_and_streams(disagg_http_stack):
    """/prefill returns an opaque octet-stream payload; /admit on a
    sibling replica returns the monolithic /generate result bit-exact,
    buffered AND as the NDJSON stream; healthz exposes the per-phase
    counters (satellite 3)."""
    (_, _, pf_url), (_, _, de_url) = disagg_http_stack
    h0 = np.random.RandomState(7).randn(3, H).astype(np.float32)
    with _post(de_url + "/generate",
               {"inputs": {"h0": h0.tolist()},
                "timeout_ms": 60000}) as r:
        want = json.load(r)["outputs"]
    with _post(pf_url + "/prefill/default",
               {"inputs": {"h0": h0.tolist()}}) as r:
        assert r.headers["Content-Type"] == "application/octet-stream"
        assert r.headers[REQUEST_ID_HEADER]
        payload = r.read()
    octet = {"Content-Type": "application/octet-stream"}
    with _post(de_url + "/admit/default", payload, headers=octet) as r:
        got = json.load(r)["outputs"]
    np.testing.assert_array_equal(np.asarray(got["ids"]),
                                  np.asarray(want["ids"]))
    np.testing.assert_array_equal(
        np.asarray(got["scores"], np.float32),
        np.asarray(want["scores"], np.float32))
    # streamed admission: same payload, token events then the terminal
    # done with the same bit-exact outputs
    with _post(de_url + "/admit/default?stream=1&timeout_ms=60000",
               payload, headers=octet) as r:
        assert "ndjson" in r.headers["Content-Type"]
        events = [json.loads(line) for line in r if line.strip()]
    kinds = [e["event"] for e in events]
    assert kinds[-1] == "done" and kinds.count("token") >= 2
    np.testing.assert_array_equal(
        np.asarray(events[-1]["outputs"]["ids"]),
        np.asarray(want["ids"]))
    with urllib.request.urlopen(pf_url + "/healthz", timeout=30) as r:
        load = json.load(r)["load"]
    assert load["prefills_total"] == 1
    assert load["handoffs_admitted_total"] == 0
    with urllib.request.urlopen(de_url + "/healthz", timeout=30) as r:
        load = json.load(r)["load"]
    assert load["handoffs_admitted_total"] == 2
    assert load["free_slots"] == load["max_slots"] \
        - load["active_slots"]


def test_http_admit_schema_mismatch_is_409(disagg_http_stack):
    """A payload whose schema identity disagrees with the admitting
    artifact → 409 with kind=HandoffSchemaError and the rollout fix in
    the message (NOT a retryable 503: a same-version sibling would
    reject it identically). Garbage bytes → 400."""
    (_, _, pf_url), (_, _, de_url) = disagg_http_stack
    h0 = np.zeros((1, H), np.float32)
    with _post(pf_url + "/prefill", {"inputs": {"h0": h0.tolist()}}) \
            as r:
        payload = r.read()
    # tamper the header's state fingerprint, keeping the layout valid
    (hlen,) = struct.unpack_from(">I", payload, 5)
    hdr = json.loads(payload[9:9 + hlen].decode())
    hdr["state_fingerprint"] = "deadbeef00000000"
    new_hdr = json.dumps(hdr, sort_keys=True,
                         separators=(",", ":")).encode()
    bad = (payload[:5] + struct.pack(">I", len(new_hdr)) + new_hdr
           + payload[9 + hlen:])
    octet = {"Content-Type": "application/octet-stream"}
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(de_url + "/admit", bad, headers=octet)
    assert ei.value.code == 409
    err = json.load(ei.value)
    assert err["kind"] == "HandoffSchemaError"
    assert "fleetctl rollout" in err["error"]
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(de_url + "/admit", b"garbage bytes", headers=octet)
    assert ei.value.code == 400


# ------------------------------------------------------ router: phases -----


def test_replica_phase_validation_scoring_and_pick():
    """Per-class JSQ: a prefill replica scores on queue depth +
    compute backlog (queue age; its decode pool never fills), a decode
    replica on how few FREE slots remain; pick(phase=...) only
    considers that class and monolithic (phase=None) replicas keep the
    original formula."""
    r = Router()
    with pytest.raises(ValueError, match="phase"):
        r.add_replica("http://127.0.0.1:9001", phase="encode")
    pf = r.add_replica("http://127.0.0.1:9001", name="pf",
                       phase="prefill")
    de = r.add_replica("http://127.0.0.1:9002", name="de",
                       phase="decode")
    mono = r.add_replica("http://127.0.0.1:9003", name="mono")
    for x in (pf, de, mono):
        x.up = True
    pf.snapshot = {"queue_depth": 2, "queue_age_ms": 1000.0,
                   "active_slots": 3, "max_slots": 4}
    assert pf.score() == pytest.approx(2 + 1.0)  # slots ignored
    de.snapshot = {"queue_depth": 0, "active_slots": 1, "max_slots": 4}
    assert de.score() == pytest.approx(-3.0)  # minus free slots
    mono.snapshot = {"queue_depth": 1, "active_slots": 2}
    assert mono.score() == pytest.approx(3.0)
    assert r.pick(phase="prefill").name == "pf"
    assert r.pick(phase="decode").name == "de"
    assert r.pick().name == "de"  # monolithic pick sees every replica
    # a decode replica with MORE free slots wins the decode pick
    de2 = r.add_replica("http://127.0.0.1:9004", name="de2",
                        phase="decode")
    de2.up = True
    de2.snapshot = {"queue_depth": 0, "active_slots": 0,
                    "max_slots": 4}
    assert r.pick(phase="decode").name == "de2"
    # an exhausted class picks NOTHING — it never spills into the
    # other class or the monolithic pool (dispatch turns this into
    # the retryable NoReplicaError)
    assert r.pick(exclude=("pf",), phase="prefill") is None
    r.close()


def test_router_phase_metric_families():
    """Satellite 3: the unified /metrics surface grows per-PHASE
    aggregate gauges (new pt_phase_* families — the per-replica series
    keep their labels)."""
    reg = obs_metrics.MetricsRegistry()
    router = Router(registry=reg)
    pf_sim, de_sim = SimReplica(slots=4), SimReplica(slots=4)
    try:
        pf = router.add_replica(pf_sim.url, name="pf", phase="prefill")
        de = router.add_replica(de_sim.url, name="de", phase="decode")
        assert router.probe_one(pf) and router.probe_one(de)
        fams = promparse.parse_text(reg.render())
        for fam in ("pt_phase_replicas", "pt_phase_queue_depth",
                    "pt_phase_inflight", "pt_phase_free_slots"):
            phases = {s[1]["phase"] for s in fams[fam].samples}
            assert phases == {"prefill", "decode"}, fam
        reps = {s[1]["phase"]: s[2]
                for s in fams["pt_phase_replicas"].samples}
        assert reps == {"prefill": 1.0, "decode": 1.0}
        free = {s[1]["phase"]: s[2]
                for s in fams["pt_phase_free_slots"].samples}
        assert free["decode"] == 4.0
    finally:
        router.close()
        pf_sim.kill()
        de_sim.kill()


# ------------------------------------------- dispatcher over sim fleets ----


def _phased_sims(n_prefill=1, n_decode=1, fingerprint="fp-v1",
                 registry=None, **sim_kw):
    reg = registry or obs_metrics.MetricsRegistry()
    router = Router(probe_interval_s=0.05, registry=reg).start()
    pf_sims = [SimReplica(fingerprint=fingerprint, **sim_kw)
               for _ in range(n_prefill)]
    de_sims = [SimReplica(fingerprint=fingerprint, **sim_kw)
               for _ in range(n_decode)]
    for i, s in enumerate(pf_sims):
        router.add_replica(s.url, name=f"pf{i}", phase="prefill")
    for i, s in enumerate(de_sims):
        router.add_replica(s.url, name=f"de{i}", phase="decode")
    _wait_until(lambda: all(r.up for r in router.replicas()),
                msg="sim replicas up")
    return reg, router, pf_sims, de_sims


def test_dispatcher_splits_generate_across_phases():
    """/generate through a disagg RouterServer: prefill runs on the
    prefill sim, the payload ships, decode admits — buffered and
    streamed — and the transfer metrics land on the router registry."""
    reg, router, (pf_sim,), (de_sim,) = _phased_sims()
    server = make_router_server(router,
                                disagg=DisaggDispatcher(router))
    server.serve_background()
    url = f"http://127.0.0.1:{server.port}"
    try:
        with _post(url + "/generate",
                   {"sim_prefill_ms": 5, "sim_decode_ms": 5,
                    "tokens": 3}) as r:
            assert r.status == 200
            out = json.load(r)
        assert out["outputs"]["ids"] == [[3]]
        assert pf_sim.prefills_total == 1
        assert de_sim.handoffs_admitted_total == 1
        with _post(url + "/generate",
                   {"stream": True, "tokens": 4, "sim_decode_ms": 20,
                    "timeout_ms": 30000}) as r:
            assert "ndjson" in r.headers["Content-Type"]
            events = [json.loads(line) for line in r if line.strip()]
        kinds = [e["event"] for e in events]
        assert kinds.count("token") == 4 and kinds[-1] == "done"
        assert de_sim.handoffs_admitted_total == 2
        render = reg.render()
        fams = promparse.parse_text(render)
        assert fams["pt_handoff_total"].samples[0][2] == 2.0
        assert fams["pt_handoff_bytes_total"].samples[0][2] > 0
        assert "pt_handoff_seconds_bucket" in render
        assert fams["pt_disagg_reprefills_total"].samples[0][2] == 0.0
    finally:
        server.shutdown()
        server.server_close()
        router.close()
        pf_sim.kill()
        de_sim.kill()


def test_decode_failover_reships_same_payload():
    """Single-replica decode death is absorbed by the router's normal
    dispatch failover: the SAME payload lands on the next-best decode
    replica, no re-prefill spent."""
    reg, router, (pf_sim,), (de0, de1) = _phased_sims(n_decode=2)
    server = make_router_server(router,
                                disagg=DisaggDispatcher(router))
    server.serve_background()
    url = f"http://127.0.0.1:{server.port}"
    try:
        de0.kill()  # connection refused → failover inside dispatch
        with _post(url + "/generate", {"tokens": 2}) as r:
            assert r.status == 200
        assert pf_sim.prefills_total == 1  # prefill ran ONCE
        assert de1.handoffs_admitted_total == 1
        fams = promparse.parse_text(reg.render())
        assert fams["pt_disagg_reprefills_total"].samples[0][2] == 0.0
    finally:
        server.shutdown()
        server.server_close()
        router.close()
        pf_sim.kill()
        de1.kill()


def test_decode_class_death_reprefills_then_retryable_503():
    """Class-wide decode refusal: ONE re-prefill on a DIFFERENT
    prefill replica, then a retryable 503 (Retry-After). Registering a
    fresh decode replica afterwards recovers without operator help."""
    reg, router, (pf0, pf1), (de0,) = _phased_sims(n_prefill=2)
    server = make_router_server(router,
                                disagg=DisaggDispatcher(router))
    server.serve_background()
    url = f"http://127.0.0.1:{server.port}"
    try:
        de0.kill()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(url + "/generate", {"tokens": 2}, timeout=30)
        assert ei.value.code == 503
        assert ei.value.headers["Retry-After"]
        fams = promparse.parse_text(reg.render())
        assert fams["pt_disagg_reprefills_total"].samples[0][2] == 1.0
        # the re-prefill went to the OTHER prefill replica
        assert pf0.prefills_total + pf1.prefills_total == 2
        assert {pf0.prefills_total, pf1.prefills_total} == {1}
        # recovery: a fresh decode replica joins, traffic flows again
        de1 = SimReplica(fingerprint="fp-v1")
        r_new = router.add_replica(de1.url, name="de1", phase="decode")
        _wait_until(lambda: r_new.up, msg="replacement decode up")
        try:
            with _post(url + "/generate", {"tokens": 2}) as r:
                assert r.status == 200
            assert de1.handoffs_admitted_total == 1
        finally:
            de1.kill()
    finally:
        server.shutdown()
        server.server_close()
        router.close()
        pf0.kill()
        pf1.kill()
        de0.kill()


def test_schema_mismatch_is_not_retried_across_siblings():
    """A 409 from /admit is relayed to the client verbatim — the
    dispatcher must NOT burn a re-prefill or try a same-version
    sibling (it would reject identically; the fix is a rollout)."""
    reg = obs_metrics.MetricsRegistry()
    router = Router(probe_interval_s=0.05, registry=reg).start()
    pf_sim = SimReplica(fingerprint="fp-A")
    de_sims = [SimReplica(fingerprint="fp-B") for _ in range(2)]
    router.add_replica(pf_sim.url, name="pf0", phase="prefill")
    for i, s in enumerate(de_sims):
        router.add_replica(s.url, name=f"de{i}", phase="decode")
    _wait_until(lambda: all(r.up for r in router.replicas()),
                msg="sims up")
    server = make_router_server(router,
                                disagg=DisaggDispatcher(router))
    server.serve_background()
    url = f"http://127.0.0.1:{server.port}"
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(url + "/generate", {"tokens": 1}, timeout=30)
        assert ei.value.code == 409
        assert json.load(ei.value)["kind"] == "HandoffSchemaError"
        assert sum(s.handoffs_admitted_total for s in de_sims) == 0
        fams = promparse.parse_text(reg.render())
        assert fams["pt_disagg_reprefills_total"].samples[0][2] == 0.0
    finally:
        server.shutdown()
        server.server_close()
        router.close()
        pf_sim.kill()
        for s in de_sims:
            s.kill()


def test_dispatcher_rejects_unknown_quant():
    with pytest.raises(ValueError, match="quant"):
        DisaggDispatcher(Router(), quant="fp4")


# -------------------------------------------------- fleet: two classes -----


def _sim_spawner(**kw):
    def spawn():
        return SimReplica(**kw)
    return spawn


def test_disagg_fleet_deficit_promotion_replaces_dead_prefill():
    """One warm pool, two classes: when the prefill member dies, the
    supervisor's phase-agnostic replacement lands in the PREFILL class
    because that's the class below target (deficit assignment)."""
    reg = obs_metrics.MetricsRegistry()
    router = Router(probe_interval_s=0.05, registry=reg)
    fleet = DisaggFleet(_sim_spawner(), prefill_replicas=1,
                        decode_replicas=1, standby=1, router=router,
                        supervise_interval_s=0.1, ready_timeout_s=10.0)
    fleet.start()
    try:
        _wait_until(lambda: fleet.phase_counts()
                    == {"prefill": 1, "decode": 1},
                    msg="both classes populated")
        d = fleet.describe()
        assert d["phases"]["prefill"]["target"] == 1
        assert d["phases"]["decode"]["target"] == 1
        pf_name = next(r.name for r in router.replicas()
                       if r.phase == "prefill")
        fleet._procs[pf_name].kill()
        _wait_until(lambda: pf_name not in fleet._procs
                    and fleet.phase_counts()
                    == {"prefill": 1, "decode": 1},
                    timeout=15, msg="prefill replacement")
        new_pf = [r for r in router.replicas()
                  if r.phase == "prefill" and not r.draining]
        assert len(new_pf) == 1 and new_pf[0].name != pf_name
    finally:
        fleet.stop()


def test_disagg_fleet_targeted_scale_and_per_class_floor():
    reg = obs_metrics.MetricsRegistry()
    router = Router(probe_interval_s=0.05, registry=reg)
    fleet = DisaggFleet(_sim_spawner(), prefill_replicas=1,
                        decode_replicas=1, standby=1, router=router,
                        supervise_interval_s=0.2, ready_timeout_s=10.0)
    fleet.start()
    try:
        _wait_until(lambda: fleet.phase_counts()
                    == {"prefill": 1, "decode": 1}, msg="fleet up")
        with pytest.raises(ValueError, match="phase"):
            PhaseFleet(fleet, "encode")
        pf_view = PhaseFleet(fleet, "prefill")
        assert pf_view.size() == 1
        # targeted scale-up promotes a standby INTO the class and
        # bumps its target
        names = []
        _wait_until(lambda: bool(
            names.extend(fleet.scale_up(1, phase="prefill")) or names),
            msg="standby promoted")
        assert fleet.targets["prefill"] == 2
        assert pf_view.size() == 2
        promoted = [r for r in router.replicas() if r.name in names]
        assert promoted and promoted[0].phase == "prefill"
        # the phase view only sees its class
        assert {r.phase for r in pf_view.router.replicas()} \
            == {"prefill"}
        # scale-down retires back to one; the last member of a class
        # is never retired
        victims = fleet.scale_down(1, drain_timeout_s=5.0,
                                   phase="prefill")
        assert len(victims) == 1
        _wait_until(lambda: pf_view.size() == 1, msg="retired")
        assert fleet.scale_down(1, phase="prefill") == []
        assert fleet.scale_down(1, phase="decode") == []
        assert fleet.targets["prefill"] == 1
    finally:
        fleet.stop()


def test_phase_autoscalers_distinct_metric_families():
    """Satellite: the two per-class control loops are stock
    Autoscalers under distinct metric families — both render on ONE
    registry without colliding, and the default family is unchanged
    for monolithic fleets."""
    import inspect

    assert inspect.signature(Autoscaler.__init__) \
        .parameters["family"].default == "pt_autoscale"
    reg = obs_metrics.MetricsRegistry()
    router = Router(probe_interval_s=0.05, registry=reg)
    fleet = DisaggFleet(_sim_spawner(), prefill_replicas=1,
                        decode_replicas=1, router=router,
                        supervise_interval_s=0.2, ready_timeout_s=10.0)
    fleet.start()
    try:
        pair = make_phase_autoscalers(fleet)
        res = pair.tick()
        assert set(res) == {"prefill", "decode"}
        st = pair.stats()
        assert st["prefill"] != st["decode"]
        render = reg.render()
        assert "pt_autoscale_prefill_replicas" in render
        assert "pt_autoscale_decode_replicas" in render
        # the prefill loop's occupancy signal is disabled (a prefill
        # replica's decode pool is always empty)
        assert pair.prefill.cfg.up_occupancy > 1.0
        assert pair.decode.cfg.up_occupancy <= 1.0
    finally:
        fleet.stop()


# ------------------------------------------------------- trace mix ---------


def test_trace_disagg_mix_is_digest_stable():
    """Satellite 2: the disagg fields follow the guarded-draw
    contract — fraction=0 specs consume NO randomness (pre-disagg
    traces replay byte-identically), fraction>0 marks events with a
    bounded lognormal prefill cost + short decode budget,
    deterministically."""
    base = TraceSpec(duration_s=10.0, seed=7)
    explicit = TraceSpec(duration_s=10.0, seed=7, disagg_fraction=0.0)
    assert trace_digest(generate_trace(base)) \
        == trace_digest(generate_trace(explicit))
    spec = TraceSpec(duration_s=10.0, seed=7, disagg_fraction=0.6)
    t1, t2 = generate_trace(spec), generate_trace(spec)
    assert trace_digest(t1) == trace_digest(t2)
    assert trace_digest(t1) != trace_digest(generate_trace(base))
    marked = [e for e in t1 if "prefill_ms" in e]
    assert marked
    for e in marked:
        assert 0.0 < e["prefill_ms"] <= spec.max_prefill_ms
        assert (spec.decode_tokens_min <= e["decode_tokens"]
                <= spec.decode_tokens_max)
    frac = len(marked) / len(t1)
    assert 0.4 < frac < 0.8
    d = spec.describe()
    assert d["disagg_fraction"] == 0.6
    assert json.loads(json.dumps(d)) == d
    with pytest.raises(ValueError):
        TraceSpec(disagg_fraction=1.5)
    with pytest.raises(ValueError):
        TraceSpec(decode_tokens_min=0)
    with pytest.raises(ValueError):
        TraceSpec(decode_tokens_min=9, decode_tokens_max=3)


# ------------------------------------------------------------ lint ---------

# blocking network/clock calls banned from the phase-pick path — the
# same contract (and call list) as test_router's Router.pick lint,
# minus Router.dispatch itself, which OWNS every round-trip, and
# minus "join" (the query string is str.join-ed; thread joins are
# caught by "wait")
_BLOCKING_CALLS = {
    "urlopen", "request", "getresponse", "read", "readline", "recv",
    "send", "sendall", "connect", "sleep", "wait", "select",
    "accept", "probe_one", "_attempt",
}
_BLOCKING_NAMES = {"HTTPConnection", "urlopen", "socket",
                   "create_connection"}

# host-sync calls banned from the admission hot path: the ONE d2h
# fence lives in prefill's gather_handoff_rows; admission is
# device_put + the jitted pool_admit, never a host round-trip
_HOST_SYNC_CALLS = {"device_get", "block_until_ready", "tolist",
                    "item", "copy_to_host_async"}


def _find_method(tree, cls, name):
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == cls:
            for item in node.body:
                if isinstance(item, ast.FunctionDef) and \
                        item.name == name:
                    return item
    return None


def _find_function(tree, name):
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == name:
            return node
    return None


def _called_names(fn):
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            f = node.func
            yield (f.attr if isinstance(f, ast.Attribute)
                   else f.id if isinstance(f, ast.Name) else None)


def test_dispatcher_generate_has_no_direct_io():
    """AST lint (satellite 5): DisaggDispatcher.generate performs NO
    blocking I/O itself — every network round-trip goes through
    Router.dispatch, so phase-picking inherits the pick path's
    latency guarantees."""
    import paddle_tpu.serving.disagg.dispatch as mod

    with open(mod.__file__) as f:
        tree = ast.parse(f.read())
    fn = _find_method(tree, "DisaggDispatcher", "generate")
    assert fn is not None, "DisaggDispatcher.generate not found"
    for called in _called_names(fn):
        assert called not in _BLOCKING_CALLS, (
            f"DisaggDispatcher.generate calls blocking {called!r} "
            "outside Router.dispatch")
        assert called not in _BLOCKING_NAMES, (
            f"DisaggDispatcher.generate constructs {called!r}")


def test_handoff_admit_hot_path_has_no_host_sync():
    """AST lint (satellite 5): submit_handoff and the restore helper
    never host-sync — shipped state is device_put straight into the
    pool-admit path; the only d2h fence of the whole handoff is
    prefill's gather_handoff_rows."""
    import paddle_tpu.pipeline.elastic as elastic_mod
    import paddle_tpu.serving.scheduler as sched_mod

    with open(sched_mod.__file__) as f:
        sched_tree = ast.parse(f.read())
    with open(elastic_mod.__file__) as f:
        elastic_tree = ast.parse(f.read())
    targets = [
        ("ContinuousScheduler.submit_handoff",
         _find_method(sched_tree, "ContinuousScheduler",
                      "submit_handoff")),
        ("elastic.restore_handoff_rows",
         _find_function(elastic_tree, "restore_handoff_rows")),
    ]
    for label, fn in targets:
        assert fn is not None, f"{label} not found (lint is stale)"
        for called in _called_names(fn):
            assert called not in _HOST_SYNC_CALLS, (
                f"{label} calls host-syncing {called!r} in the "
                "handoff admission hot path")
            assert called not in _BLOCKING_CALLS or called == "read", (
                f"{label} calls blocking {called!r}")


def test_handoff_wire_module_imports_no_jax():
    """The serialize side of the hot path is pure host numpy: the wire
    module never imports jax at the top level (pack/unpack must not
    drag device state or tracing into byte shuffling)."""
    import paddle_tpu.serving.disagg.handoff as mod

    with open(mod.__file__) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                assert not alias.name.split(".")[0] == "jax"
        elif isinstance(node, ast.ImportFrom):
            mod_name = (node.module or "").split(".")[0]
            assert mod_name != "jax"


# ----------------------------------------------------------- perfetto ------


def test_perfetto_capture_links_phases_by_request_id(gen_model_dir):
    """Satellite 3: ONE armed capture over the full in-process
    topology (prefill replica, router+dispatcher, decode replica)
    shows the prefill → transfer → decode span chain, every span
    carrying the same X-PT-Request-Id."""
    stacks = []
    for _ in range(2):
        reg = ModelRegistry()
        reg.add("default", model_dir=gen_model_dir,
                policy=BucketPolicy(max_batch_size=8),
                scheduler_kw={"max_slots": 2}, timeout_ms=60000.0)
        srv = make_server(reg)
        srv.serve_background()
        stacks.append((reg, srv))
    router = Router(probe_interval_s=0.05).start()
    router.add_replica(f"http://127.0.0.1:{stacks[0][1].port}",
                       name="pf0", phase="prefill")
    router.add_replica(f"http://127.0.0.1:{stacks[1][1].port}",
                       name="de0", phase="decode")
    _wait_until(lambda: all(r.up for r in router.replicas()),
                msg="replicas up")
    server = make_router_server(router,
                                disagg=DisaggDispatcher(router))
    server.serve_background()
    url = f"http://127.0.0.1:{server.port}"
    rid = "disagg-e2e-1"
    try:
        h0 = np.random.RandomState(3).randn(2, H).astype(np.float32)
        with obs_trace.tracing() as tr:
            with _post(url + "/generate",
                       {"inputs": {"h0": h0.tolist()},
                        "timeout_ms": 60000},
                       headers={REQUEST_ID_HEADER: rid}) as r:
                assert r.status == 200
                assert r.headers[REQUEST_ID_HEADER] == rid
                json.load(r)
        doc = tr.to_chrome()
        assert obs_trace.validate_chrome_trace(doc) == []
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]

        def linked(name):
            return [e for e in spans if e["name"] == name
                    and e.get("args", {}).get("request_id") == rid]

        chain = ["http.prefill", "gen.prefill", "disagg.handoff",
                 "http.admit", "gen.admit"]
        got = {name: linked(name) for name in chain}
        for name, evs in got.items():
            assert evs, f"no {name} span linked to {rid}"
        # the phases happen in order: prefill completes before the
        # transfer starts, the transfer starts before decode admission
        pf_end = max(e["ts"] + e["dur"] for e in got["gen.prefill"])
        ho_start = min(e["ts"] for e in got["disagg.handoff"])
        adm_start = min(e["ts"] for e in got["gen.admit"])
        assert pf_end <= ho_start + 1e-3
        assert ho_start <= adm_start + 1e-3
    finally:
        server.shutdown()
        server.server_close()
        router.close()
        for reg, srv in stacks:
            srv.shutdown()
            reg.stop()
            srv.server_close()


# ------------------------------------------------------- fleet e2e ---------


@pytest.mark.fleet
def test_disagg_sim_fleet_e2e_survives_decode_churn():
    """Fleet e2e under the fleet budget: a DisaggFleet of sims behind
    the disagg RouterServer serves a request mix while a decode
    replica dies mid-run — clients only ever see successes or
    retryable 503s, the supervisor restores the class, and the
    phase counters reconcile."""
    reg = obs_metrics.MetricsRegistry()
    router = Router(probe_interval_s=0.05, registry=reg)
    fleet = DisaggFleet(_sim_spawner(slots=4), prefill_replicas=1,
                        decode_replicas=2, standby=1, router=router,
                        supervise_interval_s=0.1, ready_timeout_s=10.0)
    fleet.start()
    server = make_router_server(
        router, fleet=fleet, disagg=DisaggDispatcher(router))
    server.serve_background()
    url = f"http://127.0.0.1:{server.port}"
    ok, retryable = 0, 0
    try:
        _wait_until(lambda: fleet.phase_counts()
                    == {"prefill": 1, "decode": 2}, msg="fleet up")
        for i in range(12):
            if i == 5:  # kill one decode replica mid-run
                de_name = next(r.name for r in router.replicas()
                               if r.phase == "decode"
                               and r.name in fleet._procs)
                fleet._procs[de_name].kill()
            try:
                with _post(url + "/generate",
                           {"tokens": 2, "sim_prefill_ms": 2,
                            "sim_decode_ms": 2}, timeout=30) as r:
                    assert r.status == 200
                    ok += 1
            except urllib.error.HTTPError as e:
                assert e.code == 503, "only retryable errors allowed"
                retryable += 1
        assert ok >= 8
        _wait_until(lambda: fleet.phase_counts()
                    == {"prefill": 1, "decode": 2}, timeout=15,
                    msg="decode class restored")
        # the router counted exactly one admitted handoff per client
        # success (the dead sim took its tally with it, so count at
        # the dispatcher)
        fams = promparse.parse_text(reg.render())
        assert fams["pt_handoff_total"].samples[0][2] == float(ok)
        # /admin/fleet surfaces the per-phase topology
        with urllib.request.urlopen(url + "/admin/fleet",
                                    timeout=10) as r:
            admin = json.load(r)
        assert set(admin["fleet"]["phases"]) == {"prefill", "decode"}
    finally:
        server.shutdown()
        server.server_close()
        fleet.stop()
