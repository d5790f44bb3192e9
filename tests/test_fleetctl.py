"""Fleet control plane tests (ISSUE 16): multi-tenant SLO admission,
autoscaler hysteresis/cooldown/reaction, elastic scale-up/down with
metric-series retirement, and zero-downtime rollout under load.

The process-shaped pieces run over `fleetctl.sim.SimReplica` —
in-process HTTP servers speaking the replica wire protocol around the
REAL AdmissionQueue — so Fleet/Router/Autoscaler/RolloutManager are
exercised end to end without jax subprocess spawns (the spawned-`cli
serve` e2e lives in test_fleet.py)."""

import ast
import json
import random
import threading
import time
import urllib.error
import urllib.request

import pytest

from paddle_tpu.fleetctl import (Autoscaler, AutoscalerConfig,
                                 RolloutError, RolloutManager)
from paddle_tpu.fleetctl.sim import SimReplica
from paddle_tpu.fleetctl.tenancy import (BATCH, INTERACTIVE, SLO_HEADER,
                                         SLOPolicy, resolve_class)
from paddle_tpu.fleetctl.traces import (TraceSpec, generate_trace,
                                        trace_digest)
from paddle_tpu.obs import metrics as obs_metrics
from paddle_tpu.obs import promparse
from paddle_tpu.serving.batcher import AdmissionQueue, ShedError
from paddle_tpu.serving.metrics import MetricSet
from paddle_tpu.serving.router import Fleet, Router, make_router_server

# ------------------------------------------------------------- tenancy -----


def test_resolve_class_is_demotion_only():
    assert resolve_class(INTERACTIVE, None) == INTERACTIVE
    assert resolve_class(INTERACTIVE, BATCH) == BATCH  # self-demote ok
    assert resolve_class(BATCH, INTERACTIVE) == BATCH  # no self-PROMOTE
    assert resolve_class(BATCH, BATCH) == BATCH
    with pytest.raises(ValueError):
        resolve_class(INTERACTIVE, "platinum")


def test_slo_policy_from_specs():
    pol = SLOPolicy.from_specs(["bulk=batch", "chat=interactive"])
    assert pol.class_of("bulk") == BATCH
    assert pol.class_of("chat") == INTERACTIVE
    assert pol.class_of("unlisted") == INTERACTIVE  # safe default
    with pytest.raises(ValueError):
        SLOPolicy.from_specs(["bulk"])
    with pytest.raises(ValueError):
        SLOPolicy.from_specs(["bulk=gold"])


# -------------------------------------------- two-tier admission queue -----


class _Req:
    def __init__(self, slo, deadline=None):
        self.slo_class = slo
        self.deadline = deadline or (time.monotonic() + 60.0)
        self.enqueued_at = 0.0
        self.error = None

    def fail(self, exc):
        self.error = exc


def _make_aq(max_queue):
    cond = threading.Condition()
    metrics = MetricSet("ptserving", registry=obs_metrics.MetricsRegistry())
    return AdmissionQueue(max_queue, cond, metrics, prefix="t_"), cond


def test_admission_queue_serves_interactive_tier_first():
    aq, cond = _make_aq(8)
    b1, i1, b2, i2 = (_Req(BATCH), _Req(INTERACTIVE), _Req(BATCH),
                      _Req(INTERACTIVE))
    for r in (b1, i1, b2, i2):
        aq.put(r)
    with cond:
        order = [aq.pop() for _ in range(4)]
    # interactive tier to exhaustion (FIFO within it), then batch FIFO
    assert order == [i1, i2, b1, b2]


def test_admission_queue_interactive_displaces_newest_batch():
    aq, cond = _make_aq(2)
    b1, b2 = _Req(BATCH), _Req(BATCH)
    aq.put(b1)
    aq.put(b2)
    late = _Req(INTERACTIVE)
    aq.put(late)  # at capacity: displaces b2, does NOT raise
    assert isinstance(b2.error, ShedError) and b1.error is None
    with cond:
        assert aq.pop() is late


def test_admission_queue_property_batch_sheds_strictly_first():
    """Seeded random workload property: NO interactive request is ever
    shed while any batch request occupies the queue — the admission
    invariant the SLO-class design promises (shed order is strictly
    batch-first)."""
    rng = random.Random(1234)
    aq, cond = _make_aq(6)
    queued = []  # our model of what's inside (for cross-checking)
    interactive_sheds = 0
    batch_sheds = 0
    for _ in range(2000):
        op = rng.random()
        if op < 0.6:  # arrival, biased to keep the queue full
            cls = BATCH if rng.random() < 0.5 else INTERACTIVE
            r = _Req(cls)
            batch_waiting = aq.depth_by_class()[BATCH]
            try:
                aq.put(r)
                queued.append(r)
            except ShedError:
                # the ARRIVAL was shed: legal for interactive only
                # when zero batch requests were queued
                if cls == INTERACTIVE:
                    interactive_sheds += 1
                    assert batch_waiting == 0, (
                        "interactive request shed while "
                        f"{batch_waiting} batch requests were queued")
                else:
                    batch_sheds += 1
        else:  # service
            with cond:
                r = aq.pop()
            if r is not None:
                queued.remove(r)
        # displaced victims must ALWAYS be batch
        for r in list(queued):
            if r.error is not None:
                assert r.slo_class == BATCH, (
                    "a queued interactive request was displaced")
                assert isinstance(r.error, ShedError)
                queued.remove(r)
    # the workload must actually have exercised both shed paths
    assert batch_sheds > 0
    assert interactive_sheds > 0  # happens only on all-interactive queues


def test_admission_queue_age_and_class_depths():
    aq, cond = _make_aq(8)
    assert aq.oldest_enqueued() is None
    first = _Req(BATCH)
    aq.put(first)
    time.sleep(0.02)
    aq.put(_Req(INTERACTIVE))
    assert aq.depth_by_class() == {INTERACTIVE: 1, BATCH: 1}
    oldest = aq.oldest_enqueued()
    assert oldest == pytest.approx(first.enqueued_at)
    assert time.monotonic() - oldest >= 0.02


# ------------------------------------------------------ per-class JSQ ------


def test_router_pick_scores_by_class_depth():
    """A replica drowning in batch backlog still looks short to
    interactive traffic; the batch pick goes the other way."""
    router = Router(registry=obs_metrics.MetricsRegistry())
    a = router.add_replica("http://127.0.0.1:1", name="a")
    b = router.add_replica("http://127.0.0.1:2", name="b")
    a.snapshot = {"queue_depth": 10, "active_slots": 0,
                  "classes": {INTERACTIVE: 0, BATCH: 10}}
    b.snapshot = {"queue_depth": 3, "active_slots": 0,
                  "classes": {INTERACTIVE: 3, BATCH: 0}}
    assert a.score(INTERACTIVE) < b.score(INTERACTIVE)
    assert b.score(BATCH) < a.score(BATCH)
    assert a.score() > b.score()  # total-depth JSQ unchanged w/o class
    picked = router.pick(slo=INTERACTIVE)
    assert picked is a
    router._release(picked)
    picked = router.pick(slo=BATCH)
    assert picked is b
    router._release(picked)


def test_pick_scan_preserves_half_open_probe_budget():
    """The JSQ candidate scan must not consume a HALF_OPEN loser's
    probe slot: only the winning replica pays breaker.admit(). A scan
    that burned the budget would leave the breaker refusing traffic
    with no probe ever dispatched."""
    router = Router(registry=obs_metrics.MetricsRegistry())
    healthy = router.add_replica("http://127.0.0.1:1", name="healthy")
    flaky = router.add_replica("http://127.0.0.1:2", name="flaky")
    for _ in range(flaky.breaker.failure_threshold):
        flaky.breaker.record_failure()
    flaky.breaker.reset_timeout_s = 0.0  # OPEN -> HALF_OPEN instantly
    healthy.snapshot = {"queue_depth": 0, "active_slots": 0}
    flaky.snapshot = {"queue_depth": 50, "active_slots": 0}
    for _ in range(5):  # each scan sees flaky HALF_OPEN and passes it
        assert router.pick() is healthy
        router._release(healthy)
    # the probe budget survived the scans: excluding the winner, the
    # half-open replica still has its one probe to give
    assert flaky.breaker.would_admit()
    assert router.pick(exclude=("healthy",)) is flaky


# ------------------------------------------------- autoscaler decisions ----


class _FakeRouter:
    def __init__(self):
        self.registry = obs_metrics.MetricsRegistry()

    def replicas(self):
        return []


class _FakeFleet:
    def __init__(self, size=2, warm=1):
        self.router = _FakeRouter()
        self._size = size
        self.warm_ready = warm
        self.ups = []
        self.downs = []

    def size(self):
        return self._size

    def scale_up(self, n=1):
        if not self.warm_ready:
            return []
        self.warm_ready -= 1
        self._size += 1
        name = f"r{self._size}"
        self.ups.append(name)
        return [name]

    def scale_down(self, n=1, drain_timeout_s=30.0):
        if self._size <= 1:
            return []
        self._size -= 1
        name = f"r{self._size + 1}"
        self.downs.append(name)
        return [name]


def _sig(replicas=2.0, depth=0.0, age=0.0, occ=0.0, p99=0.0):
    return {"replicas": replicas, "queue_depth_per_replica": depth,
            "queue_age_ms": age, "slot_occupancy": occ,
            "first_token_p99_ms": p99}


def _scaler(fleet=None, **cfg_kw):
    fleet = fleet or _FakeFleet()
    cfg = AutoscalerConfig(max_replicas=4, up_stable_ticks=2,
                           down_stable_ticks=3, cooldown_s=5.0, **cfg_kw)
    clock = {"t": 100.0}
    sc = Autoscaler(fleet, cfg, registry=fleet.router.registry,
                    clock=lambda: clock["t"])
    return sc, fleet, clock


def test_autoscaler_hysteresis_requires_stable_pressure():
    sc, fleet, clock = _scaler()
    # one pressured reading is NOT enough (streak < up_stable_ticks)
    assert sc.decide(_sig(depth=10.0), now=100.0) is None
    assert sc.decide(_sig(depth=10.0), now=100.25) == "up"
    # a reading inside the band resets the streak
    sc2, _, _ = _scaler()
    assert sc2.decide(_sig(depth=10.0), now=1.0) is None
    assert sc2.decide(_sig(depth=2.0), now=1.25) is None  # band: reset
    assert sc2.decide(_sig(depth=10.0), now=1.5) is None  # streak back to 1


def test_autoscaler_cooldown_gates_consecutive_actions():
    sc, fleet, clock = _scaler()
    fleet.warm_ready = 2  # enough standbys for two promotions
    assert sc.tick() is None
    clock["t"] += 0.25
    # signals() sees no replicas -> fake the reading through decide by
    # driving tick()'s inputs: monkeypatch signals for determinism
    sc.signals = lambda: _sig(replicas=float(fleet.size()), depth=10.0)
    assert sc.tick() is None  # streak 1 (tick ran once already w/ idle)
    clock["t"] += 0.25
    assert sc.tick() == "up"
    assert fleet.ups == ["r3"]
    # pressure persists, streak rebuilds, but cooldown (5 s) blocks
    for _ in range(6):
        clock["t"] += 0.25
        assert sc.tick() is None
    clock["t"] += 5.0  # past the cooldown window
    assert sc.tick() == "up"
    assert len(fleet.ups) == 2


def test_autoscaler_scale_down_needs_long_idle_and_floor():
    fleet = _FakeFleet(size=2)
    sc, fleet, clock = _scaler(fleet)
    sc.signals = lambda: _sig(replicas=float(fleet.size()))
    acts = []
    for _ in range(8):
        clock["t"] += 0.25
        acts.append(sc.tick())
    assert acts.count("down") == 1  # down_stable_ticks=3 then cooldown
    assert fleet.downs == ["r2"]
    # at the floor (min_replicas=1) idleness never retires the last one
    clock["t"] += 50.0
    for _ in range(8):
        clock["t"] += 0.25
        assert sc.tick() is None
    assert fleet.size() == 1


def test_autoscaler_blocked_promotion_keeps_streak_and_cooldown():
    fleet = _FakeFleet(size=2, warm=0)  # nothing warmed
    sc, fleet, clock = _scaler(fleet)
    sc.signals = lambda: _sig(replicas=float(fleet.size()), depth=10.0)
    clock["t"] += 0.25
    assert sc.tick() is None
    clock["t"] += 0.25
    assert sc.tick() is None  # wanted up, no standby: BLOCKED
    reg = fleet.router.registry
    assert reg.counter_value("pt_autoscale_blocked_total") >= 1
    assert reg.counter_value("pt_autoscale_up_total") == 0
    # the moment a standby warms, the NEXT tick takes it — no cooldown
    # was burned by the blocked attempts
    fleet.warm_ready = 1
    clock["t"] += 0.25
    assert sc.tick() == "up"
    assert sc.last_reaction_s is not None and sc.last_reaction_s > 0


def test_autoscaler_metrics_in_unified_registry():
    sc, fleet, clock = _scaler()
    sc.signals = lambda: _sig(replicas=float(fleet.size()), depth=10.0)
    clock["t"] += 0.25
    sc.tick()
    clock["t"] += 0.25
    sc.tick()
    fams = promparse.parse_text(fleet.router.registry.render())
    for name in ("pt_autoscale_up_total", "pt_autoscale_down_total",
                 "pt_autoscale_blocked_total", "pt_autoscale_replicas",
                 "pt_autoscale_pressure",
                 "pt_autoscale_reaction_seconds"):
        assert name in fams, f"{name} missing from scrape"
    up = [s for s in fams["pt_autoscale_up_total"].samples]
    assert up[0][2] == 1.0
    # one reaction observed, and it appears in the histogram count
    cnt = [s for s in fams["pt_autoscale_reaction_seconds"].samples
           if s[0].endswith("_count")]
    assert cnt and cnt[0][2] == 1.0
    assert sc.stats()["up_total"] == 1


# ----------------------------------------------------------- AST lints -----

_BLOCKING_CALLS = {
    "urlopen", "request", "getresponse", "read", "readline", "recv",
    "send", "sendall", "connect", "sleep", "wait", "join", "select",
    "accept", "probe_one", "dispatch", "_attempt",
}
_BLOCKING_NAMES = {"HTTPConnection", "urlopen", "socket",
                   "create_connection"}


def _find_method(tree, cls, name):
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == cls:
            for item in node.body:
                if isinstance(item, ast.FunctionDef) and item.name == name:
                    return item
    return None


def test_autoscaler_tick_has_no_blocking_io():
    """AST lint (the Router.pick lint pattern): the control loop's
    signal read, decision, and tick body must never perform blocking
    I/O — a slow replica must not be able to stall the loop that would
    scale AROUND it. Actuation is non-blocking by design (scale_up
    takes only ready standbys; scale_down drains in the background)."""
    import paddle_tpu.fleetctl.autoscaler as as_mod

    with open(as_mod.__file__) as f:
        tree = ast.parse(f.read())
    checked = 0
    for meth in ("signals", "decide", "tick"):
        fn = _find_method(tree, "Autoscaler", meth)
        assert fn is not None, f"Autoscaler.{meth} not found (stale lint)"
        checked += 1
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f_ = node.func
            called = (f_.attr if isinstance(f_, ast.Attribute)
                      else f_.id if isinstance(f_, ast.Name) else None)
            assert called not in _BLOCKING_CALLS, (
                f"Autoscaler.{meth} calls blocking {called!r}")
            assert called not in _BLOCKING_NAMES, (
                f"Autoscaler.{meth} constructs {called!r}")
    assert checked == 3


# ------------------------------------------------------------- traces ------


def test_trace_generation_is_bit_identical():
    spec = TraceSpec(duration_s=20.0, seed=11, base_rps=10.0,
                     flash_crowds=((0.5, 3.0, 4.0),),
                     models=(("chat", 2.0, INTERACTIVE),
                             ("bulk", 1.0, BATCH)),
                     stream_fraction=0.1)
    a, b = generate_trace(spec), generate_trace(spec)
    assert a == b and trace_digest(a) == trace_digest(b)
    assert generate_trace(spec, seed=12) != a
    assert {e["slo"] for e in a} == {INTERACTIVE, BATCH}
    # flash crowd: the multiplier window carries visibly more arrivals
    crowd = sum(1 for e in a if 10.0 <= e["t"] < 13.0)
    calm = sum(1 for e in a if 3.0 <= e["t"] < 6.0)
    assert crowd > 2 * calm


def test_trace_spec_validation():
    with pytest.raises(ValueError):
        TraceSpec(diurnal_amplitude=1.5)
    with pytest.raises(ValueError):
        TraceSpec(pareto_alpha=1.0)
    with pytest.raises(ValueError):
        TraceSpec(models=(("m", 1.0, "gold"),))


# --------------------------------------- sim fleet: scale + retirement -----


def _sim_spawner(fingerprint="fp-v1", service_ms=5.0, **kw):
    def spawn():
        return SimReplica(service_ms=service_ms, fingerprint=fingerprint,
                          **kw)
    return spawn


def _wait_until(pred, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.mark.fleet
def test_fleet_scale_down_retires_metric_series():
    """Satellite 3: deliberate scale-down REMOVES the victim's labeled
    pt_router_* counter series from the registry (failure removal keeps
    them — test_fleet pins that side)."""
    reg = obs_metrics.MetricsRegistry()
    router = Router(probe_interval_s=0.1, registry=reg)
    fleet = Fleet(_sim_spawner(), replicas=3, router=router,
                  supervise_interval_s=0.1, ready_timeout_s=10.0)
    fleet.start()
    try:
        def routed_series():
            fams = promparse.parse_text(reg.render())
            fam = fams.get("pt_router_routed_total")
            return {s[1]["replica"] for s in fam.samples} if fam else set()

        before = routed_series()
        assert len(before) == 3
        victims = fleet.scale_down(1)
        assert len(victims) == 1
        _wait_until(lambda: victims[0] not in routed_series(),
                    msg="victim series retirement")
        after = routed_series()
        assert after == before - set(victims)
        assert len(router.replicas()) == 2
        _wait_until(lambda: fleet.retired_total == 1
                    and fleet.describe()["retiring"] == [],
                    msg="retiring drain")
        # gauges are rendered from live membership: no dead series
        fams = promparse.parse_text(reg.render())
        gauge_names = {s[1]["replica"]
                       for s in fams["pt_replica_up"].samples}
        assert gauge_names == after
    finally:
        fleet.stop()


@pytest.mark.fleet
def test_fleet_scale_up_promotes_warm_standby():
    reg = obs_metrics.MetricsRegistry()
    router = Router(probe_interval_s=0.1, registry=reg)
    fleet = Fleet(_sim_spawner(), replicas=1, standby=1, router=router,
                  supervise_interval_s=0.1, ready_timeout_s=10.0)
    fleet.start()
    try:
        _wait_until(lambda: fleet.describe()["warm_ready"] >= 1,
                    msg="standby warm")
        t0 = time.monotonic()
        promoted = fleet.scale_up(1)
        took = time.monotonic() - t0
        assert len(promoted) == 1
        assert fleet.size() == 2
        # promotion is a TAKE of an already-ready standby, not a spawn
        assert took < 2.0
        # scale_up beyond what's warmed only takes what's ready
        assert fleet.size() + len(fleet.scale_up(5)) <= 3
    finally:
        fleet.stop()


# -------------------------------------------- rollout under live load ------


def _write_artifact(tmp_path, name, fingerprint):
    d = tmp_path / name
    d.mkdir()
    (d / "meta.json").write_text(json.dumps(
        {"program_fingerprint": fingerprint}))
    return str(d)


@pytest.mark.fleet
def test_rollout_under_load_zero_client_errors(tmp_path):
    """Satellite 4 + tentpole (c): mid-load version flip. An NDJSON
    stream in flight on the OLD version runs to its terminal "done"
    event; requests issued after the flip land on the NEW fingerprint;
    no client observes an error."""
    v1 = _write_artifact(tmp_path, "v1", "fp-v1")
    v2 = _write_artifact(tmp_path, "v2", "fp-v2")

    def spawn_template(model_dir):
        with open(model_dir + "/meta.json") as f:
            fp = json.load(f)["program_fingerprint"]
        return _sim_spawner(fingerprint=fp, slots=4)

    reg = obs_metrics.MetricsRegistry()
    router = Router(probe_interval_s=0.05, registry=reg)
    fleet = Fleet(spawn_template(v1), replicas=2, router=router,
                  supervise_interval_s=0.1, ready_timeout_s=10.0)
    fleet.spawn_template = spawn_template
    fleet.start()
    server = make_router_server(router, fleet=fleet)
    server.serve_background()
    url = f"http://127.0.0.1:{server.port}"
    errors = []
    stream_events = []

    def long_stream():
        # ~2 s of tokens: the flip happens mid-stream
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"stream": True, "tokens": 20,
                             "sim_ms": 2000}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                for line in r:
                    if line.strip():
                        stream_events.append(json.loads(line))
        except Exception as e:  # pragma: no cover - the failure signal
            errors.append(e)

    t = threading.Thread(target=long_stream)
    t.start()
    _wait_until(lambda: len(stream_events) >= 2, msg="stream underway")
    report = RolloutManager(fleet).rollout(v2, drain_timeout_s=20.0)
    assert report["status"] == "ok"
    assert report["fingerprint"] == "fp-v2"
    t.join(timeout=30.0)
    assert not t.is_alive(), "old-version stream never finished"
    assert errors == []
    # the in-flight stream completed ON the old version
    assert stream_events[-1]["event"] == "done"
    assert stream_events[-1]["fingerprint"] == "fp-v1"
    assert sum(1 for e in stream_events if e["event"] == "token") == 20
    # post-flip requests land on the new version, zero errors
    for _ in range(3):
        req = urllib.request.Request(
            url + "/predict", data=b'{"inputs": {}}',
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            assert json.load(r)["fingerprint"] == "fp-v2"
    # old replicas drained OUT of the rotation, series retired
    assert {r.versions.get("default") for r in router.replicas()} \
        == {"fp-v2"}
    assert len(router.replicas()) == 2
    fams = promparse.parse_text(reg.render())
    live = {s[1]["replica"]
            for s in fams["pt_router_routed_total"].samples}
    assert set(report["old"]).isdisjoint(live)
    # a repeat rollout of the SAME artifact is a noop
    assert RolloutManager(fleet).rollout(v2)["status"] == "noop"
    server.shutdown()
    server.server_close()
    fleet.stop()


def test_rollout_refuses_unverifiable_artifact(tmp_path):
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "meta.json").write_text("{}")  # no fingerprint
    reg = obs_metrics.MetricsRegistry()
    router = Router(probe_interval_s=0.2, registry=reg)
    fleet = Fleet(_sim_spawner(), replicas=1, router=router,
                  supervise_interval_s=0.2, ready_timeout_s=10.0)
    fleet.spawn_template = lambda d: _sim_spawner()
    fleet.start()
    try:
        with pytest.raises(RolloutError):
            RolloutManager(fleet).rollout(str(bad))
        # pre-flip abort: the fleet is untouched
        assert fleet.size() == 1
    finally:
        fleet.stop()


def test_rollout_verify_mismatch_aborts_before_flip(tmp_path):
    v2 = _write_artifact(tmp_path, "v2", "fp-v2")
    reg = obs_metrics.MetricsRegistry()
    router = Router(probe_interval_s=0.2, registry=reg)
    fleet = Fleet(_sim_spawner(fingerprint="fp-v1"), replicas=1,
                  router=router, supervise_interval_s=0.2,
                  ready_timeout_s=10.0)
    # a spawn template that LIES: serves fp-imposter instead of what
    # the artifact's meta.json promises
    fleet.spawn_template = lambda d: _sim_spawner(
        fingerprint="fp-imposter")
    fleet.start()
    try:
        old = set(fleet._procs)
        with pytest.raises(RolloutError, match="verify failed"):
            RolloutManager(fleet).rollout(v2)
        assert set(fleet._procs) == old  # rotation untouched
        assert all(not r.draining for r in router.replicas())
    finally:
        fleet.stop()


# ------------------------------------------ SLO routing through a fleet ----


@pytest.mark.fleet
def test_router_forwards_slo_class_to_replicas():
    """The router resolves a request's class once and forwards it in
    X-PT-SLO-Class, so the replica's admission tiers agree with the
    per-class pick. Demotion comes from the body's "slo" field too."""
    reg = obs_metrics.MetricsRegistry()
    router = Router(probe_interval_s=0.1, registry=reg)
    fleet = Fleet(_sim_spawner(), replicas=1, router=router,
                  supervise_interval_s=0.1, ready_timeout_s=10.0)
    fleet.start()
    server = make_router_server(router)
    server.serve_background()
    url = f"http://127.0.0.1:{server.port}"
    try:
        sim = next(iter(fleet._procs.values()))
        req = urllib.request.Request(
            url + "/predict", data=json.dumps({"slo": BATCH}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.status == 200
        # the sim replica admitted it into the BATCH tier
        admitted = sim.registry.counter_value(
            "pt_slo_admitted_total", labels={"slo": BATCH})
        assert admitted == 1
        req = urllib.request.Request(
            url + "/predict", data=b"{}",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.status == 200
        assert sim.registry.counter_value(
            "pt_slo_admitted_total", labels={"slo": INTERACTIVE}) == 1
        # an unknown class is a 400 at the ROUTER, not a replica error
        req = urllib.request.Request(
            url + "/predict", data=json.dumps({"slo": "gold"}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 400
    finally:
        server.shutdown()
        server.server_close()
        fleet.stop()
