"""Run-wide observability (ISSUE 8): span tracing + unified metrics.

The contract under test, in four layers:

1. trace.py — spans nest and never cross threads, ring overflow drops
   the OLDEST events and counts them (pt_trace_dropped_total — no
   silent truncation), exported JSON is valid Chrome trace-event format
   (schema-checked), disarmed tracing is a single-boolean no-op and an
   AST lint bans armed-path work (kwargs dicts, context mutation)
   outside `_armed` guards on the hot loops.
2. metrics.py — one process-wide registry: Prometheus-compliant render
   (HELP/TYPE once per family, escaped label values), counters
   pre-registered so scrapers never see a missing series, the trainer/
   guard/checkpoint-writer/fault families ride the same scrape the
   serving histograms do.
3. promparse.py — the renderer round-trips through the strict parser;
   the tier-1 smoke test scrapes /metrics twice and asserts every
   family parses and every counter is monotonic.
4. correlation — request_id appears on every span of a served
   generation request (queue→admit→pool-step→stream), step/window ids
   link prefetch→enqueue→hostSync→checkpoint across threads, and the
   mixed-run acceptance exports ONE trace with spans on >= 4 threads.
"""

import ast
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import obs, profiler
from paddle_tpu.obs import promparse
from paddle_tpu.obs import trace as obs_trace
from paddle_tpu.obs.metrics import registry

# ----------------------------------------------------------------- helpers --


def _spans(doc):
    return [e for e in doc["traceEvents"] if e["ph"] == "X"]


def _instants(doc):
    return [e for e in doc["traceEvents"] if e["ph"] == "i"]


def _assert_nested_per_thread(doc):
    """Chrome X events on one tid must form a proper nesting: sorted by
    start, a later span either starts after the previous ends or lies
    entirely inside it."""
    by_tid = {}
    for e in _spans(doc):
        by_tid.setdefault(e["tid"], []).append((e["ts"], e["ts"] + e["dur"]))
    for tid, ivs in by_tid.items():
        stack = []
        for s, t in sorted(ivs, key=lambda it: (it[0], -it[1])):
            while stack and s >= stack[-1] - 1e-6:
                stack.pop()
            assert not stack or t <= stack[-1] + 1e-6, (
                f"tid {tid}: span [{s}, {t}] crosses enclosing span "
                f"ending at {stack[-1]}")
            stack.append(t)


# ------------------------------------------------------------------- trace --


def test_disarmed_hooks_are_noops():
    assert not obs_trace.armed()
    s1 = obs_trace.span("a", x=1)
    s2 = obs_trace.span("b")
    assert s1 is s2  # the shared null singleton: no per-call allocation
    with s1:
        pass
    obs_trace.instant("i", y=2)
    obs_trace.counter("c", 3)
    obs_trace.set_context(step=9)
    assert obs_trace.get_context() == {}


def test_span_nesting_and_context_args():
    with obs_trace.tracing() as tr:
        obs_trace.set_context(step=7)
        with obs_trace.span("outer", cat="t"):
            with obs_trace.span("inner", cat="t", extra=1):
                time.sleep(0.001)
        obs_trace.instant("mark")
    doc = tr.to_chrome()
    assert obs_trace.validate_chrome_trace(doc) == []
    _assert_nested_per_thread(doc)
    spans = {e["name"]: e for e in _spans(doc)}
    assert set(spans) == {"outer", "inner"}
    # sticky thread context lands on every event; explicit args merge in
    assert spans["outer"]["args"]["step"] == 7
    assert spans["inner"]["args"] == {"step": 7, "extra": 1}
    (mark,) = _instants(doc)
    assert mark["args"]["step"] == 7
    # inner is contained in outer on the same tid
    assert spans["inner"]["tid"] == spans["outer"]["tid"]
    assert spans["inner"]["ts"] >= spans["outer"]["ts"]
    assert (spans["inner"]["ts"] + spans["inner"]["dur"]
            <= spans["outer"]["ts"] + spans["outer"]["dur"] + 1e-6)


def test_spans_never_cross_threads():
    """Each thread's spans land in its own ring with its own tid; the
    per-thread context never leaks to another thread."""
    # all four alive at once: a thread that ended before the next started
    # would hand it its ident (seen under a loaded 6-worker run)
    together = threading.Barrier(4)

    def work(n):
        obs_trace.set_context(worker=n)
        with obs_trace.span(f"w{n}", cat="t"):
            together.wait(timeout=30)
            time.sleep(0.002)

    with obs_trace.tracing() as tr:
        threads = [threading.Thread(target=work, args=(n,), name=f"obs-w{n}")
                   for n in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    doc = tr.to_chrome()
    assert obs_trace.validate_chrome_trace(doc) == []
    _assert_nested_per_thread(doc)
    spans = _spans(doc)
    assert len(spans) == 4
    assert len({e["tid"] for e in spans}) == 4
    names = {e["name"]: e for e in spans}
    for n in range(4):
        assert names[f"w{n}"]["args"]["worker"] == n
    # thread-name metadata is emitted per ring
    meta = {e["args"]["name"] for e in doc["traceEvents"]
            if e["ph"] == "M"}
    assert {f"obs-w{n}" for n in range(4)} <= meta


def test_ring_overflow_drops_oldest_and_counts():
    with obs_trace.tracing(ring_size=8) as tr:
        for i in range(20):
            obs_trace.instant("ev", i=i)
        assert tr.dropped_total() == 12
        doc = tr.to_chrome()
        kept = [e["args"]["i"] for e in _instants(doc)]
        assert kept == list(range(12, 20))  # oldest dropped, newest kept
        assert doc["otherData"]["dropped_events"] == 12
        # the drop counter is scrapeable while armed...
        fams = promparse.parse_text(registry().render())
        assert fams["pt_trace_dropped_total"].value() >= 12
        assert fams["pt_trace_armed"].value() == 1
    # ...and survives the session ending (monotonic across sessions)
    assert obs_trace.dropped_total() >= 12


def test_export_schema_rejects_garbage():
    assert obs_trace.validate_chrome_trace([]) != []
    assert obs_trace.validate_chrome_trace({"traceEvents": [{"ph": "Q"}]})
    assert obs_trace.validate_chrome_trace(
        {"traceEvents": [{"ph": "X", "name": "a", "pid": 1, "tid": 1,
                          "ts": -5, "dur": 1}]})
    ok = {"traceEvents": [{"ph": "X", "name": "a", "pid": 1, "tid": 1,
                           "ts": 0.0, "dur": 1.0}]}
    assert obs_trace.validate_chrome_trace(ok) == []


def test_export_to_file_and_open_span_closure(tmp_path):
    path = str(tmp_path / "t.json")
    with obs_trace.tracing(out=path):
        obs_trace._begin("left_open", "t")  # deliberately not ended
    doc = json.load(open(path))
    assert obs_trace.validate_chrome_trace(doc) == []
    assert any(e["name"] == "left_open" for e in _spans(doc))


def test_context_manager_scopes_and_restores():
    with obs_trace.tracing():
        obs_trace.set_context(a=1)
        with obs_trace.context(a=2, b=3):
            assert obs_trace.get_context() == {"a": 2, "b": 3}
        assert obs_trace.get_context() == {"a": 1}


def test_xprof_bracket_smoke(tmp_path):
    """tracing(xprof_dir=...) wraps the capture in profiler.profiler()
    so host spans and device kernels share an interval (degrades to a
    no-op where jax tracing is unsupported)."""
    import jax.numpy as jnp

    with obs_trace.tracing(xprof_dir=str(tmp_path)) as tr:
        with obs_trace.span("device_work"):
            (jnp.ones((8,)) * 2).block_until_ready()
    assert any(e[1] == "device_work"
               for b in tr._bufs for e in b.events)


def test_profiler_timer_emits_spans_when_armed():
    ss = profiler.StatSet()
    with ss.timer("gated"):  # timers off, tracing off: no-op
        pass
    assert "gated" not in ss.stats
    with obs_trace.tracing() as tr:
        with ss.timer("gated"):
            pass
    assert "gated" not in ss.stats  # tracing does not force accumulation
    assert [e for b in tr._bufs for e in b.events
            if e[0] == "X" and e[1] == "gated"]


# ------------------------------------------------------- profiler satellites


def test_statset_thread_safe_hammer():
    """StatSet.get dict insertion + Stat.add under 8 hammering threads:
    exact counts, no lost updates (the serving pool / checkpoint writer
    race the satellite fixes)."""
    ss = profiler.StatSet(keep_samples=16)
    N_THREADS, N_ADDS = 8, 2000
    names = [f"t{i}" for i in range(4)]

    def hammer(seed):
        for i in range(N_ADDS):
            ss.get(names[(seed + i) % len(names)]).add(0.001)

    threads = [threading.Thread(target=hammer, args=(s,))
               for s in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = sum(s.count for s in ss.stats.values())
    assert total == N_THREADS * N_ADDS, total
    for s in ss.stats.values():
        assert abs(s.total - s.count * 0.001) < 1e-6


def test_stat_median_exported():
    ss = profiler.StatSet(keep_samples=5)
    for v in (0.01, 0.03, 0.5):
        ss.get("k").add(v)
    d = ss.as_dict()["k"]
    assert d["median"] == 0.03
    table = ss.print_all_status()
    assert "med(ms)" in table
    # retention off: no median key (zero-overhead default unchanged)
    ss2 = profiler.StatSet()
    ss2.get("k").add(0.1)
    assert "median" not in ss2.as_dict()["k"]
    assert "med(ms)" not in ss2.print_all_status()


# ----------------------------------------------------------------- metrics --


def test_registry_prometheus_compliance_and_roundtrip():
    reg = registry()
    reg.counter_inc("pt_t_req_total", help="reqs",
                    labels={"model": 'we"ird\\mo\ndel'})
    reg.counter_inc("pt_t_req_total", by=2, labels={"model": "plain"})
    reg.gauge("pt_t_depth", lambda: 3, help="queue depth")
    h = reg.histogram("pt_t_lat", buckets=(0.1, 1.0), help="latency")
    h.observe(0.05)
    h.observe(5.0)
    text = reg.render()
    # HELP/TYPE exactly once per family
    for fam in ("pt_t_req_total", "pt_t_depth", "pt_t_lat"):
        assert text.count(f"# TYPE {fam} ") == 1, fam
    # quantile convenience gauges are typed families of their own
    assert "# TYPE pt_t_lat_p99 gauge" in text
    fams = promparse.parse_text(text)  # strict parse of the whole render
    assert fams["pt_t_req_total"].type == "counter"
    # escaped label value round-trips exactly
    assert fams["pt_t_req_total"].value({"model": 'we"ird\\mo\ndel'}) == 1
    assert fams["pt_t_req_total"].value({"model": "plain"}) == 2
    assert fams["pt_t_depth"].value() == 3
    hist = fams["pt_t_lat"]
    assert hist.type == "histogram"
    buckets = {lb["le"]: v for n, lb, v in hist.samples
               if n == "pt_t_lat_bucket"}
    assert buckets == {"0.1": 1, "1": 1, "+Inf": 2}


def test_registry_counter_declared_before_first_inc():
    reg = registry()
    reg.declare_counter("pt_t_pre_total", help="pre-registered")
    fams = promparse.parse_text(reg.render())
    assert fams["pt_t_pre_total"].value() == 0.0
    reg.counter_inc("pt_t_pre_total")
    assert reg.counter_value("pt_t_pre_total") == 1.0


def test_registry_dead_gauge_skipped():
    reg = registry()
    reg.gauge("pt_t_dead", lambda: None, help="dead weakref source")
    text = reg.render()
    assert "pt_t_dead " not in text  # series skipped, no NaN noise


def test_fault_counts_in_unified_render():
    from paddle_tpu.resilience import faults

    faults.arm("executor.step", hit=1)
    with pytest.raises(faults.InjectedFault):
        faults.fire("executor.step")
    try:
        fams = promparse.parse_text(registry().render())
        assert fams["pt_fault_hits_total"].value(
            {"point": "executor.step"}) == 1
        assert fams["pt_fault_fired_total"].value(
            {"point": "executor.step"}) == 1
    finally:
        faults.disarm()


def test_promparse_rejects_malformed():
    for bad in ("metric_without_value",
                'm{le="0.1} 1',          # unterminated label value
                'm{le=0.1} 1',           # unquoted label value
                "m 1 2 3",               # trailing garbage
                "# TYPE m wrongtype",
                "9metric 1"):
        with pytest.raises(promparse.ParseError):
            promparse.parse_text(bad)
    # conflicting duplicate TYPE for one family is the renderer bug the
    # smoke test exists to catch
    with pytest.raises(promparse.ParseError):
        promparse.parse_text("# TYPE m counter\n# TYPE m gauge\nm 1")
    fams = promparse.parse_text(
        '# TYPE m counter\nm{a="x"} 2\nm{a="y"} +Inf\n')
    assert fams["m"].value({"a": "x"}) == 2
    assert fams["m"].value({"a": "y"}) == float("inf")


# ------------------------------------------------ serving smoke (tier-1 CI) -


def _dense_model_dir(tmp_path):
    pt.reset()
    pt.default_startup_program().random_seed = 3
    x = pt.layers.data("x", shape=[4])
    pred = pt.layers.fc(x, size=2)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    d = str(tmp_path / "dense")
    pt.io.save_inference_model(d, ["x"], [pred])
    return d


def test_metrics_smoke_scrape_parses_and_counters_monotonic(tmp_path):
    """The CI smoke test the ISSUE names: scrape /metrics, assert every
    exported family parses and every counter is monotonic across two
    scrapes — with traffic in between. Also: the serving counters are
    pre-registered, so the FIRST scrape (zero requests served) already
    exposes the full family surface at 0."""
    from paddle_tpu.serving import BucketPolicy, ModelRegistry, make_server

    d = _dense_model_dir(tmp_path)
    reg = ModelRegistry()
    reg.add("default", model_dir=d, policy=BucketPolicy(max_batch_size=8),
            timeout_ms=20000.0)
    srv = make_server(reg)
    srv.serve_background()
    try:
        url = f"http://127.0.0.1:{srv.port}"

        def scrape():
            with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
                return promparse.parse_text(r.read().decode())

        first = scrape()
        for fam in ("ptserving_requests_total", "ptserving_shed_total",
                    "ptserving_deadline_exceeded_total",
                    "ptserving_circuit_open_total",
                    "ptserving_compile_cache_hits_total",
                    "ptserving_compile_cache_misses_total",
                    "ptserving_dispatches_total",
                    "ptserving_syncs_total"):
            assert first[fam].value() == 0.0, fam  # pre-registered
        assert first["ptserving_queue_depth"].type == "gauge"
        # the unified surface: trace + engine families in ONE scrape
        assert "pt_trace_dropped_total" in first

        body = json.dumps(
            {"inputs": {"x": [[0.0, 1.0, 2.0, 3.0]]}}).encode()
        for _ in range(3):
            urllib.request.urlopen(urllib.request.Request(
                url + "/predict", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=60).read()
        second = scrape()
        assert second["ptserving_requests_total"].value() >= 3
        for name, fam in first.items():
            if fam.type != "counter":
                continue
            after = second.get(name)
            assert after is not None, f"counter family {name} vanished"
            for sname, labels, v in fam.samples:
                later = [v2 for n2, lb2, v2 in after.samples
                         if n2 == sname and lb2 == labels]
                assert later and later[0] >= v, (
                    f"counter {sname}{labels} went {v} -> {later}")
    finally:
        srv.shutdown()
        reg.stop()
        srv.server_close()


# ------------------------------------------- correlation: generation spans --

V, E, H = 12, 8, 16
BOS, EOS = 0, 1
K, T = 3, 6


def _gen_model_dir(tmp_path):
    """Tiny GRU-ish decoder (the test_gen_serving model) saved with the
    generation meta sidecar."""
    pt.reset()
    pt.default_startup_program().random_seed = 3
    h0 = pt.layers.data("h0", shape=[-1, H], append_batch_size=False)
    gen = pt.layers.BeamSearchDecoder(beam_size=K, max_len=T,
                                      bos_id=BOS, eos_id=EOS)
    with gen.step():
        prev = gen.prev_ids()
        h_prev = gen.memory(init=h0)
        emb = pt.layers.embedding(prev, size=[V, E], param_attr="o_emb")
        h = pt.layers.fc(
            pt.layers.concat([emb, h_prev], axis=1), size=H, act="tanh",
            param_attr="o_w", bias_attr=pt.ParamAttr(name="o_b"))
        gen.update_memory(h_prev, h)
        gen.output_logits(pt.layers.fc(
            h, size=V, param_attr="o_wo",
            bias_attr=pt.ParamAttr(name="o_bo")))
    ids, scores, lengths = gen()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    d = str(tmp_path / "gen")
    pt.io.save_inference_model(d, ["h0"], [ids, scores, lengths])
    return d


def test_request_id_on_every_span_of_a_generation_request(tmp_path):
    """queue→admit→pool-step→stream: every request-scoped span/instant
    of a served generation request carries its request_id, across the
    client thread and the scheduler worker thread."""
    from paddle_tpu.serving import BucketPolicy, ServingEngine

    d = _gen_model_dir(tmp_path)
    eng = ServingEngine(d, policy=BucketPolicy(max_batch_size=8),
                        model_name="g")
    sched = eng.scheduler(max_slots=2)
    rng = np.random.RandomState(5)
    with obs_trace.tracing() as tr:
        handle = sched.submit({"h0": rng.randn(1, H).astype(np.float32)})
        out = handle.result(timeout=60)
    assert out["ids"].shape[0] == 1
    rid = handle.request_id
    assert rid and rid.startswith("gen-")
    doc = tr.to_chrome()
    assert obs_trace.validate_chrome_trace(doc) == []
    evs = _spans(doc) + _instants(doc)
    gen_evs = {e["name"]: e for e in evs if e.get("cat") == "gen"
               and e["name"] != "gen.pool_step"}
    # the full request-scoped chain, each event tagged with THE id
    for name in ("gen.enqueue", "gen.prefix", "gen.admit",
                 "gen.first_token", "gen.retire"):
        assert name in gen_evs, (name, sorted(gen_evs))
        assert gen_evs[name]["args"]["request_id"] == rid, name
    # enqueue happened on the client thread, admission on the worker
    assert gen_evs["gen.enqueue"]["tid"] != gen_evs["gen.admit"]["tid"]
    # the shared pool-step spans exist and carry step/active args
    steps = [e for e in _spans(doc) if e["name"] == "gen.pool_step"]
    assert steps and all("active" in e["args"] for e in steps)
    sched.stop()


# ------------------------------------------------- the mixed-run acceptance -


def test_mixed_run_single_trace_four_threads(tmp_path):
    """ISSUE 8 acceptance: one armed capture over a training pass AND
    served generation requests exports ONE schema-valid Chrome trace
    with spans on >= 4 distinct threads, at least one request whose
    queue→admit→first-token chain shares a request_id, and at least one
    step whose prefetch→enqueue(forwardBackward)→hostSync→checkpoint
    spans are linked by batch/step correlation ids."""
    from paddle_tpu.serving import BucketPolicy, ModelRegistry, make_server

    gen_dir = _gen_model_dir(tmp_path)
    out_path = str(tmp_path / "mixed.trace.json")

    reg = ModelRegistry()
    reg.add("gen", model_dir=gen_dir,
            policy=BucketPolicy(max_batch_size=8),
            scheduler_kw={"max_slots": 2}, timeout_ms=60000.0)
    srv = make_server(reg)
    srv.serve_background()

    # training side: mnist-ish mlp with background checkpointing and the
    # device prefetcher (its producer thread is one of the >= 4)
    prog, startup = pt.Program(), pt.Program()
    startup.random_seed = 11
    with pt.program_guard(prog, startup):
        x = pt.layers.data("x", shape=[16])
        y = pt.layers.data("y", shape=[1])
        hmid = pt.layers.fc(x, size=32, act="tanh")
        pred = pt.layers.fc(hmid, size=1)
        loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.SGD(learning_rate=0.01).minimize(loss)
    cc = pt.CheckpointConfig(str(tmp_path / "ck"), epoch_interval=0,
                             step_interval=4)
    trainer = pt.Trainer(loss, main_program=prog, startup_program=startup,
                         checkpoint_config=cc)
    rng = np.random.RandomState(0)
    batches = [{"x": rng.randn(8, 16).astype(np.float32),
                "y": rng.randn(8, 1).astype(np.float32)}
               for _ in range(10)]

    def reader():
        yield from batches

    url = f"http://127.0.0.1:{srv.port}"
    try:
        with obs_trace.tracing(out=out_path):
            trainer.train(reader, num_passes=1, log_interval=4,
                          prefetch_to_device=2)
            h0 = rng.randn(2, H).astype(np.float32)
            body = json.dumps({"inputs": {"h0": h0.tolist()},
                               "timeout_ms": 60000}).encode()
            with urllib.request.urlopen(urllib.request.Request(
                    url + "/generate/gen", data=body,
                    headers={"Content-Type": "application/json"}),
                    timeout=60) as r:
                assert json.load(r)["outputs"]["ids"]
    finally:
        srv.shutdown()
        reg.stop()
        srv.server_close()

    doc = json.load(open(out_path))
    assert obs_trace.validate_chrome_trace(doc) == []
    _assert_nested_per_thread(doc)
    spans = _spans(doc)
    # >= 4 distinct threads hold spans: trainer main, prefetch producer,
    # checkpoint writer, scheduler worker, HTTP handler(s)
    assert len({e["tid"] for e in spans}) >= 4, (
        sorted({(e["tid"], e["name"]) for e in spans}))

    # (a) one request's queue→admit→first-token chain, one id
    evs = spans + _instants(doc)
    rids = {e["args"]["request_id"] for e in evs
            if e["name"] == "gen.enqueue"}
    assert rids
    rid = rids.pop()
    chain = {e["name"] for e in evs
             if e.get("args", {}).get("request_id") == rid}
    assert {"gen.enqueue", "gen.admit", "gen.first_token"} <= chain, chain

    # (b) one training step's prefetch→enqueue→sync spans linked by the
    # batch/step correlation ids, across >= 2 threads
    pf = {e["args"]["batch"]: e for e in spans
          if e["name"] == "prefetch.batch"}
    fb = {e["args"]["batch"]: e for e in spans
          if e["name"] == "forwardBackward"}
    shared = set(pf) & set(fb)
    assert shared, (sorted(pf), sorted(fb))
    b = min(shared)
    assert pf[b]["tid"] != fb[b]["tid"]  # producer thread vs trainer
    syncs = [e for e in spans if e["name"] == "hostSync"
             and "step" in e.get("args", {})]
    assert syncs
    # (c) the background checkpoint commit carries the step id on the
    # writer thread, linked to the snapshot on the trainer thread
    commits = [e for e in spans if e["name"] == "checkpointCommit"]
    snaps = [e for e in spans if e["name"] == "checkpointSnapshot"]
    assert commits and snaps
    assert commits[0]["tid"] != snaps[0]["tid"]
    assert commits[0]["args"]["step"] == snaps[0]["args"]["step"]


# ------------------------------------------------------------ trainer stats -


def test_trainer_stats_line_and_registry_gauges(caplog):
    import logging

    pt.reset()
    x = pt.layers.data("x", shape=[4])
    y = pt.layers.data("y", shape=[1])
    pred = pt.layers.fc(x, size=1)
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    trainer = pt.Trainer(cost=loss)
    rng = np.random.RandomState(0)

    def reader():
        for _ in range(5):
            yield {"x": rng.randn(4, 4).astype(np.float32),
                   "y": rng.randn(4, 1).astype(np.float32)}

    saved = pt.FLAGS.stats_period
    pt.FLAGS.stats_period = 2
    try:
        with caplog.at_level(logging.INFO, logger="paddle_tpu.stats"):
            trainer.train(reader, num_passes=1)
    finally:
        pt.FLAGS.stats_period = saved
    lines = [r.message for r in caplog.records
             if r.name == "paddle_tpu.stats"]
    assert any("step=4" in ln and "dispatches=" in ln for ln in lines), lines
    fams = promparse.parse_text(registry().render())
    assert fams["pt_trainer_step"].value() == 5
    assert fams["pt_trainer_dispatches_total"].value() == 5
    assert fams["pt_ckpt_commits_total"].value() == 0
    assert fams["pt_guard_rollbacks_total"].value() == 0


def test_dead_trainer_gauges_disappear():
    pt.reset()
    x = pt.layers.data("x", shape=[4])
    loss = pt.layers.mean(pt.layers.fc(x, size=1))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    trainer = pt.Trainer(cost=loss)
    assert "pt_trainer_step 0" in registry().render()
    del trainer
    import gc

    gc.collect()
    assert "pt_trainer_step" not in registry().render()


# ------------------------------------------------------------------- CLI ----


def test_cli_stats_file(tmp_path, capsys):
    from paddle_tpu import cli

    registry().counter_inc("pt_demo_total", help="demo",
                           labels={"kind": "a"})
    p = tmp_path / "m.prom"
    p.write_text(registry().render())
    assert cli.main(["stats", "--file", str(p)]) == 0
    out = capsys.readouterr().out
    assert "pt_demo_total" in out and "families parsed OK" in out
    assert "pt_tune" not in out and "tuned coverage" not in out


def test_cli_stats_rejects_malformed_file(tmp_path):
    from paddle_tpu import cli

    p = tmp_path / "bad.prom"
    p.write_text("this is { not an exposition\n")
    with pytest.raises(SystemExit, match="did not parse"):
        cli.main(["stats", "--file", str(p)])


# ------------------------------------------------ lint: disarmed = zero work


_TRACE_HOT_FNS = {"set_context", "span", "instant", "counter",
                  "_begin", "_end", "get_context", "new_request_id"}

# (module, function) pairs whose bodies are per-step/per-token hot
# paths: EVERY trace hook call inside them must sit under an
# `if <alias>._armed` guard so the disarmed path does zero allocations
# (the kwargs dict of an unguarded span()/set_context() call is real
# work the disarmed branch must not pay).
_HOT_PATHS = [
    ("paddle_tpu.trainer", "_step_pass"),
    ("paddle_tpu.trainer", "_scan_pass"),
    ("paddle_tpu.trainer", "_scan_one"),
    ("paddle_tpu.data.feeder", "produce"),
    ("paddle_tpu.serving.scheduler", "_step_once"),
    # serving v3 hot loops: the speculative round (per-round, streams
    # up to draft_k tokens per slot) and the prefix-cache lookup
    # (per-admission)
    ("paddle_tpu.serving.scheduler", "_spec_round"),
    ("paddle_tpu.serving.prefix_cache", "get"),
    # every step's way to its function (ISSUE 55: a build's spans are
    # opened by `_first_call`, on the miss alone)
    ("paddle_tpu.core.executor", "_planned_fn"),
    ("paddle_tpu.core.executor", "run"),
    ("paddle_tpu.core.executor", "run_window"),
]


def _find_funcs(tree, name):
    return [n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and n.name == name]


def _armed_guard_ranges(fn_node):
    """Line ranges of if-blocks whose test reads *._armed (or a local
    `armed` bool derived from it)."""
    ranges = []
    for node in ast.walk(fn_node):
        if isinstance(node, ast.If) and "_armed" in ast.dump(node.test) \
                or (isinstance(node, ast.If)
                    and isinstance(node.test, ast.Name)
                    and node.test.id == "armed"):
            end = max(getattr(n, "end_lineno", node.lineno)
                      for n in ast.walk(node))
            ranges.append((node.lineno, end))
    return ranges


def test_disarmed_tracing_zero_alloc_lint():
    """Extend the test_scan_trainer AST-lint pattern to tracing: on the
    hot loops, trace-hook calls (which build kwargs dicts / mutate
    context) may only appear inside `if ..._armed` branches."""
    import importlib

    for mod_name, fn_name in _HOT_PATHS:
        mod = importlib.import_module(mod_name)
        with open(mod.__file__) as f:
            tree = ast.parse(f.read())
        fns = _find_funcs(tree, fn_name)
        assert fns, f"{mod_name}.{fn_name} not found (lint is stale)"
        for fn in fns:
            guards = _armed_guard_ranges(fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                f_ = node.func
                if not (isinstance(f_, ast.Attribute)
                        and f_.attr in _TRACE_HOT_FNS
                        and isinstance(f_.value, ast.Name)
                        and "trace" in f_.value.id):
                    continue
                line = node.lineno
                assert any(lo <= line <= hi for lo, hi in guards), (
                    f"{mod_name}.{fn_name}:{line} calls trace hook "
                    f"{f_.attr}() outside an `if ..._armed` guard — "
                    "that work runs on the DISARMED step path")


def test_planned_fn_hit_branch_runs_no_build_code_lint():
    """`_planned_fn` on a call that finds its function: one test of
    `plan.fn`, the hit count, the return. Everything of a build is under
    the `if plan.fn is None:` and the `if cached is None:` inside it, and
    neither `run` nor `run_window` names the build module or a clock."""
    import inspect
    import textwrap

    from paddle_tpu.core.executor import Executor

    tree = ast.parse(textwrap.dedent(inspect.getsource(Executor._planned_fn)))
    fn = tree.body[0]
    body = [n for n in fn.body if not (isinstance(n, ast.Expr) and
                                       isinstance(n.value, ast.Constant))]
    miss, count, ret = body
    assert isinstance(miss, ast.If) and \
        ast.unparse(miss.test) == "plan.fn is None"
    assert ast.unparse(count) == "self.cache_stats['hits'] += 1"
    assert ast.unparse(ret) == "return plan.fn"
    outside = "\n".join(ast.unparse(n) for n in (count, ret))
    assert "_first_call" not in outside and "builds" not in outside
    assert "_first_call" in ast.unparse(miss)
    for method in (Executor.run, Executor.run_window):
        src = inspect.getsource(method)
        assert "builds." not in src and "perf_counter" not in src \
            and "_first_call" not in src
