#!/usr/bin/env python3
"""chipbench's own checks; no chip, no JAX backend. Run by hand:

    python3 chipbench/selftest.py

1. the trace reduction (`xplane.reduce`) on made-up traces (overlapping and
   nested events, the idle share, the custom-call share, collectives, the
   division by steps, the idle gaps' labels, the op table and its scopes)
   and on two small traces recorded on a TPU v5e, each two steps of
   gpt2-small.train cut from a traced run's `xplane.load`:
   `testdata/gpt2_two_steps.trace.json.gz` (PR 23) and
   `testdata/gpt2_two_steps_scoped.trace.json.gz` (PR 26: with the events'
   `op_names` and the Program's ops, so the readers by scope are checked
   against recorded totals);
2. the percentile rule (refuses fewer than 100 intervals);
3. the manifest: BENCHMARK.json's names, units and limits, and that every
   cell resolves to a workload file, a configuration with someone to count
   its FLOPs, a driver, and a reader for each of its metrics;
4. `roofline.share` on a hand-made kernel, the window's registry deltas on a
   made-up snapshot, the wire-format reader on a hand-made `XSpace`;
5. what a train cell owes (`drivers/train.py:correct`, `compared`, `info`)
   on a hand-made run record: no memory book is compared with anything, a
   startup that did not repeat fails by name, `run.py` books the peaks from
   the driver's reading, the names a plain program is compared by and the two
   a program that keeps k of a row's candidates adds, and "the loss fell" on
   the smallest of the last three cost reads.

`tests/test_harness.py` holds what needs JAX or pytest.
"""

from __future__ import annotations

import gzip
import importlib.util
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def _load(path):
    spec = importlib.util.spec_from_file_location(
        "st_" + re.sub(r"\W", "_", os.path.basename(path)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


xplane = _load(os.path.join(HERE, "xplane.py"))
from chipbench import stats  # noqa: E402 — the module the readers import

CHECKS = []


def check(fn):
    CHECKS.append(fn)
    return fn


def _trace(ops, host=(), extra_planes=()):
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [list(e) for e in ops]},
        {"name": "XLA Modules", "events": [["jit_step", 0, 10**9]]}]}]
    planes += list(extra_planes)
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [list(e) for e in host]}]})
    return {"planes": planes}


PALLAS = ('%flash.1 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} %p), '
          'custom_call_target="tpu_custom_call"')
CONCAT = ('%custom-call.67 = f32[128,2048]{1,0} custom-call(f32[32,2048]{1,0} '
          '%s), custom_call_target="ConcatBitcast"')
FUSION = ('%fusion.10 = bf16[128000,2048]{1,0:T(8,128)(2,1)} fusion(s32[128000]'
          '{0:T(1024)S(1)} %gte.1), kind=kCustom, calls=%fused_computation.261')
WHILE = ('%while.11 = (s32[]{:T(128)}, bf16[512,2048]{1,0:T(8,128)(2,1)S(1)}) '
         'while((s32[]{:T(128)}, bf16[512,2048]{1,0}) %tuple.3), '
         'condition=%cond, body=%body')
ALLREDUCE = ('%all-reduce.3 = f32[512,2048]{1,0} all-reduce(f32[512,2048]{1,0} '
             '%x), replica_groups={{0,1,2,3}}, to_apply=%add')
ARSTART = ('%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %y), '
           'replica_groups={{0,1,2,3}}, to_apply=%add')


@check
def opcodes_from_instruction_text():
    assert xplane.opcode(FUSION) == "fusion"
    assert xplane.opcode(WHILE) == "while"          # tuple-shaped result
    assert xplane.opcode(PALLAS) == "custom-call"
    assert xplane.opcode(ALLREDUCE) == "all-reduce"
    assert xplane.opcode("fusion.12") == "fusion"    # a bare name
    assert xplane.is_custom_call(PALLAS) and not xplane.is_custom_call(CONCAT)
    assert xplane.is_collective(ALLREDUCE) and xplane.is_collective(ARSTART)
    assert xplane.is_container(WHILE) and not xplane.is_container(FUSION)
    assert xplane.short(FUSION) == "%fusion.10 fusion bf16[128000,2048]"


@check
def busy_is_a_union_not_a_sum():
    # window 0..1000 by the annotations. Ops: 100..300, an overlapping
    # 200..400, one nested inside (250..260); then 600..700. A while
    # covering 0..900 must not count: only its body's ops are busy time
    host = [("chipbench.prepare_and_dispatch", 0, 400),
            ("chipbench.cost_read", 400, 600)]
    ops = [(WHILE, 0, 900), (FUSION, 100, 200), (PALLAS, 200, 200),
           (CONCAT, 250, 10), (ALLREDUCE, 600, 100)]
    r = xplane.reduce(_trace(ops, host), chips=1)
    p = r["planes"][0]
    assert r["window_s"] == 1000 / 1e9, r["window_s"]
    assert p["busy_ns"] == 300 + 100, p            # 100..400 and 600..700
    assert p["custom_call_ns"] == 200, p           # the Pallas call only
    assert p["collective_ns"] == 100, p
    assert r["busy_s_mean"] == 400 / 1e9
    # idle: 0..100 under prepare_and_dispatch, 400..600 and 700..1000
    # under cost_read
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert abs(gaps["prepare_and_dispatch"] - 100e-9) < 1e-15, gaps
    assert abs(gaps["cost_read"] - 500e-9) < 1e-15, gaps
    top = dict(r["breakdown"]["device_ops"])
    assert abs(top["%while.11 while s32[] (body included)"] - 900e-9) < 1e-15
    assert len(r["breakdown"]["device_ops"]) <= 10


@check
def events_are_clipped_to_the_window():
    host = [("chipbench.wait_for_batch", 1000, 1000)]   # window 1000..2000
    ops = [(FUSION, 500, 700), (FUSION, 1900, 500)]     # 500..1200, 1900..2400
    p = xplane.reduce(_trace(ops, host))["planes"][0]
    assert p["busy_ns"] == 200 + 100, p


@check
def innermost_host_event_labels_a_gap():
    host = [("chipbench.prepare_and_dispatch", 0, 1000),
            ("PjitFunction(raw)", 100, 700),
            ("chipbench.cost_read", 1000, 100)]
    ops = [(FUSION, 800, 300)]
    gaps = dict(xplane.reduce(_trace(ops, host))["breakdown"]["idle_gaps"])
    assert abs(gaps["PjitFunction(raw)"] - 800e-9) < 1e-15, gaps


@check
def per_layer_readers_divide_by_steps():
    host = [("chipbench.cost_read", 0, 10**9)]
    ops = [(FUSION, 0, 4 * 10**8), (PALLAS, 5 * 10**8, 10**8),
           (ARSTART, 7 * 10**8, 10**8)]
    second = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [[FUSION, 0, 2 * 10**8]]}]}
    run = {"steps": 4, "trace": xplane.reduce(
        _trace(ops, host, [second]), chips=2)}

    def metric(name):
        return _load(os.path.join(HERE, "layer_metrics", name + ".py")
                     ).compute(run)
    assert abs(metric("step.device_ms") - 600.0 / 4) < 1e-9
    assert abs(metric("kernel.pallas_share_pct") - 100.0 / 6) < 1e-9
    assert run["trace"]["planes"][0]["collective_ns"] == 10**8
    assert abs(metric("device.idle_pct") - 80.0) < 1e-9   # the idler chip
    assert abs(run["trace"]["busy_s_mean"] - 0.4) < 1e-12
    assert metric("step.device_ms") is not None
    assert _load(os.path.join(HERE, "layer_metrics", "step.device_ms.py")
                 ).compute({"steps": 4}) is None          # nothing to read


@check
def recorded_trace_reduces_to_the_recorded_numbers():
    path = os.path.join(HERE, "testdata", "gpt2_two_steps.trace.json.gz")
    with gzip.open(path) as f:
        trace = json.load(f)
    r = xplane.reduce(trace, chips=1)
    p = r["planes"][0]
    # as reduced when the trace was cut (PR 23, TPU v5 lite)
    assert p["op_events"] == 13434, p      # inside the window
    assert p["busy_ns"] == 383193873, p
    assert p["custom_call_ns"] == 80673222, p
    assert p["collective_ns"] == 0, p
    assert abs(r["window_s"] - 0.498756179) < 1e-12, r["window_s"]
    # 48 flash kernels a step (forward twice, dq, dkv for 12 layers)
    names = [ev[0] for pl in trace["planes"] for ln in pl["lines"]
             if ln["name"] == "XLA Ops" for ev in ln["events"]]
    assert sum(map(xplane.is_custom_call, names)) == 2 * 48, \
        sum(map(xplane.is_custom_call, names))
    assert dict(r["breakdown"]["idle_gaps"]).keys() >= {"prepare_and_dispatch"}


@check
def scopes_from_op_names():
    assert xplane.scope_of(
        "jit(raw)/transpose(jvp(mul.fc_394.tmp_395))/dot_general:") == (
        "mul.fc_394.tmp_395", "transpose(jvp")
    assert xplane.scope_of(
        "jit(raw)/jvp(flash_attention.tfm.h8.attn.tmp_275)/"
        "jit(flash_attention)/pallas_call:") == (
        "flash_attention.tfm.h8.attn.tmp_275", "jvp")
    assert xplane.scope_of("jit(raw)/adam.tfm.tok_emb/sub:") == (
        "adam.tfm.tok_emb", "")
    assert xplane.scope_of("jit(accum_fold)/add") == ("", "")
    assert xplane.scope_of("") == ("", "")


@check
def op_table_has_one_row_per_instruction():
    host = [("chipbench.cost_read", 0, 1000)]
    ops = [(WHILE, 0, 900), (FUSION, 100, 200), (FUSION, 400, 100),
           (PALLAS, 500, 50), (CONCAT, 600, 10), (FUSION, 950, 100)]
    trace = _trace(ops, host)
    trace["planes"][0]["op_names"] = {
        FUSION: "jit(raw)/transpose(jvp(mul.fc_1.tmp_0))/dot_general:",
        PALLAS: "jit(raw)/flash_attention.a.tmp_1/jit(flash_attention)/"
                "pallas_call:"}
    r = xplane.reduce(trace)
    rows = {row["name"]: row for row in r["ops"]}
    assert len(r["ops"]) == 4 and r["ops"][0]["name"] == "%while.11"
    assert rows["%while.11"]["container"] and rows["%while.11"]["ns"] == 900
    f = rows["%fusion.10"]
    assert (f["count"], f["ns"], f["opcode"], f["shape"]) == (
        3, 350, "fusion", "bf16[128000,2048]")        # the last one clipped
    assert (f["scope"], f["transform"], f["target"]) == (
        "mul.fc_1.tmp_0", "transpose(jvp", None)
    k = rows["%flash.1"]
    assert (k["target"], k["scope"], k["transform"]) == (
        "tpu_custom_call", "flash_attention.a.tmp_1", "")
    c = rows["%custom-call.67"]
    assert (c["target"], c["scope"], c["op_name"]) == ("ConcatBitcast", "", "")
    leaves = [row for row in r["ops"] if not row["container"]]
    assert sum(row["ns"] for row in leaves) == r["planes"][0]["busy_ns"] == 410


def _varint(n):
    out = b""
    while n >= 0x80:
        out += bytes([n & 0x7F | 0x80])
        n >>= 7
    return out + bytes([n])


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


@check
def op_names_from_the_wire_format():
    stat_meta = [_field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, n)))
                 for i, n in ((1, b"flops"), (2, b"tf_op"),
                              (3, b"jit(raw)/adam.w/sub:"))]

    def event_meta(i, name, stats):
        return _field(4, _field(1, i) + _field(2, _field(1, i) + _field(
            2, name) + b"".join(_field(5, st) for st in stats)))

    plane = _field(2, b"/device:TPU:0") + b"".join(stat_meta)
    plane += _field(3, _field(2, b"XLA Ops") + _field(4, _field(1, 7)))
    plane += event_meta(7, b"%a = f32[8] fusion()", [
        _field(1, 1) + _field(3, 99),
        _field(1, 2) + _field(5, b"jit(raw)/mul.x.tmp_0/dot_general:")])
    plane += event_meta(8, b"%b = f32[8] fusion()", [
        _field(1, 2) + _field(7, 3)])                 # a ref_value
    plane += event_meta(9, b"%c = f32[8] copy()", [
        _field(1, 1) + b"\x11" + b"\0" * 8])          # a double, no tf_op
    space = _field(1, plane) + _field(1, _field(2, b"/host:CPU"))
    assert xplane.event_op_names(space) == {"/device:TPU:0": {
        "%a = f32[8] fusion()": "jit(raw)/mul.x.tmp_0/dot_general:",
        "%b = f32[8] fusion()": "jit(raw)/adam.w/sub:"}}


@check
def recorded_scoped_trace_reduces_to_the_recorded_numbers():
    path = os.path.join(HERE, "testdata", "gpt2_two_steps_scoped.trace.json.gz")
    with gzip.open(path) as f:
        trace = json.load(f)
    r = xplane.reduce(trace, chips=1)
    p = r["planes"][0]
    # as reduced when the trace was cut (PR 26, TPU v5 lite, seed 2147486001)
    assert p["op_events"] == 14496 and p["busy_ns"] == 387688522, p
    assert p["custom_call_ns"] == 80484130, p
    leaves = [row for row in r["ops"] if not row["container"]]
    assert len(r["ops"]) == 7356 and len(leaves) == 7356   # no loop in it
    # no two ops overlap on this device, so the leaf rows' plain sum is the
    # busy union
    assert sum(row["ns"] for row in leaves) == p["busy_ns"]
    assert sum(row["count"] for row in leaves) == p["op_events"]
    assert sum(row["ns"] for row in leaves if not row["scope"]) == 13126749
    assert sum(row["ns"] for row in leaves
               if row["transform"].startswith("transpose")) == 167345999
    with open(os.path.join(HERE, "configs", "gpt2-small", "config.json")) as f:
        config = json.load(f)
    run = {"steps": 2, "trace": r, "program_ops": trace["program_ops"],
           "config": config, "cell": {"batch": 12, "seqlen": 1024},
           "device": {"kind": "TPU v5 lite"}}

    def metric(name):
        return _load(os.path.join(HERE, "layer_metrics", name + ".py")
                     ).compute(run)
    assert abs(metric("step.device_ms") - 193.844261) < 1e-9
    assert abs(metric("head.device_ms") - 52.5517205) < 1e-9
    assert abs(metric("opt.device_ms") - 1.709014) < 1e-9
    assert abs(metric("kernel.flash_roofline") - 8.785213093442836) < 1e-9


@check
def roofline_share_on_a_hand_made_kernel():
    from chipbench import roofline

    # 197 TFLOP and 1 byte in 2 s: half the compute roof
    assert roofline.share(197e12, 1.0, 2.0, "TPU v5 lite") == (50.0, "compute")
    # 819 GB and 1 FLOP in 4 s: a quarter of the memory roof
    assert roofline.share(1.0, 819e9, 4.0, "TPU v5 lite") == (25.0, "memory")
    # a count that is too high shows: never clamped
    assert roofline.share(197e12, 819e9, 0.5, "TPU v5 lite")[0] == 200.0
    try:
        roofline.share(1.0, 1.0, 1.0, "TPU v9")
    except SystemExit:
        pass
    else:
        raise AssertionError("a share on an unknown device")


@check
def registry_deltas_on_a_made_up_snapshot():
    train = _load(os.path.join(HERE, "drivers", "train.py"))
    text = ("# TYPE pt_tokens_total counter\n"
            'pt_tokens_total{expert="0"} 5\npt_tokens_total{expert="1"} 7\n'
            "# TYPE pt_executor_donated_bytes gauge\n"
            "pt_executor_donated_bytes 1960000000\n"
            "# TYPE pt_lat histogram\n"
            'pt_lat_bucket{le="+Inf"} 3\npt_lat_sum 0.5\npt_lat_count 3\n')
    before = train.registry_snapshot(text)
    after = train.registry_snapshot(
        text.replace("} 5", "} 9").replace("pt_lat_count 3", "pt_lat_count 4")
        + "# TYPE pt_dropped_total counter\npt_dropped_total 2\n")
    assert train.registry_delta(before, after) == {
        'pt_tokens_total{expert="0"}': 4.0, 'pt_tokens_total{expert="1"}': 0.0,
        "pt_executor_donated_bytes": 1960000000.0, "pt_lat_sum": 0.0,
        "pt_lat_count": 1.0, "pt_dropped_total": 2.0}
    reader = _load(os.path.join(HERE, "layer_metrics", "step.donated_gib.py"))
    got = reader.compute({"registry": train.registry_delta(before, after)})
    assert abs(got - 1960000000.0 / 2**30) < 1e-12


@check
def a_train_cell_owes_no_memory_to_the_yardstick():
    train = _load(os.path.join(HERE, "drivers", "train.py"))
    run_py = _load(os.path.join(HERE, "run.py"))
    run = {"costs": [9.0, 7.5], "first_cost": 10.8, "bad_intervals": 0,
           "reference_first_cost": 10.8001, "startup_differs": [],
           "gradient_errors": {"w": 0.012, "b": 0.049},
           "tolerances": {"reference_tol": 2e-5, "grad_tol": 0.05},
           "counters": {"programs_built": 0, "cache_misses": 0},
           "steps": 2, "window_s": 0.3, "intervals_s": [0.15, 0.15],
           "peak_after_startup": {"in_use": 5, "reserved": 0, "bytes": 5},
           "after_window_s": {"second_startup": 1.0, "reference": 2.0},
           # a step that needs less than any reference would: still the peak
           "memory_stats": [{"peak_bytes_in_use": 7, "peak_bytes_reserved": 3},
                            {"peak_bytes_in_use": 6, "peak_bytes_reserved": 5}],
           "setup_split_s": {"reach_chip": 12.0, "startup": 3.0,
                             "step_program_compile_or_cache_read": 6.0,
                             "warmup": 9.0}}
    run_py.book_memory(run)
    assert run["memory_peaks"] == {"in_use": 7, "reserved": 5, "bytes": 11}
    assert run["memory_peak_bytes"] == 11
    assert train.correct(run) == []
    assert "memory" not in " ".join(train.compared(run))
    info = train.info(run)
    assert info["setup_split_s"] is run["setup_split_s"]
    assert info["peak_final"] == run["memory_peaks"]
    assert "peak_after_reference" not in info
    compared = train.compared(run)
    # a plain program's names and no other: a routed one adds the second
    # reading's two and the choice's four, one that keeps k of a row's
    # candidates the second reading's and `kept_sets_off_rule`,
    # `kept_turned_not_near_tie`
    assert list(compared) == [
        "startup_tensors_differing", "cost_reads_not_finite",
        "last_cost_over_first", "first_cost_off_reference",
        "programs_built_in_window", "cache_misses_in_window",
        "gradient_error_nearest_limit"], list(compared)
    kept = {"sets_off_rule": 0.0, "turned_not_near_tie": 2.0}
    assert list(train.compared(dict(run, kept=[kept])))[7:] == [
        "kept_sets_off_rule", "kept_turned_not_near_tie"]
    assert any("kept_turned_not_near_tie 2.0" in b
               for b in train.correct(dict(run, kept=[kept])))
    # the loss fell: the smallest of the last three reads against the first
    assert compared["last_cost_over_first"] == [7.5 / 10.8, 1.0]
    spiked = dict(run, costs=[9.0, 7.5, 8.0, 14.83])
    assert train.correct(spiked) == []
    assert train.compared(spiked)["last_cost_over_first"] == [7.5 / 10.8, 1.0]
    bad = train.correct(dict(run, costs=[9.0, 11.0, 12.0, 14.83]))
    assert len(bad) == 1 and "the loss did not fall" in bad[0], bad
    assert compared["gradient_error_nearest_limit"] == [0.049, 0.05]
    assert abs(compared["first_cost_off_reference"][0] - 1e-4 / 10.8001) < 1e-12
    bad = train.correct(dict(run, startup_differs=["w"]))
    assert len(bad) == 1 and bad[0].startswith(
        "startup did not reproduce the weights"), bad
    bad = train.correct(dict(run, gradient_errors={"w": 0.012, "b": 0.051}))
    assert len(bad) == 1 and "gradient of b" in bad[0], bad
    bad = train.correct(dict(run, reference_first_cost=10.8 * (1 + 1e-4)))
    assert len(bad) == 1 and "first cost" in bad[0], bad


@check
def a_trace_without_a_device_is_an_error():
    try:
        xplane.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})
    except ValueError:
        return
    raise AssertionError("reduced a trace with no device plane")


@check
def percentile_rule():
    vals = [float(i) for i in range(1, 101)]
    assert stats.percentile(vals, 90) == 90.0
    assert stats.percentile(vals[::-1], 90) == 90.0
    assert stats.percentile(vals + [1000.0] * 20, 90) == 1000.0  # no trimming
    try:
        stats.percentile(vals[:99], 90)
    except stats.TooFewSamples:
        pass
    else:
        raise AssertionError("a percentile over 99 samples")
    reader = _load(os.path.join(HERE, "end_to_end", "step_ms_p90.py"))
    assert reader.compute({"intervals_s": [v / 1e3 for v in vals]}) == 90.0
    try:
        reader.compute({"intervals_s": [0.1] * 50})
    except stats.TooFewSamples:
        pass
    else:
        raise AssertionError("step_ms_p90 over 50 intervals")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _line(text, where):
    assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text, \
        (where, len(text))


@check
def manifest_keeps_the_contract():
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    flops = _load(os.path.join(HERE, "flops.py"))
    with open(path) as f:
        m = json.load(f)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}, set(m)
    assert 1 <= len(m["command"]) <= 32 and 1 <= len(m["paths"]) <= 16
    for w in m["command"]:
        _line(w, "command")
        assert not w.startswith("/") and ".." not in w.split("/"), w
    for p in m["paths"]:
        assert PATH.match(p) and os.path.isdir(os.path.join(ROOT, p)), p
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["configs"]) <= 24 and 1 <= len(m["workloads"]) <= 24
    assert 1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128
    names = set()
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c
        assert NAME.match(c["name"]) and c["name"] not in names, c["name"]
        names.add(c["name"])
        _line(c["why"], c["name"])
        _line(c["source"], c["name"])
        assert len(c["reduced"]) <= 16 and all(map(NAME.match, c["reduced"]))
        assert any(c["file"].startswith(p + "/") for p in m["paths"]), c["file"]
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert isinstance(cfg, dict) and cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"], c["name"]
        assert os.path.exists(os.path.join(HERE, "drivers", cfg["driver"] + ".py"))
        config_dir = os.path.dirname(os.path.join(ROOT, c["file"]))
        assert os.path.exists(os.path.join(config_dir, "model.py"))
        assert os.path.exists(os.path.join(config_dir, "reference.py"))
        # someone counts its FLOPs: flops.py or the configuration's own
        assert callable(flops.family_arithmetic(cfg, config_dir)), c["name"]
        # one limit for every tensor since PR 36: no configuration names any
        assert "routed_parameters" not in cfg, c["name"]
    assert len({c["file"] for c in m["configs"]}) == len(m["configs"])
    cells, pairs, used = set(), set(), set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]), w
        assert w["name"] not in cells and w["config"] in names, w
        assert (w["config"], w["traffic"]) not in pairs and w["chips"] in (1, 4)
        cells.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        _line(w["why"], w["name"])
        with open(os.path.join(HERE, "workloads", w["name"] + ".json")) as f:
            cell = json.load(f)
        for k in ("config", "traffic", "chips"):
            assert cell[k] == w[k], (w["name"], k)
        assert cell["warmup_steps"] % cell["sync_every"] == 0, w["name"]
        assert cell["warmup_steps"] >= 2 * cell["sync_every"], w["name"]
    assert used == names, "a configuration no cell uses"
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4), four
    metrics, e2e = set(), set()
    for sec, folder, keys in (
            ("end_to_end", "end_to_end",
             {"name", "unit", "better", "bound", "source"}),
            ("per_layer", "layer_metrics",
             {"name", "unit", "better", "source", "layer", "moves"})):
        for x in m[sec]:
            assert set(x) - {"workloads"} == keys, x
            assert NAME.match(x["name"]) and x["name"] not in metrics, x
            metrics.add(x["name"])
            assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
            assert x["source"] in SOURCES, x
            assert set(x.get("workloads", [])) <= cells, x
            assert os.path.exists(os.path.join(HERE, folder, x["name"] + ".py")), \
                f"no reader for {x['name']}"
            if sec == "end_to_end":
                e2e.add(x["name"])
                assert x["source"] in ("host_clock", "device_trace"), x
                assert 0.01 <= x["bound"] <= 0.1, x
            else:
                _line(x["layer"], x["name"])
                assert x["moves"] in e2e and x["moves"] != "setup_s", x
                mover = next(e for e in m["end_to_end"] if e["name"] == x["moves"])
                assert set(x.get("workloads", cells)) <= set(
                    mover.get("workloads", cells)), x
    assert "setup_s" in e2e
    checks = 2 + 14 * 24
    assert checks * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@check
def every_reader_has_a_manifest_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    for sec, folder in (("end_to_end", "end_to_end"),
                        ("per_layer", "layer_metrics")):
        have = {f[:-3] for f in os.listdir(os.path.join(HERE, folder))
                if f.endswith(".py")}
        assert have == {x["name"] for x in m[sec]}, (folder, have)


@check
def flops_arithmetic():
    flops = _load(os.path.join(HERE, "flops.py"))
    with open(os.path.join(HERE, "configs", "gpt2-small", "config.json")) as f:
        g = json.load(f)
    r = {"flops_family": "lstm2", "hidden_size": 512, "embedding_size": 128}
    assert flops.train_flops_per_item(g, {"seqlen": 1024}) == 797815296.0
    assert flops.train_flops_per_item(r, {"seqlen": 100}) == 20447232.0
    assert flops.peak_flops("TPU v5 lite") == 197e12
    # a family flops.py does not know comes with its configuration; without
    # the file the refusal names the family and the file to add
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        own = {"flops_family": "moe_lm", "active": 3}
        try:
            flops.family_arithmetic(own, d)
        except SystemExit as e:
            assert "'moe_lm'" in str(e) and "flops.py" in str(e), e
        else:
            raise AssertionError("an unknown family with no flops.py")
        with open(os.path.join(d, "flops.py"), "w") as f:
            f.write("def train_flops_per_item(config, cell):\n"
                    "    return 2.0 * config['active'] * cell['seqlen']\n")
        assert flops.train_flops_per_item(own, {"seqlen": 4}, d) == 24.0
        # a configuration may not override a family flops.py knows
        assert flops.train_flops_per_item(g, {"seqlen": 1024}, d) == 797815296.0
    try:
        flops.peak_flops("TPU v9")
    except SystemExit:
        pass
    else:
        raise AssertionError("a peak for an unknown device")


def main() -> int:
    failed = 0
    for fn in CHECKS:
        try:
            fn()
            print(f"ok   {fn.__name__}")
        except Exception as e:  # noqa: BLE001 — report every check
            failed += 1
            print(f"FAIL {fn.__name__}: {type(e).__name__}: {e}")
    print(f"{len(CHECKS) - failed} of {len(CHECKS)} checks passed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
