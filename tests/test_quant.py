"""paddle_tpu.quant: post-training int8 quantization fast path.

The contract under test (ISSUE 15 acceptance): calibration is
deterministic (same samples → byte-identical scales, which is what lets
the meta.json scales digest double as a staleness check), the converted
artifact round-trips save/load bit-identically, mixed programs report
every skipped site loudly, quantized outputs stay within a bounded
delta of fp32, every tune-space candidate the int8 family emits is
legal by its own model, a quantized artifact serves through the
bucketed engine with zero post-warmup compiles, and a tampered
artifact (program or payload edited after export) fails LOUDLY at load
instead of serving garbage with stale scales.

Plus the zero-cost lint (the test_obs pattern extended to the quant
hot path): the dispatch-path functions must never recompute scales,
touch numpy, or host-sync — scales are convert-time artifacts.
"""

import ast
import json
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import amp, quant
from paddle_tpu.io import QuantMetaError
from paddle_tpu.quant.convert import SCALE_SUFFIX
from paddle_tpu.ops import quant_kernels as qk
from paddle_tpu.serving import BucketPolicy, ServingEngine
from paddle_tpu.tune import space as tune_space

# ---------------------------------------------------------------- fixtures --


def _build_mlp(dirname, in_dim=16, hidden=32, out_dim=8, seed=5):
    """Seeded 3-matmul MLP saved as an fp32 inference artifact."""
    pt.reset()
    pt.default_startup_program().random_seed = seed
    x = pt.layers.data("x", shape=[in_dim])
    h1 = pt.layers.fc(x, size=hidden, act="relu", name="tq_fc1")
    h2 = pt.layers.fc(h1, size=hidden, act="relu", name="tq_fc2")
    pred = pt.layers.fc(h2, size=out_dim, name="tq_fc3")
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    pt.io.save_inference_model(dirname, ["x"], [pred])
    return exe


def _samples(n=4, batch=4, in_dim=16, seed=0):
    rng = np.random.RandomState(seed)
    return [{"x": rng.standard_normal((batch, in_dim))
             .astype(np.float32)} for _ in range(n)]


def _load_convert(model_dir, samples=None, **kw):
    """fp artifact → (program, feeds, fetches, scope, exe, report)."""
    scope = pt.Scope()
    exe = pt.Executor()
    program, feeds, fetches = pt.io.load_inference_model(model_dir,
                                                         scope=scope)
    samples = samples or _samples()
    calib = quant.calibrate(program, samples, scope=scope, exe=exe)
    report = quant.convert(program, scope=scope, calib=calib,
                           check_feed=samples[0], fetch_list=fetches,
                           exe=exe, **kw)
    return program, feeds, fetches, scope, exe, report


@pytest.fixture
def mlp_dir(tmp_path):
    d = str(tmp_path / "fp32")
    _build_mlp(d)
    return d


@pytest.fixture(autouse=True)
def _fresh_quant_stats():
    quant.reset_stats()
    yield
    quant.reset_stats()


# ----------------------------------------------------- precision policy ----


def test_precision_policy_one_table():
    """Satellite 1: ONE policy table drives both amp exclusion and
    quant eligibility — softmax/batch_norm can never be quantized nor
    amp-downcast, matmuls are both."""
    assert amp.precision_policy("softmax") == "high"
    assert amp.precision_policy("batch_norm") == "high"
    assert amp.precision_policy("mul") == "low"
    assert amp.precision_policy("relu") == "follow"
    assert amp.QUANTIZABLE_OPS <= amp.LOW_PRECISION_OPS
    assert not (amp.QUANTIZABLE_OPS & amp.HIGH_PRECISION_OPS)


# --------------------------------------------------------- calibration ----


def test_calibration_deterministic(mlp_dir):
    """Same samples → byte-identical ranges (twice over fresh loads,
    the property the scales digest depends on)."""
    ranges = []
    for _ in range(2):
        scope = pt.Scope()
        program, _, _ = pt.io.load_inference_model(mlp_dir, scope=scope)
        calib = quant.calibrate(program, _samples(), scope=scope)
        assert calib.sample_count == 4
        ranges.append(calib.act_ranges)
    assert ranges[0] == ranges[1]
    # one range per quantizable site's activation, all observed > 0
    assert len(ranges[0]) == 3
    assert all(v > 0 for v in ranges[0].values())


def test_calibrate_needs_samples(mlp_dir):
    scope = pt.Scope()
    program, _, _ = pt.io.load_inference_model(mlp_dir, scope=scope)
    with pytest.raises(ValueError, match="at least one sample"):
        quant.calibrate(program, [], scope=scope)


# ------------------------------------------------------------- convert ----


def test_convert_save_load_bit_identical(mlp_dir, tmp_path):
    """int8 payloads and f32 scales survive save→load byte-for-byte,
    and the reloaded program serves the exact same outputs."""
    program, feeds, fetches, scope, exe, report = _load_convert(mlp_dir)
    assert len(report.quantized) == 3 and not report.skipped
    q_dir = str(tmp_path / "int8")
    pt.io.save_inference_model(q_dir, feeds, fetches,
                               main_program=program, scope=scope)
    scope2 = pt.Scope()
    p2, _, t2 = pt.io.load_inference_model(q_dir, scope=scope2)
    for site in report.quantized:
        w1, w2 = scope.get(site["w"]), scope2.get(site["w"])
        assert np.asarray(w1).dtype == np.int8
        np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
        sname = site["w"] + SCALE_SUFFIX
        np.testing.assert_array_equal(np.asarray(scope.get(sname)),
                                      np.asarray(scope2.get(sname)))
    feed = _samples(1, seed=9)[0]
    out1 = exe.run(program, feed=feed, fetch_list=fetches, scope=scope)
    out2 = exe.run(p2, feed=feed, fetch_list=t2, scope=scope2)
    np.testing.assert_array_equal(np.asarray(out1[0]),
                                  np.asarray(out2[0]))
    assert p2._quant_meta["mode"] == "int8"
    assert p2._quant_meta["sites"] == 3


def test_convert_accuracy_bounded(mlp_dir):
    """Per-channel int8 on a seeded MLP: output delta vs fp32 stays
    within 5% of the fp32 output range on a held-out feed."""
    program, _, fetches, scope, exe, report = _load_convert(mlp_dir)
    s3 = pt.Scope()
    p3, _, t3 = pt.io.load_inference_model(mlp_dir, scope=s3)
    feed = _samples(1, seed=123)[0]
    out_q = np.asarray(exe.run(program, feed=feed, fetch_list=fetches,
                               scope=scope)[0], np.float32)
    out_fp = np.asarray(exe.run(p3, feed=feed, fetch_list=t3,
                                scope=s3)[0], np.float32)
    delta = float(np.max(np.abs(out_q - out_fp)))
    assert delta <= 0.05 * float(np.max(np.abs(out_fp))), delta
    # the convert-time self-check recorded a delta of the same order
    assert report.accuracy_delta is not None
    assert report.accuracy_delta < 1.0


def test_mixed_program_fallback_report(tmp_path):
    """A site whose activation calibrates to absmax 0 (dead input on
    the sample feed) stays fp and the report says so LOUDLY; the rest
    of the program still quantizes."""
    pt.reset()
    pt.default_startup_program().random_seed = 7
    x = pt.layers.data("x", shape=[8])
    live = pt.layers.fc(x, size=16, act="relu", name="mx_live")
    dead_in = pt.layers.scale(x, scale=0.0)  # always-zero activation
    dead = pt.layers.fc(dead_in, size=16, name="mx_dead")
    pred = pt.layers.fc(pt.layers.elementwise_add(live, dead), size=4,
                        name="mx_out")
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    d = str(tmp_path / "mixed")
    pt.io.save_inference_model(d, ["x"], [pred])

    program, feeds, fetches, scope, exe, report = _load_convert(
        d, samples=_samples(2, in_dim=8))
    assert len(report.quantized) == 2  # live + output matmul
    assert len(report.skipped) == 1
    assert "absmax 0" in report.skipped[0]["reason"]
    text = report.summary()
    assert "LEFT AT HIGHER PRECISION" in text
    assert "mixed-precision" in text
    # skipped site kept its fp op type
    types = [op.type for b in program.blocks for op in b.ops]
    assert types.count("quantized_mul") == 2
    assert types.count("mul") == 1
    # sidecar carries the skip count through save
    assert report.meta()["skipped"] == 1


def test_convert_nothing_quantizable_raises():
    """An all-fp program (no persistable 2-D weights) is an operator
    error, not a silent no-op."""
    pt.reset()
    x = pt.layers.data("x", shape=[4])
    pred = pt.layers.relu(x)
    prog = pt.default_main_program()
    calib = quant.CalibrationResult({}, 1)
    with pytest.raises(ValueError, match="no quantizable matmul"):
        quant.convert(prog, scope=pt.global_scope(), calib=calib)


def test_convert_rejects_unknown_mode(mlp_dir):
    scope = pt.Scope()
    program, _, _ = pt.io.load_inference_model(mlp_dir, scope=scope)
    calib = quant.calibrate(program, _samples(1), scope=scope)
    with pytest.raises(ValueError, match="unsupported quant mode"):
        quant.convert(program, scope=scope, calib=calib, mode="int4")


# ------------------------------------------------------- stale sidecar ----


def test_stale_program_fails_loudly(mlp_dir, tmp_path):
    """Satellite 2: editing program.json after export breaks the
    fingerprint → QuantMetaError at load, BEFORE anything serves."""
    program, feeds, fetches, scope, _, _ = _load_convert(mlp_dir)
    q_dir = str(tmp_path / "int8")
    pt.io.save_inference_model(q_dir, feeds, fetches,
                               main_program=program, scope=scope)
    p = os.path.join(q_dir, "program.json")
    with open(p) as f:
        d = json.load(f)
    for op in d["blocks"][0]["ops"]:
        if op["type"] == "quantized_mul":
            op["attrs"]["x_scale"] *= 2.0  # "retuned" by hand
            break
    with open(p, "w") as f:
        json.dump(d, f)
    with pytest.raises(QuantMetaError, match="stale"):
        pt.io.load_inference_model(q_dir, scope=pt.Scope())


def test_tampered_scales_fail_loudly(mlp_dir, tmp_path):
    """Swapping the int8/scale payload after export breaks the scales
    digest → QuantMetaError naming the mismatch."""
    program, feeds, fetches, scope, _, report = _load_convert(mlp_dir)
    q_dir = str(tmp_path / "int8")
    pt.io.save_inference_model(q_dir, feeds, fetches,
                               main_program=program, scope=scope)
    p = os.path.join(q_dir, "params.npz")
    payload = dict(np.load(p))
    sname = report.quantized[0]["w"] + "@quant_scale"
    payload[sname] = payload[sname] * 1.5
    np.savez(p, **payload)
    with pytest.raises(QuantMetaError, match="digest"):
        pt.io.load_inference_model(q_dir, scope=pt.Scope())


# ---------------------------------------------------------- tune space ----


def test_quant_tune_space_legality_property():
    """Every candidate the int8 family emits passes its own legality
    predicate, which also refuses a tile that does not divide; the
    default is always a member; tiles respect int8's (32,128) minimum
    unless they span the whole dim."""
    fam = tune_space.FAMILIES["quant_matmul"]
    shapes = [(1, 16, 8), (4, 64, 128), (8, 512, 1024), (32, 128, 96),
              (128, 1024, 2048), (256, 2048, 256), (7, 33, 130)]
    for M, K, N in shapes:
        params = fam.normalize({"M": M, "K": K, "N": N}, "int8")
        cands = fam.candidates(params)
        assert cands, (M, K, N)
        default = fam.default(params)
        assert default in cands, (M, K, N, default)
        for cfg in cands:
            bm, bn = cfg["block_m"], cfg["block_n"]
            assert M % bm == 0 and N % bn == 0, (params, cfg)
            assert bm % 32 == 0 or bm == M, (params, cfg)
            assert bn % 128 == 0 or bn == N, (params, cfg)
            assert tune_space.quant_matmul_legal(bm, bn, M, K, N)
            assert fam.legal(params, cfg)
        assert not fam.legal(params, {"block_m": M + 1, "block_n": N})


def test_quant_case_exact_all_candidates():
    """Integer contraction: every candidate tile must be EXACT vs the
    reference lowering (tol=0.0 — a fast-but-wrong tile never wins)."""
    from paddle_tpu.tune import harness

    fam = tune_space.FAMILIES["quant_matmul"]
    params = fam.normalize({"M": 64, "K": 32, "N": 256}, "int8")
    case = fam.make_case(params, "int8")
    assert case.tol == 0.0
    ref = case.reference()
    for cfg in fam.candidates(params):
        thunk = case.make(cfg)
        assert harness._numerics_ok(thunk(), ref, 0.0), cfg


def test_quant_dtype_rejected_for_other_families():
    """int8 is a quant_matmul dtype, not a blanket one — nothing stops
    normalize() on other families, but the space's DTYPES gate accepts
    it (tune CLI parity)."""
    assert "int8" in tune_space.DTYPES
    params = tune_space.FAMILIES["quant_matmul"].normalize(
        {"M": 8, "K": 8, "N": 8}, "int8")
    assert params["dtype"] == "int8"
    with pytest.raises(ValueError, match="dtype"):
        tune_space.FAMILIES["quant_matmul"].normalize(
            {"M": 8, "K": 8, "N": 8}, "fp16")


# -------------------------------------------------------------- serving ----


def test_engine_buckets_and_zero_compile_warmup(mlp_dir, tmp_path):
    """A quantized artifact through the bucketed engine: warmup
    pre-compiles every bucket, traffic is then 100% cache hits, and
    bucket padding slices away bit-exactly vs the exact-shape path."""
    program, feeds, fetches, scope, _, _ = _load_convert(mlp_dir)
    q_dir = str(tmp_path / "int8")
    pt.io.save_inference_model(q_dir, feeds, fetches,
                               main_program=program, scope=scope)
    eng = ServingEngine(q_dir, policy=BucketPolicy(max_batch_size=8),
                        model_name="tq", quantize="int8")
    oracle = ServingEngine(q_dir, model_name="tq_oracle")
    n = eng.warmup()
    assert n == len(eng.policy.batch_buckets) == eng.compiled_programs()
    before = eng.exe.cache_stats["misses"]
    rng = np.random.RandomState(3)
    for k in rng.randint(1, 9, size=12):
        xv = rng.standard_normal((k, 16)).astype(np.float32)
        got = eng.predict({"x": xv})[0]
        want = oracle.predict({"x": xv}, bucketed=False)[0]
        assert got.shape[0] == k
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert eng.exe.cache_stats["misses"] == before, \
        "quantized traffic recompiled after warmup"
    # the engine advertises the artifact's quant footprint
    s = eng.stats()
    assert s["quant"]["mode"] == "int8" and s["quant"]["sites"] == 3


def test_engine_quantize_knob_validation(mlp_dir, tmp_path):
    """quantize='int8' on an fp artifact fails loudly (pointing at the
    quant CLI); unknown modes fail; a quantized artifact also serves
    with NO knob (it's just a program)."""
    with pytest.raises(ValueError, match="paddle_tpu quant"):
        ServingEngine(mlp_dir, quantize="int8")
    with pytest.raises(ValueError, match="int8"):
        ServingEngine(mlp_dir, quantize="int4")
    program, feeds, fetches, scope, _, _ = _load_convert(mlp_dir)
    q_dir = str(tmp_path / "int8")
    pt.io.save_inference_model(q_dir, feeds, fetches,
                               main_program=program, scope=scope)
    eng = ServingEngine(q_dir)  # no knob: serves quantized anyway
    out = eng.predict({"x": _samples(1)[0]["x"]})
    assert np.asarray(out[0]).shape == (4, 8)


def test_quant_obs_gauges(mlp_dir):
    """pt_quant_* gauges appear in the unified registry after a convert
    (and not before — collector emits nothing when inactive)."""
    from paddle_tpu.obs.metrics import registry

    assert "pt_quant_sites_quantized" not in registry().render()
    _load_convert(mlp_dir)
    text = registry().render()
    assert "pt_quant_sites_quantized 3" in text
    assert "pt_quant_bytes_saved" in text
    assert "pt_quant_accuracy_delta" in text


# ------------------------------------------------ lint: hot path is cold ----

# dispatch-path functions of the quant fast path: nothing in them may
# recompute a scale (quantize_weight/act_scale are convert-time ONLY),
# call into numpy (host round-trip inside a traced kernel), or
# host-sync (.item()/.tolist()/np.asarray on traced values)
_QUANT_HOT_FNS = ("quantized_mul_kernel", "quantized_matmul_kernel",
                  "quant_matmul", "_quantize_act", "_dequant_epilogue")
_BANNED_CALLS = {"quantize_weight", "act_scale", "item", "tolist",
                 "block_until_ready"}
# np.* is banned on the hot path except static host-shape arithmetic
_NP_ALLOWED = {"prod"}


def test_quant_hot_path_zero_cost_lint():
    """Satellite 5: the AST lint of test_obs extended to the quant
    dispatch path — no per-call scale recompute, no numpy, no host
    syncs inside the traced kernels."""
    import paddle_tpu.ops.quant_kernels as mod

    with open(mod.__file__) as f:
        tree = ast.parse(f.read())
    found = set()
    for name in _QUANT_HOT_FNS:
        fns = [n for n in ast.walk(tree)
               if isinstance(n, ast.FunctionDef) and n.name == name]
        assert fns, f"{name} not found (lint is stale)"
        found.add(name)
        for fn in fns:
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                f_ = node.func
                cname = f_.id if isinstance(f_, ast.Name) else (
                    f_.attr if isinstance(f_, ast.Attribute) else None)
                assert cname not in _BANNED_CALLS, (
                    f"{name}:{node.lineno} calls {cname}() on the quant "
                    "dispatch path — scales are convert-time artifacts, "
                    "never recomputed or host-synced per call")
                if (isinstance(f_, ast.Attribute)
                        and isinstance(f_.value, ast.Name)
                        and f_.value.id == "np"):
                    assert f_.attr in _NP_ALLOWED, (
                        f"{name}:{node.lineno} calls np.{f_.attr}() in "
                        "a traced quant kernel — host numpy on the hot "
                        "path")
    assert found == set(_QUANT_HOT_FNS)
