"""Autotuner subsystem tests (paddle_tpu/tune/).

The contracts under test, in dependency order:
- space: every candidate a generator emits passes the SHARED legality
  predicate, and the runtime accepts exactly that config (the property
  that makes "tuner can never emit an illegal tile" true);
- cache: JSON table round-trips, atomic-ish save, corrupt-file
  recovery, schema-version gating, fingerprint stability;
- overrides: precedence (forced > env > table > analytic), the legacy
  PT_ATTN_BBLK env knob routed through the registry, fingerprint
  reactivity (the Executor jit-cache-key contract);
- harness: the CPU determinism guard (refuses to time off-TPU), and
  the measurement loop mechanics in interpret mode;
- golden numerics: a forced tuned config reproduces the analytic
  default path bit-for-bit (tile size partitions the batch; per-row
  math must be identical);
- io/serving: tuning provenance travels in meta.json and warmup warns
  on a stale table.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.flags import FLAGS
from paddle_tpu.tune import cache as tcache
from paddle_tpu.tune import harness, overrides, space


@pytest.fixture
def tmp_table(tmp_path):
    path = str(tmp_path / "tuned.json")
    overrides.set_table_path(path)
    yield path
    overrides.reset()


# ----------------------------------------------------------- space ------
BAHDANAU_GRID = [
    # (B, S, A, C, dtype)
    (8, 10, 128, 128, "float32"),
    (16, 60, 512, 512, "bfloat16"),
    (256, 60, 512, 512, "bfloat16"),
    (4, 7, 128, 256, "float32"),
    (2, 100, 128, 128, "bfloat16"),
    (24, 33, 256, 128, "float32"),
]


@pytest.mark.parametrize("B,S,A,C,dtype", BAHDANAU_GRID)
def test_bahdanau_candidates_all_legal(B, S, A, C, dtype):
    """Property: every emitted candidate passes the shared legality
    predicate AND is accepted verbatim by the runtime's _bblk when
    forced — no candidate can compile-fail on Mosaic tile rules."""
    from paddle_tpu.ops.bahdanau_kernels import _bblk

    Sp = space.pad_s(S)
    item = 2 if dtype == "bfloat16" else 4
    params = {"B": B, "Sp": Sp, "A": A, "C": C, "dtype": dtype}
    cands = space.bahdanau_candidates(params)
    assert cands, f"no candidates at {params}"
    for cfg in cands:
        b = cfg["bblk"]
        assert space.bahdanau_blk_legal(b, B, Sp, A, C, item), cfg
        # Mosaic divisibility rules restated independently:
        assert B % b == 0
        assert b % 8 == 0 or b == B
        with overrides.forcing("bahdanau_attention", cfg):
            assert _bblk(B, Sp, A, C, item) == b
    # the analytic default is itself in the candidate set
    default = space.bahdanau_default(params)
    assert default in cands


def test_flash_candidates_all_legal():
    for Tq, Tk in [(1024, 1024), (2048, 512), (4096, 4096), (1280, 1280)]:
        cands = space.flash_candidates({"Tq": Tq, "Tk": Tk})
        assert cands
        for cfg in cands:
            assert space.flash_block_legal(cfg["block_q"], cfg["block_k"],
                                           Tq, Tk), (cfg, Tq, Tk)
        assert space.flash_default({"Tq": Tq, "Tk": Tk}) in cands


def test_rnn_space_matches_runtime_default():
    """The fused_lstm/fused_gru default mirrors lstm_supported /
    gru_supported exactly (same measured windows + hard gates)."""
    from paddle_tpu.ops.pallas_kernels import gru_supported, lstm_supported

    prev = FLAGS.fused_rnn_interpret
    FLAGS.fused_rnn_interpret = True  # neutralize the backend gate
    try:
        for B, H in [(128, 512), (128, 384), (128, 256), (64, 1280),
                     (8, 128), (12, 128)]:
            p = {"B": B, "H": H, "dtype": "bfloat16"}
            assert space._rnn_default("lstm")(p)["fused"] == lstm_supported(
                B, H, "sigmoid", "tanh", "tanh", None, itemsize=2)
            assert space._rnn_default("gru")(p)["fused"] == gru_supported(
                B, H, "sigmoid", "tanh", itemsize=2)
    finally:
        FLAGS.fused_rnn_interpret = prev


# ----------------------------------------------------------- cache ------
def test_cache_round_trip(tmp_path):
    path = str(tmp_path / "t.json")
    t = tcache.TunedTable(path, autoload=False)
    params = {"B": 16, "Sp": 16, "A": 128, "C": 128}
    t.put("bahdanau_attention", params, "float32", {"bblk": 16},
          device="cpu", meta={"median_s": 1e-3})
    fp = t.fingerprint()
    t.save()
    t2 = tcache.TunedTable(path)
    assert t2.get("bahdanau_attention", params, "float32",
                  device="cpu") == {"bblk": 16}
    assert t2.fingerprint() == fp
    # dtype and device are key dimensions: both must miss
    assert t2.get("bahdanau_attention", params, "bfloat16",
                  device="cpu") is None
    assert t2.get("bahdanau_attention", params, "float32",
                  device="tpu-v5-lite") is None
    # a 'dtype' key inside params must not change the signature
    # (space.normalize carries it; runtime lookups don't)
    assert t2.get("bahdanau_attention", dict(params, dtype="float32"),
                  "float32", device="cpu") == {"bblk": 16}


def test_cache_corrupt_file_recovery(tmp_path):
    path = str(tmp_path / "t.json")
    with open(path, "w") as f:
        f.write('{"version": 1, "entries": {truncated')
    with pytest.warns(UserWarning, match="corrupt"):
        t = tcache.TunedTable(path)
    assert len(t) == 0
    assert os.path.exists(path + ".corrupt")
    assert not os.path.exists(path)
    # the quarantined table must not break a subsequent save/load cycle
    t.put("k", {"a": 1}, "float32", {"x": 1}, device="cpu")
    t.save()
    assert tcache.TunedTable(path).get("k", {"a": 1}, "float32",
                                       device="cpu") == {"x": 1}


def test_cache_version_mismatch_ignored(tmp_path):
    path = str(tmp_path / "t.json")
    with open(path, "w") as f:
        json.dump({"version": 999, "entries": {
            "k|a=1|float32|cpu": {"config": {"x": 1}, "meta": {}}}}, f)
    with pytest.warns(UserWarning, match="schema version"):
        t = tcache.TunedTable(path)
    assert len(t) == 0  # analytic defaults apply


def test_cache_missing_file_is_empty(tmp_path):
    t = tcache.TunedTable(str(tmp_path / "absent.json"))
    assert len(t) == 0
    assert t.get("k", {"a": 1}, "float32") is None


# ------------------------------------------------------- overrides ------
def test_override_precedence(tmp_table, monkeypatch):
    from paddle_tpu.ops.bahdanau_kernels import _bblk

    params = {"B": 16, "Sp": 16, "A": 128, "C": 128}
    # table layer
    t = overrides.table()
    t.put("bahdanau_attention", params, "float32", {"bblk": 16})
    assert _bblk(16, 16, 128, 128, 4) == 16
    # env layer beats table (legacy PT_ATTN_BBLK still honored)
    monkeypatch.setenv("PT_ATTN_BBLK", "8")
    assert _bblk(16, 16, 128, 128, 4) == 8
    # programmatic force beats env
    with overrides.forcing("bahdanau_attention", {"bblk": 16}):
        assert _bblk(16, 16, 128, 128, 4) == 16
    # flag kill-switch drops the table layer
    monkeypatch.delenv("PT_ATTN_BBLK")
    FLAGS.use_tuned_table = False
    try:
        assert _bblk(16, 16, 128, 128, 4) == 8  # analytic default
    finally:
        FLAGS.use_tuned_table = True
    assert _bblk(16, 16, 128, 128, 4) == 16


def test_flash_and_bahdanau_consult_overrides(tmp_table):
    """flash_ops._v5e_block_sizes and bahdanau_kernels._bblk consult the
    registry before their analytic defaults."""
    import jax.numpy as jnp2

    from paddle_tpu.ops.bahdanau_kernels import _bblk
    from paddle_tpu.ops.flash_ops import _v5e_block_sizes

    # analytic defaults first
    bs = _v5e_block_sizes(1024, 1024, jnp2.bfloat16)
    assert (bs.block_q, bs.block_k) == (512, 512)
    assert _bblk(64, 64, 512, 512, 2) == 8
    # tuned table entries take over
    t = overrides.table()
    t.put("flash_attention", {"Tq": 1024, "Tk": 1024}, "bfloat16",
          {"block_q": 256, "block_k": 128})
    t.put("bahdanau_attention", {"B": 64, "Sp": 64, "A": 512, "C": 512},
          "bfloat16", {"bblk": 16})
    bs = _v5e_block_sizes(1024, 1024, jnp2.bfloat16)
    assert (bs.block_q, bs.block_k) == (256, 128)
    assert _bblk(64, 64, 512, 512, 2) == 16
    # a stale flash entry (doesn't divide T) is ignored, not fatal
    t.put("flash_attention", {"Tq": 512, "Tk": 512}, "bfloat16",
          {"block_q": 768, "block_k": 768})
    bs = _v5e_block_sizes(512, 512, jnp2.bfloat16)
    assert (bs.block_q, bs.block_k) == (512, 512)
    # forced illegal batch tile warns and disables the fused path
    with overrides.forcing("bahdanau_attention", {"bblk": 12}):
        with pytest.warns(UserWarning, match="fails eligibility"):
            assert _bblk(64, 64, 512, 512, 2) == 0


def test_rnn_dispatch_consults_overrides(tmp_table):
    """The tuner's {"fused": bool} verdict overrides the measured
    H-window (but can never force an ineligible shape fused)."""
    from paddle_tpu.ops.pallas_kernels import gru_supported

    prev = FLAGS.fused_rnn_interpret
    FLAGS.fused_rnn_interpret = True
    try:
        # H=384 sits outside the GRU measured window -> scan by default
        assert not gru_supported(128, 384, "sigmoid", "tanh", itemsize=2)
        overrides.table().put("fused_gru", {"B": 128, "H": 384},
                              "bfloat16", {"fused": True})
        assert gru_supported(128, 384, "sigmoid", "tanh", itemsize=2)
        # hard illegality (B % 8) wins over any table verdict
        overrides.table().put("fused_gru", {"B": 12, "H": 384},
                              "bfloat16", {"fused": True})
        assert not gru_supported(12, 384, "sigmoid", "tanh", itemsize=2)
    finally:
        FLAGS.fused_rnn_interpret = prev


def test_forced_illegal_warns_and_disables(tmp_table):
    from paddle_tpu.ops.bahdanau_kernels import _bblk

    with overrides.forcing("bahdanau_attention", {"bblk": 3}):
        with pytest.warns(UserWarning, match="fails eligibility"):
            assert _bblk(16, 16, 128, 128, 4) == 0


def test_stale_table_entry_falls_back_to_analytic(tmp_table):
    """A shipped table must never break a model: an entry that fails
    legality at lookup time (schema drift, hand-edit) is ignored."""
    from paddle_tpu.ops.bahdanau_kernels import _bblk

    params = {"B": 16, "Sp": 16, "A": 128, "C": 128}
    overrides.table().put("bahdanau_attention", params, "float32",
                          {"bblk": 3})  # not a legal tile for B=16
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")  # and it must not warn either
        assert _bblk(16, 16, 128, 128, 4) == 8


def test_env_knob_still_warns_when_illegal(tmp_table, monkeypatch):
    from paddle_tpu.ops.bahdanau_kernels import _bblk

    monkeypatch.setenv("PT_ATTN_BBLK", "6")
    with pytest.warns(UserWarning, match="fails eligibility"):
        assert _bblk(16, 16, 128, 128, 4) == 0


def test_fingerprint_reacts_to_every_source(tmp_table, monkeypatch):
    fp0 = overrides.fingerprint()
    # forced config
    overrides.force("bahdanau_attention", {"bblk": 16})
    fp1 = overrides.fingerprint()
    assert fp1 != fp0
    overrides.force("bahdanau_attention", None)
    assert overrides.fingerprint() == fp0
    # legacy env knob
    monkeypatch.setenv("PT_ATTN_BBLK", "8")
    assert overrides.fingerprint() != fp0
    monkeypatch.delenv("PT_ATTN_BBLK")
    # table content
    overrides.table().put("fused_lstm", {"B": 128, "H": 512},
                          "bfloat16", {"fused": True})
    assert overrides.fingerprint() != fp0
    # flag
    FLAGS.use_tuned_table = False
    try:
        fp_off = overrides.fingerprint()
    finally:
        FLAGS.use_tuned_table = True
    assert fp_off not in (fp0, overrides.fingerprint())


def test_executor_retraces_on_override_change(tmp_table):
    """The jit-cache-key contract: flipping a kernel knob re-traces
    (one new miss) instead of reusing the stale compiled program."""
    x = pt.layers.data("x", shape=[4])
    y = pt.layers.fc(x, size=4)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    feed = {"x": np.zeros((2, 4), np.float32)}
    exe.run(feed=feed, fetch_list=[y])
    misses0 = exe.cache_stats["misses"]
    exe.run(feed=feed, fetch_list=[y])
    assert exe.cache_stats["misses"] == misses0  # warm hit
    overrides.force("bahdanau_attention", {"bblk": 4})
    exe.run(feed=feed, fetch_list=[y])
    assert exe.cache_stats["misses"] == misses0 + 1  # knob -> re-trace


@pytest.mark.parametrize("flag", [
    "use_fused_rnn", "fused_rnn_interpret", "use_fused_attention",
    "fused_attention_interpret", "use_fused_conv"])
def test_trace_key_follows_every_kernel_flag(monkeypatch, flag):
    """The other half of the contract: each flag that picks a kernel at
    trace time is part of the key, so flipping it on a live Executor
    re-traces."""
    prog = pt.default_main_program()
    key = pt.Executor._program_trace_key(prog)
    monkeypatch.setattr(FLAGS, flag, not getattr(FLAGS, flag))
    assert pt.Executor._program_trace_key(prog) != key


# --------------------------------------------------------- harness ------
def test_harness_refuses_to_time_off_tpu():
    assert jax.default_backend() != "tpu"  # the suite's invariant
    with pytest.raises(harness.TuningUnavailable):
        harness.ensure_timeable()
    with pytest.raises(harness.TuningUnavailable):
        harness.tune_case("bahdanau", {"B": 8, "Sp": 16, "A": 128,
                                       "C": 128}, "float32")


def test_harness_loop_mechanics_interpret(tmp_table):
    """The measurement loop itself (candidate sweep, numeric
    cross-check, table write) exercised in interpret mode with the TPU
    requirement waived — production entry points keep require_tpu."""
    t = overrides.table()
    rep = harness.tune_case("bahdanau", {"B": 16, "Sp": 16, "A": 128,
                                         "C": 128}, "float32",
                            table=t, iters=2, warmup=1, require_tpu=False)
    assert {r["config"]["bblk"] for r in rep["rows"]} == {8, 16}
    assert all(r["numerics_ok"] for r in rep["rows"])
    assert rep["best"] in [r["config"] for r in rep["rows"]]
    assert rep["default"] == {"bblk": 8}
    # the winner landed in the table under the runtime's lookup key
    assert t.get("bahdanau_attention",
                 {"B": 16, "Sp": 16, "A": 128, "C": 128},
                 "float32") == rep["best"]


def test_stat_median_of_k():
    from paddle_tpu.profiler import StatSet

    s = StatSet(keep_samples=5)
    for v in (0.5, 0.01, 0.02, 0.03, 100.0):
        s.get("t").add(v)
    assert s.get("t").median == 0.03  # outliers shrugged off
    # default StatSet keeps the zero-overhead aggregate behavior
    s2 = StatSet()
    s2.get("t").add(1.0)
    assert s2.get("t").samples is None
    assert s2.get("t").median == 1.0  # falls back to avg


# -------------------------------------------------- golden numerics ------
@pytest.fixture
def interpret_flag():
    FLAGS.fused_attention_interpret = True
    yield
    FLAGS.fused_attention_interpret = False


def _decoder_inputs(B=16, S=10, T=4, E=128, C=128, A=128, H=128):
    rng = np.random.RandomState(7)
    f32 = jnp.float32
    enc_b = jnp.asarray(rng.randn(B, S, C) * 0.3, f32)
    enc_proj = jnp.asarray(rng.randn(B, S, A) * 0.3, f32)
    lens = rng.randint(S // 2, S + 1, (B,))
    enc_mask = jnp.asarray(np.arange(S)[None, :] < lens[:, None])
    trg_b = jnp.asarray(rng.randn(T, B, E) * 0.3, f32)
    trg_mask = jnp.ones((T, B), f32)
    h0 = jnp.asarray(rng.randn(B, H) * 0.1, f32)
    wa_dec = jnp.asarray(rng.randn(H, A) / np.sqrt(H), f32)
    v_att = jnp.asarray(rng.randn(A) / np.sqrt(A), f32)
    wx = jnp.asarray(rng.randn(E + C, 3 * H) / np.sqrt(E + C), f32)
    wh = jnp.asarray(rng.randn(H, 3 * H) / np.sqrt(H), f32)
    bias = jnp.asarray(rng.randn(3 * H) * 0.05, f32)
    return (enc_b, enc_proj, enc_mask, trg_b, trg_mask, h0, wa_dec,
            v_att, wx, wh, bias)


def test_forced_tuned_config_bit_identical(interpret_flag, tmp_table):
    """Golden numerics: a tuned tile (bblk=16) partitions the batch
    differently but must reproduce the analytic default (bblk=8)
    BIT-FOR-BIT for the forward and every per-row gradient — per-row
    math is tile-invariant. The one principled exception is d(v): its
    reduction crosses batch tiles, so the tile size changes the f32
    summation ORDER (2 partial sums at bblk=8 vs 1 at bblk=16) — that
    gradient is pinned to f32-rounding tightness instead. This is the
    guarantee that lets a tuned table ship without a numerics
    qualification run."""
    from paddle_tpu.ops.bahdanau_kernels import (_bblk,
                                                 fused_attention_decoder)

    args = _decoder_inputs()

    def loss(enc_proj, v_att):
        a = list(args)
        a[1], a[7] = enc_proj, v_att
        return jnp.sum(fused_attention_decoder(*a) ** 2)

    grad_fn = jax.grad(loss, argnums=(0, 1))

    assert _bblk(16, 16, 128, 128, 4) == 8  # analytic default engaged
    h_default = np.asarray(fused_attention_decoder(*args))
    g_default = [np.asarray(g) for g in grad_fn(args[1], args[7])]

    with overrides.forcing("bahdanau_attention", {"bblk": 16}):
        assert _bblk(16, 16, 128, 128, 4) == 16  # tuned tile engaged
        h_tuned = np.asarray(fused_attention_decoder(*args))
        g_tuned = [np.asarray(g) for g in grad_fn(args[1], args[7])]

    np.testing.assert_array_equal(h_tuned, h_default)
    np.testing.assert_array_equal(g_tuned[0], g_default[0])  # d(enc_proj)
    np.testing.assert_allclose(g_tuned[1], g_default[1],     # d(v)
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------------- io/serving ------
def _save_tiny_model(tmp_path):
    x = pt.layers.data("x", shape=[4])
    y = pt.layers.fc(x, size=2, act="softmax")
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    model_dir = str(tmp_path / "model")
    pt.io.save_inference_model(model_dir, ["x"], [y])
    return model_dir


def test_meta_json_records_tuning_provenance(tmp_path, tmp_table):
    model_dir = _save_tiny_model(tmp_path)
    with open(os.path.join(model_dir, "meta.json")) as f:
        meta = json.load(f)
    assert meta["tuning"]["device_kind"] == tcache.device_kind()
    assert meta["tuning"]["table_fingerprint"] == \
        overrides.table().fingerprint()


def test_serving_warmup_warns_on_stale_table(tmp_path, tmp_table):
    from paddle_tpu.serving import ServingEngine

    model_dir = _save_tiny_model(tmp_path)
    engine = ServingEngine(model_dir)
    # provenance matches (same process, same table): no warning
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        assert engine.check_tuned_table()
    # the serving host's table changes (retune without re-export):
    overrides.table().put("fused_lstm", {"B": 128, "H": 512},
                          "bfloat16", {"fused": True})
    with pytest.warns(UserWarning, match="stale"):
        assert not engine.check_tuned_table()
    # pre-tuner artifact (no provenance recorded): silently fine
    engine.tuning_meta = None
    with _w.catch_warnings():
        _w.simplefilter("error")
        assert engine.check_tuned_table()


# ------------------------------------------------------ model sweep ------
def test_cases_from_program_finds_flash_sites():
    q = pt.layers.data("q", shape=[1024, 256])
    k = pt.layers.data("k", shape=[1024, 256])
    v = pt.layers.data("v", shape=[1024, 256])
    pt.layers.multi_head_attention(q, k, v, num_heads=2, causal=False)
    sites = space.cases_from_program()
    flash = [s for s in sites if s["family"] == "flash_attention"]
    assert flash and flash[0]["params"] == {"Tq": 1024, "Tk": 1024}


def test_resnet_sweep_has_no_case_for_a_kernel_it_will_not_run():
    """The model sweep tunes what the program dispatches, nothing else:
    ResNet-50's fused_conv_bn ops run as XLA convolutions with no tile
    to choose, so they yield no case, and every case the sweep does
    yield names a family the tuner lists."""
    from paddle_tpu import models

    x = pt.layers.data("img", shape=[224, 224, 3])
    models.resnet_imagenet(x, class_dim=10, data_format="NHWC")
    ops = [op.type for op in pt.default_main_program().global_block().ops]
    assert "fused_conv_bn" in ops
    sites = space.cases_from_program()
    assert not [s for s in sites if s["op"] == "fused_conv_bn"], sites
    assert {s["family"] for s in sites} <= set(space.FAMILIES)


def test_every_listed_family_is_consulted_at_default_flags(monkeypatch,
                                                           tmp_table):
    """A family the tuner lists but no dispatch consults is a kernel
    that never runs being tuned. Each op's own eligibility predicate or
    tile picker runs here with every flag at its default (only the
    backend gate is neutralised: it is the platform, not an option), and
    the registry must see a consult for exactly the families listed."""
    from paddle_tpu.ops import (bahdanau_kernels, flash_ops, pallas_kernels,
                                quant_kernels)

    seen = set()
    real_lookup = overrides.lookup

    def lookup(kernel, *args, **kwargs):
        seen.add(kernel)
        return real_lookup(kernel, *args, **kwargs)

    monkeypatch.setattr(overrides, "lookup", lookup)
    monkeypatch.setattr(pallas_kernels, "backend_ok", lambda flag: True)
    assert bahdanau_kernels.fused_decoder_eligible(
        64, 50, 512, 512, jnp.bfloat16)
    flash_ops._v5e_block_sizes(1024, 1024, jnp.bfloat16)
    assert pallas_kernels.lstm_supported(
        128, 512, "sigmoid", "tanh", "tanh", None, itemsize=2)
    assert pallas_kernels.gru_supported(
        128, 512, "sigmoid", "tanh", itemsize=2)
    jax.eval_shape(quant_kernels.quant_matmul,
                   jax.ShapeDtypeStruct((256, 512), jnp.int8),
                   jax.ShapeDtypeStruct((512, 512), jnp.int8))
    assert seen == set(space.FAMILIES)


def _build_decoder_program(B=16, C=32, A=24, S=8):
    enc = pt.layers.data("enc", shape=[B, S, C], append_batch_size=False,
                         lod_level=1)
    trg = pt.layers.data("trg", shape=[B, 6], append_batch_size=False,
                         lod_level=1)
    boot = pt.layers.data("boot", shape=[B, A], append_batch_size=False)
    pt.layers.attention_gru_decoder(enc, trg, boot, size=A,
                                    src_max_len=S, trg_max_len=S)


def test_cases_from_program_mesh_local_batch():
    """ISSUE-10 tentpole (d): under a dp mesh the fused kernels
    dispatch at the PER-SHARD batch (mesh_dispatch.local_batch), so the
    sweep must key tuning cases on B/dp — and skip sites dp does not
    divide (the runtime scans there; a global-batch entry would tune a
    shape that never dispatches)."""
    _build_decoder_program(B=16)
    bah = [s for s in space.cases_from_program()
           if s["family"] == "bahdanau_attention"]
    assert bah and bah[0]["params"]["B"] == 16
    bah4 = [s for s in space.cases_from_program(dp=4)
            if s["family"] == "bahdanau_attention"]
    assert bah4 and bah4[0]["params"]["B"] == 4
    # everything but the batch is shard-invariant
    assert {k: v for k, v in bah4[0]["params"].items() if k != "B"} == \
        {k: v for k, v in bah[0]["params"].items() if k != "B"}
    # non-divisible dp: the site is skipped, not mis-keyed
    assert not [s for s in space.cases_from_program(dp=3)
                if s["family"] == "bahdanau_attention"]
    # flash keys on sequence lengths only — dp leaves it untouched
    pt.reset()
    q = pt.layers.data("q", shape=[1024, 256])
    pt.layers.multi_head_attention(q, num_heads=2, causal=False)
    f1 = [s for s in space.cases_from_program()
          if s["family"] == "flash_attention"]
    f4 = [s for s in space.cases_from_program(dp=4)
          if s["family"] == "flash_attention"]
    assert f1 and [s["params"] for s in f1] == [s["params"] for s in f4]


# ===================================================== Autotuner v2 ======
# -------------------------------------------------- shape interpolation --
def _put_cpu(t, fam, params, dtype, cfg, **meta_kw):
    t.put(fam, params, dtype, cfg, **meta_kw)


def test_consult_order_forced_env_exact_interpolated_analytic(
        tmp_table, monkeypatch):
    """THE v2 precedence chain, one layer peeled off at a time."""
    params = {"B": 16, "Sp": 16, "A": 128, "C": 128}
    near = {"B": 32, "Sp": 16, "A": 128, "C": 128}
    t = overrides.table()
    t.put("bahdanau_attention", near, "float32", {"bblk": 8})
    t.put("bahdanau_attention", params, "float32", {"bblk": 16})
    monkeypatch.setenv("PT_ATTN_BBLK", "4")
    with overrides.forcing("bahdanau_attention", {"bblk": 2}):
        ov = overrides.lookup("bahdanau_attention", params, "float32")
        assert (ov.config, ov.source) == ({"bblk": 2}, "forced")
    ov = overrides.lookup("bahdanau_attention", params, "float32")
    assert (ov.config, ov.source) == ({"bblk": 4}, "env")
    monkeypatch.delenv("PT_ATTN_BBLK")
    ov = overrides.lookup("bahdanau_attention", params, "float32")
    assert (ov.config, ov.source) == ({"bblk": 16}, "table")
    # drop the exact entry -> nearest neighbor (B=32, one octave away)
    t.entries.pop(tcache.entry_key(
        "bahdanau_attention", tcache.make_sig(params), "float32",
        tcache.device_kind()))
    t._lru.clear()
    t._fp = None
    ov = overrides.lookup("bahdanau_attention", params, "float32")
    assert (ov.config, ov.source) == ({"bblk": 8}, "interpolated")
    assert ov.origin == tcache.make_sig(near)
    # interpolation off -> analytic (None)
    FLAGS.tune_interpolate = False
    try:
        assert overrides.lookup("bahdanau_attention", params,
                                "float32") is None
    finally:
        FLAGS.tune_interpolate = True
    # empty pool -> analytic
    t.entries.clear()
    t._lru.clear()
    t._fp = None
    assert overrides.lookup("bahdanau_attention", params, "float32") is None


INTERP_TARGETS = [
    # neighbors whose configs are NOT legal at the target must be
    # rejected by the re-check, never returned
    ({"B": 16, "Sp": 16, "A": 128, "C": 128}, "float32"),
    ({"B": 24, "Sp": 32, "A": 128, "C": 128}, "float32"),
    ({"B": 8, "Sp": 16, "A": 128, "C": 128}, "bfloat16"),
    ({"B": 48, "Sp": 48, "A": 256, "C": 128}, "bfloat16"),
    ({"B": 128, "Sp": 64, "A": 512, "C": 512}, "bfloat16"),
]


def test_interpolated_config_always_legal_property(tmp_table):
    """Property (ISSUE-10 acceptance): whatever is in the neighbor
    pool, an interpolated consult either returns a config that passes
    space.config_legal for the TARGET shape, or returns nothing. The
    pool deliberately mixes legal tiles, tiles only legal at their own
    shape (bblk=32/64), and garbage."""
    t = overrides.table()
    pool = [
        ({"B": 32, "Sp": 16, "A": 128, "C": 128}, "float32", {"bblk": 32}),
        ({"B": 64, "Sp": 16, "A": 128, "C": 128}, "float32", {"bblk": 64}),
        ({"B": 32, "Sp": 32, "A": 128, "C": 128}, "float32", {"bblk": 8}),
        ({"B": 16, "Sp": 32, "A": 128, "C": 128}, "bfloat16", {"bblk": 8}),
        ({"B": 64, "Sp": 64, "A": 256, "C": 128}, "bfloat16", {"bblk": 8}),
        ({"B": 96, "Sp": 64, "A": 512, "C": 512}, "bfloat16", {"bblk": 8}),
        ({"B": 32, "Sp": 16, "A": 128, "C": 128}, "float32",
         {"bogus": "x"}),
    ]
    for p, dt, cfg in pool:
        t.put("bahdanau_attention", p, dt, cfg)
    from paddle_tpu.ops.bahdanau_kernels import _bblk

    for params, dtype in INTERP_TARGETS:
        ov = overrides.lookup("bahdanau_attention", params, dtype)
        if ov is not None and ov.source == "interpolated":
            assert space.config_legal("bahdanau_attention", params,
                                      dtype, ov.config), (params, ov)
        # and the runtime consult can never produce an illegal tile:
        item = 2 if dtype == "bfloat16" else 4
        b = _bblk(params["B"], params["Sp"], params["A"], params["C"],
                  item)
        if b:
            assert space.bahdanau_blk_legal(
                b, params["B"], params["Sp"], params["A"], params["C"],
                item)


def test_interpolation_rejects_illegal_neighbor_falls_to_analytic(
        tmp_table):
    """The NEAREST neighbor's config is illegal at the target (bblk=32
    does not divide B=24): the re-check must skip it and take the next
    legal neighbor; with no other neighbor, analytic (None)."""
    t = overrides.table()
    target = {"B": 24, "Sp": 16, "A": 128, "C": 128}
    t.put("bahdanau_attention", {"B": 32, "Sp": 16, "A": 128, "C": 128},
          "float32", {"bblk": 32})  # nearest, illegal at B=24
    assert overrides.lookup("bahdanau_attention", target,
                            "float32") is None
    t.put("bahdanau_attention", {"B": 48, "Sp": 16, "A": 128, "C": 128},
          "float32", {"bblk": 8})  # farther, legal at B=24
    overrides.reload_table()  # drop the memoized miss
    t = overrides.table()
    t.put("bahdanau_attention", {"B": 32, "Sp": 16, "A": 128, "C": 128},
          "float32", {"bblk": 32})
    t.put("bahdanau_attention", {"B": 48, "Sp": 16, "A": 128, "C": 128},
          "float32", {"bblk": 8})
    ov = overrides.lookup("bahdanau_attention", target, "float32")
    assert ov is not None and ov.source == "interpolated"
    assert ov.config == {"bblk": 8}


def test_interpolation_respects_distance_cap(tmp_table):
    """A donor beyond INTERP_MAX_DIST (B=128 vs B=8 is ~2.8 octaves =
    ln(16) > 1.5) must not transfer — far shapes have different tile
    economics and the analytic default is the better guess."""
    t = overrides.table()
    t.put("bahdanau_attention", {"B": 128, "Sp": 16, "A": 128, "C": 128},
          "float32", {"bblk": 8})
    assert overrides.lookup(
        "bahdanau_attention", {"B": 8, "Sp": 16, "A": 128, "C": 128},
        "float32") is None


def test_runtime_consult_uses_interpolated_tile(tmp_table):
    """End to end through the kernel's own consult point: _bblk at an
    untuned shape picks up the neighbor's tile when legal (and the
    golden-numerics test already proves any legal tile is
    bit-identical)."""
    from paddle_tpu.ops.bahdanau_kernels import _bblk

    t = overrides.table()
    t.put("bahdanau_attention", {"B": 32, "Sp": 16, "A": 128, "C": 128},
          "float32", {"bblk": 16})
    # B=16: tile 16 is legal (spans nothing illegal) -> interpolated win
    assert _bblk(16, 16, 128, 128, 4) == 16
    st = overrides.consult_stats()
    assert st["interpolated"] >= 1


# ------------------------------------------------- fleet database --------
def test_merge_precedence_measured_beats_interpolated_then_newer():
    measured_old = {"config": {"bblk": 8},
                    "meta": {"provenance": "measured", "updated_at": 100}}
    measured_new = {"config": {"bblk": 16},
                    "meta": {"provenance": "measured", "updated_at": 200}}
    interp_newer = {"config": {"bblk": 4},
                    "meta": {"provenance": "interpolated",
                             "updated_at": 999}}
    legacy = {"config": {"bblk": 2}, "meta": {}}
    # measured beats interpolated regardless of age
    assert tcache.merge_entry(measured_old, interp_newer) is measured_old
    assert tcache.merge_entry(interp_newer, measured_old) is measured_old
    # same provenance: newest wins; ties keep the incumbent
    assert tcache.merge_entry(measured_old, measured_new) is measured_new
    assert tcache.merge_entry(measured_new, measured_old) is measured_new
    assert tcache.merge_entry(measured_old, measured_old) is measured_old
    # anything beats a legacy no-provenance entry
    assert tcache.merge_entry(legacy, interp_newer) is interp_newer
    assert tcache.merge_entry(interp_newer, legacy) is interp_newer
    # absent incumbent: theirs
    assert tcache.merge_entry(None, legacy) is legacy


def test_table_merge_from_stats(tmp_path):
    a = tcache.TunedTable(str(tmp_path / "a.json"), autoload=False)
    b = tcache.TunedTable(str(tmp_path / "b.json"), autoload=False)
    p1, p2, p3 = ({"B": 8, "H": 128}, {"B": 16, "H": 128},
                  {"B": 32, "H": 128})
    a.put("fused_gru", p1, "bfloat16", {"fused": True},
          device="d", meta={"provenance": "measured", "updated_at": 10})
    a.put("fused_gru", p2, "bfloat16", {"fused": True},
          device="d", meta={"provenance": "interpolated",
                            "updated_at": 10})
    b.put("fused_gru", p1, "bfloat16", {"fused": False},
          device="d", meta={"provenance": "interpolated",
                            "updated_at": 99})   # loses: interp vs measured
    b.put("fused_gru", p2, "bfloat16", {"fused": False},
          device="d", meta={"provenance": "measured", "updated_at": 5})
    b.put("fused_gru", p3, "bfloat16", {"fused": True},
          device="d", meta={"provenance": "measured", "updated_at": 5})
    st = a.merge_from(b)
    assert st == {"added": 1, "replaced": 1, "kept": 1}
    assert a.get("fused_gru", p1, "bfloat16", device="d") == {"fused": True}
    assert a.get("fused_gru", p2, "bfloat16", device="d") == {
        "fused": False}
    assert a.get("fused_gru", p3, "bfloat16", device="d") == {"fused": True}


def test_export_import_round_trip_bit_identical(tmp_path):
    """export -> import into empty -> export again: BYTE-identical
    files (the fleet exchange contract: moving a table through a
    colleague's machine must not mutate it)."""
    src = tcache.TunedTable(str(tmp_path / "src.json"), autoload=False)
    src.put("bahdanau_attention", {"B": 256, "Sp": 64, "A": 512,
                                   "C": 512},
            "bfloat16", {"bblk": 8}, device="tpu-v5-lite",
            meta={"provenance": "measured", "updated_at": 123,
                  "median_s": 3.2e-4})
    src.put("flash_attention", {"Tq": 2048, "Tk": 2048}, "bfloat16",
            {"block_q": 512, "block_k": 512}, device="tpu-v5-lite",
            meta={"provenance": "measured", "updated_at": 124})
    exp1 = str(tmp_path / "exp1.json")
    src.save(exp1)
    mid = tcache.TunedTable(str(tmp_path / "mid.json"), autoload=False)
    mid.merge_from(tcache.load_strict(exp1))
    exp2 = str(tmp_path / "exp2.json")
    mid.save(exp2)
    with open(exp1, "rb") as f1, open(exp2, "rb") as f2:
        assert f1.read() == f2.read()
    assert mid.fingerprint() == src.fingerprint()


def test_import_schema_version_gated(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"version": 999, "entries": {}}))
    with pytest.raises(tcache.TableFormatError, match="schema version"):
        tcache.load_strict(str(bad))
    trunc = tmp_path / "trunc.json"
    trunc.write_text('{"version": 1, "entries": {oops')
    with pytest.raises(tcache.TableFormatError, match="not JSON"):
        tcache.load_strict(str(trunc))
    malformed = tmp_path / "mal.json"
    malformed.write_text(json.dumps(
        {"version": 1, "entries": {"k": {"config": 7}}}))
    with pytest.raises(tcache.TableFormatError, match="malformed"):
        tcache.load_strict(str(malformed))


def test_base_table_read_through(tmp_table, tmp_path, monkeypatch):
    """A shipped per-device base table is consulted beneath the local
    table: base-only keys hit (source "table"), a local entry shadows
    the base one, and the base feeds the interpolation pool. The
    overrides fingerprint must react to the base layer (jit-cache-key
    contract)."""
    base_dir = tmp_path / "tables"
    base_dir.mkdir()
    base = tcache.TunedTable(
        str(base_dir / f"{tcache.device_kind()}.json"), autoload=False)
    pA = {"B": 64, "Sp": 16, "A": 128, "C": 128}
    pB = {"B": 32, "Sp": 16, "A": 128, "C": 128}
    base.put("bahdanau_attention", pA, "float32", {"bblk": 64},
             provenance="measured")
    base.put("bahdanau_attention", pB, "float32", {"bblk": 32},
             provenance="measured")
    base.save()
    fp_nobase = overrides.fingerprint()
    monkeypatch.setenv("PT_TUNE_TABLES_DIR", str(base_dir))
    overrides.reload_table()
    assert overrides.fingerprint() != fp_nobase
    # base-only key: read-through hit
    ov = overrides.lookup("bahdanau_attention", pA, "float32")
    assert (ov.config, ov.source) == ({"bblk": 64}, "table")
    # local entry shadows the base layer
    overrides.table().put("bahdanau_attention", pA, "float32",
                          {"bblk": 8})
    ov = overrides.lookup("bahdanau_attention", pA, "float32")
    assert ov.config == {"bblk": 8}
    # base entries seed interpolation for nearby shapes (B=16 target:
    # nearest donor is pB at one octave; its bblk=32 is illegal at
    # B=16 -> next duty falls to the legal local bblk=8 at pA)
    ov = overrides.lookup(
        "bahdanau_attention", {"B": 16, "Sp": 16, "A": 128, "C": 128},
        "float32")
    assert ov is not None and ov.source == "interpolated"
    assert space.config_legal(
        "bahdanau_attention", {"B": 16, "Sp": 16, "A": 128, "C": 128},
        "float32", ov.config)


def test_shipped_v5lite_base_table_is_valid():
    """The table the package actually ships: loads strict (current
    schema), every entry is keyed for tpu-v5-lite with measured
    provenance, and every config passes its OWN shape's legality —
    shipping can never hand any device an illegal tile, and on CPU
    (device_kind 'cpu') it is never even consulted."""
    path = os.path.join(os.path.dirname(space.__file__), "tables",
                        "tpu-v5-lite.json")
    t = tcache.load_strict(path)
    assert len(t) >= 20
    for key, e in t.entries.items():
        kernel, sig, dtype, device = tcache.parse_key(key)
        assert device == "tpu-v5-lite"
        assert e["meta"]["provenance"] == "measured"
        params = tcache.sig_to_params(sig)
        assert space.config_legal(kernel, params, dtype, e["config"]), key
    # and the default CPU base-table resolution ignores it
    assert tcache.base_table_path() is None


# ------------------------------------------------ provenance counters ----
def test_consult_counters_and_metrics_export(tmp_table):
    pt.reset()  # zero the counters
    overrides.set_table_path(tmp_table)
    t = overrides.table()
    params = {"B": 16, "Sp": 16, "A": 128, "C": 128}
    assert overrides.lookup("bahdanau_attention", params,
                            "float32") is None  # analytic
    t.put("bahdanau_attention", params, "float32", {"bblk": 8})
    overrides.lookup("bahdanau_attention", params, "float32")  # table
    t.put("bahdanau_attention", {"B": 32, "Sp": 16, "A": 128, "C": 128},
          "float32", {"bblk": 8})
    overrides.lookup("bahdanau_attention",
                     {"B": 64, "Sp": 16, "A": 128, "C": 128},
                     "float32")  # interpolated (B=32 donor, legal)
    with overrides.forcing("bahdanau_attention", {"bblk": 8}):
        overrides.lookup("bahdanau_attention", params, "float32")
    st = overrides.consult_stats()
    assert st["analytic"] >= 1 and st["table"] >= 1
    assert st["interpolated"] >= 1 and st["forced"] >= 1
    # the unified registry renders every source label, 0s included
    from paddle_tpu.obs import metrics as obs_metrics
    from paddle_tpu.obs import promparse

    text = obs_metrics.registry().render()
    fams = promparse.parse_text(text)
    series = {lb["source"]: v for _, lb, v in
              fams["pt_tune_consults_total"].samples}
    assert set(series) == {"forced", "env", "table", "interpolated",
                           "analytic"}
    assert series["env"] == 0
    assert series["interpolated"] >= 1
    # classify() must NOT move the counters (warmup coverage contract)
    before = overrides.consult_stats()
    overrides.classify("bahdanau_attention", params, "float32")
    assert overrides.consult_stats() == before


def test_engine_decode_tune_cases_mesh_local(tmp_path, tmp_table):
    """ISSUE-10 tentpole (d), serving side: a mesh replica's decode
    tune cases key on the PER-SHARD batch (bucket/dp), and buckets the
    dp axis does not divide are skipped — mirroring what the fused
    kernels actually dispatch inside shard_map."""
    from paddle_tpu.parallel import mesh_from_spec
    from paddle_tpu.serving import BucketPolicy, ServingEngine

    enc = pt.layers.data("enc", shape=[8, 8, 128],
                         append_batch_size=False, lod_level=1)
    trg = pt.layers.data("trg", shape=[8, 6], append_batch_size=False,
                         lod_level=1)
    boot = pt.layers.data("boot", shape=[8, 128],
                          append_batch_size=False)
    dec = pt.layers.attention_gru_decoder(enc, trg, boot, size=128,
                                          src_max_len=8, trg_max_len=8)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    d = str(tmp_path / "dec_model")
    pt.io.save_inference_model(d, ["enc", "trg", "boot"], [dec])

    pol = BucketPolicy(max_batch_size=4, batch_buckets=(2, 4))
    single = ServingEngine(d, policy=pol)
    b_single = sorted(c["params"]["B"] for c in single.decode_tune_cases()
                      if c["family"] == "bahdanau_attention")
    assert b_single == [2, 4]  # the bucket grid itself, K=1
    meshed = ServingEngine(d, policy=pol, mesh=mesh_from_spec("dp2"))
    b_mesh = sorted(c["params"]["B"] for c in meshed.decode_tune_cases()
                    if c["family"] == "bahdanau_attention")
    assert b_mesh == [1, 2]  # per-shard: bucket/dp
    # coverage classification keys on the same per-shard shapes
    # (Sp = pad_s(8) = 16; B=4 is the program's own concrete-batch site
    # 8/dp — also per-shard via cases_from_program(dp=2))
    sigs = {c["sig"] for c in meshed.tune_coverage()
            if c["family"] == "bahdanau_attention"}
    assert sigs == {"A=128,B=1,C=128,Sp=16", "A=128,B=2,C=128,Sp=16",
                    "A=128,B=4,C=128,Sp=16"}


# ------------------------------------------- warmup coverage report ------
def test_serving_warmup_names_untuned_and_interpolated(tmp_path,
                                                       tmp_table):
    """The upgraded stale-table warning: names WHICH kernels/shapes are
    untuned vs interpolated and gives the actionable tune command."""
    from paddle_tpu.serving import ServingEngine

    q = pt.layers.data("q", shape=[1024, 256])
    out = pt.layers.multi_head_attention(q, num_heads=2, causal=False)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    model_dir = str(tmp_path / "model")
    pt.io.save_inference_model(model_dir, ["q"], [out])
    engine = ServingEngine(model_dir)
    # make provenance stale so the warning fires
    overrides.table().put("fused_lstm", {"B": 128, "H": 512},
                          "bfloat16", {"fused": True})
    with pytest.warns(UserWarning) as rec:
        assert not engine.check_tuned_table()
    msg = "\n".join(str(w.message) for w in rec)
    assert "untuned (analytic defaults)" in msg
    assert "flash_attention[Tk=1024,Tq=1024" in msg
    assert "paddle_tpu tune" in msg
    # tune the shape's neighbor -> same site reports interpolated
    overrides.table().put("flash_attention", {"Tq": 2048, "Tk": 2048},
                          "float32", {"block_q": 512, "block_k": 512})
    cov = engine.tune_coverage()
    flash = [c for c in cov if c["family"] == "flash_attention"]
    assert flash and flash[0]["source"] == "interpolated"
    assert flash[0]["origin"] == "Tk=2048,Tq=2048"
    with pytest.warns(UserWarning) as rec:
        engine.check_tuned_table()
    msg = "\n".join(str(w.message) for w in rec)
    assert "interpolated from nearby shapes" in msg
    # exact-tune the shape -> coverage goes clean, warning loses it
    overrides.table().put("flash_attention", {"Tq": 1024, "Tk": 1024},
                          "float32", {"block_q": 512, "block_k": 512})
    cov = engine.tune_coverage()
    flash = [c for c in cov if c["family"] == "flash_attention"]
    assert flash and flash[0]["source"] == "table"
