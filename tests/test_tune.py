"""Kernel tile selection tests (paddle_tpu/tune/).

The contracts under test:
- space: every candidate a generator emits passes the family's legality
  predicate and the runtime accepts exactly that config when it is
  forced; `space.pick` is the one choice: the forced config where it is
  legal, a warning and the family's "off" where it is not, else the
  default rule;
- the retired tuned table: every row it shipped is what the rule picks
  (the deletion changed no pick), every benchmark cell gets the
  parent's flash blocks, and nothing outside the program (a file in the
  user's home, an environment variable) steers a kernel or a trace key;
- overrides: forced_key() is () with nothing forced, reads no file, and
  a change of a forced config re-traces a live Executor;
- harness: refuses to time off a TPU; the sweep's loop mechanics in
  interpret mode;
- golden numerics: a forced tile reproduces the default's results
  bit-for-bit (tile size partitions the batch; per-row math must be
  identical);
- io/serving: an artifact that carries an older exporter's `tuning`
  block still loads and serves.
"""

import builtins
import glob
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.flags import FLAGS
from paddle_tpu.tune import harness, overrides, space

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tmp_table():
    """Forced configs do not outlive the test. (Named for the table file
    it pointed at before PR 48; test_executor_retraces_on_override_change
    keeps the name so that it reads as it did then.)"""
    yield
    overrides.reset()


@pytest.fixture
def rnn_backend_on(monkeypatch):
    """The backend gate is the platform, not a choice of tile."""
    monkeypatch.setattr(FLAGS, "fused_rnn_interpret", True)


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


def test_rnn_space_matches_runtime_default(rnn_backend_on):
    """The measured windows and the hard gates, through the dispatch the
    ops call: LSTM fuses for 384 <= H <= 1280, GRU for 128 <= H <= 1280
    but 384, both only where the batch tiles (B % 8) and the backward
    kernel's VMEM model fits (LSTM bf16 at H 1280 fits B 32, not 64)."""
    from paddle_tpu.ops.pallas_kernels import gru_supported, lstm_supported

    want = {  # (B, H): (lstm, gru)
        (128, 512): (True, True),
        (128, 384): (True, False),
        (128, 256): (False, True),
        (128, 128): (False, True),
        (128, 1408): (False, False),
        (32, 1280): (True, True),
        (64, 1280): (False, True),
        (8, 128): (False, True),
        (12, 512): (False, False),
    }
    for (B, H), (lstm, gru) in want.items():
        assert lstm_supported(B, H, "sigmoid", "tanh", "tanh", None,
                              itemsize=2) == lstm, (B, H)
        assert gru_supported(B, H, "sigmoid", "tanh",
                             itemsize=2) == gru, (B, H)
    # gate forms the kernels do not implement never fuse
    assert not lstm_supported(128, 512, "sigmoid", "tanh", "tanh",
                              object(), itemsize=2)
    assert not gru_supported(128, 512, "relu", "tanh", itemsize=2)


# ------------------------------------------------ the retired table ------
# The 22 rows of tune/tables/tpu-v5-lite.json as PR 48 found and deleted
# it: (family, shape, dtype, the row's config).
_BAH = {"A": 512, "C": 512, "Sp": 64}
RETIRED_ROWS = [
    ("bahdanau_attention", dict(_BAH, B=1024), "bfloat16", {"bblk": 8}),
    ("bahdanau_attention", dict(_BAH, B=128), "bfloat16", {"bblk": 8}),
    ("bahdanau_attention", dict(_BAH, B=256), "bfloat16", {"bblk": 8}),
    ("bahdanau_attention", dict(_BAH, B=256), "float32", {"bblk": 8}),
    ("bahdanau_attention", dict(_BAH, B=32), "bfloat16", {"bblk": 8}),
    ("bahdanau_attention", dict(_BAH, B=512), "bfloat16", {"bblk": 8}),
    ("bahdanau_attention", dict(_BAH, B=64), "bfloat16", {"bblk": 8}),
    ("flash_attention", {"Tq": 1024, "Tk": 1024}, "bfloat16",
     {"block_q": 512, "block_k": 512}),
    ("flash_attention", {"Tq": 4096, "Tk": 1024}, "bfloat16",
     {"block_q": 512, "block_k": 512}),
    ("flash_attention", {"Tq": 16384, "Tk": 16384}, "bfloat16",
     {"block_q": 1024, "block_k": 1024}),
    ("flash_attention", {"Tq": 2048, "Tk": 2048}, "bfloat16",
     {"block_q": 512, "block_k": 512}),
    ("flash_attention", {"Tq": 4096, "Tk": 4096}, "bfloat16",
     {"block_q": 512, "block_k": 512}),
    ("flash_attention", {"Tq": 8192, "Tk": 8192}, "bfloat16",
     {"block_q": 1024, "block_k": 1024}),
    ("fused_gru", {"B": 128, "H": 1280}, "bfloat16", {"fused": True}),
    ("fused_gru", {"B": 128, "H": 128}, "bfloat16", {"fused": True}),
    ("fused_gru", {"B": 128, "H": 384}, "bfloat16", {"fused": False}),
    ("fused_gru", {"B": 128, "H": 512}, "bfloat16", {"fused": True}),
    ("fused_lstm", {"B": 128, "H": 1024}, "bfloat16", {"fused": True}),
    # the one row the issue read as overruling the window ("1.13x at
    # 1280"): it restates the VMEM model, which keeps B 128 H 1280 on
    # the scan before the window is asked (17.8M against the 15M
    # budget). On the chip in PR 48 both forced arms ran the scan there,
    # and the kernel called past the gate read 1.13x over it: the window
    # stands, the gate decides, the row changed nothing
    ("fused_lstm", {"B": 128, "H": 1280}, "bfloat16", {"fused": False}),
    ("fused_lstm", {"B": 128, "H": 256}, "bfloat16", {"fused": False}),
    ("fused_lstm", {"B": 128, "H": 512}, "bfloat16", {"fused": True}),
    ("fused_lstm", {"B": 128, "H": 768}, "bfloat16", {"fused": True}),
]


def _row_id(row):
    family, params, dtype, _ = row
    return "|".join((family, ",".join(f"{k}={params[k]}"
                                      for k in sorted(params)), dtype))


@pytest.mark.parametrize("row", RETIRED_ROWS, ids=_row_id)
def test_retired_row_is_the_rule(row):
    """With nothing forced the rule picks what the table's row said, at
    every one of its 22 shapes: deleting the table changed no pick."""
    family, params, dtype, config = row
    assert space.pick(family, params, dtype) == config


def test_lstm_window_upper_end_as_read_on_the_chip():
    """What the table DID change, through interpolation from its B 128
    H 1280 row: every LSTM shape near it ran the scan on a v5e. The rule
    fuses there, and the chip agrees (PR 48: kernel over scan 1.17x at
    B 128 H 1152, 1.73x at B 32 H 1280, 1.81x at B 32 H 1152)."""
    for B, H in [(128, 1152), (32, 1280), (32, 1152), (128, 1024)]:
        assert space.pick("fused_lstm", {"B": B, "H": H},
                          "bfloat16") == {"fused": True}, (B, H)
    assert space.pick("fused_lstm", {"B": 128, "H": 1408},
                      "bfloat16") == {"fused": False}


def _cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = {}
    for w in bench["workloads"]:
        with open(os.path.join(REPO, "chipbench", "workloads",
                               w["name"] + ".json")) as f:
            out.setdefault(w["config"], set()).add(json.load(f)["seqlen"])
    return out


# configuration -> (the T its cells train at, the parent's q and k block)
CELL_BLOCKS = {
    "gpt2-small": (1024, 512),
    "olmoe-1b-7b": (4096, 512),
    "nemotron-3-nano-30b-a3b": (8192, 1024),
    "glm-4.7-flash": (8192, 1024),
    "trinity-mini": (8192, 1024),
    "ouro-2.6b": (4096, 512),
    # PR 49: the first cell at 16 k keys; the rule's 1024 x 1024 from 8192 up
    "lfm2-24b-a2b": (16384, 1024),
    # PR 57: twelve launches a step at 20 heads of 64, one under a window
    "phi-4-mini-flash-reasoning": (8192, 1024),
    # PR 60: four launches a step under a keep operand whose [block_q, 128]
    # words hold a k block's 8 key tiles (32 is a multiple of them)
    "keye-vl-2.0-30b-a3b": (16384, 1024),
}


@pytest.mark.parametrize("name", sorted(CELL_BLOCKS))
def test_cell_attention_blocks(name):
    """Every attention site of a benchmark cell's step program gets the
    blocks the parent gave it (there the table's row, here the rule):
    512 x 512 at T 1024 and 4096, 1024 x 1024 at 8192 and at 16 384."""
    from paddle_tpu.ops.flash_ops import FlashBlocks, _v5e_block_sizes

    assert sorted(CELL_BLOCKS) == sorted(
        os.path.basename(os.path.dirname(p)) for p in glob.glob(
            os.path.join(REPO, "chipbench", "configs", "*", "config.json")))
    T, blk = CELL_BLOCKS[name]
    assert _cells()[name] == {T}
    with open(os.path.join(REPO, "chipbench", "configs", name,
                           "config.json")) as f:
        cfg = json.load(f)
    assert T <= cfg.get("max_position_embeddings", cfg.get("n_positions"))
    assert _v5e_block_sizes(T, T, jnp.bfloat16) == FlashBlocks(blk, blk)


def _picks():
    from paddle_tpu.ops.bahdanau_kernels import _bblk
    from paddle_tpu.ops.flash_ops import _v5e_block_sizes

    return ([space.pick(f, p, d) for f, p, d, _ in RETIRED_ROWS],
            _bblk(64, 64, 512, 512, 2),
            tuple(_v5e_block_sizes(8192, 8192, jnp.bfloat16)),
            pt.Executor._program_trace_key(pt.default_main_program()))


HOSTILE_TABLE = {"version": 1, "device_kind": "cpu", "entries": {
    f"{_row_id(row)}|{device}": {
        "config": {"bblk": 64, "block_q": 128, "block_k": 128,
                   "fused": not row[3].get("fused", False)},
        "meta": {"provenance": "measured", "updated_at": 4102444800}}
    for row in RETIRED_ROWS for device in ("cpu", "tpu-v5-lite")}}


@pytest.mark.parametrize("source", ["home_file", "PT_TUNE_CACHE",
                                    "PT_ATTN_BBLK"])
def test_nothing_outside_the_program_steers_a_kernel(source, tmp_path,
                                                     monkeypatch):
    """What steered every kernel pick and every Executor's trace key at
    the parent: a tuned.json under the user's home (which a served model
    warmed up on a TPU wrote there), the variable that named another
    one, and the legacy batch-tile variable. None changes a thing."""
    before = _picks()
    table = tmp_path / ".cache" / "paddle_tpu" / "tuned.json"
    table.parent.mkdir(parents=True)
    table.write_text(json.dumps(HOSTILE_TABLE))
    if source == "home_file":
        monkeypatch.setenv("HOME", str(tmp_path))
        monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / ".cache"))
    elif source == "PT_TUNE_CACHE":
        monkeypatch.setenv("PT_TUNE_CACHE", str(table))
        monkeypatch.setenv("PT_TUNE_TABLES_DIR", str(table.parent))
    else:
        monkeypatch.setenv("PT_ATTN_BBLK", "16")
    assert _picks() == before


def test_trace_key_reads_no_file_and_hashes_nothing(monkeypatch):
    """Executor.run builds this key on every step: with nothing forced
    its tuner part is the empty tuple, and building it opens no file."""
    def no_open(*a, **k):
        raise AssertionError(f"open{a} while building the trace key")

    prog = pt.default_main_program()
    monkeypatch.setattr(builtins, "open", no_open)
    monkeypatch.setattr(os, "open", no_open)
    key = pt.Executor._program_trace_key(prog)
    assert overrides.forced_key() == () and key[-1] == ()
    with overrides.forcing("flash_attention", {"block_q": 256,
                                               "block_k": 128}):
        assert pt.Executor._program_trace_key(prog)[-1] == (
            ("flash_attention", (("block_k", 128), ("block_q", 256))),)


# ------------------------------------------------------- overrides ------
def test_override_precedence():
    """pick: a forced config that is legal at the shape beats the
    default; one family's force leaves the others alone; unforcing
    restores the default."""
    from paddle_tpu.ops.bahdanau_kernels import _bblk

    assert _bblk(16, 16, 128, 128, 4) == 8  # the rule
    with overrides.forcing("bahdanau_attention", {"bblk": 16}):
        assert _bblk(16, 16, 128, 128, 4) == 16
        with overrides.forcing("bahdanau_attention", {"bblk": 8}):
            assert _bblk(16, 16, 128, 128, 4) == 8  # innermost wins
        assert _bblk(16, 16, 128, 128, 4) == 16
        assert space.pick("flash_attention", {"Tq": 1024, "Tk": 1024},
                          "bfloat16") == {"block_q": 512, "block_k": 512}
    assert _bblk(16, 16, 128, 128, 4) == 8
    overrides.force("bahdanau_attention", {"bblk": 16})
    assert overrides.forced_config("bahdanau_attention") == \
        overrides.Override({"bblk": 16}, "forced")
    overrides.force("bahdanau_attention", None)
    assert overrides.forced_config("bahdanau_attention") is None


def test_flash_and_bahdanau_consult_overrides():
    """flash_ops._v5e_block_sizes and bahdanau_kernels._bblk take a
    forced config over their rule."""
    from paddle_tpu.ops.bahdanau_kernels import _bblk
    from paddle_tpu.ops.flash_ops import _v5e_block_sizes

    bs = _v5e_block_sizes(1024, 1024, jnp.bfloat16)
    assert (bs.block_q, bs.block_k) == (512, 512)
    assert _v5e_block_sizes(1280, 1280) == (256, 256)  # largest divisor
    assert _bblk(64, 64, 512, 512, 2) == 8
    with overrides.forcing("flash_attention", {"block_q": 256,
                                               "block_k": 128}), \
            overrides.forcing("bahdanau_attention", {"bblk": 16}):
        bs = _v5e_block_sizes(1024, 1024, jnp.bfloat16)
        assert (bs.block_q, bs.block_k) == (256, 128)
        assert _bblk(64, 64, 512, 512, 2) == 16
    # forced flash blocks that do not divide T: a warning, and the rule's
    # blocks (these kernels have no unfused path to fall to)
    with overrides.forcing("flash_attention", {"block_q": 768,
                                               "block_k": 768}):
        with pytest.warns(UserWarning, match="fails eligibility"):
            bs = _v5e_block_sizes(512, 512, jnp.bfloat16)
        assert (bs.block_q, bs.block_k) == (512, 512)
    # a sequence no block divides has no kernel at all
    with pytest.raises(ValueError, match="128-aligned"):
        _v5e_block_sizes(1000, 1024, jnp.bfloat16)
    # forced illegal batch tile warns and disables the fused path
    with overrides.forcing("bahdanau_attention", {"bblk": 12}):
        with pytest.warns(UserWarning, match="fails eligibility"):
            assert _bblk(64, 64, 512, 512, 2) == 0


def test_rnn_dispatch_consults_overrides(rnn_backend_on):
    """A forced {"fused": bool} overrules the measured H-window (but can
    never force an ineligible shape fused)."""
    from paddle_tpu.ops.pallas_kernels import gru_supported

    # H=384 sits outside the GRU measured window -> scan by default
    assert not gru_supported(128, 384, "sigmoid", "tanh", itemsize=2)
    with overrides.forcing("fused_gru", {"fused": True}):
        assert gru_supported(128, 384, "sigmoid", "tanh", itemsize=2)
        # hard illegality (B % 8) wins over any forced verdict
        with pytest.warns(UserWarning, match="fails eligibility"):
            assert not gru_supported(12, 384, "sigmoid", "tanh",
                                     itemsize=2)
    with overrides.forcing("fused_gru", {"fused": False}):
        assert not gru_supported(128, 512, "sigmoid", "tanh", itemsize=2)


def test_forced_illegal_warns_and_disables(tmp_table):
    from paddle_tpu.ops.bahdanau_kernels import _bblk

    with overrides.forcing("bahdanau_attention", {"bblk": 3}):
        with pytest.warns(UserWarning, match="fails eligibility"):
            assert _bblk(16, 16, 128, 128, 4) == 0


def test_fingerprint_reacts_to_every_source():
    """forced_key() follows the forced configs, the one source there is,
    and nothing else."""
    assert overrides.forced_key() == ()
    overrides.force("bahdanau_attention", {"bblk": 16})
    k1 = overrides.forced_key()
    assert k1 == (("bahdanau_attention", (("bblk", 16),)),)
    overrides.force("bahdanau_attention", {"bblk": 8})
    assert overrides.forced_key() not in ((), k1)
    overrides.force("fused_lstm", {"fused": True})
    assert [k for k, _ in overrides.forced_key()] == [
        "bahdanau_attention", "fused_lstm"]
    hash(overrides.forced_key())  # it is a dict key in the Executor
    overrides.force("bahdanau_attention", None)
    overrides.force("fused_lstm", None)
    assert overrides.forced_key() == ()
    overrides.force("fused_gru", {"fused": False})
    overrides.reset()
    assert overrides.forced_key() == ()


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


def test_harness_loop_mechanics_interpret():
    """The sweep itself (every legal candidate timed under forcing, the
    numeric cross-check, the ranking) exercised in interpret mode with
    the TPU requirement waived; the CLI keeps require_tpu. It leaves
    nothing forced and writes nothing."""
    rep = harness.tune_case("bahdanau", {"B": 16, "Sp": 16, "A": 128,
                                         "C": 128}, "float32",
                            iters=2, warmup=1, require_tpu=False)
    assert {r["config"]["bblk"] for r in rep["rows"]} == {8, 16}
    assert all(r["numerics_ok"] for r in rep["rows"])
    medians = [r["median_s"] for r in rep["rows"]]
    assert medians == sorted(medians) and medians[0] > 0  # a ranking
    assert rep["best"] == rep["rows"][0]["config"]
    assert rep["default"] == {"bblk": 8}
    assert [r["is_default"] for r in rep["rows"]].count(True) == 1
    assert rep["speedup_vs_default"] >= 1.0
    assert overrides.forced_key() == ()
    # the same candidates the dry run lists
    assert harness.list_candidates(
        "bahdanau", {"B": 16, "Sp": 16, "A": 128, "C": 128},
        "float32")["candidates"] == sorted(
            (r["config"] for r in rep["rows"]), key=lambda c: c["bblk"])


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


def test_forced_tuned_config_bit_identical(interpret_flag):
    """Golden numerics: a forced tile (bblk=16) partitions the batch
    differently but must reproduce the rule's tile (bblk=8)
    BIT-FOR-BIT for the forward and every per-row gradient — per-row
    math is tile-invariant. The one principled exception is d(v): its
    reduction crosses batch tiles, so the tile size changes the f32
    summation ORDER (2 partial sums at bblk=8 vs 1 at bblk=16) — that
    gradient is pinned to f32-rounding tightness instead. This is the
    guarantee that lets a sweep's finding become the rule without a
    numerics qualification run."""
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


def test_artifact_with_a_tuning_block_still_loads(tmp_path):
    """An artifact saved before PR 48 carries the exporter's device kind
    and tuned-table fingerprint in meta.json. The block is ignored: the
    model loads, warms up and serves without a warning; a new export
    writes no such block."""
    import warnings as _w

    from paddle_tpu.serving import ServingEngine

    model_dir = _save_tiny_model(tmp_path)
    meta_path = os.path.join(model_dir, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    assert "tuning" not in meta
    meta["tuning"] = {"device_kind": "tpu-v5-lite",
                      "table_fingerprint": "0123456789abcdef"}
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with _w.catch_warnings():
        _w.simplefilter("error")
        engine = ServingEngine(model_dir)
        assert engine.warmup() >= 1
        out = engine.predict({"x": np.ones((2, 4), np.float32)})
    (probs,) = out.values() if isinstance(out, dict) else out
    np.testing.assert_allclose(np.asarray(probs).sum(-1), 1.0, rtol=1e-5)


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


def test_every_listed_family_is_consulted_at_default_flags(monkeypatch):
    """A family the sweep tool lists but no dispatch asks for is a
    kernel that never runs being swept. Each op's own eligibility
    predicate or tile picker runs here with every flag at its default
    (only the backend gate is neutralised: it is the platform, not an
    option), and space.pick must be asked for exactly the families
    listed."""
    from paddle_tpu.ops import (bahdanau_kernels, flash_ops, pallas_kernels,
                                quant_kernels)

    seen = set()
    real_pick = space.pick

    def pick(family, *args, **kwargs):
        seen.add(family)
        return real_pick(family, *args, **kwargs)

    monkeypatch.setattr(space, "pick", pick)
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
