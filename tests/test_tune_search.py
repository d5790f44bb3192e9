"""Autotuner v2 guided search (paddle_tpu/tune/search.py).

The ISSUE-10 acceptance bar, proven on the injectable oracle (the same
protocol the real compile+measure loop implements — harness.py refuses
to time off-TPU, which is exactly why the searcher takes the oracle as
a parameter): guided search reaches >= 95% of exhaustive-search quality
while timing <= 40% of the candidate space, the successive-halving
mechanics stop early on a stable leader, and a config that fails the
oracle (numerics) can never win.
"""

import math

import pytest

from paddle_tpu.tune import harness, overrides, search, space
from paddle_tpu.tune import cache as tcache

# spaces large enough that the 40% budget actually prunes (flash is the
# quadratic one the guided search exists for)
BIG_CASES = [
    ("flash_attention", {"Tq": 2048, "Tk": 2048}),   # 25 candidates
    ("flash_attention", {"Tq": 4096, "Tk": 4096}),   # 25
    ("flash_attention", {"Tq": 8192, "Tk": 8192}),   # 25
    ("flash_attention", {"Tq": 4096, "Tk": 1024}),   # 20
    ("quant_matmul", {"M": 1024, "K": 1024, "N": 4096}),   # 28
]


# ------------------------------------------------------- cost model ------
def test_predicted_cost_finite_and_ranking_total():
    """The model scores every legal candidate of every family with a
    finite positive cost, and rank_candidates is a permutation of the
    candidate set (nothing dropped, nothing invented)."""
    cases = BIG_CASES + [
        ("bahdanau_attention", {"B": 256, "Sp": 64, "A": 512, "C": 512}),
        ("fused_lstm", {"B": 128, "H": 512}),
        ("fused_gru", {"B": 128, "H": 384}),
    ]
    for fam_name, params in cases:
        fam = space.get_family(fam_name)
        norm = fam.normalize(params, "bfloat16")
        cands = fam.candidates(norm)
        ranked = search.rank_candidates(fam_name, params, "bfloat16")
        assert sorted(map(search.config_key, ranked)) == \
            sorted(map(search.config_key, cands))
        for cfg in cands:
            c = search.predicted_cost(fam_name, norm, cfg)
            assert math.isfinite(c) and c > 0, (fam_name, cfg, c)
        # deterministic: same call, same order
        assert ranked == search.rank_candidates(fam_name, params,
                                                "bfloat16")


def test_cost_model_prefers_measured_bahdanau_winner():
    """At the NMT shapes the measured winner is bblk=8 (the 256k-vs-217k
    tok/s sweep the tuner was built around): the VMEM-pressure term must
    rank it above the budget-saturating bblk=16."""
    norm = {"B": 256, "Sp": 64, "A": 512, "C": 512, "dtype": "bfloat16"}
    ranked = search.rank_candidates(
        "bahdanau_attention", {"B": 256, "Sp": 64, "A": 512, "C": 512},
        "bfloat16")
    assert ranked[0] == {"bblk": 8}, ranked


# ------------------------------------------------- search mechanics ------
def test_guided_search_respects_probe_budget():
    for fam_name, params in BIG_CASES:
        ranked = search.rank_candidates(fam_name, params, "bfloat16")
        oracle = search.SimulatedOracle(fam_name, params, "bfloat16")
        res = search.guided_search(ranked, oracle)
        n = len(ranked)
        assert res.n_candidates == n
        assert res.n_timed == oracle.timed
        assert res.n_timed <= max(3, int(0.4 * n))
        assert res.timed_fraction <= 0.4 + 1e-9, (fam_name, params,
                                                  res.timed_fraction)


def test_guided_search_stops_early_on_stable_leader():
    """A surface with one clear winner: after two rungs with the same
    leader the search stops without running the last rung over the
    whole survivor set."""
    cands = [{"x": i} for i in range(20)]
    calls = []

    def oracle(cfg, iters):
        calls.append((cfg["x"], iters))
        return 1.0 + cfg["x"]  # candidate 0 always wins

    res = search.guided_search(cands, oracle, rungs=(1, 3, 7, 15))
    assert res.best == {"x": 0}
    assert res.stopped_early
    assert res.rungs_run == 2  # leader stable after the second rung
    assert res.n_timed == 8  # floor(0.4 * 20)


def test_guided_search_drops_failed_candidates():
    """oracle -> +inf marks numerics failure: the config is out
    immediately and can never be the winner; all-inf raises."""
    cands = [{"x": i} for i in range(10)]

    def oracle(cfg, iters):
        return float("inf") if cfg["x"] == 0 else float(cfg["x"])

    res = search.guided_search(cands, oracle)
    assert res.best == {"x": 1}
    with pytest.raises(RuntimeError, match="every probed candidate"):
        search.guided_search(cands, lambda c, i: float("inf"))


def test_simulated_oracle_deterministic():
    o1 = search.SimulatedOracle("flash_attention",
                                {"Tq": 2048, "Tk": 2048}, "bfloat16",
                                seed=3)
    o2 = search.SimulatedOracle("flash_attention",
                                {"Tq": 2048, "Tk": 2048}, "bfloat16",
                                seed=3)
    cfg = {"block_q": 512, "block_k": 512}
    assert o1(cfg, 1) == o2(cfg, 1)
    # a different seed is a different surface
    o3 = search.SimulatedOracle("flash_attention",
                                {"Tq": 2048, "Tk": 2048}, "bfloat16",
                                seed=4)
    assert o3(cfg, 1) != o1(cfg, 1)


# ---------------------------------------------- quality acceptance ------
def test_guided_reaches_95pct_of_exhaustive_at_40pct_probes():
    """THE acceptance property, over every big-space case and 8
    device-quirk seeds: the guided winner's TRUE time is within 5% of
    the exhaustive-search optimum, having timed at most 40% of the
    space. Deterministic (SimulatedOracle is seeded sha256, no RNG
    state)."""
    for fam_name, params in BIG_CASES:
        fam = space.get_family(fam_name)
        norm = fam.normalize(params, "bfloat16")
        cands = fam.candidates(norm)
        ranked = search.rank_candidates(fam_name, params, "bfloat16")
        for seed in range(8):
            oracle = search.SimulatedOracle(fam_name, params, "bfloat16",
                                            seed=seed)
            res = search.guided_search(ranked, oracle)
            _, true_best_s = oracle.exhaustive_best(cands)
            quality = true_best_s / oracle.true_time(res.best)
            assert quality >= 0.95, (fam_name, params, seed, quality)
            assert res.timed_fraction <= 0.4 + 1e-9


# ------------------------------------------- harness integration ------
@pytest.fixture
def tmp_table(tmp_path):
    path = str(tmp_path / "tuned.json")
    overrides.set_table_path(path)
    yield path
    overrides.reset()


def test_tune_case_guided_with_injected_oracle(tmp_table):
    """tune_case(mode="guided", oracle=...) never compiles anything
    (the injected oracle IS the timing source), prunes the space, and
    records the winner with provenance "measured"."""
    params = {"Tq": 2048, "Tk": 2048}
    oracle = search.SimulatedOracle("flash_attention", params, "bfloat16",
                                    seed=0)
    t = overrides.table()
    rep = harness.tune_case("flash_attention", params, "bfloat16",
                            table=t, iters=7, oracle=oracle)
    s = rep["search"]
    assert s["mode"] == "guided"
    assert s["timed"] <= int(0.4 * s["candidates"])
    assert any(not r["timed"] for r in rep["rows"])  # space was pruned
    # winner is in the table under the runtime key, stamped measured
    cfg = t.get("flash_attention", params, "bfloat16")
    assert cfg == rep["best"]
    key = tcache.entry_key("flash_attention", tcache.make_sig(params),
                           "bfloat16", tcache.device_kind())
    meta = t.entries[key]["meta"]
    assert meta["provenance"] == "measured"
    assert meta["updated_at"] > 0


def test_tune_case_exhaustive_mode_times_everything(tmp_table):
    params = {"Tq": 2048, "Tk": 2048}
    oracle = search.SimulatedOracle("flash_attention", params, "bfloat16",
                                    seed=0)
    rep = harness.tune_case("flash_attention", params, "bfloat16",
                            iters=3, mode="exhaustive", oracle=oracle)
    assert rep["search"] == {"mode": "exhaustive",
                             "candidates": 25, "timed": 25,
                             "timed_fraction": 1.0}
    assert all(r["timed"] for r in rep["rows"])
    assert "speedup_vs_default" in rep
    # on the same surface, exhaustive and guided agree on the winner
    # whenever the guided probe set contains the true best
    oracle2 = search.SimulatedOracle("flash_attention", params,
                                     "bfloat16", seed=0)
    rep_g = harness.tune_case("flash_attention", params, "bfloat16",
                              iters=3, oracle=oracle2)
    assert oracle2.true_time(rep_g["best"]) <= \
        1.0 / 0.95 * oracle2.true_time(rep["best"])


def test_tune_case_guided_small_space_times_all(tmp_table):
    """min_probes floors tiny spaces: a 2-candidate bahdanau case is
    fully swept even in guided mode (nothing to prune)."""
    params = {"B": 16, "Sp": 16, "A": 128, "C": 128}
    oracle = search.SimulatedOracle("bahdanau_attention", params,
                                    "float32", seed=0)
    rep = harness.tune_case("bahdanau", params, "float32", iters=2,
                            oracle=oracle)
    assert rep["search"]["timed"] == rep["search"]["candidates"] == 2
    assert {r["config"]["bblk"] for r in rep["rows"]} == {8, 16}
