"""Cost-model-guided candidate search (Autotuner v2).

v1's harness swept EVERY legal candidate per shape signature — fine for
the bahdanau space (a handful of divisors) but quadratic for flash
(|q blocks| x |k blocks|) and a cold table meant minutes of warmup
timing. CUDA-L2 (arXiv:2512.02551) and CLBlast (arXiv:1705.05249 §3)
both land on the same recipe this module implements:

1. a LIGHTWEIGHT COST MODEL ranks candidates before anything is timed.
   The features are computable from tune/space.py's legality model
   alone — no hardware, no compile: estimated HBM traffic (the
   arithmetic-intensity term), kernel grid steps (the per-dispatch
   overhead term), and VMEM pressure (working-set bytes against
   ops/pallas_kernels._VMEM_BUDGET — the spill term; every measured
   "big tile loses" result in PERF.md is a spill, not a bandwidth
   effect, so the penalty is quadratic once the working set passes half
   the budget: borderline configs flip with the compiler's scratch
   scheduling, pallas_kernels.py's hard-won comment);

2. SUCCESSIVE HALVING times only the top-ranked fraction: every
   survivor gets a cheap low-iteration probe, the better half advances
   to a higher-iteration rung, and the search stops EARLY when the
   leader is stable across rungs — so the expensive high-confidence
   medians are spent on the 2-3 genuine contenders, not the whole
   space.

The searcher takes an INJECTABLE timing oracle (`oracle(config, iters)
-> median seconds`) because harness.py refuses to time off-TPU: the
real oracle wraps the compile+measure loop, and the tier-1 CPU suite
proves guided-vs-exhaustive quality on a deterministic SimulatedOracle
instead (same protocol, synthetic-but-plausible timing surface). The
guided-search acceptance bar — >= 95% of exhaustive-search quality
while timing <= 40% of the candidate space — is asserted against that
oracle in tests; it has not been measured on a chip.
"""

from __future__ import annotations

import hashlib
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from . import space

Config = Dict[str, Any]

# effective-bandwidth / per-grid-step-overhead constants: these only
# need to produce a sane RANKING (the oracle decides the winner), so
# one set serves every device generation. v5e-ish: ~800 GB/s HBM,
# ~2 us of grid/dispatch overhead per kernel grid step.
_HBM_BYTES_PER_S = 8e11
_GRID_STEP_S = 2e-6
# spill penalty engages past this fraction of the VMEM budget
# (pallas_kernels.py: borderline working sets flip between compiling
# and overflowing with the compiler's scratch scheduling)
_SPILL_KNEE = 0.5
_SPILL_GAIN = 4.0


def config_key(config: Config) -> Tuple[Tuple[str, Any], ...]:
    """Canonical hashable identity of a candidate config."""
    return tuple(sorted(config.items()))


# ------------------------------------------------------ cost features --
def _features_bahdanau(params: Dict[str, Any], cfg: Config):
    B, Sp, A, C = params["B"], params["Sp"], params["A"], params["C"]
    item = 2 if params.get("dtype") == "bfloat16" else 4
    b = int(cfg["bblk"])
    grid = B // max(1, b)
    # io traffic is tile-invariant (every ep/enc/dep byte moves once);
    # what varies is the dispatch overhead and the five f32 [b, Sp, A]
    # working arrays' VMEM take (the spill axis the 8-vs-16 NMT
    # measurement lives on)
    hbm = (2 * Sp * (A + C) + Sp * A) * B * item
    ws = ((2 * Sp * (A + C) + Sp * A) * b * item + 5 * b * Sp * A * 4)
    return hbm, grid, ws


def _features_flash(params: Dict[str, Any], cfg: Config):
    Tq, Tk = params["Tq"], params["Tk"]
    item = 2 if params.get("dtype", "bfloat16") == "bfloat16" else 4
    D = 128  # nominal head dim: a constant scale, irrelevant to ranking
    bq, bk = int(cfg["block_q"]), int(cfg["block_k"])
    grid = (Tq // max(1, bq)) * (Tk // max(1, bk))
    # k/v stream through VMEM once per q block (the flash loop): small
    # q blocks re-read the whole kv sequence
    hbm = (Tq * D + (Tq // max(1, bq)) * 2 * Tk * D) * item
    ws = (bq * D + 2 * bk * D) * item + bq * bk * 4 + bq * D * 4
    return hbm, grid, ws


def _features_rnn(kind: str):
    def f(params: Dict[str, Any], cfg: Config):
        B, H = params["B"], params["H"]
        item = 2 if params.get("dtype") == "bfloat16" else 4
        g = 4 if kind == "lstm" else 3
        if cfg.get("fused"):
            from ..ops import pallas_kernels as pk

            dw = (pk._LSTM_FUSED_DW_MAX_H if kind == "lstm"
                  else pk._GRU_FUSED_DW_MAX_H)
            return (g * H * H * item + B * H * item, 1,
                    pk._bwd_vmem_bytes(B, H, g, item, dw))
        # scan formulation: weights re-stream per step (T unknown at
        # tune time; 32 is a nominal sequence), no VMEM pressure
        return (32 * g * H * H * item, 32, 0)

    return f


def _features_quant_matmul(params: Dict[str, Any], cfg: Config):
    """int8 GEMM features: with dtype 'int8' the x/w panels stream at
    1 B/elem (plus the f32 dequant epilogue write); the SAME formula at
    a float dtype models the unquantized matmul the site replaced."""
    M, K, N = params["M"], params["K"], params["N"]
    item = _FEATURE_ITEMSIZE.get(params.get("dtype", "int8"), 1)
    bm = int(cfg.get("block_m", M) or M)
    bn = int(cfg.get("block_n", N) or N)
    gm, gn = M // max(1, bm), N // max(1, bn)
    grid = gm * gn
    # x panel re-streams per n-block, w panel per m-block; the output
    # writes once — int32 accumulator materialized at 4 B then scaled
    hbm = gn * M * K * item + gm * K * N * item + M * N * 4
    ws = 2 * (bm * K + K * bn) * item + bm * bn * 4
    return hbm, grid, ws


_FEATURE_ITEMSIZE = {"int8": 1, "bfloat16": 2, "float32": 4}


_FEATURES: Dict[str, Callable] = {
    "bahdanau_attention": _features_bahdanau,
    "flash_attention": _features_flash,
    "fused_lstm": _features_rnn("lstm"),
    "fused_gru": _features_rnn("gru"),
    "quant_matmul": _features_quant_matmul,
}


def predicted_cost(family: str, params: Dict[str, Any],
                   config: Config) -> float:
    """Model-predicted wall seconds for one dispatch of `config` at
    `params`. Absolute scale is nominal — only the ORDERING feeds the
    guided search."""
    fam = space.get_family(family)
    hbm, grid, ws = _FEATURES[fam.name](params, config)
    mem_s = hbm / _HBM_BYTES_PER_S
    overhead_s = grid * _GRID_STEP_S
    frac = ws / space._vmem_budget()
    spill = mem_s * _SPILL_GAIN * max(0.0, frac - _SPILL_KNEE) ** 2 \
        / (1.0 - _SPILL_KNEE) ** 2
    return mem_s + overhead_s + spill


def rank_candidates(family: str, params: Dict[str, Any],
                    dtype: str) -> List[Config]:
    """The family's legal candidates, best-predicted first (ties broken
    by config key for determinism)."""
    fam = space.get_family(family)
    norm = fam.normalize(params, dtype)
    cands = fam.candidates(norm)
    return sorted(cands, key=lambda c: (predicted_cost(fam.name, norm, c),
                                        config_key(c)))


# ------------------------------------------------------ guided search --
class SearchResult:
    """What the guided searcher hands back: the winner, its median, and
    the audit trail (which configs were timed, at which rungs, and why
    the search stopped)."""

    def __init__(self, best: Config, best_s: float,
                 timings: Dict[Tuple, float], n_candidates: int,
                 rungs_run: int, stopped_early: bool):
        self.best = best
        self.best_s = best_s
        self.timings = timings  # config_key -> best median observed
        self.n_candidates = n_candidates
        self.rungs_run = rungs_run
        self.stopped_early = stopped_early

    @property
    def n_timed(self) -> int:
        return len(self.timings)

    @property
    def timed_fraction(self) -> float:
        return self.n_timed / max(1, self.n_candidates)


def guided_search(
    candidates: Sequence[Config],
    oracle: Callable[[Config, int], float],
    *,
    ranked: bool = True,
    budget_fraction: float = 0.4,
    min_probes: int = 3,
    rungs: Sequence[int] = (1, 3, 7),
    stable_rounds: int = 2,
) -> SearchResult:
    """Successive-halving search over `candidates` (already cost-model
    ranked when `ranked`; pass ranked=False to shuffle-free-sweep an
    unranked list — the A/B baseline).

    - probes the top max(min_probes, budget_fraction * |space|)
      candidates, never more than the space holds;
    - rung r times every survivor at `rungs[r]` iterations and keeps
      the better half (the oracle's median at higher iters REPLACES the
      cheaper estimate — a lucky low-iter probe can't coast to a win);
    - stops early once the leader has been the same config for
      `stable_rounds` consecutive rungs, or when one survivor remains.

    The oracle returns median seconds for (config, iters); +inf marks a
    config that failed numerics/compile and drops it immediately.
    """
    cands = list(candidates)
    if not cands:
        raise ValueError("guided_search: empty candidate list")
    # floor, not ceil: "time at most budget_fraction of the space" must
    # hold exactly for spaces where the bound bites (8 candidates at
    # 0.4 probes 3, not 4); min_probes floors only the tiny spaces
    # where a fraction would probe nothing
    k = min(len(cands), max(int(min_probes),
                            int(budget_fraction * len(cands))))
    survivors = cands[:k]
    timings: Dict[Tuple, float] = {}
    leader: Optional[Tuple] = None
    stable = 0
    rungs_run = 0
    stopped_early = False
    for iters in rungs:
        rungs_run += 1
        scored = []
        for cfg in survivors:
            t = oracle(cfg, iters)
            key = config_key(cfg)
            timings[key] = t if key not in timings \
                else (t if t != float("inf") else timings[key])
            if t != float("inf"):
                scored.append((t, key, cfg))
        if not scored:
            raise RuntimeError(
                "guided_search: every probed candidate failed the "
                "oracle (numerics/compile) — refusing to pick a winner")
        scored.sort(key=lambda x: (x[0], x[1]))
        new_leader = scored[0][1]
        stable = stable + 1 if new_leader == leader else 1
        leader = new_leader
        if len(scored) == 1:
            break
        if stable >= stable_rounds:
            stopped_early = True
            break
        survivors = [cfg for _, _, cfg in
                     scored[:max(1, math.ceil(len(scored) / 2))]]
    best_s, best_key, best = scored[0]
    return SearchResult(best, best_s, timings, len(cands), rungs_run,
                        stopped_early)


# --------------------------------------------------- simulated oracle --
class SimulatedOracle:
    """Deterministic synthetic timing surface for off-TPU tests.

    The surface is the cost model's shape DISTORTED per config: each
    config's true time is predicted_cost times a deterministic
    pseudo-random factor in [1-noise, 1+noise] (sha256 of seed+config —
    reproducible across processes, no RNG state), so the model's #1
    pick is frequently NOT the true best and the searcher has to earn
    the win by probing. `calls` counts oracle invocations and `timed`
    the distinct configs probed — the two numbers the <=40% acceptance
    bound reads."""

    def __init__(self, family: str, params: Dict[str, Any], dtype: str,
                 seed: int = 0, noise: float = 0.10):
        fam = space.get_family(family)
        self.family = fam.name
        self.params = fam.normalize(params, dtype)
        self.seed = seed
        self.noise = noise
        self.calls = 0
        self._timed: set = set()

    def _jitter(self, key: Tuple) -> float:
        h = hashlib.sha256(
            f"{self.seed}|{self.family}|{sorted(self.params.items())}"
            f"|{key}".encode()).digest()
        u = int.from_bytes(h[:8], "big") / 2 ** 64  # [0, 1)
        return 1.0 + self.noise * (2.0 * u - 1.0)

    def true_time(self, config: Config) -> float:
        key = config_key(config)
        return predicted_cost(self.family, self.params, config) \
            * self._jitter(key)

    def __call__(self, config: Config, iters: int) -> float:
        self.calls += 1
        self._timed.add(config_key(config))
        return self.true_time(config)

    @property
    def timed(self) -> int:
        return len(self._timed)

    def exhaustive_best(self, candidates: Sequence[Config]) \
            -> Tuple[Config, float]:
        """Ground truth: the true best over the whole space (what an
        exhaustive sweep would find), without counting probes."""
        best, best_s = None, float("inf")
        for cfg in candidates:
            t = self.true_time(cfg)
            if t < best_s or (t == best_s and best is not None
                              and config_key(cfg) < config_key(best)):
                best, best_s = cfg, t
        return best, best_s
