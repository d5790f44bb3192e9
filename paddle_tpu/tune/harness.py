"""Empirical measurement loop: compile, warm up, time, cross-check.

Methodology (the CLBlast recipe, arXiv:1705.05249 §3, adapted to XLA):

- each candidate config is traced+compiled with the config FORCED in
  the override registry (overrides.forcing), so the measurement
  exercises the exact consult path production dispatch uses;
- warmup runs absorb the compile + first-dispatch cost, then the timed
  runs block on the result (`jax.block_until_ready`) so the timer sees
  device work, not async enqueue (profiler.py's design note);
- the score is the MEDIAN of k timed runs (profiler.Stat keeps the
  samples when asked) — medians shrug off the one-off d2h/interrupt
  outliers that poisoned round-1's RNN measurements (PERF.md);
- every candidate's output is cross-checked against the family's
  reference lowering before it may win: a fast-but-wrong tile (e.g. one
  that silently overflows an accumulator) must never enter the table.

Autotuner v2: the default search mode is GUIDED (tune/search.py) — a
cost model over the legality features ranks the space and successive
halving times only the top fraction, with the exhaustive v1 sweep kept
as the A/B baseline (`mode="exhaustive"` / CLI `--search exhaustive`).
Timing goes through an injectable ORACLE (make_oracle builds the real
compile+measure one), so search quality is testable off-TPU against
recorded/simulated timings without weakening the refusal below.

Determinism guard: timing is REFUSED off-TPU (TuningUnavailable) — a
CPU/interpret timing would write meaningless configs into the
per-device table, and the tier-1 CPU suite must stay byte-deterministic.
Lookups off-TPU still work and simply miss (device_kind mismatch), so
the untimed path falls back to analytic defaults deterministically.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from .. import profiler
from . import cache as _cache
from . import overrides, search as _search, space


class TuningUnavailable(RuntimeError):
    """Raised when empirical timing is requested on a backend whose
    timings must not enter the per-device table."""


def ensure_timeable() -> None:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise TuningUnavailable(
            f"refusing to time kernels on backend {backend!r}: empirical "
            "timings off-TPU would poison the per-device table. Run on "
            "TPU hardware, or use --dry-run to list candidates.")


def measure(thunk, iters: int = 5, warmup: int = 2,
            stat_set: Optional[profiler.StatSet] = None,
            name: str = "tune/measure") -> float:
    """Median-of-k wall seconds for `thunk()` (a zero-arg compiled
    call). Samples land in a StatSet so the full distribution is
    inspectable (`stat_set.get(name).samples`)."""
    import jax

    stats = stat_set if stat_set is not None \
        else profiler.StatSet(keep_samples=iters)
    for _ in range(max(0, warmup)):
        jax.block_until_ready(thunk())
    for _ in range(max(1, iters)):
        with stats.timer(name, always=True):
            jax.block_until_ready(thunk())
    return stats.get(name).median


def _numerics_ok(got, want: List[np.ndarray], tol: float) -> bool:
    import jax

    got_leaves = [np.asarray(g, np.float32)
                  for g in jax.tree_util.tree_leaves(got)]
    if len(got_leaves) != len(want):
        return False
    return all(
        np.allclose(g, np.asarray(w, np.float32), rtol=tol, atol=tol)
        for g, w in zip(got_leaves, want))


def make_oracle(case: space.Case, ref, warmup: int = 2,
                stat_set: Optional[profiler.StatSet] = None):
    """The REAL timing oracle over a runnable Case: compile-once per
    config (thunks are memoized), numeric cross-check ONCE per config
    before any timing (a fast-but-wrong tile must never win), then
    median-of-`iters` wall timing. Protocol: oracle(config, iters) ->
    median seconds, +inf for a config that failed numerics. The guided
    searcher takes any callable with this protocol — tests inject
    search.SimulatedOracle instead, which is the whole
    reason the oracle is a parameter and not a hard-wired loop."""
    thunks: Dict[tuple, Any] = {}

    def oracle(config: Dict[str, Any], iters: int) -> float:
        key = _search.config_key(config)
        if key not in thunks:
            thunk = case.make(config)
            thunks[key] = thunk if _numerics_ok(thunk(), ref, case.tol) \
                else None
        thunk = thunks[key]
        if thunk is None:
            return float("inf")
        return measure(thunk, iters=iters, warmup=warmup,
                       stat_set=stat_set, name=f"tune/{case.kernel}")

    return oracle


def tune_case(family: str, params: Dict[str, Any], dtype: str,
              table: Optional[_cache.TunedTable] = None,
              iters: int = 5, warmup: int = 2,
              require_tpu: bool = True,
              mode: str = "guided",
              budget_fraction: float = 0.4,
              oracle=None) -> Dict[str, Any]:
    """Tune one (kernel family, shape, dtype) case and optionally
    record the winner in `table` (provenance "measured"). Returns the
    report dict the CLI renders:

      {kernel, params, dtype, device_kind, default, best,
       rows: [{config, median_s, numerics_ok, is_default}, ...],
       search: {mode, candidates, timed, timed_fraction, ...}}

    `mode` picks the searcher: "guided" (default — cost-model ranking +
    successive-halving early stop, times a fraction of the space;
    tune/search.py) or "exhaustive" (v1 behavior: every candidate at
    full iters — the A/B baseline and the `--search exhaustive` CLI
    path). Untimed candidates appear in rows with median_s None.

    `oracle` overrides the timing source (protocol: oracle(config,
    iters) -> median seconds, +inf = failed). Default None builds the
    real compile+measure oracle — which is why `require_tpu` stays
    True for production entry points; an injected oracle skips the
    backend check entirely (recorded/simulated timings are
    deterministic anywhere, and the tier-1 guided-vs-exhaustive
    quality tests run exactly that way).

    `require_tpu=False` exists for the CPU test suite to exercise the
    loop mechanics in interpret mode — production entry points
    (cli tune) always require TPU.
    """
    if mode not in ("guided", "exhaustive"):
        raise ValueError(f"mode must be guided or exhaustive, got {mode!r}")
    fam = space.get_family(family)
    params = fam.normalize(params, dtype)
    if oracle is None:
        if require_tpu:
            ensure_timeable()
        case = fam.make_case(params, dtype)
        oracle = make_oracle(case, case.reference(), warmup=warmup)
    cands = fam.candidates(params)
    if not cands:
        raise ValueError(
            f"{fam.name}: no legal candidates at {params} — the shape "
            "is outside the fused kernel's eligibility entirely")
    default_cfg = fam.default(params)

    if mode == "guided":
        ranked = sorted(cands, key=lambda c: (
            _search.predicted_cost(fam.name, params, c),
            _search.config_key(c)))
        result = _search.guided_search(
            ranked, oracle, budget_fraction=budget_fraction,
            rungs=(max(1, iters // 4), max(2, iters // 2), iters))
        timings = result.timings
        best_cfg, best_s = result.best, result.best_s
        search_info = {
            "mode": "guided",
            "candidates": result.n_candidates,
            "timed": result.n_timed,
            "timed_fraction": result.timed_fraction,
            "rungs_run": result.rungs_run,
            "stopped_early": result.stopped_early,
        }
    else:
        timings = {}
        for cfg in cands:
            timings[_search.config_key(cfg)] = oracle(cfg, iters)
        finite = {k: v for k, v in timings.items() if v != float("inf")}
        if not finite:
            raise RuntimeError(
                f"{fam.name}: every candidate failed the numeric "
                f"cross-check at {params} — refusing to tune (kernel "
                "bug, not a slow config)")
        best_key = min(finite, key=lambda k: (finite[k], k))
        best_cfg = dict(best_key)
        best_s = finite[best_key]
        search_info = {"mode": "exhaustive", "candidates": len(cands),
                       "timed": len(cands), "timed_fraction": 1.0}

    rows = []
    for cfg in cands:
        key = _search.config_key(cfg)
        med = timings.get(key)
        rows.append({
            "config": cfg,
            "median_s": med if med != float("inf") else float("inf"),
            "numerics_ok": med != float("inf"),  # untimed: presumed-legal
            "is_default": cfg == default_cfg,
            "timed": key in timings,
        })
    report = {
        "kernel": fam.name,
        "params": params,
        "dtype": dtype,
        "device_kind": _cache.device_kind(),
        "default": default_cfg,
        "best": best_cfg,
        "rows": rows,
        "search": search_info,
    }
    dkey = _search.config_key(default_cfg) if default_cfg else None
    if dkey in timings and timings[dkey] not in (None, float("inf")):
        report["speedup_vs_default"] = (
            timings[dkey] / best_s if best_s > 0 else 1.0)
    if table is not None:
        table.put(fam.name, params, dtype, best_cfg,
                  meta={"median_s": best_s, "iters": iters,
                        "default": default_cfg},
                  provenance=_cache.MEASURED)
    return report


def list_candidates(family: str, params: Dict[str, Any],
                    dtype: str) -> Dict[str, Any]:
    """The --dry-run half: enumerate legal candidates without compiling
    or timing anything (works on any backend)."""
    fam = space.get_family(family)
    params = fam.normalize(params, dtype)
    return {
        "kernel": fam.name,
        "params": params,
        "dtype": dtype,
        "default": fam.default(params),
        "candidates": fam.candidates(params),
    }
