"""The sweep tool: time every legal candidate of a kernel family at a
shape, and rank them.

- each candidate is traced+compiled with its config FORCED
  (overrides.forcing), so the measurement runs the exact path
  production dispatch takes through space.pick;
- warmup runs absorb the compile + first-dispatch cost, then the timed
  runs block on the result (`jax.block_until_ready`) so the timer sees
  device work, not async enqueue (profiler.py's design note);
- the score is the MEDIAN of k timed runs (profiler.Stat keeps the
  samples when asked);
- every candidate's output is cross-checked against the family's
  reference lowering before it may rank: a fast-but-wrong tile (e.g.
  one that silently overflows an accumulator) never wins.

A sweep writes nothing. What it finds becomes a rule in space.py's
default for the family, with the reading in PERF.md. Timing is REFUSED
off-TPU (TuningUnavailable): a CPU or interpret-mode timing says
nothing of the chip.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from .. import profiler
from . import space


class TuningUnavailable(RuntimeError):
    """Raised when timing is requested on a backend that is not the
    chip."""


def ensure_timeable() -> None:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise TuningUnavailable(
            f"refusing to time kernels on backend {backend!r}: a timing "
            "off the TPU says nothing of the chip. Run on TPU hardware, "
            "or use --dry-run to list candidates.")


def device_kind() -> str:
    """jax's device_kind string (e.g. 'TPU v5 lite'), lowercased with
    spaces collapsed: the device a ranking was read on."""
    import jax

    return "-".join(str(jax.devices()[0].device_kind).lower().split())


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
    """The timing oracle over a runnable Case: oracle(config, iters) ->
    median seconds, +inf for a config that failed the numeric
    cross-check (made once, before any timing)."""
    def oracle(config: Dict[str, Any], iters: int) -> float:
        thunk = case.make(config)
        if not _numerics_ok(thunk(), ref, case.tol):
            return float("inf")
        return measure(thunk, iters=iters, warmup=warmup,
                       stat_set=stat_set, name=f"tune/{case.kernel}")

    return oracle


def tune_case(family: str, params: Dict[str, Any], dtype: str,
              iters: int = 5, warmup: int = 2,
              require_tpu: bool = True) -> Dict[str, Any]:
    """Time every legal candidate of one (kernel family, shape, dtype)
    case and return the ranking the CLI prints:

      {kernel, params, dtype, device_kind, default, best,
       speedup_vs_default,
       rows: [{config, median_s, numerics_ok, is_default}, ...]}

    rows are sorted fastest first; a candidate that failed the numeric
    cross-check has median_s inf and ranks last.

    `require_tpu=False` exists for the CPU test suite to exercise the
    loop mechanics in interpret mode; the CLI always requires a TPU.
    """
    fam = space.get_family(family)
    params = fam.normalize(params, dtype)
    if require_tpu:
        ensure_timeable()
    cands = fam.candidates(params)
    if not cands:
        raise ValueError(
            f"{fam.name}: no legal candidates at {params}: the shape "
            "is outside the fused kernel's eligibility entirely")
    case = fam.make_case(params, dtype)
    oracle = make_oracle(case, case.reference(), warmup=warmup)
    default_cfg = fam.default(params)
    inf = float("inf")
    rows = []
    for cfg in cands:
        median_s = oracle(cfg, iters)
        rows.append({"config": cfg, "median_s": median_s,
                     "numerics_ok": median_s != inf,
                     "is_default": cfg == default_cfg})
    rows.sort(key=lambda r: (r["median_s"], sorted(r["config"].items())))
    if rows[0]["median_s"] == inf:
        raise RuntimeError(
            f"{fam.name}: every candidate failed the numeric "
            f"cross-check at {params}: a kernel bug, not a slow config")
    report = {
        "kernel": fam.name,
        "params": params,
        "dtype": dtype,
        "device_kind": device_kind(),
        "default": default_cfg,
        "best": rows[0]["config"],
        "rows": rows,
    }
    default_s = next((r["median_s"] for r in rows if r["is_default"]), inf)
    if default_s != inf and rows[0]["median_s"] > 0:
        report["speedup_vs_default"] = default_s / rows[0]["median_s"]
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
