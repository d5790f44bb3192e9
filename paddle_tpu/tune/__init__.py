"""paddle_tpu.tune: empirical kernel autotuner with a persistent
per-device config cache.

Why this exists: every fused Pallas kernel in the repo picks its tile
sizes from hand-derived analytic cost models (`_bblk` in
ops/bahdanau_kernels.py, `_v5e_block_sizes` in ops/flash_ops.py, the
measured H-windows in ops/pallas_kernels.py). Those models encode one device generation's
measurements — the bahdanau comment itself records a 256k-vs-217k tok/s
gap found only by hand-sweeping PT_ATTN_BBLK. CLBlast (arXiv:1705.05249)
and the per-shape serving buckets in paddle_tpu.serving both apply the
same lesson: empirical per-device, per-shape search beats analytic
defaults across hardware generations, IF the search result is cached and
consulted as a first-class input to dispatch.

Module layout:

  space.py     per-kernel candidate generators. The legality predicates
               (Mosaic tile rules + the VMEM-budget models) are defined
               HERE and imported by the runtime kernels, so the tuner
               can never emit a config the runtime would reject, and the
               runtime can never accept a config the tuner can't
               enumerate.
  harness.py   the measurement loop: compile each candidate, warm up,
               median-of-k wall timing via profiler.StatSet, numeric
               cross-check against the reference lowering. REFUSES to
               time on non-TPU backends (a CPU timing would poison the
               per-device table) — lookups then fall back to analytic
               defaults deterministically. The timing oracle is
               INJECTABLE (make_oracle builds the real one), so the
               search quality is testable on recorded timings in the
               CPU suite.
  search.py    Autotuner v2's guided searcher: a lightweight cost model
               (HBM traffic + grid overhead + VMEM-pressure features
               from space.py's legality model) ranks candidates, and
               successive halving with early stop times only the
               top-ranked fraction — >= 95% of exhaustive quality at
               <= 40% of the space (tests/test_tune_search.py).
  cache.py     the persistent JSON table keyed by (kernel,
               shape-signature, dtype, device_kind): atomic writes,
               schema versioning, corrupt-file recovery, an in-process
               LRU front. Also the fleet EXCHANGE format: entry meta
               carries provenance (measured/interpolated) + updated_at,
               and merge_entry resolves conflicts measured-first,
               newest-second (tune export/import/merge CLI).
  overrides.py the one consult point kernels call at trace time:
               forced override (programmatic or env, e.g. PT_ATTN_BBLK)
               -> exact table (local, then the pre-tuned base table the
               package ships per device_kind under tune/tables/) ->
               nearest-shape interpolation re-validated against the
               target's legality -> None (analytic default). Records
               per-source consult counts (pt_tune_consults_total) and
               exports the fingerprint the Executor folds into its jit
               cache key, so flipping ANY kernel knob re-traces instead
               of silently reusing a stale tile choice.

CLI: `python -m paddle_tpu tune --kernel bahdanau --shape B=256,S=60,\
A=512,C=512 [--dry-run] [--search guided|exhaustive]`, plus
`tune export/import/merge` for moving tables between fleet hosts —
see cli.py.
"""

from . import cache  # noqa: F401
from . import space  # noqa: F401
from . import overrides  # noqa: F401
from . import harness  # noqa: F401
from . import search  # noqa: F401
from .cache import TunedTable, device_kind  # noqa: F401
from .harness import TuningUnavailable, make_oracle, tune_case  # noqa: F401
from .overrides import force, forcing, lookup  # noqa: F401
from .search import (SimulatedOracle, guided_search,  # noqa: F401
                     predicted_cost, rank_candidates)
