"""paddle_tpu.fleetctl: the fleet CONTROL PLANE.

PRs 8-9 built every serving *mechanism* — WarmPool promotion, Fleet
death detection, the JSQ Router, per-replica load snapshots, one
unified obs registry — but nothing decided *policy* (ROADMAP open
item 3; the reference's Go master/pserver layer is the lineage). This
package is that layer:

- `autoscaler` — a control loop over the obs signals the fleet already
  exports (queue depth, slot occupancy, queue age, first-token p99)
  that promotes warm standbys on pressure and retires idle replicas,
  with hysteresis bands and a cooldown after every action.
- `tenancy`    — per-model SLO classes (interactive / batch): priority
  admission (the batch tier sheds before interactive ever queues) and
  per-class JSQ scoring in the Router.
- `rollout`    — zero-downtime model rollout: warm the new artifact
  version in standby replicas, verify the meta.json program
  fingerprint, flip the router atomically, drain the old version.
- `sim`        — in-process simulated replicas speaking the replica
  wire protocol (process-like API): a fake for deterministic
  control-plane tests, imported from `paddle_tpu.fleetctl.sim`.
- `traces`     — seeded, bit-identically replayable load traces
  (diurnal ramps, flash crowds, heavy-tailed request lengths,
  multi-model mixes).

`tenancy` is imported eagerly (serving/batcher.py depends on its
class constants); the rest load lazily so the serving -> tenancy
import never cycles back through this package's heavier modules.
"""

from .tenancy import (BATCH, INTERACTIVE, SLO_CLASSES,  # noqa: F401
                      SLO_HEADER, SLOPolicy, resolve_class)

__all__ = [
    "BATCH",
    "INTERACTIVE",
    "SLO_CLASSES",
    "SLO_HEADER",
    "SLOPolicy",
    "resolve_class",
    "Autoscaler",
    "AutoscalerConfig",
    "RolloutError",
    "RolloutManager",
    "TraceSpec",
    "generate_trace",
]

_LAZY = {
    "Autoscaler": "autoscaler",
    "AutoscalerConfig": "autoscaler",
    "RolloutError": "rollout",
    "RolloutManager": "rollout",
    "TraceSpec": "traces",
    "generate_trace": "traces",
}


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    import importlib

    return getattr(importlib.import_module(f".{mod}", __name__), name)
