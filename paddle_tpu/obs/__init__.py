"""paddle_tpu.obs: run-wide observability.

The layer every scale-out PR instruments instead of growing one-off
counters (ISSUE 8). Three pieces:

- `trace`     — structured span tracing: bounded per-thread ring
                buffers, zero-cost disarmed (the resilience.faults
                contract), correlation ids propagated across thread
                hand-offs, Chrome trace-event JSON export for
                Perfetto / chrome://tracing. One of the three sinks of
                the step path's one span primitive,
                `profiler.StatSet.timer` (the others: the StatSet, and
                a `jax.profiler.TraceAnnotation`, which every armed
                span here is too: a `jax.profiler` capture holds the
                host spans on the device's clock).
- `metrics`   — ONE process-wide MetricsRegistry unifying the global
                profiler.StatSet, trainer dispatch/sync/checkpoint/
                guard counters, fault-registry hit/fire counts, and
                the serving histograms/gauges behind one compliant
                Prometheus text renderer; serving `/metrics` is a view
                of it, training runs log/dump the same surface.
- `promparse` — a minimal Prometheus text parser: the smoke test that
                proves the renderer's output round-trips, and the
                `paddle_tpu stats` pretty-printer.

Quick start::

    from paddle_tpu import obs

    with obs.tracing("/tmp/run.trace.json"):
        trainer.train(...)            # spans land per thread
    # open the JSON in https://ui.perfetto.dev

    print(obs.registry().render())    # the unified Prometheus text
"""

from . import metrics  # noqa: F401
from . import promparse  # noqa: F401
from . import trace  # noqa: F401
from .metrics import MetricsRegistry, registry  # noqa: F401
from .trace import Trace, span, tracing, validate_chrome_trace  # noqa: F401

__all__ = [
    "MetricsRegistry",
    "Trace",
    "metrics",
    "promparse",
    "registry",
    "span",
    "trace",
    "tracing",
    "validate_chrome_trace",
]
