"""ServingEngine: shape-bucketed inference over a saved model.

Reference surface: paddle/capi drives ONE request at a time through the
inference runtime (gradient_machine.h:27-94 forward per request); this
engine is the concurrent-traffic half the reference never needed to
solve for a jitted-XLA backend. The problem is compile-cache blowup:
the Executor jits one XLA program per feed-shape signature
(core/executor.py `_feed_signature`), so serving raw traffic — every
request a different batch size / sequence length — would compile an
unbounded program set and spend seconds of trace time on the tail of
novel shapes.

The fix is the same per-configuration discipline CLBlast applies to
per-shape kernel tuning (PAPERS.md): quantize the shape space into a
small set of BUCKETS, pad every request up to its bucket, and let the
Executor's cache converge onto at most `len(buckets)` programs. Batch
sizes bucket to powers of two (bounded by `max_batch_size`); sequence
lengths bucket to an explicit user list (opt-in, because padding a
sequence dim is only transparent for position-wise or mask-consuming
models — the serving contract states it, README "Serving").

Padding policy:
- batch axis (0): EDGE-replicate the last real row. Zero rows can
  manufacture non-finite values in padded lanes (l2_normalize divides
  by a zero norm) which FLAGS.check_nan_inf would then flag; a
  replicated row is always as finite as the real traffic.
- sequence axis: ZERO-pad. Masked models treat zeros as padding
  already; position-wise models never mix positions.
Outputs are sliced back to the request's true batch/sequence extents,
so callers never see bucket geometry.

Cache accounting is two-level: the engine counts bucket-key hits and
misses (a miss = the first time a bucket signature is seen = one XLA
compile), and the Executor itself counts jit-cache hits/misses
(`Executor.cache_stats`) — the two must agree, and `stats()` exposes
both so a divergence (e.g. a trace-affecting flag flipped mid-serve)
is visible in /metrics rather than silent.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.executor import Executor, Scope
from ..core.lod import LoDArray
from ..io import load_inference_model
from .. import profiler
from ..resilience import faults
from .metrics import MetricSet

__all__ = ["BucketPolicy", "ServingEngine"]


def _pow2_buckets(max_batch_size: int) -> Tuple[int, ...]:
    out, b = [], 1
    while b < max_batch_size:
        out.append(b)
        b *= 2
    out.append(max_batch_size)
    return tuple(out)


class BucketPolicy:
    """Quantizes request shapes onto the bounded bucket grid.

    `batch_buckets` defaults to the powers of two up to
    `max_batch_size` (inclusive — a non-power-of-two max is itself the
    last bucket, so the micro-batcher's full batches never re-pad).
    `seq_len_buckets` is empty by default: sequence bucketing is opt-in
    and applies to feed axis `seq_axis` of every array with more than
    `seq_axis` dimensions."""

    def __init__(
        self,
        max_batch_size: int = 64,
        batch_buckets: Optional[Sequence[int]] = None,
        seq_len_buckets: Sequence[int] = (),
        seq_axis: int = 1,
    ):
        if max_batch_size < 1:
            raise ValueError(f"max_batch_size must be >= 1, got "
                             f"{max_batch_size}")
        self.max_batch_size = max_batch_size
        self.batch_buckets = tuple(sorted(
            batch_buckets if batch_buckets is not None
            else _pow2_buckets(max_batch_size)))
        if not self.batch_buckets:
            raise ValueError("batch_buckets must not be empty")
        self.seq_len_buckets = tuple(sorted(seq_len_buckets))
        self.seq_axis = seq_axis

    def batch_bucket(self, n: int) -> int:
        for b in self.batch_buckets:
            if n <= b:
                return b
        raise ValueError(
            f"request batch {n} exceeds the largest batch bucket "
            f"{self.batch_buckets[-1]}; split the request or raise "
            f"max_batch_size")

    def seq_bucket(self, t: int) -> int:
        for b in self.seq_len_buckets:
            if t <= b:
                return b
        # beyond the configured grid (or no grid): serve the exact
        # length — correctness first, one extra compile per novel tail
        # length, and the miss shows up in the cache accounting
        return t

    def max_programs(self, num_seq_lens: int = 0) -> int:
        """Upper bound on compiled programs for in-grid traffic."""
        s = max(1, len(self.seq_len_buckets)) if num_seq_lens == 0 \
            else num_seq_lens
        return len(self.batch_buckets) * s


class ServingEngine:
    """Owns one loaded model: scope + program + Executor + bucket cache.

    Thread-safe: `predict` serializes on an internal lock (one XLA
    computation runs at a time per engine; concurrency above this layer
    comes from the micro-batcher coalescing requests INTO a call, not
    from parallel calls)."""

    def __init__(
        self,
        model_dir: str,
        policy: Optional[BucketPolicy] = None,
        model_name: str = "default",
        metrics: Optional[MetricSet] = None,
        mesh=None,
        batch_axis: Optional[str] = None,
        quantize: Optional[str] = None,
    ):
        self.model_name = model_name
        self.model_dir = model_dir
        self.policy = policy or BucketPolicy()
        self.scope = Scope()
        self.program, self.feed_names, self.fetch_names = (
            load_inference_model(model_dir, scope=self.scope)
        )
        # low-precision fast path (quant/): `quantize="int8"` asserts
        # the artifact IS a converted one (quant sidecar present —
        # load_inference_model already validated scales against the
        # program) rather than quietly serving the fp program at fp
        # cost. A quantized artifact also serves fine WITHOUT the knob:
        # it is just a program + params; the knob is the operator's
        # declared intent, so a misrouted fp artifact fails here.
        # artifact identity: the exporter's program fingerprint
        # (meta.json since the fleet-control PR); recomputed for older
        # artifacts so /healthz "versions" always has a value — this is
        # what a zero-downtime rollout verifies before flipping traffic
        from ..io import program_fingerprint as _pfp

        self.fingerprint = (
            getattr(self.program, "_program_fingerprint", None)
            or _pfp(self.program))
        self.quant_meta = getattr(self.program, "_quant_meta", None)
        self.quantize = quantize
        if quantize is not None:
            if quantize != "int8":
                raise ValueError(
                    f"unsupported quantize mode {quantize!r} (only "
                    "'int8')")
            if not self.quant_meta:
                raise ValueError(
                    f"model {model_name!r}: quantize='int8' requested "
                    f"but {model_dir} carries no quant sidecar — run "
                    "`paddle_tpu quant --model_dir <fp artifact> --out "
                    "<dir>` and serve the converted artifact")
            if self.quant_meta.get("mode") != quantize:
                raise ValueError(
                    f"model {model_name!r}: artifact was quantized as "
                    f"{self.quant_meta.get('mode')!r}, not {quantize!r}")
        if self.quant_meta:
            # the replica's /metrics advertises the quant footprint it
            # dispatches (pt_quant_* via the obs registry collector)
            from .. import quant as _quant

            _quant.note_serving(self.quant_meta)
        # mesh-sharded replica (scale-out serving): with `mesh` given,
        # the engine runs over ParallelExecutor — parameters carrying a
        # partition spec (restored by load_inference_model from the
        # artifact's sharding sidecar) are placed sharded over the mesh,
        # everything else replicated, so ONE large model serves across
        # chips while the HTTP surface stays identical to a one-device
        # replica. batch_axis defaults to "dp" when the mesh has it,
        # else feeds are effectively replicated (dp absent ⇒ no feed
        # axis to shard over).
        self.mesh = mesh
        self.sharding_meta = getattr(self.program, "_sharding_meta", None)
        if mesh is not None:
            from ..parallel.data_parallel import ParallelExecutor
            from ..parallel.mesh import DP

            axis_names = tuple(mesh.axis_names)
            missing = [
                a for a in (self.sharding_meta or {}).get("mesh_axes", [])
                if a not in axis_names
            ]
            if missing:
                raise ValueError(
                    f"model {model_name!r} was exported with parameters "
                    f"sharded over mesh axes {missing} which the serving "
                    f"mesh {axis_names} does not have")
            if batch_axis is None:
                batch_axis = DP if DP in axis_names else axis_names[0]
            d = int(mesh.shape.get(batch_axis, 1))
            if d > 1:
                bad = [b for b in self.policy.batch_buckets if b % d]
                if bad:
                    raise ValueError(
                        f"batch buckets {bad} are not divisible by the "
                        f"mesh's {batch_axis}={d} axis; pass a policy "
                        f"whose buckets are multiples of {d}")
            self.batch_axis = batch_axis
            self.exe: Executor = ParallelExecutor(
                mesh=mesh, batch_axis=batch_axis)
        else:
            self.batch_axis = None
            self.exe = Executor()
        self.feed_specs: Dict[str, Dict[str, Any]] = {}
        # meta.json (io.save_inference_model) records feed dtypes/shapes
        # since the serving PR; older artifacts fall back to program vars
        meta = getattr(self.program, "_serving_meta", None)
        for n in self.feed_names:
            spec = (meta or {}).get(n) if meta else None
            if spec is None:
                try:
                    v = self.program.global_block().var(n)
                    spec = {"dtype": np.dtype(v.dtype).name,
                            "shape": list(v.shape)}
                except KeyError:
                    spec = {"dtype": "float32", "shape": []}
            self.feed_specs[n] = spec
        # generation sidecar (io.save_inference_model since the
        # continuous-batching PR): beam geometry + decode-state specs so
        # the scheduler can allocate its slot pool without re-tracing
        self.generation_meta = getattr(self.program, "_generation_meta",
                                       None)
        # draft-model sidecar (io.save_inference_model(draft_model=...)
        # since serving v3): the exporter's recommended speculative-
        # decoding companion; the scheduler resolves it relative to
        # model_dir unless overridden by --draft_model
        self.draft_meta = getattr(self.program, "_draft_meta", None)
        from ..ops import generation_ops as _G

        _gen_op = _G.find_generation_op(self.program)
        self._gen_spec = (_G.gen_spec_from_op(_gen_op)
                          if _gen_op is not None else None)
        self._scheduler = None
        self.metrics = metrics or MetricSet(
            stat_set=profiler.global_stat_set())
        self._lock = threading.RLock()
        self._seen_buckets: Dict[tuple, int] = {}
        self.cache_hits = 0
        self.cache_misses = 0
        # uniform dispatch/sync accounting (Trainer.dispatches_total /
        # syncs_total parity): every predict issues one XLA dispatch and
        # — because it returns numpy — pays exactly one d2h fence. bench
        # and the Prometheus surface read the SAME counters the trainer
        # A/B tests assert on, so "how often does the host wait" means
        # one thing across training and serving.
        self.dispatches_total = 0
        self.syncs_total = 0
        self._lat = self.metrics.histogram(
            "engine_run_seconds",
            help="end-to-end ServingEngine.predict latency (pad + XLA "
                 "run + slice)")
        # pre-register every counter this engine can emit so a scraper
        # never sees a missing series before the first request
        self.metrics.declare_counter(
            "compile_cache_hits_total",
            help="requests served by an already-compiled bucket program")
        self.metrics.declare_counter(
            "compile_cache_misses_total",
            help="requests that triggered a bucket compile")
        self.metrics.declare_counter(
            "dispatches_total",
            help="XLA program dispatches issued by this engine")
        self.metrics.declare_counter(
            "syncs_total",
            help="host d2h fences paid by this engine (numpy fetch "
                 "per predict)")

    # ------------------------------------------------------------------
    def set_feed_specs(self, specs: Dict[str, Dict[str, Any]]) -> None:
        self.feed_specs.update(specs)

    def coerce_feed(self, inputs: Dict[str, Any]) -> Dict[str, np.ndarray]:
        """JSON-side input conversion: nested lists → ndarrays at the
        model's declared feed dtype (ids stay int32, not float64)."""
        feed = {}
        for n in self.feed_names:
            if n not in inputs:
                raise KeyError(f"missing input {n!r}; model "
                               f"{self.model_name} feeds {self.feed_names}")
            dt = np.dtype(self.feed_specs.get(n, {}).get("dtype", "float32"))
            feed[n] = np.asarray(inputs[n], dtype=dt)
        return feed

    # ------------------------------------------------------------------
    def _pad_feed(self, feed: Dict[str, np.ndarray]):
        """Returns (padded feed, n_rows, per-feed original seq lens)."""
        pol = self.policy
        rows = {k: v.shape[0] for k, v in feed.items() if v.ndim >= 1}
        if not rows:
            raise ValueError("empty feed")
        n = next(iter(rows.values()))
        if any(r != n for r in rows.values()):
            raise ValueError(
                f"serving feeds must share the batch axis; got rows "
                f"{rows}")
        nb = pol.batch_bucket(n)
        padded: Dict[str, np.ndarray] = {}
        seq_lens: Dict[str, int] = {}
        for k, v in feed.items():
            if isinstance(v, LoDArray):
                raise TypeError(
                    "LoD feeds are not supported by the serving engine "
                    "yet; pad ragged requests client-side")
            if v.ndim == 0:
                padded[k] = v  # scalar feed: nothing to bucket
                continue
            pad = [(0, 0)] * v.ndim
            pad[0] = (0, nb - n)
            if pol.seq_len_buckets and v.ndim > pol.seq_axis:
                t = v.shape[pol.seq_axis]
                tb = pol.seq_bucket(t)
                if tb != t:
                    seq_lens[k] = t
                    # zero-pad seq positions AFTER edge-padding batch
                    # rows so padded rows carry real sequence content
                    sp = [(0, 0)] * v.ndim
                    sp[pol.seq_axis] = (0, tb - t)
                    v = np.pad(np.pad(v, pad, mode="edge"), sp)
                    padded[k] = v
                    continue
                seq_lens[k] = t
            padded[k] = np.pad(v, pad, mode="edge") if nb != n else v
        return padded, n, seq_lens

    def _slice_outputs(self, outs: List[np.ndarray], n: int, nb: int,
                       seq_lens: Dict[str, int]):
        """Cut fetches back to the request's true extents. The batch
        axis is sliced when it matches the padded bucket; a padded
        sequence axis is sliced when the fetch kept its length (the
        position-wise contract)."""
        tset = {self.policy.seq_bucket(t) for t in seq_lens.values()}
        tmap = {self.policy.seq_bucket(t): t for t in seq_lens.values()}
        result = []
        for o in outs:
            o = np.asarray(o)
            if o.ndim >= 1 and o.shape[0] == nb and nb != n:
                o = o[:n]
            ax = self.policy.seq_axis
            if (o.ndim > ax and o.shape[ax] in tset
                    and o.shape[ax] != tmap[o.shape[ax]]):
                sl = [slice(None)] * o.ndim
                sl[ax] = slice(0, tmap[o.shape[ax]])
                o = o[tuple(sl)]
            result.append(o)
        return result

    # ------------------------------------------------------------------
    def predict(self, feed: Dict[str, np.ndarray],
                bucketed: bool = True) -> List[np.ndarray]:
        """Run one request (a dict of [n, ...] arrays); returns the
        model's fetches sliced to the request's extents.

        bucketed=False bypasses padding entirely — the exact-shape
        oracle path (one compile per novel shape); tests pin the
        bucketed path's numerics against it."""
        t0 = time.perf_counter()
        with self._lock, profiler.timer(
                f"serving/{self.model_name}/predict", always=True):
            # chaos hook: an armed serving.predict fault is an engine
            # failure — it must fan out to the batch, feed the circuit
            # breaker, and surface as HTTP 500, never wedge the worker
            faults.fire("serving.predict", model=self.model_name)
            if bucketed:
                padded, n, seq_lens = self._pad_feed(feed)
                nb = next(iter(padded.values())).shape[0]
            else:
                padded, seq_lens = dict(feed), {}
                n = nb = next(iter(feed.values())).shape[0]
            key = (self.model_name, tuple(
                (k, padded[k].shape, padded[k].dtype.name)
                for k in sorted(padded)))
            if key in self._seen_buckets:
                self.cache_hits += 1
                self.metrics.counter_inc(
                    "compile_cache_hits_total",
                    help="requests served by an already-compiled "
                         "bucket program")
            else:
                self.cache_misses += 1
                self.metrics.counter_inc(
                    "compile_cache_misses_total",
                    help="requests that triggered a bucket compile")
            self._seen_buckets[key] = self._seen_buckets.get(key, 0) + 1
            self.dispatches_total += 1
            self.syncs_total += 1  # numpy fetches fence the dispatch queue
            self.metrics.counter_inc(
                "dispatches_total",
                help="XLA program dispatches issued by this engine")
            self.metrics.counter_inc(
                "syncs_total",
                help="host d2h fences paid by this engine (numpy fetch "
                     "per predict)")
            outs = self.exe.run(
                self.program,
                feed=padded,
                fetch_list=list(self.fetch_names),
                scope=self.scope,
            )
            outs = self._slice_outputs(outs, n, nb, seq_lens)
        self._lat.observe(time.perf_counter() - t0)
        return outs

    # -- generation (continuous batching) ------------------------------
    def generation_spec(self):
        """The model's beam_search_group GenSpec, or None for
        feed-forward models."""
        return self._gen_spec

    def scheduler(self, **kwargs):
        """The engine's ContinuousScheduler (created + started lazily;
        kwargs apply on first call only — pass max_slots etc. up front
        or build a ContinuousScheduler yourself)."""
        if self._gen_spec is None:
            raise ValueError(
                f"model {self.model_name!r} is not a generation model "
                "(no beam_search_group op)")
        with self._lock:
            if self._scheduler is None:
                from .scheduler import ContinuousScheduler

                self._scheduler = ContinuousScheduler(
                    self, metrics=self.metrics, **kwargs)
            elif kwargs:
                raise ValueError(
                    "scheduler already built; kwargs only apply on the "
                    "first scheduler() call")
            return self._scheduler.start()

    def generate(self, feed: Dict[str, Any],
                 timeout_ms: Optional[float] = None) -> Dict[str, Any]:
        """Run one generation request through the continuous-batching
        scheduler (token-level admission into a shared decode pool —
        per-request results are bit-identical to the batch-mode
        `predict()` decode). Returns {"ids": [n,K,T], "scores": [n,K],
        "lengths": [n,K]}. For streaming, use
        `scheduler().submit(feed).events()`."""
        return self.scheduler().generate(feed, timeout_ms=timeout_ms)

    # ------------------------------------------------------------------
    def _zero_bucket_feed(self, nb: int, tb: Optional[int]):
        """Zero feed at one (batch bucket, seq bucket) geometry, or None
        when the model's feed shapes aren't fully concrete past the
        batch axis (those buckets compile lazily)."""
        pol = self.policy
        feed = {}
        for n in self.feed_names:
            spec = self.feed_specs.get(n) or {}
            dims = list(spec.get("shape", []))[1:]
            if tb is not None and len(dims) >= pol.seq_axis:
                dims[pol.seq_axis - 1] = tb
            if any(not isinstance(d, int) or d <= 0 for d in dims):
                return None
            feed[n] = np.zeros(
                (nb, *dims), np.dtype(spec.get("dtype", "float32")))
        return feed

    def warmup(self) -> int:
        """Pre-compile every bucket program derivable from the model's
        feed specs (zero feeds at each bucket geometry), so live
        traffic never pays a cold trace+compile — the CLI does this at
        startup. For generation models the scheduler's slot machinery
        (pool step + admit + per-bucket prefix programs) warms too.

        Returns the number of bucket programs touched; models whose
        feed shapes aren't fully concrete past the batch axis are
        skipped (their buckets compile lazily)."""
        pol = self.policy
        compiled = 0
        for nb in pol.batch_buckets:
            for tb in (pol.seq_len_buckets or (None,)):
                feed = self._zero_bucket_feed(nb, tb)
                if feed is None:
                    continue
                self.predict(feed)
                compiled += 1
        if self._gen_spec is not None:
            compiled += self.scheduler().warmup()
        return compiled

    def compiled_programs(self) -> int:
        """Number of XLA programs the underlying Executor holds."""
        return self.exe.cache_size()

    def hit_rate(self) -> float:
        total = self.cache_hits + self.cache_misses
        return self.cache_hits / total if total else 0.0

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "model": self.model_name,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "hit_rate": self.hit_rate(),
                "compiled_programs": self.compiled_programs(),
                "dispatches_total": self.dispatches_total,
                "syncs_total": self.syncs_total,
                "executor_cache": dict(self.exe.cache_stats),
                "executor_donation": self.exe.donation_stats,
                "buckets": {
                    "batch": list(self.policy.batch_buckets),
                    "seq_len": list(self.policy.seq_len_buckets),
                },
                "bucket_counts": {
                    str(k[1]): c for k, c in self._seen_buckets.items()
                },
                **({"quant": {
                    "mode": self.quant_meta.get("mode"),
                    "sites": self.quant_meta.get("sites"),
                    "bytes_saved": self.quant_meta.get("bytes_saved"),
                    **({"accuracy_delta":
                        self.quant_meta["accuracy_delta"]}
                       if self.quant_meta.get("accuracy_delta")
                       is not None else {}),
                }} if self.quant_meta else {}),
                **({"mesh": {
                    "axes": {str(a): int(self.mesh.shape[a])
                             for a in self.mesh.axis_names},
                    "batch_axis": self.batch_axis,
                    "sharded_params": sorted(
                        (self.sharding_meta or {}).get("specs", {})),
                }} if self.mesh is not None else {}),
                **({"generation": self._scheduler.stats()}
                   if self._scheduler is not None else {}),
            }
