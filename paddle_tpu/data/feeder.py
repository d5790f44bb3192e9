"""DataFeeder: host batches → device-ready feed dicts.

Reference: python/paddle/v2/fluid/data_feeder.py and
paddle/py_paddle/dataprovider_converter.py:25-125 (dense / index /
sequence scanners building Arguments). Here dense slots stack to arrays
and lod_level=1 slots build LoDArray with *bucketed* capacity so XLA
recompiles only when a batch overflows the current bucket (the TPU answer
to the reference's no-padding variable-length batches).
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from .. import profiler
from ..core.lod import LoDArray
from ..core.program import Variable
from ..core.sparse import SparseArray
from ..obs import trace as obs_trace


class DataFeeder:
    def __init__(self, feed_list: Sequence[Variable], bucket: int = 256,
                 max_seqs: int = None):
        self.feed_list = list(feed_list)
        self.bucket = bucket
        self.max_seqs = max_seqs

    def feed(self, batch: List[Sequence]) -> Dict[str, object]:
        """batch: list of samples, each a tuple aligned with feed_list."""
        out = {}
        for slot_idx, var in enumerate(self.feed_list):
            vals = [sample[slot_idx] for sample in batch]
            if getattr(var, "sparse_format", None):
                # sparse_binary/sparse_float slots (SparseBinaryScanner /
                # SparseFloatScanner parity): each sample is a list of
                # active indices, or of (index, value) pairs
                dim = int(var.shape[-1])
                out[var.name] = SparseArray.from_batch(
                    vals, dim=dim, format=var.sparse_format,
                    bucket=self.bucket, dtype=np.dtype(var.dtype),
                )
            elif var.lod_level == 0:
                arr = np.asarray(vals, dtype=np.dtype(var.dtype))
                want = tuple(d for d in var.shape if d != -1)
                if arr.ndim == 1 and want:
                    arr = arr.reshape((len(batch),) + want)
                out[var.name] = arr
            else:
                seqs = [
                    np.asarray(v, dtype=np.dtype(var.dtype)).reshape(
                        (-1,) + tuple(d for d in var.shape[1:] if d != -1)
                    )
                    for v in vals
                ]
                out[var.name] = LoDArray.from_sequences(
                    seqs,
                    bucket=self.bucket,
                    max_seqs=self.max_seqs or len(batch),
                )
        return out


class FeedWindow:
    """K device-committed batches stacked along a leading window axis —
    the unit the windowed (lax.scan) training loop dispatches. `k` may be
    short of the configured window for the ragged tail of a pass (or a
    feed-signature change mid-stream); Executor.run_window compiles one
    extra program per distinct k, which the jit cache absorbs."""

    __slots__ = ("feed", "k")

    def __init__(self, feed, k: int):
        self.feed = feed
        self.k = int(k)

    def slice(self, i: int):
        """One step's feed as a window of 1 (keeps the leading axis) —
        the guard-hot fallback runs these for step-granular recovery."""
        import jax

        return {
            name: jax.tree_util.tree_map(lambda a: a[i:i + 1], v)
            for name, v in self.feed.items()
        }


def _stack_feeds(feeds):
    """Stack K same-signature feed dicts to a leading window axis. The
    leaves are already device-committed, so the stack itself is one
    dispatched device op (issued from the prefetch thread — it overlaps
    the training window in flight)."""
    import jax
    import jax.numpy as jnp

    stacked = {
        name: jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *(f[name] for f in feeds))
        for name in feeds[0]
    }
    return FeedWindow(stacked, len(feeds))


class DevicePrefetcher:
    """Async double-buffered host→device pipeline.

    Reference: DataProvider's double-buffered async loading
    (gserver/dataproviders/DataProvider.h:292,328 — background thread at
    :375 fills a queue while the trainer consumes). TPU version: a daemon
    thread walks the reader, converts batches (optionally through a
    DataFeeder) and jax.device_put's them `depth` batches ahead, so the
    h2d transfer of batch N+1 overlaps the device compute of batch N —
    the single biggest win when the host link is slow.

    Trainer.train runs its input through this by default
    (FLAGS.prefetch_to_device, depth 2) on executors that don't own
    input placement themselves; the committed arrays it yields then skip
    Executor.run's per-feed jnp.asarray normalization entirely.

    Usage::

        for feed in DevicePrefetcher(reader, feeder, depth=2):
            exe.run(prog, feed=feed, ...)
    """

    window = 0  # see __init__

    def __init__(self, reader, feeder=None, depth: int = 2, device=None,
                 window: int = 0):
        self.reader = reader
        self.feeder = feeder
        self.depth = max(1, int(depth))
        self.device = device
        # window > 0: group consecutive same-signature batches and yield
        # FeedWindow objects of up to `window` stacked batches instead of
        # single feed dicts (the scan-window trainer path). depth then
        # counts windows, so the effective prefetch depth in batches is
        # depth*window >= window — the "auto-raised to >= K" guarantee.
        # A signature change (e.g. a LoD bucket overflow) or the end of
        # the pass flushes a partial window.
        self.window = max(0, int(window))

    def __iter__(self):
        import queue as _queue
        import threading

        import jax

        q: "_queue.Queue" = _queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        END, ERR = object(), object()

        def put(v):
            # an array already on the target device must pass through:
            # re-putting a committed device array round-trips its bytes
            # through the host (for a ResNet batch that is 77 MB each way)
            # device=None means "the effective default device" — resolve it
            # so an array committed to a DIFFERENT local device still gets
            # placed (jax.device_put(x, None) is the identity for committed
            # arrays). Resolution handles a string jax_default_device and
            # stays process-local; multi-device (sharded) arrays pass
            # through untouched — re-placing them would gather.
            target = self.device
            if target is None:
                target = jax.config.jax_default_device
                if isinstance(target, str):
                    target = jax.local_devices(backend=target)[0]
                elif target is None:
                    target = jax.local_devices()[0]
            if isinstance(v, jax.Array) and (
                len(v.devices()) > 1 or v.devices() == {target}
            ):
                return v
            return jax.device_put(v, target)

        def read():  # the user's reader, each next() under its span
            batches = iter(self.reader())
            while True:
                with profiler.timer("prefetch.read"):
                    batch = next(batches, END)
                if batch is END:
                    return
                yield batch

        def put_window(buf):
            with profiler.timer("prefetch.window"):
                q.put(_stack_feeds(buf))
            buf.clear()

        def produce():
            from ..core.executor import _feed_signature

            buf, sig = [], None
            try:
                for i, batch in enumerate(read()):
                    if stop.is_set():
                        return
                    if obs_trace._armed:
                        # the SAME index the trainer's step spans carry
                        obs_trace.set_context(batch=i)
                    with profiler.timer("prefetch.batch"):
                        if self.feeder:
                            batch = self.feeder.feed(batch)
                        feed = {k: jax.tree.map(put, v)
                                for k, v in batch.items()}
                    if not self.window:
                        q.put(feed)
                        continue
                    s = _feed_signature(feed)
                    if buf and s != sig:
                        # shape change mid-stream: flush the partial
                        # window so every window stays one compiled shape
                        put_window(buf)
                    sig = s
                    buf.append(feed)
                    if len(buf) == self.window:
                        put_window(buf)
                if buf:  # ragged tail window at pass end
                    put_window(buf)
                q.put(END)
            except BaseException as e:  # surface reader errors to consumer
                q.put((ERR, e))

        t = threading.Thread(target=produce, daemon=True, name="pt-prefetch")
        t.start()
        try:
            while True:
                with profiler.timer("prefetchWait"):
                    item = q.get()
                if item is END:
                    return
                if isinstance(item, tuple) and len(item) == 2 and item[0] is ERR:
                    raise item[1]
                yield item
        finally:
            stop.set()
            # drain so a blocked producer can observe stop and exit
            while not q.empty():
                try:
                    q.get_nowait()
                except _queue.Empty:
                    break
