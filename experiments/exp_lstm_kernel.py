"""Microbench: Pallas fused LSTM vs lax.scan, isolated recurrence, real TPU.

Writes benchmarks/lstm_kernel_microbench.json (the VERDICT-required
evidence for defaulting the fused kernel on). Each timed region chains
many iterations inside one jit and reads a scalar once.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import pallas_kernels
from paddle_tpu.ops.rnn_ops import lstm_scan


def timeit(f, *args, reps=1):
    r = f(*args)
    np.asarray(jax.tree.leaves(r)[0].ravel()[0])
    t0 = time.perf_counter()
    r = f(*args)
    np.asarray(jax.tree.leaves(r)[0].ravel()[0])
    return (time.perf_counter() - t0) / reps


def bench(T, B, H, dtype, reps=30):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(T, B, 4 * H) * 0.1, dtype)
    w = jnp.asarray(rng.randn(H, 4 * H) * 0.05, dtype)
    mask = jnp.ones((T, B), jnp.float32)

    def many(core):
        # chain `reps` evaluations; the carry must REALLY depend on the
        # gradients (tiny nonzero scale, same dtype) or XLA dead-code
        # eliminates the whole backward pass — `x + 0.0 * dx` gets folded
        # and the "fwd+bwd" bench silently times forward only
        def loss(x, w):
            h_seq, (hT, cT) = core(x, mask, w)
            return jnp.sum(hT.astype(jnp.float32))

        @jax.jit
        def run(x, w):
            def body(carry, _):
                x, w = carry
                l, (dx, dw) = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
                eps = jnp.asarray(1e-12, x.dtype)
                return (x + eps * dx, w + eps * dw), l
            (x, w), ls = jax.lax.scan(body, (x, w), None, length=reps)
            return ls[-1]
        return run

    scan_core = lambda x, m, w: lstm_scan(x, m, w, None)
    fused_core = lambda x, m, w: pallas_kernels.lstm_fused(x, m, w)
    t_scan = timeit(many(scan_core), x, w, reps=reps)
    t_fused = timeit(many(fused_core), x, w, reps=reps)
    flops = 3 * 2 * T * B * H * 4 * H  # fwd+bwd ~3x; MACs x2
    row = {
        "T": T, "B": B, "H": H, "dtype": str(dtype.__name__),
        "scan_ms": round(t_scan * 1e3, 3),
        "fused_ms": round(t_fused * 1e3, 3),
        "speedup": round(t_scan / t_fused, 3),
        "fused_tflops": round(flops / t_fused / 1e12, 2),
    }
    print(row, flush=True)
    return row


if __name__ == "__main__":
    rows = [
        bench(100, 128, 512, jnp.bfloat16),
        bench(100, 128, 512, jnp.float32),
        bench(200, 128, 256, jnp.bfloat16),
        bench(50, 256, 512, jnp.bfloat16),
    ]
    out = {
        "bench": "fused LSTM recurrence (fwd+bwd) vs lax.scan, one chip",
        "device": str(jax.devices()[0].device_kind),
        "method": "chained in-jit reps, single d2h readback",
        "rows": rows,
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks",
        "lstm_kernel_microbench.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", path)
