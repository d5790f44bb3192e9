"""Breaking the dispatch floor (ISSUE 6): µs/step of the small-cell
LSTM train step through Trainer at scan_window K ∈ {1, 8, 32}.

PERF.md round 4 attributed the reference-grid h256/bs64 LSTM cell to a
30-55 µs/step host-dispatch floor, and the round-5 async loop only HIDES
that floor (the host stops waiting per step, but still issues one
`Executor.run` per step). The scan window removes it: K steps compile
into one lax.scan program, so the host issues 1/K as many dispatches.
This experiment drives the SAME Trainer loop in five arms — sync
(per-step fence), async (cadence fence), scan K ∈ {1, 8, 32} — over a
fixed-seed 2-layer LSTM classifier at the small-cell shape, interleaved
(PERF.md methodology), and records µs/step + the deterministic
dispatch/sync counters to benchmarks/scan_window.json.

Run: python experiments/exp_scan_window.py   (on the TPU;
JAX_PLATFORMS=cpu for a host-overhead-only reading — on CPU the
per-step python/dispatch overhead stands in for the device dispatch
floor, same mechanism, different constant).

Env: STEPS (default 64), BATCH (64), HIDDEN (256), SEQLEN (CPU default
8 to keep compute out of the way; use 100 on TPU for the grid cell),
REPS (3 interleaved rounds).
"""
import json
import os
import time

import numpy as np

STEPS = int(os.environ.get("STEPS", 64))
BATCH = int(os.environ.get("BATCH", 64))
HIDDEN = int(os.environ.get("HIDDEN", 256))
REPS = int(os.environ.get("REPS", 3))


def build(batch, hidden, seqlen, vocab=3000, emb_dim=128):
    import paddle_tpu as pt
    from paddle_tpu import models

    pt.reset()
    prog, startup = pt.Program(), pt.Program()
    startup.random_seed = 11
    with pt.program_guard(prog, startup):
        words = pt.layers.data("words", shape=[-1], dtype=np.int32,
                               lod_level=1, append_batch_size=False)
        label = pt.layers.data("label", shape=[1], dtype=np.int32)
        logits = models.lstm_benchmark_net(
            words, vocab_size=vocab, emb_dim=emb_dim, hidden=hidden,
            max_len=seqlen)
        loss = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, label))
        pt.optimizer.Adam(learning_rate=2e-3).minimize(loss)
    return prog, startup, loss


def make_reader(batch, seqlen, vocab=3000):
    from paddle_tpu.core.lod import LoDArray

    rng = np.random.RandomState(0)
    data = []
    for _ in range(STEPS):
        seqs = [rng.randint(0, vocab, (seqlen,)).astype(np.int32)
                for _ in range(batch)]
        data.append({
            "words": LoDArray.from_sequences(
                seqs, capacity=batch * seqlen, max_seqs=batch),
            "label": rng.randint(0, 2, (batch, 1)).astype(np.int32),
        })

    def reader():
        yield from data
    return reader


def run_arm(mode, interval, window, seqlen):
    import paddle_tpu as pt

    prog, startup, loss = build(BATCH, HIDDEN, seqlen)
    trainer = pt.Trainer(loss, main_program=prog, startup_program=startup)
    reader = make_reader(BATCH, seqlen)
    # pass 0 pays compiles (incl. the committed-sharding variant); the
    # timed passes are steady state
    trainer.train(reader, num_passes=1, log_interval=interval,
                  scan_window=window)
    best = None
    for _ in range(REPS):
        s0, d0 = trainer.host_sync_count, trainer.host_dispatch_count
        t0 = time.perf_counter()
        trainer.train(reader, num_passes=1, log_interval=interval,
                      scan_window=window)
        dt = time.perf_counter() - t0
        rec = {
            "us_per_step": round(1e6 * dt / STEPS, 1),
            "dispatches_per_step": round(
                (trainer.host_dispatch_count - d0) / STEPS, 4),
            "syncs_per_step": round(
                (trainer.host_sync_count - s0) / STEPS, 4),
        }
        if best is None or rec["us_per_step"] < best["us_per_step"]:
            best = rec
    print(f"  {mode:10s} {best['us_per_step']:10.1f} us/step  "
          f"{best['dispatches_per_step']:.3f} disp/step  "
          f"{best['syncs_per_step']:.3f} sync/step")
    return best


def main():
    import jax

    kind = jax.devices()[0].device_kind
    on_cpu = jax.default_backend() == "cpu"
    seqlen = int(os.environ.get("BENCH_SEQLEN" if not on_cpu else "SEQLEN",
                                100 if not on_cpu else 8))
    print(f"device={kind} steps={STEPS} batch={BATCH} hidden={HIDDEN} "
          f"seqlen={seqlen}")
    arms = [
        ("sync", 1, 0),
        ("async", STEPS, 0),
        ("scan_k1", STEPS, 1),
        ("scan_k8", STEPS, 8),
        ("scan_k32", STEPS, 32),
    ]
    out = {
        "experiment": "scan_window_dispatch_floor",
        "device_kind": kind,
        "steps": STEPS, "batch": BATCH, "hidden": HIDDEN, "seqlen": seqlen,
        "arms": {},
    }
    for mode, interval, window in arms:
        out["arms"][mode] = run_arm(mode, interval, window, seqlen)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "scan_window.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
