"""In-framework A/B: fused conv+BN protocol vs unfused, ResNet-50 train.

Same-process interleaved measurement (cross-process absolutes are not
compared): both programs built and
compiled once, then timed in alternating chained blocks.

Run on TPU: python experiments/exp_fusedresnet.py
"""
import os
import time

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.flags import FLAGS

BATCH = int(os.environ.get("BATCH", 128))
STEPS = int(os.environ.get("STEPS", 40))
REPS = int(os.environ.get("REPS", 3))


def build(fused):
    FLAGS.use_fused_conv = fused
    prog, startup = pt.Program(), pt.Program()
    startup.random_seed = 7
    with pt.program_guard(prog, startup):
        img = pt.layers.data("img", shape=[224, 224, 3])
        label = pt.layers.data("label", shape=[1], dtype=np.int32)
        logits = models.resnet_imagenet(img, class_dim=1000,
                                        data_format="NHWC")
        loss = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, label))
        pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(loss)
    prog.set_amp("bfloat16")
    return prog, startup, loss


def main():
    import jax

    rng = np.random.RandomState(0)
    feed_np = {
        "img": rng.randn(BATCH, 224, 224, 3).astype(np.float32),
        "label": rng.randint(0, 1000, (BATCH, 1)).astype(np.int32),
    }
    progs = {}
    exe = pt.Executor()
    for fused in (False, True):
        progs[fused] = build(fused)
    feed = {k: jax.device_put(v) for k, v in feed_np.items()}
    for v in feed.values():
        np.asarray(v.ravel()[0])  # force h2d now (block_until_ready no-op)

    losses = {}
    for fused in (False, True):
        prog, startup, loss = progs[fused]
        exe.run(startup)
        for _ in range(3):  # compile + warm
            (l,) = exe.run(prog, feed=feed, fetch_list=[loss])
        losses[fused] = float(l)
        assert np.isfinite(l), f"fused={fused} non-finite loss {l}"
    print(f"warm losses: unfused={losses[False]:.4f} "
          f"fused={losses[True]:.4f}", flush=True)

    times = {False: [], True: []}
    for rep in range(REPS):
        for fused in (False, True):
            prog, _, loss = progs[fused]
            t0 = time.perf_counter()
            for _ in range(STEPS):
                (l,) = exe.run(prog, feed=feed, fetch_list=[loss],
                               return_numpy=False)
            float(np.asarray(l))  # single d2h readback forces the chain
            dt = (time.perf_counter() - t0) / STEPS
            times[fused].append(dt)
            print(f"rep{rep} fused={int(fused)}: {dt*1e3:.1f} ms/step "
                  f"({BATCH/dt:.0f} img/s)", flush=True)

    for fused in (False, True):
        best = min(times[fused])
        med = sorted(times[fused])[len(times[fused]) // 2]
        mfu = (3 * 8.2e9 * BATCH / med) / 197e12
        print(f"fused={int(fused)}: median {med*1e3:.1f} ms/step, "
              f"{BATCH/med:.0f} img/s, MFU {mfu*100:.1f}% "
              f"(best {BATCH/best:.0f})")
    print(f"speedup (median): "
          f"{sorted(times[False])[REPS//2]/sorted(times[True])[REPS//2]:.3f}x")


if __name__ == "__main__":
    main()
