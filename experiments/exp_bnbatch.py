"""ResNet escape route B: batch sweep with restructured BN stats.

VERDICT r3 #4: bs 128/256/512 x {f32-upcast stats (default), bf16-compute
stats with f32 reduction accumulation} — the one unexplored path to >35%
on train-mode-BN ResNet-50 named by PERF.md r3. FLAGS.bn_bf16_stats
switches batch_norm's stats pass to square in bf16 and reduce with f32
accumulation (jnp.mean/var dtype=f32 over the bf16 activation).

Run on TPU: python experiments/exp_bnbatch.py
"""
import os
import time

import numpy as np

STEPS = {128: 30, 256: 15, 512: 8}


def build(batch):
    import paddle_tpu as pt
    from paddle_tpu import models

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

    import paddle_tpu as pt

    exe = pt.Executor()
    for batch in (128, 256, 512):
        rng = np.random.RandomState(0)
        feed = {
            "img": rng.randn(batch, 224, 224, 3).astype(np.float32),
            "label": rng.randint(0, 1000, (batch, 1)).astype(np.int32),
        }
        feed = {k: jax.device_put(v) for k, v in feed.items()}
        for v in feed.values():
            np.asarray(v.ravel()[0])
        steps = STEPS[batch]
        for bf16_stats in ("0", "1"):
            __import__("paddle_tpu").flags.FLAGS.bn_bf16_stats = bf16_stats == "1"
            prog, startup, loss = build(batch)
            exe.run(startup)
            for _ in range(2):
                (l,) = exe.run(prog, feed=feed, fetch_list=[loss])
            assert np.isfinite(l), f"bs{batch} bf16_stats={bf16_stats}: {l}"
            for rep in range(2):
                t0 = time.perf_counter()
                for _ in range(steps):
                    (l,) = exe.run(prog, feed=feed, fetch_list=[loss],
                                   return_numpy=False)
                float(np.asarray(l))
                dt = (time.perf_counter() - t0) / steps
                mfu = (3 * 8.2e9 * batch / dt) / 197e12
                print(f"bs={batch} bf16_stats={bf16_stats} rep{rep}: "
                      f"{dt*1e3:6.1f} ms/step {batch/dt:7.0f} img/s "
                      f"MFU {mfu*100:.1f}%", flush=True)
        del feed


if __name__ == "__main__":
    main()
