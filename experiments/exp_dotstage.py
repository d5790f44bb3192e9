"""Stage-selective dot dispatch sweep for the fused conv protocol.

XLA's conv emitter loses ~2x to a plain dot at late-stage shapes
(exp_protomicro: 2048->512 convgen 15.4ms vs dot 8.4ms) while early
stages prefer convs (relayout cost scales with tensor size). Sweep the
N-threshold below which the protocol's 1x1 convs run as 2-D dots
(PT_FUSED_CONV_DOT_MAX_N), with and without the Pallas kernel.

Run on TPU: python experiments/exp_dotstage.py
"""
import os
import time

import numpy as np

BATCH = 128
STEPS = 30


def build():
    import paddle_tpu as pt
    from paddle_tpu import models
    from paddle_tpu.flags import FLAGS

    FLAGS.use_fused_conv = True
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

    rng = np.random.RandomState(0)
    feed = {
        "img": rng.randn(BATCH, 224, 224, 3).astype(np.float32),
        "label": rng.randint(0, 1000, (BATCH, 1)).astype(np.int32),
    }
    feed = {k: jax.device_put(v) for k, v in feed.items()}
    for v in feed.values():
        np.asarray(v.ravel()[0])

    # (dot_max_n, pallas): 6272 = stage5 only; 25088 = stages 4+5;
    # 100352 = stages 3+4+5
    from paddle_tpu.flags import FLAGS

    configs = [(0, "0"), (6272, "0"), (25088, "0"), (100352, "0"),
               (25088, "1"), (6272, "1")]
    variants = {}
    exe = pt.Executor()
    for thr, pal in configs:
        # the op kernel reads these FLAGS at trace time (first run below)
        FLAGS.fused_conv_dot_max_n = thr
        FLAGS.fused_conv_pallas = pal == "1"
        prog, startup, loss = build()
        exe.run(startup)
        for _ in range(2):
            (l,) = exe.run(prog, feed=feed, fetch_list=[loss])
        assert np.isfinite(l)
        variants[(thr, pal)] = (prog, loss)
        print(f"compiled thr={thr} pallas={pal}: loss {float(l):.4f}",
              flush=True)

    for rep in range(2):
        for (thr, pal), (prog, loss) in variants.items():
            t0 = time.perf_counter()
            for _ in range(STEPS):
                (l,) = exe.run(prog, feed=feed, fetch_list=[loss],
                               return_numpy=False)
            float(np.asarray(l))
            dt = (time.perf_counter() - t0) / STEPS
            print(f"rep{rep} thr={thr:6d} pallas={pal}: {dt*1e3:6.1f} "
                  f"ms/step ({BATCH/dt:.0f} img/s)", flush=True)


if __name__ == "__main__":
    main()
