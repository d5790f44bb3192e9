"""Ablation: where does the fused conv+BN protocol's time go?

Variants (same process, interleaved):
  unfused   — baseline conv2d+batch_norm graph
  proto4d   — raw-stats protocol, 4-D conv_general formulation (default)
  proto2d   — protocol with every eligible 1x1 conv as a 2-D jnp dot
              (fused_conv_dot_max_n=inf): isolates the relayout cost
  pallas    — 2-D dispatch through the hand-written Pallas kernel
Each timed fwd-only and full-train.

Run on TPU: python experiments/exp_fusedresnet2.py
"""
import os
import time

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.flags import FLAGS

BATCH = int(os.environ.get("BATCH", 128))
STEPS = int(os.environ.get("STEPS", 30))


def build(fused, train, dot_max_n=0, pallas=False):
    FLAGS.use_fused_conv = fused
    FLAGS.fused_conv_dot_max_n = dot_max_n
    FLAGS.fused_conv_pallas = pallas
    prog, startup = pt.Program(), pt.Program()
    startup.random_seed = 7
    with pt.program_guard(prog, startup):
        img = pt.layers.data("img", shape=[224, 224, 3])
        label = pt.layers.data("label", shape=[1], dtype=np.int32)
        logits = models.resnet_imagenet(img, class_dim=1000,
                                        data_format="NHWC")
        loss = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, label))
        if train:
            pt.optimizer.Momentum(learning_rate=0.1,
                                  momentum=0.9).minimize(loss)
    prog.set_amp("bfloat16")
    return prog, startup, loss


def main():
    import jax

    rng = np.random.RandomState(0)
    feed = {
        "img": rng.randn(BATCH, 224, 224, 3).astype(np.float32),
        "label": rng.randint(0, 1000, (BATCH, 1)).astype(np.int32),
    }
    feed = {k: jax.device_put(v) for k, v in feed.items()}
    for v in feed.values():
        np.asarray(v.ravel()[0])

    BIG = 1 << 30
    configs = {}
    for train in (False, True):
        t = "train" if train else "fwd"
        configs[f"unfused-{t}"] = (False, train, 0, False)
        configs[f"proto4d-{t}"] = (True, train, 0, False)
        configs[f"proto2d-{t}"] = (True, train, BIG, False)
        configs[f"pallas-{t}"] = (True, train, BIG, True)

    exe = pt.Executor()
    variants = {}
    for name, cfg in configs.items():
        # the op kernels read the dispatch FLAGS at TRACE time (the first
        # exe.run), so each variant must build AND warm before the next
        # variant's flags are set
        prog, startup, loss = build(*cfg)
        exe.run(startup)
        for _ in range(2):
            (l,) = exe.run(prog, feed=feed, fetch_list=[loss])
        assert np.isfinite(l), f"{name}: loss {l}"
        print(f"compiled {name}: loss {float(l):.4f}", flush=True)
        variants[name] = (prog, startup, loss)

    for rep in range(2):
        for name, (prog, startup, loss) in variants.items():
            t0 = time.perf_counter()
            for _ in range(STEPS):
                (l,) = exe.run(prog, feed=feed, fetch_list=[loss],
                               return_numpy=False)
            float(np.asarray(l))
            dt = (time.perf_counter() - t0) / STEPS
            print(f"rep{rep} {name}: {dt*1e3:.1f} ms/step", flush=True)


if __name__ == "__main__":
    main()
