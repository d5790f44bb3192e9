"""Fused conv+BN protocol parity (ops/fused_conv_ops.py).

Reference: the cuDNN fused conv path (gserver/layers/CudnnConvBaseLayer.cpp)
— the reference's conv hot path is never naive composed ops. Here the
fused raw-stats formulation (1x1 convs with BN prologue/epilogue) must
match the unfused conv2d+batch_norm formulation:
forward losses, gradients, running-stat updates, and checkpoint parameter
names (so train-mode fused checkpoints load into eval-mode unfused
graphs).
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu.flags import FLAGS


def _build_tower(fused, batch=8, hw=8, cin=16, ch=8, seed=5):
    """Two stacked bottleneck blocks (one with projection+stride) ending
    in a mean loss; returns (loss_var, feed, param_names)."""
    pt.reset()
    FLAGS.use_fused_conv = fused
    from paddle_tpu.models.image import _bottleneck

    pt.default_startup_program().random_seed = seed
    x = pt.layers.data("x", shape=[hw, hw, cin])
    t = _bottleneck(x, ch, stride=2, is_test=False, data_format="NHWC",
                    name="blk1")
    t = _bottleneck(t, ch, stride=1, is_test=False, data_format="NHWC",
                    name="blk2")
    loss = pt.layers.mean(t)
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(batch, hw, hw, cin).astype(np.float32)}
    return loss, feed


def _train_steps(fused, steps=3, **kw):
    loss, feed = _build_tower(fused, **kw)
    opt = pt.optimizer.Momentum(learning_rate=0.05, momentum=0.9)
    opt.minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    losses = []
    for _ in range(steps):
        (l,) = exe.run(feed=feed, fetch_list=[loss])
        losses.append(float(l))
    scope = pt.core.executor.global_scope()
    params = {}
    for name in sorted(pt.default_main_program().global_block().vars):
        if name not in scope.vars or not getattr(
                pt.default_main_program().global_block().var(name),
                "persistable", False):
            continue
        # optimizer accumulators carry an auto-counter prefix that
        # legitimately differs between builds; key them by param suffix
        key = ("velocity." + name.split(".velocity.", 1)[1]
               if ".velocity." in name else name)
        if key.endswith(".lr"):
            continue
        params[key] = np.asarray(scope.vars[name])
    return losses, params


def test_fused_matches_unfused_training():
    """3 momentum steps: identical init -> losses, every parameter, and
    every BN running stat agree between the two formulations."""
    losses_u, params_u = _train_steps(fused=False)
    losses_f, params_f = _train_steps(fused=True)
    np.testing.assert_allclose(losses_f, losses_u, rtol=2e-4, atol=2e-5)
    assert set(params_f) == set(params_u), (
        "checkpoint name parity broken: "
        f"{set(params_f) ^ set(params_u)}")
    for name in params_u:
        np.testing.assert_allclose(
            params_f[name], params_u[name], rtol=5e-3, atol=5e-4,
            err_msg=name)


def test_fused_train_checkpoint_loads_into_eval_graph(tmp_path):
    """Train fused (NHWC train graph), save params, rebuild is_test=True
    (always unfused) and load — names must line up and eval must run."""
    loss, feed = _build_tower(fused=True)
    pt.optimizer.SGD(learning_rate=0.01).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    exe.run(feed=feed, fetch_list=[loss])
    pt.io.save_params(str(tmp_path), pt.default_main_program())

    pt.reset()
    from paddle_tpu.models.image import _bottleneck

    x = pt.layers.data("x", shape=[8, 8, 16])
    t = _bottleneck(x, 8, stride=2, is_test=True, data_format="NHWC",
                    name="blk1")
    t = _bottleneck(t, 8, stride=1, is_test=True, data_format="NHWC",
                    name="blk2")
    out = pt.layers.mean(t)
    exe2 = pt.Executor()
    exe2.run(pt.default_startup_program())
    pt.io.load_params(str(tmp_path), pt.default_main_program())
    (v,) = exe2.run(feed=feed, fetch_list=[out])
    assert np.isfinite(v)


def test_resnet_builds_fused_nhwc():
    """resnet_imagenet NHWC train graph contains fused_conv_bn ops; the
    NCHW and eval graphs contain none."""
    pt.reset()
    FLAGS.use_fused_conv = True
    from paddle_tpu import models

    x = pt.layers.data("img", shape=[224, 224, 3])
    models.resnet_imagenet(x, class_dim=10, data_format="NHWC")
    ops = [op.type for op in pt.default_main_program().global_block().ops]
    assert ops.count("fused_conv_bn") == 36  # 16 bottlenecks x 2 + 4 proj
    assert ops.count("bn_stats") == 16

    pt.reset()
    x = pt.layers.data("img", shape=[3, 224, 224])
    models.resnet_imagenet(x, class_dim=10, data_format="NCHW")
    ops = [op.type for op in pt.default_main_program().global_block().ops]
    assert ops.count("fused_conv_bn") == 0
