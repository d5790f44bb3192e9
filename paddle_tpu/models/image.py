"""Image-classification model zoo.

Reference configs (behavioral parity, re-written for the TPU layer DSL):
benchmark/paddle/image/{resnet,vgg,alexnet,googlenet,smallnet_mnist_cifar}.py
and the book image_classification nets (python/paddle/v2/fluid/tests/book/
test_image_classification_train.py). All take an NCHW image Variable and
return logits; callers attach loss/optimizer.
"""

from __future__ import annotations

import paddle_tpu.layers as layers


# ----------------------------------------------------------------- ResNet --
def _cbn_attrs(name):
    """Explicit parameter names for a conv+BN pair (conv `{name}.w_0`,
    BN `{name}_bn.{w_0,b_0,mean,variance}`) so the fused and unfused
    formulations — whose auto-name counters diverge — produce identical
    checkpoints. None falls back to auto-naming."""
    from paddle_tpu.param_attr import ParamAttr

    if name is None:
        return dict(conv_attr=None, bn_name=None, bn_w=None, bn_b=None)
    return dict(
        conv_attr=ParamAttr(name=f"{name}.w_0"),
        bn_name=f"{name}_bn",
        bn_w=ParamAttr(name=f"{name}_bn.w_0"),
        bn_b=ParamAttr(name=f"{name}_bn.b_0"),
    )


def conv_bn_layer(input, num_filters, filter_size, stride=1, padding=None,
                  act="relu", is_test=False, data_format="NCHW", name=None):
    if padding is None:
        padding = (filter_size - 1) // 2
    a = _cbn_attrs(name)
    conv = layers.conv2d(
        input, num_filters=num_filters, filter_size=filter_size,
        stride=stride, padding=padding, bias_attr=False,
        param_attr=a["conv_attr"], data_format=data_format,
    )
    return layers.batch_norm(conv, act=act, is_test=is_test,
                             param_attr=a["bn_w"], bias_attr=a["bn_b"],
                             name=a["bn_name"], data_format=data_format)


def _shortcut(input, ch_out, stride, is_test, data_format="NCHW", name=None):
    ch_in = input.shape[1] if data_format == "NCHW" else input.shape[-1]
    if ch_in != ch_out or stride != 1:
        return conv_bn_layer(input, ch_out, 1, stride, 0, act=None,
                             is_test=is_test, data_format=data_format,
                             name=name)
    return input


def _bottleneck(input, ch_out, stride, is_test, data_format="NCHW",
                name=None):
    from paddle_tpu.flags import FLAGS

    if data_format == "NHWC" and not is_test and FLAGS.use_fused_conv:
        return _bottleneck_fused(input, ch_out, stride, name)
    short = _shortcut(input, ch_out * 4, stride, is_test, data_format,
                      name=name and f"{name}_branch1")
    conv1 = conv_bn_layer(input, ch_out, 1, 1, 0, is_test=is_test,
                          data_format=data_format,
                          name=name and f"{name}_branch2a")
    conv2 = conv_bn_layer(conv1, ch_out, 3, stride, 1, is_test=is_test,
                          data_format=data_format,
                          name=name and f"{name}_branch2b")
    conv3 = conv_bn_layer(conv2, ch_out * 4, 1, 1, 0, act=None,
                          is_test=is_test, data_format=data_format,
                          name=name and f"{name}_branch2c")
    return layers.relu(layers.elementwise_add(conv3, short))


def _bottleneck_fused(input, ch_out, stride, name=None):
    """Bottleneck through the fused raw-stats conv+BN protocol
    (ops/fused_conv_ops.py — the reference's cuDNN-fused-path analogue,
    gserver/layers/CudnnConvBaseLayer.cpp). The two 1x1 convs emit
    their BN stats beside their output; conv3
    additionally applies conv2's BN+ReLU inside its prologue, so conv2's
    output is never materialized normalized. Explicit parameter names
    (shared with the unfused path via _cbn_attrs) keep checkpoints
    interchangeable with the eval-mode (unfused) graph."""

    def fused_cbn(x, filters, stride=1, prologue_act="relu", nm=None):
        a = _cbn_attrs(nm)
        return layers.fused_conv_bn(
            x, filters, stride=stride, prologue_act=prologue_act,
            param_attr=a["conv_attr"], bn_param_attr=a["bn_w"],
            bn_bias_attr=a["bn_b"], name=a["bn_name"])

    ch_in = input.shape[-1]
    has_proj = ch_in != ch_out * 4 or stride != 1
    if has_proj:
        rp = fused_cbn(input, ch_out * 4, stride=stride,
                       nm=name and f"{name}_branch1")
        short = layers.bn_apply(rp, act=None)
    else:
        short = input
    r1 = fused_cbn(input, ch_out, nm=name and f"{name}_branch2a")
    conv1 = layers.bn_apply(r1, act="relu")
    a2 = _cbn_attrs(name and f"{name}_branch2b")
    conv2 = layers.conv2d(conv1, ch_out, 3, stride, 1, bias_attr=False,
                          param_attr=a2["conv_attr"], data_format="NHWC")
    s2 = layers.bn_stats(conv2, param_attr=a2["bn_w"],
                         bias_attr=a2["bn_b"], name=a2["bn_name"])
    r3 = fused_cbn(s2, ch_out * 4, prologue_act="relu",
                   nm=name and f"{name}_branch2c")
    conv3 = layers.bn_apply(r3, act=None)
    return layers.relu(layers.elementwise_add(conv3, short))


def _basicblock(input, ch_out, stride, is_test, data_format="NCHW"):
    short = _shortcut(input, ch_out, stride, is_test, data_format)
    conv1 = conv_bn_layer(input, ch_out, 3, stride, 1, is_test=is_test,
                          data_format=data_format)
    conv2 = conv_bn_layer(conv1, ch_out, 3, 1, 1, act=None, is_test=is_test,
                          data_format=data_format)
    return layers.relu(layers.elementwise_add(conv2, short))


def resnet_imagenet(input, class_dim=1000, depth=50, is_test=False,
                    data_format="NCHW"):
    """ResNet-50/101/152 (reference: benchmark/paddle/image/resnet.py

    layout; bottleneck counts per the standard table). data_format="NHWC"
    runs channels-minor — the TPU-preferred layout (input must then be
    [H, W, C])."""
    cfg = {50: [3, 4, 6, 3], 101: [3, 4, 23, 3], 152: [3, 8, 36, 3]}[depth]
    conv = conv_bn_layer(input, 64, 7, 2, 3, is_test=is_test,
                         data_format=data_format, name="conv1")
    pool = layers.pool2d(conv, pool_size=3, pool_stride=2, pool_padding=1,
                         data_format=data_format)
    ch = [64, 128, 256, 512]
    for stage, count in enumerate(cfg):
        for i in range(count):
            stride = 2 if i == 0 and stage > 0 else 1
            suffix = chr(97 + i) if i < 26 else f"b{i}"  # res4b26... past z
            pool = _bottleneck(pool, ch[stage], stride, is_test, data_format,
                               name=f"res{stage + 2}{suffix}")
    pool = layers.pool2d(pool, pool_type="avg", global_pooling=True,
                         data_format=data_format)
    return layers.fc(pool, size=class_dim)


def resnet_cifar10(input, class_dim=10, depth=32, is_test=False):
    """Reference: book image_classification resnet_cifar10."""
    assert (depth - 2) % 6 == 0
    n = (depth - 2) // 6
    conv = conv_bn_layer(input, 16, 3, 1, 1, is_test=is_test)
    for stage, ch in enumerate([16, 32, 64]):
        for i in range(n):
            stride = 2 if i == 0 and stage > 0 else 1
            conv = _basicblock(conv, ch, stride, is_test)
    pool = layers.pool2d(conv, pool_type="avg", global_pooling=True)
    return layers.fc(pool, size=class_dim)


# -------------------------------------------------------------------- VGG --
def vgg(input, class_dim=1000, depth=16, is_test=False):
    """VGG-16/19 with BN (reference: benchmark/paddle/image/vgg.py)."""
    cfg = {
        11: [1, 1, 2, 2, 2],
        13: [2, 2, 2, 2, 2],
        16: [2, 2, 3, 3, 3],
        19: [2, 2, 4, 4, 4],
    }[depth]
    channels = [64, 128, 256, 512, 512]
    tmp = input
    for block, convs in enumerate(cfg):
        for _ in range(convs):
            tmp = conv_bn_layer(tmp, channels[block], 3, 1, 1, is_test=is_test)
        tmp = layers.pool2d(tmp, pool_size=2, pool_stride=2)
    tmp = layers.fc(tmp, size=4096, act="relu")
    tmp = layers.dropout(tmp, 0.5, is_test=is_test)
    tmp = layers.fc(tmp, size=4096, act="relu")
    tmp = layers.dropout(tmp, 0.5, is_test=is_test)
    return layers.fc(tmp, size=class_dim)


# ---------------------------------------------------------------- AlexNet --
def alexnet(input, class_dim=1000, is_test=False):
    """Reference: benchmark/paddle/image/alexnet.py (conv-lrn-pool x2,

    3 convs, 2 fc4096 + dropout)."""
    t = layers.conv2d(input, 64, 11, stride=4, padding=2, act="relu")
    t = layers.lrn(t)
    t = layers.pool2d(t, pool_size=3, pool_stride=2)
    t = layers.conv2d(t, 192, 5, padding=2, act="relu")
    t = layers.lrn(t)
    t = layers.pool2d(t, pool_size=3, pool_stride=2)
    t = layers.conv2d(t, 384, 3, padding=1, act="relu")
    t = layers.conv2d(t, 256, 3, padding=1, act="relu")
    t = layers.conv2d(t, 256, 3, padding=1, act="relu")
    t = layers.pool2d(t, pool_size=3, pool_stride=2)
    t = layers.fc(t, size=4096, act="relu")
    t = layers.dropout(t, 0.5, is_test=is_test)
    t = layers.fc(t, size=4096, act="relu")
    t = layers.dropout(t, 0.5, is_test=is_test)
    return layers.fc(t, size=class_dim)


# -------------------------------------------------------------- GoogLeNet --
def _inception(input, c1, c3r, c3, c5r, c5, proj):
    b1 = layers.conv2d(input, c1, 1, act="relu")
    b3 = layers.conv2d(input, c3r, 1, act="relu")
    b3 = layers.conv2d(b3, c3, 3, padding=1, act="relu")
    b5 = layers.conv2d(input, c5r, 1, act="relu")
    b5 = layers.conv2d(b5, c5, 5, padding=2, act="relu")
    bp = layers.pool2d(input, pool_size=3, pool_stride=1, pool_padding=1)
    bp = layers.conv2d(bp, proj, 1, act="relu")
    return layers.concat([b1, b3, b5, bp], axis=1)


def googlenet(input, class_dim=1000, is_test=False):
    """Reference: benchmark/paddle/image/googlenet.py (Inception v1; the

    two aux heads are omitted — they only affect training regularization)."""
    t = layers.conv2d(input, 64, 7, stride=2, padding=3, act="relu")
    t = layers.pool2d(t, pool_size=3, pool_stride=2, pool_padding=1)
    t = layers.conv2d(t, 64, 1, act="relu")
    t = layers.conv2d(t, 192, 3, padding=1, act="relu")
    t = layers.pool2d(t, pool_size=3, pool_stride=2, pool_padding=1)
    t = _inception(t, 64, 96, 128, 16, 32, 32)
    t = _inception(t, 128, 128, 192, 32, 96, 64)
    t = layers.pool2d(t, pool_size=3, pool_stride=2, pool_padding=1)
    t = _inception(t, 192, 96, 208, 16, 48, 64)
    t = _inception(t, 160, 112, 224, 24, 64, 64)
    t = _inception(t, 128, 128, 256, 24, 64, 64)
    t = _inception(t, 112, 144, 288, 32, 64, 64)
    t = _inception(t, 256, 160, 320, 32, 128, 128)
    t = layers.pool2d(t, pool_size=3, pool_stride=2, pool_padding=1)
    t = _inception(t, 256, 160, 320, 32, 128, 128)
    t = _inception(t, 384, 192, 384, 48, 128, 128)
    t = layers.pool2d(t, pool_type="avg", global_pooling=True)
    t = layers.dropout(t, 0.4, is_test=is_test)
    return layers.fc(t, size=class_dim)


# ----------------------------------------------------- SmallNet (CIFAR) ---
def smallnet(input, class_dim=10, is_test=False):
    """Reference: benchmark/paddle/image/smallnet_mnist_cifar.py — the

    caffe 'cifar10_quick' net."""
    t = layers.conv2d(input, 32, 5, padding=2, act="relu")
    t = layers.pool2d(t, pool_size=3, pool_stride=2)
    t = layers.conv2d(t, 32, 5, padding=2, act="relu")
    t = layers.pool2d(t, pool_size=3, pool_stride=2, pool_type="avg")
    t = layers.conv2d(t, 64, 5, padding=2, act="relu")
    t = layers.pool2d(t, pool_size=3, pool_stride=2, pool_type="avg")
    t = layers.fc(t, size=64, act="relu")
    return layers.fc(t, size=class_dim)


# ------------------------------------------------------------------ LeNet --
def lenet(input, class_dim=10, is_test=False):
    """Reference: book recognize_digits conv net (nets.simple_img_conv_pool)."""
    t = layers.conv2d(input, 20, 5, act="relu")
    t = layers.pool2d(t, pool_size=2, pool_stride=2)
    t = layers.conv2d(t, 50, 5, act="relu")
    t = layers.pool2d(t, pool_size=2, pool_stride=2)
    return layers.fc(t, size=class_dim)
