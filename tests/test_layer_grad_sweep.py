"""test_LayerGrad-parity sweep: a finite-difference gradient case for EVERY
public layer fn in ``paddle_tpu.layers.__all__``.

Reference: gserver/tests/test_LayerGrad.cpp (91 TEST blocks, one per layer
family) driven by LayerGradUtil.h:298-306 `testLayerGrad` — the reference's
core correctness oracle perturbs inputs/params per layer and compares
numeric vs analytic gradients. Here every differentiable layer gets a case;
parameter-free layers get a trainable `fc` (or `embedding` for ragged
inputs) injected UPSTREAM so the loss→fc-weight gradient flows through the
layer's VJP — a wrong backward shows up as a wrong fc gradient.

Non-differentiable / decode-only / structural layers are listed in EXEMPT
with a one-line reason each; `test_every_layer_is_covered` asserts the
CASES ∪ EXEMPT partition is exactly __all__, so a newly added layer fails
the suite until it gets a gradient case or a justified exemption.

Composite multi-layer nets are in test_layer_grad.py; this file is the
per-layer sweep.
"""

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.layers as L
from paddle_tpu.core.lod import LoDArray

# ---------------------------------------------------------------- helpers --


def _rng():
    return np.random.RandomState(0)


def _scalar(v):
    return L.mean(L.elementwise_mul(v, v))


def _feed(name, shape, scale=0.5, dtype=np.float32):
    rng = _rng()
    if np.issubdtype(np.dtype(dtype), np.integer):
        return {name: rng.randint(0, 4, shape).astype(dtype)}
    return {name: (rng.randn(*shape) * scale).astype(dtype)}


def _pre(n=3, d=6, name="x"):
    """data [n, d] -> trainable fc(d): injects a param upstream of the
    layer under test so its VJP is exercised via the fc weight grad."""
    x = L.data(name, shape=[d])
    return L.fc(x, size=d), _feed(name, (n, d))


def _pre4(n=2, c=2, h=6, w=6, name="x"):
    """4-D [n, c, h, w] input with a trainable fc upstream."""
    x = L.data(name, shape=[c * h * w])
    hfc = L.fc(x, size=c * h * w)
    return L.reshape(hfc, (n, c, h, w)), _feed(name, (n, c * h * w))


def _pre_seq(lens=(4, 2), d=6, vocab=11, name="ids"):
    """ragged LoD rows with a trainable embedding upstream."""
    ids = L.data(name, shape=[-1], dtype=np.int32, lod_level=1,
                 append_batch_size=False)
    emb = L.embedding(ids, size=[vocab, d])
    rng = _rng()
    feed = {name: LoDArray.from_sequences(
        [rng.randint(0, vocab, (n,)).astype(np.int32) for n in lens],
        bucket=16)}
    return emb, feed


CASES = {}
TOLS = {}  # per-case overrides for (eps, rtol, atol)


def case(fn):
    CASES[fn.__name__[6:]] = fn
    return fn


def register(name, builder, tols=None):
    CASES[name] = builder
    if tols:
        TOLS[name] = tols


# ------------------------------------------------- dense unary, fc-injected -
def _unary(op, **kw):
    def build():
        h, feed = _pre()
        return _scalar(op(h, **kw)), feed
    return build


register("relu", _unary(L.relu))
register("sigmoid", _unary(L.sigmoid))
register("tanh", _unary(L.tanh))
register("softmax", _unary(L.softmax))
register("row_l2_norm", _unary(L.row_l2_norm))
register("l2_normalize", _unary(L.l2_normalize))
register("scale", _unary(L.scale, scale=1.3, bias=0.2))
register("slope_intercept", _unary(L.slope_intercept, slope=-0.7,
                                   intercept=0.3))
# clip kinks at the bounds, so the seeded fc pre-activations must keep a
# margin wider than the eps=1e-2 perturbation can close. At +-0.35 one
# element lands 1.8e-4 from the bound (central differences straddle the
# kink and read ~half the subgradient); +-0.3 leaves a 0.039 margin while
# still clipping 8 of 18 elements, so both branches stay exercised.
register("clip", _unary(L.clip, min=-0.3, max=0.3))
register("mean", _unary(L.mean))
register("sum_cost", _unary(L.sum_cost))
register("reduce_mean", _unary(L.reduce_mean, dim=1))
register("reduce_sum", _unary(L.reduce_sum, dim=0))
register("reshape", _unary(L.reshape, shape=(2, 9)))
register("transpose", _unary(L.transpose, perm=(1, 0)))
register("pad", _unary(L.pad, paddings=[1, 0, 2, 1], pad_value=0.5))
register("crop", _unary(L.crop, offsets=(1, 2), shape=(2, 3)))
register("expand", _unary(L.expand, expand_times=(2, 3)))
register("prelu", _unary(L.prelu, mode="channel"))
register("scale_shift", _unary(L.scale_shift))


@case
def build_sum_to_one_norm():
    h, feed = _pre()
    # positive rows (sigmoid) keep the normalizing denominator away from 0
    return _scalar(L.sum_to_one_norm(L.sigmoid(h))), feed


@case
def build_split():
    h, feed = _pre(3, 6)
    a, b = L.split(h, 2, dim=1)
    return _scalar(L.elementwise_sub(a, b)), feed


@case
def build_concat():
    h, feed = _pre(3, 6)
    return _scalar(L.concat([h, L.tanh(h)], axis=1)), feed


@case
def build_topk():
    # values are differentiable; k = full width keeps the loss invariant
    # under selection-order swaps so central differences see no kink when
    # a perturbation reorders near-equal elements
    h, feed = _pre(3, 6)
    vals, _ = L.topk(h, k=6)
    return _scalar(vals), feed


@case
def build_gather():
    h, feed = _pre(4, 6)
    idx = L.data("idx", shape=[3], dtype=np.int32, append_batch_size=False)
    feed["idx"] = np.array([2, 0, 3], np.int32)
    return _scalar(L.gather(h, idx)), feed


@case
def build_scatter():
    h, feed = _pre(2, 6)
    base = L.data("base", shape=[4, 6], append_batch_size=False)
    idx = L.data("idx", shape=[2], dtype=np.int32, append_batch_size=False)
    feed.update(_feed("base", (4, 6)))
    feed["idx"] = np.array([1, 3], np.int32)
    return _scalar(L.scatter(base, idx, h)), feed


@case
def build_multiplex():
    h, feed = _pre(3, 6)
    ids = L.data("ids", shape=[3], dtype=np.int32, append_batch_size=False)
    feed["ids"] = np.array([0, 1, 0], np.int32)
    return _scalar(L.multiplex([h, L.tanh(h)], ids)), feed


# ------------------------------------------------------ dense binary / misc -
@case
def build_elementwise_add():
    h, feed = _pre()
    return _scalar(L.elementwise_add(h, L.tanh(h))), feed


@case
def build_elementwise_sub():
    h, feed = _pre()
    return _scalar(L.elementwise_sub(h, L.tanh(h))), feed


@case
def build_elementwise_mul():
    h, feed = _pre()
    return _scalar(L.elementwise_mul(h, L.sigmoid(h))), feed


@case
def build_elementwise_div():
    h, feed = _pre()
    # denominator in [0.5, 1.5]: well away from 0
    den = L.scale(L.sigmoid(h), bias=0.5)
    return _scalar(L.elementwise_div(h, den)), feed


@case
def build_matmul():
    h, feed = _pre(3, 6)
    return _scalar(L.matmul(h, h, transpose_y=True)), feed


@case
def build_cos_sim():
    h, feed = _pre()
    return _scalar(L.cos_sim(h, L.tanh(h))), feed


@case
def build_dot_prod():
    h, feed = _pre()
    return _scalar(L.dot_prod(h, L.tanh(h))), feed


@case
def build_out_prod():
    h, feed = _pre(3, 4)
    return _scalar(L.out_prod(h, L.tanh(h))), feed


@case
def build_l2_distance():
    h, feed = _pre()
    return _scalar(L.l2_distance(h, L.tanh(h))), feed


@case
def build_conv_shift():
    h, feed = _pre(3, 6)
    k = L.fc(h, size=3)  # odd-width shift kernel
    return _scalar(L.conv_shift(h, k)), feed


@case
def build_interpolation():
    h, feed = _pre(3, 6)
    w = L.sigmoid(L.fc(h, size=1))
    return _scalar(L.interpolation(h, L.tanh(h), w)), feed


@case
def build_power():
    h, feed = _pre(3, 6)
    base = L.scale(L.sigmoid(h), bias=0.5)  # positive base
    w = L.sigmoid(L.fc(h, size=1))
    return _scalar(L.power(base, w)), feed


@case
def build_scaling():
    h, feed = _pre(3, 6)
    w = L.fc(h, size=1)
    return _scalar(L.scaling(h, w)), feed


@case
def build_convex_comb():
    h, feed = _pre(3, 6)
    w = L.softmax(L.fc(h, size=3))
    return _scalar(L.convex_comb(h, w)), feed


@case
def build_fc():
    x = L.data("x", shape=[5])
    h = L.fc(x, size=4, act="tanh")
    return _scalar(h), _feed("x", (3, 5))


@case
def build_bilinear_tensor_product():
    h, feed = _pre(3, 4)
    return _scalar(L.bilinear_tensor_product(h, L.tanh(h), size=2)), feed


@case
def build_factorization_machine():
    h, feed = _pre(3, 6)
    return _scalar(L.factorization_machine(h, factor_size=3)), feed


@case
def build_selective_fc():
    h, feed = _pre(3, 6)
    mask = L.data("mask", shape=[3, 4], append_batch_size=False)
    feed["mask"] = np.array([[1, 0, 1, 1]] * 3, np.float32)
    return _scalar(L.selective_fc(h, size=4, mask=mask)), feed


# ------------------------------------------------------------------- costs --
@case
def build_square_error_cost():
    h, feed = _pre(3, 4)
    lbl = L.data("lbl", shape=[4])
    feed.update(_feed("lbl", (3, 4)))
    return _scalar(L.square_error_cost(h, lbl)), feed


@case
def build_smooth_l1():
    h, feed = _pre(3, 4)
    lbl = L.data("lbl", shape=[4])
    feed.update(_feed("lbl", (3, 4)))
    return _scalar(L.smooth_l1(h, lbl)), feed


@case
def build_huber_regression_cost():
    h, feed = _pre(3, 4)
    lbl = L.data("lbl", shape=[4])
    feed.update(_feed("lbl", (3, 4)))
    return _scalar(L.huber_regression_cost(h, lbl, delta=1.0)), feed


@case
def build_huber_classification_cost():
    h, feed = _pre(3, 1)
    o = L.fc(h, size=1)
    lbl = L.data("lbl", shape=[1])
    feed["lbl"] = np.array([[1.0], [-1.0], [1.0]], np.float32)
    return _scalar(L.huber_classification_cost(o, lbl)), feed


@case
def build_binary_cross_entropy():
    h, feed = _pre(3, 4)
    p = L.sigmoid(L.fc(h, size=1))
    lbl = L.data("lbl", shape=[1])
    feed["lbl"] = np.array([[1.0], [0.0], [1.0]], np.float32)
    return _scalar(L.binary_cross_entropy(p, lbl)), feed


@case
def build_sigmoid_cross_entropy_with_logits():
    h, feed = _pre(3, 4)
    lbl = L.data("lbl", shape=[4])
    feed["lbl"] = _rng().randint(0, 2, (3, 4)).astype(np.float32)
    return _scalar(L.sigmoid_cross_entropy_with_logits(h, lbl)), feed


@case
def build_cross_entropy():
    h, feed = _pre(3, 5)
    p = L.softmax(h)
    lbl = L.data("lbl", shape=[1], dtype=np.int32)
    feed["lbl"] = np.array([[0], [3], [2]], np.int32)
    return _scalar(L.cross_entropy(p, lbl)), feed


@case
def build_cross_entropy_with_selfnorm():
    h, feed = _pre(3, 5)
    p = L.scale(L.sigmoid(h), bias=0.1)  # positive unnormalized "probs"
    lbl = L.data("lbl", shape=[1], dtype=np.int32)
    feed["lbl"] = np.array([[0], [3], [2]], np.int32)
    return _scalar(L.cross_entropy_with_selfnorm(p, lbl)), feed


@case
def build_softmax_with_cross_entropy():
    h, feed = _pre(3, 5)
    lbl = L.data("lbl", shape=[1], dtype=np.int32)
    feed["lbl"] = np.array([[0], [3], [2]], np.int32)
    return _scalar(L.softmax_with_cross_entropy(h, lbl)), feed


@case
def build_rank_cost():
    h, feed = _pre(3, 4)
    left = L.sigmoid(L.fc(h, size=1))
    right = L.sigmoid(L.fc(h, size=1))
    lbl = L.data("lbl", shape=[1])
    feed["lbl"] = np.array([[1.0], [0.0], [1.0]], np.float32)
    return _scalar(L.rank_cost(left, right, lbl)), feed


@case
def build_margin_rank_loss():
    h, feed = _pre(3, 4)
    x1 = L.fc(h, size=1)
    x2 = L.fc(h, size=1)
    lbl = L.data("lbl", shape=[1])
    feed["lbl"] = np.array([[1.0], [-1.0], [1.0]], np.float32)
    return _scalar(L.margin_rank_loss(x1, x2, lbl, margin=0.1)), feed


@case
def build_lambda_cost():
    h, feed = _pre(2, 6)
    score = L.fc(h, size=4)
    lbl = L.data("lbl", shape=[4])
    feed["lbl"] = np.array([[3.0, 2.0, 1.0, 0.0], [0.0, 1.0, 2.0, 3.0]],
                           np.float32)
    return _scalar(L.lambda_cost(score, lbl, NDCG_num=4)), feed


@case
def build_nce():
    h, feed = _pre(4, 6)
    lbl = L.data("lbl", shape=[1], dtype=np.int32)
    feed["lbl"] = np.array([[0], [3], [2], [1]], np.int32)
    cost = L.nce(h, lbl, num_classes=7, num_neg_samples=3)
    return _scalar(cost), feed


@case
def build_hsigmoid():
    h, feed = _pre(4, 6)
    lbl = L.data("lbl", shape=[1], dtype=np.int32)
    feed["lbl"] = np.array([[0], [3], [2], [1]], np.int32)
    return _scalar(L.hsigmoid(h, lbl, num_classes=7)), feed


# ------------------------------------------------------------- 4-D / conv ---
@case
def build_conv2d():
    x = L.data("x", shape=[2, 6, 6])
    h = L.conv2d(x, num_filters=3, filter_size=3, padding=1)
    return _scalar(h), _feed("x", (2, 2, 6, 6))


@case
def build_conv2d_transpose():
    x = L.data("x", shape=[2, 4, 4])
    h = L.conv2d_transpose(x, num_filters=2, filter_size=3, stride=2,
                           padding=1)
    return _scalar(h), _feed("x", (2, 2, 4, 4))


@case
def build_conv3d():
    x = L.data("x", shape=[2, 3, 4, 4])
    h = L.conv3d(x, num_filters=2, filter_size=3, padding=1)
    return _scalar(h), _feed("x", (2, 2, 3, 4, 4))


@case
def build_batch_norm():
    h4, feed = _pre4()
    return _scalar(L.batch_norm(h4)), feed


@case
def build_stacked_lstm2():
    emb, feed = _pre_seq(lens=(4, 2), d=8)
    h = L.stacked_lstm2(emb, size=8, max_len=8)
    return _scalar(L.sequence_last_step(h)), feed


@case
def build_stacked_lstm():
    # both outputs (last inter-layer fc sequence + last hidden sequence)
    # feed the loss so every weight of the stack gets a grad path
    emb, feed = _pre_seq(lens=(4, 2), d=8)
    fc_out, h = L.stacked_lstm(emb, size=8, stacked_num=2, max_len=8)
    cat = L.concat([L.sequence_last_step(fc_out),
                    L.sequence_last_step(h)], axis=1)
    return _scalar(cat), feed


@case
def build_fused_conv_bn():
    # raw-stats fused conv protocol, no-prologue unit + normalize
    x = L.data("x", shape=[4, 4, 6])
    r = L.fused_conv_bn(x, num_filters=4)
    return _scalar(L.bn_apply(r, act="relu")), _feed("x", (2, 4, 4, 6))


@case
def build_bn_stats():
    # stats-only BN feeding a fused conv's prologue (the conv2->conv3
    # seam of _bottleneck_fused)
    x = L.data("x", shape=[4, 4, 3])
    h = L.conv2d(x, num_filters=4, filter_size=3, padding=1,
                 bias_attr=False, data_format="NHWC")
    s = L.bn_stats(h)
    r = L.fused_conv_bn(s, num_filters=4, prologue_act="relu")
    return _scalar(L.bn_apply(r)), _feed("x", (2, 4, 4, 3))


@case
def build_bn_apply():
    x = L.data("x", shape=[4, 4, 3])
    h = L.conv2d(x, num_filters=4, filter_size=1, bias_attr=False,
                 data_format="NHWC")
    s = L.bn_stats(h)
    return _scalar(L.bn_apply(s, act="relu")), _feed("x", (2, 4, 4, 3))


@case
def build_layer_norm():
    h, feed = _pre(3, 8)
    return _scalar(L.layer_norm(h)), feed


@case
def build_pool2d():
    h4, feed = _pre4()
    return _scalar(L.pool2d(h4, pool_size=2, pool_type="max")), feed


@case
def build_pool3d():
    x = L.data("x", shape=[2, 3, 4, 4])
    h = L.conv3d(x, num_filters=2, filter_size=1)
    return (_scalar(L.pool3d(h, pool_size=2, pool_type="avg")),
            _feed("x", (2, 2, 3, 4, 4)))


@case
def build_maxout():
    h4, feed = _pre4(2, 4, 4, 4)
    return _scalar(L.maxout(h4, groups=2)), feed


@case
def build_lrn():
    h4, feed = _pre4(2, 4, 4, 4)
    return _scalar(L.lrn(h4, n=3)), feed


@case
def build_rotate():
    h4, feed = _pre4(2, 2, 3, 4)
    return _scalar(L.rotate(h4)), feed


@case
def build_switch_order():
    h4, feed = _pre4(2, 2, 3, 4)
    return _scalar(L.switch_order(h4)), feed


@case
def build_bilinear_interp():
    h4, feed = _pre4(2, 2, 4, 4)
    return _scalar(L.bilinear_interp(h4, out_h=7, out_w=7)), feed


@case
def build_im2sequence():
    h4, feed = _pre4(2, 2, 5, 5)
    return _scalar(L.im2sequence(h4, block_y=2, block_x=2, stride_y=1,
                                 stride_x=1)), feed


@case
def build_spp():
    h4, feed = _pre4(1, 2, 6, 6)
    return _scalar(L.spp(h4, pyramid_height=2, pool_type="avg")), feed


@case
def build_roi_pool():
    h4, feed = _pre4(1, 2, 8, 8)
    rois = L.data("rois", shape=[2, 5], append_batch_size=False)
    feed["rois"] = np.array([[0, 0, 0, 5, 5], [0, 2, 2, 7, 7]], np.float32)
    return _scalar(L.roi_pool(h4, rois, pooled_height=2, pooled_width=2)), feed


@case
def build_scale_sub_region():
    h4, feed = _pre4(2, 2, 4, 4)
    # indices: [c0, c1, h0, h1, w0, w1] 1-based inclusive region
    return _scalar(L.scale_sub_region(h4, [1, 1, 2, 3, 2, 3], scale=2.0)), feed


@case
def build_multibox_loss():
    # grads flow to the loc/conf heads (fc weights) through matching+NLL
    k = 4
    feat = L.data("feat", shape=[8])
    priors = L.data("priors", shape=[4], append_batch_size=True)
    pvar = L.data("pvar", shape=[4], append_batch_size=True)
    gt = L.data("gt", shape=[1, 4])
    gtl = L.data("gtl", shape=[1], dtype=np.int32)
    locp = L.fc(feat, size=k * 4)
    confp = L.fc(feat, size=k * 3)
    loss = L.multibox_loss(locp, confp, priors, pvar, gt, gtl,
                           overlap_threshold=0.3)
    feed = _feed("feat", (1, 8))
    feed["priors"] = np.array(
        [[0.1, 0.1, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9],
         [0.2, 0.2, 0.6, 0.6], [0.6, 0.1, 0.9, 0.4]], np.float32)
    feed["pvar"] = np.full((4, 4), 0.1, np.float32)
    feed["gt"] = np.array([[[0.12, 0.1, 0.42, 0.4]]], np.float32)
    feed["gtl"] = np.array([[1]], np.int32)
    return _scalar(loss), feed


# ------------------------------------------------------------- embeddings ---
@case
def build_embedding():
    emb, feed = _pre_seq()
    return _scalar(L.sequence_pool(emb, "sum")), feed


# -------------------------------------------------------- sequence family ---
@case
def build_sequence_pool():
    emb, feed = _pre_seq()
    return _scalar(L.sequence_pool(emb, "average")), feed


@case
def build_sequence_first_step():
    emb, feed = _pre_seq()
    return _scalar(L.sequence_first_step(emb)), feed


@case
def build_sequence_last_step():
    emb, feed = _pre_seq()
    return _scalar(L.sequence_last_step(emb)), feed


@case
def build_sequence_reverse():
    emb, feed = _pre_seq()
    rev = L.sequence_reverse(emb)
    return _scalar(L.sequence_pool(rev, "first")), feed


@case
def build_sequence_reshape():
    emb, feed = _pre_seq(lens=(4, 2), d=6)
    r = L.sequence_reshape(emb, new_dim=3)
    return _scalar(L.sequence_pool(r, "sum")), feed


@case
def build_sequence_concat():
    emb, feed = _pre_seq()
    cat = L.sequence_concat([emb, L.tanh(emb)])
    return _scalar(L.sequence_pool(cat, "sum")), feed


@case
def build_sequence_conv():
    emb, feed = _pre_seq()
    h = L.sequence_conv(emb, num_filters=4, filter_size=3)
    return _scalar(L.sequence_pool(h, "sum")), feed


@case
def build_sequence_expand():
    emb, feed = _pre_seq(lens=(3, 2))
    per_seq = L.sequence_pool(emb, "average")  # dense [2, d]
    exp = L.sequence_expand(per_seq, emb)
    return _scalar(L.sequence_pool(exp, "sum")), feed


@case
def build_sequence_slice():
    emb, feed = _pre_seq(lens=(4, 3))
    off = L.data("off", shape=[2], dtype=np.int32, append_batch_size=False)
    ln = L.data("ln", shape=[2], dtype=np.int32, append_batch_size=False)
    feed["off"] = np.array([1, 0], np.int32)
    feed["ln"] = np.array([2, 2], np.int32)
    s = L.sequence_slice(emb, off, ln)
    return _scalar(L.sequence_pool(s, "sum")), feed


@case
def build_sequence_softmax():
    emb, feed = _pre_seq(lens=(4, 2), d=6)
    scores = L.fc(emb, size=1)
    sm = L.sequence_softmax(scores)
    return _scalar(L.sequence_pool(sm, "first")), feed


@case
def build_featmap_expand():
    emb, feed = _pre_seq(lens=(2, 1), d=4)
    e = L.featmap_expand(emb, num_filters=3)
    return _scalar(L.sequence_pool(e, "sum")), feed


@case
def build_row_conv():
    emb, feed = _pre_seq()
    h = L.row_conv(emb, future_context_size=2)
    return _scalar(L.sequence_pool(h, "sum")), feed


@case
def build_sub_nested_seq():
    ids = L.data("ids", shape=[-1], dtype=np.int32, lod_level=2,
                 append_batch_size=False)
    emb = L.embedding(ids, size=[9, 4])
    sel = L.data("sel", shape=[2], dtype=np.int32, append_batch_size=False)
    sub = L.sub_nested_seq(emb, sel)
    rng = _rng()
    nested = [[rng.randint(0, 9, (2,)).astype(np.int32),
               rng.randint(0, 9, (1,)).astype(np.int32)],
              [rng.randint(0, 9, (3,)).astype(np.int32)]]
    feed = {"ids": LoDArray.from_nested_sequences(nested, bucket=8),
            "sel": np.array([2, 0], np.int32)}
    return _scalar(L.sequence_pool(sub, "sum")), feed


# --------------------------------------------------------------- recurrent --
@case
def build_dynamic_lstm():
    emb, feed = _pre_seq(lens=(4, 2), d=8)
    h = L.dynamic_lstm(emb, size=8, max_len=8)
    return _scalar(L.sequence_last_step(h)), feed


@case
def build_dynamic_lstm_peepholes():
    emb, feed = _pre_seq(lens=(4, 2), d=8)
    h = L.dynamic_lstm(emb, size=8, use_peepholes=True, max_len=8)
    return _scalar(L.sequence_last_step(h)), feed


@case
def build_dynamic_gru():
    # fluid convention: dynamic_gru input is the pre-projected gates [.., 3D]
    emb, feed = _pre_seq(lens=(4, 2), d=18)
    h = L.dynamic_gru(emb, size=6, max_len=8)
    return _scalar(L.sequence_pool(h, "sum")), feed


@case
def build_simple_rnn():
    emb, feed = _pre_seq(lens=(3, 2), d=5)
    h = L.simple_rnn(emb, size=5, max_len=8)
    return _scalar(L.sequence_pool(h, "sum")), feed


@case
def build_recurrent_group():
    emb, feed = _pre_seq(lens=(3, 2), d=4)

    def step(x_t, rnn):
        h_prev = rnn.memory(shape=[4])
        h = L.fc(L.concat([x_t, h_prev], axis=1), size=4, act="tanh")
        rnn.update_memory(h_prev, h)
        return h

    out = L.recurrent_group(step, [emb], max_len=8)
    return _scalar(L.sequence_pool(out, "sum")), feed


@case
def build_RecurrentGroup():
    emb, feed = _pre_seq(lens=(3, 2), d=4)
    rnn = L.RecurrentGroup(max_len=8)
    with rnn.step():
        x_t = rnn.step_input(emb)
        h_prev = rnn.memory(shape=[4])
        h = L.fc(L.concat([x_t, h_prev], axis=1), size=4, act="tanh")
        rnn.update_memory(h_prev, h)
        rnn.step_output(h)
    return _scalar(L.sequence_pool(rnn(), "sum")), feed


@case
def build_StaticRNN():
    # StaticRNN is the fluid name for the same ragged-step machinery;
    # exercise the reverse-direction variant here
    emb, feed = _pre_seq(lens=(3, 2), d=4)
    rnn = L.StaticRNN(is_reverse=True, max_len=8)
    with rnn.step():
        x_t = rnn.step_input(emb)
        h_prev = rnn.memory(shape=[3])
        h = L.fc(L.concat([x_t, h_prev], axis=1), size=3, act="tanh")
        rnn.update_memory(h_prev, h)
        rnn.step_output(h)
    return _scalar(L.sequence_pool(rnn(), "sum")), feed


@case
def build_NestedRecurrentGroup():
    ids = L.data("ids", shape=[-1], dtype=np.int32, lod_level=2,
                 append_batch_size=False)
    emb = L.embedding(ids, size=[9, 4])
    outer = L.NestedRecurrentGroup(max_subseqs=3, max_sublen=4)
    with outer.step():
        sub, sub_mask = outer.step_input(emb)  # [B, L, D], [B, L]
        m = L.cast(sub_mask, np.float32)
        summed = L.reduce_sum(L.elementwise_mul(sub, m, axis=0), dim=1)
        cnt = L.clip(L.reduce_sum(m, dim=1), 1.0, 1e9)
        pooled = L.elementwise_div(summed, cnt, axis=0)
        s_prev = outer.memory(shape=[4])
        s = L.fc(L.concat([pooled, s_prev], axis=1), size=4, act="tanh")
        outer.update_memory(s_prev, s)
        outer.step_output(s)
    out = outer()
    rng = _rng()
    nested = [[rng.randint(0, 9, (2,)).astype(np.int32),
               rng.randint(0, 9, (3,)).astype(np.int32)],
              [rng.randint(0, 9, (1,)).astype(np.int32)]]
    feed = {"ids": LoDArray.from_nested_sequences(nested, bucket=16)}
    return _scalar(L.sequence_pool(out, "sum")), feed


# -------------------------------------------------------- structured costs --
@case
def build_linear_chain_crf():
    emb, feed = _pre_seq(lens=(4, 3), d=6, vocab=9)
    emit = L.fc(emb, size=4)
    lbl = L.data("lbl", shape=[-1], dtype=np.int32, lod_level=1,
                 append_batch_size=False)
    rng = _rng()
    feed["lbl"] = LoDArray.from_sequences(
        [rng.randint(0, 4, (4,)).astype(np.int32),
         rng.randint(0, 4, (3,)).astype(np.int32)], bucket=16)
    nll = L.linear_chain_crf(emit, lbl, max_len=8)
    return _scalar(nll), feed


@case
def build_warpctc():
    emb, feed = _pre_seq(lens=(6, 4), d=6, vocab=9)
    logits = L.fc(emb, size=5)
    lbl = L.data("lbl", shape=[-1], dtype=np.int32, lod_level=1,
                 append_batch_size=False)
    rng = _rng()
    feed["lbl"] = LoDArray.from_sequences(
        [rng.randint(1, 5, (2,)).astype(np.int32),
         rng.randint(1, 5, (2,)).astype(np.int32)], bucket=8)
    loss = L.warpctc(logits, lbl, blank=0, max_len=8, max_label_len=4)
    return _scalar(loss), feed


# --------------------------------------------------------------- attention --
@case
def build_multi_head_attention():
    x = L.data("x", shape=[4, 8], append_batch_size=False)
    q = L.fc(x, size=8)
    q3 = L.reshape(q, (1, 4, 8))
    h = L.multi_head_attention(q3, num_heads=2, causal=True)
    return _scalar(h), _feed("x", (4, 8))


@case
def build_rms_norm():
    h, feed = _pre(3, 8)
    return _scalar(L.rms_norm(h)), feed


def _pre_btd(t=4, d=8):
    """[t, d] data -> trainable fc -> [1, t, d], as multi_head_attention's."""
    x = L.data("x", shape=[t, d], append_batch_size=False)
    return L.reshape(L.fc(x, size=d), (1, t, d)), _feed("x", (t, d))


@case
def build_rotary_embedding():
    h, feed = _pre_btd()
    return _scalar(L.rotary_embedding(h, num_heads=2)), feed


@case
def build_multi_head_attention_qk_norm_rotary():
    h, feed = _pre_btd()
    h = L.multi_head_attention(h, num_heads=2, causal=True, bias_attr=False,
                               qk_norm=True, rotary_theta=10000.0)
    return _scalar(h), feed


@case
def build_rotary_embedding_sub_range():
    # the last two lanes of each head of four turn, the first two pass
    h, feed = _pre_btd()
    return _scalar(L.rotary_embedding(h, num_heads=2, rotary_dim=2)), feed


def _fed_positions(t=4):
    """int32 [1, 3, t] positions as data: a two-token span of one row (its
    height axis stays, its width axis advances) among text tokens."""
    p = L.data("p", shape=[1, 3, t], dtype=np.int32, append_batch_size=False)
    pos = np.stack([np.arange(t)] * 3)
    pos[0, 1:3], pos[1, 1:3], pos[2, 1:3] = 1, 1, (1, 2)
    pos[:, 3:] = 3 + np.arange(t - 3)
    return p, {"p": pos[None].astype(np.int32)}


@case
def build_rotary_embedding_fed_positions():
    # two heads of four lanes: pair 0 turns by the temporal axis, pair 1 by
    # the width axis; the height axis has no pair
    h, feed = _pre_btd()
    p, fed = _fed_positions()
    return _scalar(L.rotary_embedding(h, num_heads=2, positions=p,
                                      sections=[1, 0, 1])), {**feed, **fed}


@case
def build_multi_head_attention_fed_positions():
    h, feed = _pre_btd()
    p, fed = _fed_positions()
    h = L.multi_head_attention(h, num_heads=2, causal=True, bias_attr=False,
                               qk_norm="head", rotary_theta=100.0,
                               positions=p, rope_sections=[1, 1, 0])
    return _scalar(h), {**feed, **fed}


@case
def build_sparse_attention():
    # four query heads over two K/V heads of 4 lanes, an indexer of two heads
    # of 3; a row keeps 3 of up to 6 keys (the indexer is frozen: its three
    # matrices are not checked, and get no gradient)
    h, feed = _pre_btd(6, 8)
    p, fed = _fed_positions(6)
    out = L.sparse_attention(h, p, num_heads=4, num_kv_heads=2, head_dim=4,
                             index_heads=2, index_head_dim=3, topk=3,
                             rope_sections=[1, 0, 1], rotary_theta=100.0)
    return _scalar(out), {**feed, **fed}


@case
def build_latent_attention():
    # two heads of 3 + 2 lanes through a 6-wide query latent and a 4-wide
    # key/value latent, one shared rotary key
    h, feed = _pre_btd()
    return _scalar(L.latent_attention(
        h, num_heads=2, q_rank=6, kv_rank=4, nope_dim=2, rope_dim=2,
        v_dim=4, rotary_theta=100.0)), feed


@case
def build_moe_ffn_swiglu_share_with_shared_expert():
    # a chip's share (experts 1-2 of 4) of SwiGLU experts beside a SwiGLU
    # shared expert, behind a sigmoid router
    h, feed = _pre_btd(6, 8)
    out, _, _ = L.moe_ffn(h, num_experts=4, experts_per_token=2, expert_dim=8,
                          norm_topk_prob=True, scoring="sigmoid",
                          router_bias=True, gate_scale=1.8,
                          held_experts=(1, 3), shared_expert_dim=8)
    return _scalar(out), feed


def _moe(t=6, d=8):
    h, feed = _pre_btd(t, d)
    return L.moe_ffn(h, num_experts=4, experts_per_token=2, expert_dim=8), feed


@case
def build_moe_ffn():
    # top-2 of 4 on six tokens: a finite difference that turned a choice
    # would show as a wrong gradient; the seeded logits' gaps are far wider
    # than eps
    (out, _, _), feed = _moe()
    return _scalar(out), feed


@case
def build_mamba2_mixer():
    # seven tokens over chunks of four: a whole chunk, a ragged tail and the
    # state carried between them; two heads to a group
    h, feed = _pre_btd(7, 8)
    return _scalar(L.mamba2_mixer(h, num_heads=4, head_dim=2, n_groups=2,
                                  state_size=3, chunk=4)), feed


@case
def build_short_conv_operator():
    # seven tokens under three taps: the zeros before the sequence's start,
    # every tap, both gates and the backward's own rule
    h, feed = _pre_btd(7, 8)
    return _scalar(L.short_conv_operator(h, kernel=3)), feed


@case
def build_mamba1_mixer():
    # seven tokens: the taps' zeros before the start, dt through its low
    # rank, a decay a channel and state, the gate; both outputs read
    h, feed = _pre_btd(7, 8)
    out, memory = L.mamba1_mixer(h, state_size=3, dt_rank=2,
                                 emit_memory=True)
    return L.elementwise_add(_scalar(out), _scalar(memory)), feed


@case
def build_gated_memory_unit():
    h, feed = _pre_btd(5, 8)
    memory = L.fc(h, size=12, num_flatten_dims=2)
    return _scalar(L.gated_memory_unit(h, memory)), feed


@case
def build_silu_gate():
    # a fused [gate | value] and a gate of its own
    h, feed = _pre_btd(5, 8)
    return _scalar(L.silu_gate(L.silu_gate(h),
                               L.fc(h, size=4, num_flatten_dims=2))), feed


@case
def build_differential_attention():
    # four query heads over two K/V heads of 2 lanes: two query pairs on one
    # K/V pair, a window of three keys; then a cross layer on its k and v
    h, feed = _pre_btd(6, 8)
    out, kv = L.differential_attention(h, 4, 2, depth=1, window=3,
                                       return_kv=True)
    cross = L.differential_attention(out, 4, 2, depth=3, shared_kv=kv)
    return _scalar(cross), feed


@case
def build_moe_aux_loss():
    (out, logits, counts), feed = _moe()
    return L.elementwise_add(_scalar(out),
                             L.moe_aux_loss(logits, counts, 0.5, 0.5)), feed


@case
def build_attention_gru_decoder():
    src, feed = _pre_seq(lens=(4, 3), d=15, vocab=9, name="src")
    enc = L.dynamic_gru(src, size=5, max_len=8)  # input 3*size wide
    boot = L.sequence_last_step(enc)
    trg, feed2 = _pre_seq(lens=(3, 2), d=6, vocab=9, name="trg")
    feed.update(feed2)
    dec = L.attention_gru_decoder(enc, trg, boot, size=5, src_max_len=8,
                                  trg_max_len=8)
    return _scalar(L.sequence_pool(dec, "sum")), feed


# ------------------------------------------------------------ control flow --
@case
def build_cond():
    h, feed = _pre(3, 6)
    pred = L.less_than(L.mean(h), L.fill_constant([], np.float32, 10.0))
    out = L.cond(pred, lambda: L.tanh(h), lambda: L.sigmoid(h))
    return _scalar(out), feed


@case
def build_Repeat():
    """Three turns of a tanh layer with its own parameters on the carried
    stream, and a turn output: the gradient of a weight the loop reads three
    times, through the scan and its rematerialised body."""
    h, feed = _pre(3, 6)
    loop = L.Repeat(times=3)
    with loop.block():
        h2 = L.elementwise_add(h, L.fc(h, size=6, act="tanh"))
        loop.update(h, h2)
        loop.turn_output(L.reduce_sum(h2, dim=-1))
    h_fin, rows = loop()
    return L.elementwise_add(_scalar(h_fin), _scalar(rows)), feed


@case
def build_exit_gate():
    h, feed = _pre(3, 6)
    return _scalar(L.exit_gate(h, param_attr=pt.ParamAttr(
        initializer=pt.initializer.NormalInitializer(0.0, 0.5)))), feed


@case
def build_exit_expected_cost():
    """Four exits' costs and gate logits from one trainable stream."""
    h, feed = _pre(4, 6)                       # [K = 4, 6]
    cost, _ = L.exit_expected_cost(L.elementwise_mul(h, h), L.tanh(h),
                                   beta=0.05)
    return _scalar(cost), feed


# ------------------------------------------------------------------ exempt --
EXEMPT = {
    # graph construction / constants — nothing differentiable
    "data": "graph input declaration, not a computation",
    "fill_constant": "constant source; no upstream parameters",
    # integer / boolean outputs: zero or undefined gradient by construction
    "accuracy": "metric with integer comparisons; not a training signal",
    "argmax": "integer index output",
    "cast": "int casts non-differentiable; float casts are identity-grad, exercised throughout by the AMP suite",
    "equal": "boolean output",
    "not_equal": "boolean output",
    "greater_equal": "boolean output",
    "greater_than": "boolean output",
    "less_equal": "boolean output",
    "less_than": "boolean output",
    "logical_and": "boolean output",
    "logical_not": "boolean output",
    "one_hot": "integer input; output constant w.r.t. every parameter",
    "eos_id": "integer mask output (decode helper)",
    "kmax_seq_score": "integer index output",
    "sampling_id": "stochastic integer sample",
    "ctc_greedy_decoder": "decode-only: integer label path output",
    "crf_decoding": "decode-only: integer viterbi path output",
    "detection_output": "decode-only: NMS box selection, integer/threshold logic",
    "BeamSearchDecoder": "decode-only generation driver (no training loss)",
    "attention_gru_beam_search": "decode-only generation driver",
    # the reusable decode-step surface (continuous-batching serving PR):
    # inference-only plumbing re-exported through layers.generation
    "GenSpec": "static op-description NamedTuple, not a computation",
    "DecodeState": "decode-slot state pytree (inference-only carrier)",
    "beam_step": "decode-only: one beam-search step over frozen weights",
    "find_generation_op": "program introspection helper, no computation",
    "gen_spec_from_op": "program introspection helper, no computation",
    "RawConvBN": "container type of the fused conv+BN protocol, not a "
                 "layer fn (its three producers/consumers have cases)",
    "prior_box": "constant anchor generation from static shapes",
    "num_priors": "python-side shape helper returning an int",
    "dropout": "stochastic mask (identity at is_test); moments covered by the oracle tests",
    "increment": "counter update on non-trainable state",
    "While": "boolean-condition loop scaffold; differentiable loops are covered by the StaticRNN/RecurrentGroup cases",
}


# ------------------------------------------------------------------- tests --
def test_every_layer_is_covered():
    """Every public layer fn has a gradient case or a justified exemption
    (and no stale entries) — the sweep can't silently fall behind
    layers/__all__ the way the 10-case round-2 sweep did."""
    public = set(L.__all__)
    # extra config variants of an already-covered layer (e.g. peepholes)
    variants = {n for n in CASES if n not in public
                and any(n.startswith(p + "_") for p in public)}
    covered = set(CASES) | set(EXEMPT)
    missing = sorted(public - covered)
    stale = sorted(covered - public - variants)
    overlap = sorted(set(CASES) & set(EXEMPT))
    assert not missing, f"layers without a gradient case or exemption: {missing}"
    assert not stale, f"sweep entries not in layers.__all__: {stale}"
    assert not overlap, f"layers both tested and exempted: {overlap}"


@pytest.mark.parametrize("name", sorted(CASES))
def test_layer_grad_sweep(name):
    pt.reset()
    pt.default_startup_program().random_seed = 3
    loss, feed = CASES[name]()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    eps, rtol, atol = TOLS.get(name, (1e-2, 5e-2, 2e-3))
    diffs = pt.check_gradient(loss, feed, eps=eps, rtol=rtol, atol=atol,
                              max_elements=4)
    assert diffs, f"{name}: no parameters checked"
