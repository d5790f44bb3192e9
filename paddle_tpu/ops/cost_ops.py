"""Cost-layer op kernels completing the reference's cost family.

Reference: paddle/gserver/layers/CostLayer.cpp registers ~12 cost layers
(multi_class_cross_entropy :~60, multi_class_cross_entropy_with_selfnorm
:105, soft_binary_class_cross_entropy :149, square_error :176, smooth_l1
:199, rank_cost (RankingCost) :~250, lambda_cost :347, multi_binary_label_
cross_entropy :524, huber_regression :600, huber_classification :663,
sum_cost :746), plus NCELayer.cpp and HierarchicalSigmoidLayer.cpp for the
sampled / tree-factorized softmax alternatives. Fluid analogues:
operators/{sigmoid_cross_entropy_with_logits_op,smooth_l1_loss_op,
rank_loss_op,margin_rank_loss_op,huber_loss_op}.cc.

cross_entropy / softmax_with_cross_entropy / square_error / huber_loss live
in nn_ops.py; this module adds the rest. Gradients via jax.grad.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.lod import LoDArray
from ..core.registry import register_op


def _data(x):
    return x.data if isinstance(x, LoDArray) else x


@register_op("sigmoid_cross_entropy_with_logits")
def sigmoid_ce_logits_kernel(ctx):
    x = _data(ctx.input("X"))
    label = _data(ctx.input("Label")).astype(x.dtype)
    # numerically-stable BCE-with-logits: softplus(x) - label*x
    ctx.set_output("Out", jax.nn.softplus(x) - label * x)


@register_op("binary_cross_entropy")
def binary_ce_kernel(ctx):
    """Probability-space BCE — covers soft_binary_class_cross_entropy and
    (with multi-hot labels) multi_binary_label_cross_entropy."""
    p = jnp.clip(_data(ctx.input("X")), 1e-7, 1.0 - 1e-7)
    label = _data(ctx.input("Label")).astype(p.dtype)
    out = -(label * jnp.log(p) + (1.0 - label) * jnp.log1p(-p))
    ctx.set_output("Out", out)


@register_op("cross_entropy_with_selfnorm")
def ce_selfnorm_kernel(ctx):
    """CE on unnormalized softmax plus alpha * log(Z)^2 self-norm penalty
    (CostLayer.cpp:105)."""
    x = _data(ctx.input("X"))  # probabilities-ish (unnormalized ok)
    label = _data(ctx.input("Label")).reshape(-1).astype(jnp.int32)
    alpha = ctx.attr("softmax_selfnorm_alpha", 0.1)
    z = jnp.sum(x, axis=-1)
    p = jnp.take_along_axis(x, label[:, None], axis=-1)[:, 0] / z
    out = -jnp.log(jnp.maximum(p, 1e-20)) + alpha * jnp.square(jnp.log(z))
    ctx.set_output("Out", out[:, None])


@register_op("smooth_l1")
def smooth_l1_kernel(ctx):
    """SmoothL1CostLayer / smooth_l1_loss_op: 0.5 d^2 (|d|<sigma) else
    |d| - 0.5, with inside/outside weights (Fluid) optional."""
    x = _data(ctx.input("X"))
    y = _data(ctx.input("Y"))
    sigma = ctx.attr("sigma", 1.0)
    d = x - y
    if ctx.has_input("InsideWeight"):
        d = d * _data(ctx.input("InsideWeight"))
    a = jnp.abs(d)
    s2 = sigma * sigma
    loss = jnp.where(a < 1.0 / s2, 0.5 * d * d * s2, a - 0.5 / s2)
    if ctx.has_input("OutsideWeight"):
        loss = loss * _data(ctx.input("OutsideWeight"))
    ctx.set_output("Out", jnp.sum(loss, axis=-1, keepdims=True))


@register_op("rank_cost")
def rank_cost_kernel(ctx):
    """RankingCost: pairwise logistic loss on score difference.
    C = (1-label)*o - log(sigmoid(-o)) form; label in {0, 0.5, 1}."""
    left = _data(ctx.input("Left")).reshape(-1)
    right = _data(ctx.input("Right")).reshape(-1)
    label = _data(ctx.input("Label")).reshape(-1).astype(left.dtype)
    o = left - right
    out = jax.nn.softplus(o) - label * o
    ctx.set_output("Out", out[:, None])


@register_op("margin_rank_loss")
def margin_rank_loss_kernel(ctx):
    """margin_rank_loss_op: max(0, -label*(x1-x2) + margin)."""
    x1 = _data(ctx.input("X1")).reshape(-1)
    x2 = _data(ctx.input("X2")).reshape(-1)
    label = _data(ctx.input("Label")).reshape(-1).astype(x1.dtype)
    margin = ctx.attr("margin", 0.0)
    ctx.set_output("Out", jnp.maximum(0.0, -label * (x1 - x2) + margin)[:, None])


@register_op("huber_classification")
def huber_classification_kernel(ctx):
    """HuberTwoClassification (CostLayer.cpp:663): labels {0,1}→y∈{-1,1};
    loss 0 if y*x>1, (1-y*x)^2 if -1<=y*x<=1, -4*y*x otherwise."""
    x = _data(ctx.input("X")).reshape(-1)
    label = _data(ctx.input("Label")).reshape(-1).astype(x.dtype)
    y = 2.0 * label - 1.0
    a = y * x
    out = jnp.where(a < -1.0, -4.0 * a, jnp.where(a < 1.0, jnp.square(1.0 - a), 0.0))
    ctx.set_output("Out", out[:, None])


@register_op("sum_cost")
def sum_cost_kernel(ctx):
    ctx.set_output("Out", jnp.sum(_data(ctx.input("X"))))


@register_op("lambda_cost")
def lambda_cost_kernel(ctx):
    """LambdaCost (CostLayer.cpp:347): listwise LambdaRank cost. The
    reference walks each ragged list; TPU-statically we take the padded
    list-wise form: Score/Label [L, S], Mask [L, S] (1=real). Forward cost
    is the negative truncated NDCG per list (as in the reference, which
    reports -NDCG as the cost and uses lambda gradients; here jax.grad of
    a smooth surrogate is used instead: we emit -NDCG computed with
    softmax-weighted soft ranks so it is differentiable)."""
    score = _data(ctx.input("Score"))
    label = _data(ctx.input("Label")).astype(score.dtype)
    mask = _data(ctx.input("Mask")) if ctx.has_input("Mask") else jnp.ones_like(score)
    ndcg_num = ctx.attr("NDCG_num", 5)
    # soft rank r_i = 1 + sum_j sigmoid(s_j - s_i) over real entries
    diff = (score[:, None, :] - score[:, :, None]) * 10.0
    soft_gt = jax.nn.sigmoid(diff) * mask[:, None, :]
    soft_rank = 1.0 + jnp.sum(soft_gt, axis=-1) - jax.nn.sigmoid(jnp.zeros(()))
    gain = (jnp.exp2(label) - 1.0) * mask
    disc = 1.0 / jnp.log2(1.0 + soft_rank)
    trunc = jax.nn.sigmoid((ndcg_num - soft_rank + 0.5) * 10.0)
    dcg = jnp.sum(gain * disc * trunc, axis=-1)
    # ideal DCG from hard-sorted gains (padded entries have gain 0)
    sorted_gain = jnp.sort(gain, axis=-1)[:, ::-1]
    pos = jnp.arange(score.shape[1], dtype=score.dtype)
    ideal_disc = jnp.where(pos < ndcg_num, 1.0 / jnp.log2(2.0 + pos), 0.0)
    idcg = jnp.sum(sorted_gain * ideal_disc[None, :], axis=-1)
    ndcg = dcg / jnp.maximum(idcg, 1e-12)
    ctx.set_output("Out", -ndcg[:, None])


# ----------------------------------------------------------- sampled/tree ---
@register_op("nce")
def nce_kernel(ctx):
    """NCELayer.cpp / operators/nce_op.cc: noise-contrastive estimation with
    uniform noise. Per row: BCE-with-logits on the true class (target 1) and
    num_neg sampled classes (target 0), logits shifted by log(k*q)."""
    x = _data(ctx.input("Input"))  # [N, D]
    w = _data(ctx.input("Weight"))  # [C, D]
    label = _data(ctx.input("Label")).reshape(-1).astype(jnp.int32)
    num_neg = ctx.attr("num_neg_samples", 10)
    num_classes = w.shape[0]
    n = x.shape[0]
    neg = jax.random.randint(ctx.rng(), (n, num_neg), 0, num_classes)
    log_kq = jnp.log(jnp.asarray(num_neg / num_classes, x.dtype))

    def logit(ids):  # ids [N, K] → [N, K]
        wk = w[ids]  # [N, K, D]
        s = jnp.einsum("nd,nkd->nk", x, wk)
        if ctx.has_input("Bias"):
            s = s + _data(ctx.input("Bias")).reshape(-1)[ids]
        return s - log_kq

    s_pos = logit(label[:, None])  # [N, 1]
    s_neg = logit(neg)  # [N, num_neg]
    loss = jax.nn.softplus(-s_pos)[:, 0] + jnp.sum(jax.nn.softplus(s_neg), axis=-1)
    ctx.set_output("Cost", loss[:, None])


@functools.lru_cache(maxsize=None)
def _hsigmoid_tables(num_classes: int):
    """Per-class path tables for a complete binary tree in heap layout:
    leaf code = class + num_classes; ancestors = code >> t. Matches the
    reference CodeTable/SimpleCode scheme (paddle/math/MathUtils +
    HierarchicalSigmoidLayer.cpp)."""
    max_depth = int(np.floor(np.log2(2 * num_classes - 1)))
    nodes = np.zeros((num_classes, max_depth), np.int32)
    bits = np.zeros((num_classes, max_depth), np.float32)
    valid = np.zeros((num_classes, max_depth), np.float32)
    for c in range(num_classes):
        code = c + num_classes
        depth = code.bit_length() - 1
        for j in range(depth):
            nodes[c, j] = (code >> (depth - j)) - 1  # internal node param row
            bits[c, j] = (code >> (depth - 1 - j)) & 1
            valid[c, j] = 1.0
    return nodes, bits, valid


@register_op("hsigmoid")
def hsigmoid_kernel(ctx):
    """HierarchicalSigmoidLayer.cpp: binary-tree factorized softmax;
    num_classes-1 internal nodes each with a weight row; loss is the sum of
    BCE-with-logits along the root→leaf path."""
    x = _data(ctx.input("X"))  # [N, D]
    w = _data(ctx.input("W"))  # [C-1, D]
    label = _data(ctx.input("Label")).reshape(-1).astype(jnp.int32)
    num_classes = ctx.attr("num_classes")
    nodes_t, bits_t, valid_t = _hsigmoid_tables(num_classes)
    nodes = jnp.asarray(nodes_t)[label]  # [N, depth]
    bits = jnp.asarray(bits_t)[label]
    valid = jnp.asarray(valid_t)[label]
    wn = w[nodes]  # [N, depth, D]
    s = jnp.einsum("nd,njd->nj", x, wn)
    if ctx.has_input("Bias"):
        s = s + _data(ctx.input("Bias")).reshape(-1)[nodes]
    loss = (jax.nn.softplus(s) - bits * s) * valid
    ctx.set_output("Cost", jnp.sum(loss, axis=-1, keepdims=True))


@register_op("exit_expected_cost")
def exit_expected_cost_kernel(ctx):
    """`layers.exit_expected_cost`: a looped model's K exits as one
    distribution a token, and the expected cost under it, float32 whatever
    the inputs. With gate logits s [K, ...] and turn costs c [K, ...]:

        lambda_r = sigmoid(s_r)
        p_r = lambda_r prod_{j<r} (1 - lambda_j)  (r < K),
        p_K = prod_{j<K} (1 - lambda_j)           (what is left: sum p = 1)
        Cost = sum_r p_r c_r - beta H(p),   H(p) = - sum_r p_r log p_r

    log p_r is a sum of `log_sigmoid`s, never the log of a product; the
    last turn's gate logit is not read (its gradient is zero)."""
    c = _data(ctx.input("TurnCosts")).astype(jnp.float32)
    s = _data(ctx.input("GateLogits")).astype(jnp.float32)
    if c.ndim == s.ndim + 1 and c.shape[-1] == 1:
        c = c[..., 0]
    if c.shape != s.shape:
        raise ValueError(f"exit_expected_cost: turn costs {c.shape} against "
                         f"gate logits {s.shape}")
    with jax.named_scope("repeat.exit"):
        stay = jax.nn.log_sigmoid(-s[:-1])              # log(1 - lambda_j)
        before = jnp.concatenate(
            [jnp.zeros_like(s[:1]), jnp.cumsum(stay, axis=0)], axis=0)
        log_p = before + jnp.concatenate(
            [jax.nn.log_sigmoid(s[:-1]), jnp.zeros_like(s[:1])], axis=0)
        p = jnp.exp(log_p)
        cost = jnp.sum(p * (c + float(ctx.attr("beta", 0.0)) * log_p), axis=0)
    ctx.set_output("Cost", cost)
    ctx.set_output("Probs", p)
