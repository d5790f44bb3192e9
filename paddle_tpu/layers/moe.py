"""Routed-expert layer DSL: a dropless top-k mixture of SwiGLU experts
and its auxiliary costs (ops/moe_ops.py). Beyond the 2017 reference's
layer set; the feed-forward of OLMoE / Mixtral / DeepSeek-style models.
"""

from __future__ import annotations

import numpy as np

from ..initializer import XavierInitializer
from ..param_attr import ParamAttr
from .helper import LayerHelper

__all__ = ["moe_ffn", "moe_aux_loss"]

EXPERT_TOKENS_COUNTER = "pt_moe_expert_tokens_total"


def moe_ffn(input, num_experts: int, experts_per_token: int, expert_dim: int,
            norm_topk_prob: bool = False, param_attr=None, name=None):
    """input [B, T, d] -> (out [B, T, d], router logits [B*T, E] float32,
    tokens per expert [E] int32). Each token goes to its `experts_per_token`
    highest-scoring experts of `num_experts` (softmax router in float32,
    gates not renormalised unless `norm_topk_prob`); every (token, slot)
    pair is computed: no capacity, no dropped token. Parameters, bias-free:
    `<name>.router` [d, E], `<name>.gate` and `.up` [E, d, f], `<name>.down`
    [E, f, d]; each expert is silu(x Wg) * (x Wu) -> Wd.

    The tokens-per-expert count is registered as a step statistic of the
    program (`Program.add_step_statistic`): a Trainer folds it on the device
    with the cost and publishes `pt_moe_expert_tokens_total{layer,expert}`
    at its host syncs."""
    helper = LayerHelper("moe_ffn", name=name)
    d = int(input.shape[-1])
    E, f = int(num_experts), int(expert_dim)

    def param(suffix, shape):
        # default: Glorot over ONE expert's matrix (the stock default reads
        # a 3-D shape as a convolution's and would scale by the number of
        # experts); a caller's initialiser wins
        return helper.create_parameter(
            ParamAttr.derive(param_attr, helper.name, suffix), shape,
            default_initializer=XavierInitializer(
                fan_in=shape[-2], fan_out=shape[-1]))

    router = param("router", (d, E))
    gate, up = param("gate", (E, d, f)), param("up", (E, d, f))
    down = param("down", (E, f, d))
    tokens = int(np.prod(input.shape[:-1]))
    out = helper.create_tmp_variable(input.dtype, input.shape)
    logits = helper.create_tmp_variable(np.float32, (tokens, E))
    counts = helper.create_tmp_variable(np.int32, (E,))
    helper.append_op(
        type="moe_ffn",
        inputs={"X": [input], "RouterW": [router], "GateW": [gate],
                "UpW": [up], "DownW": [down]},
        outputs={"Out": [out], "RouterLogits": [logits],
                 "TokensPerExpert": [counts]},
        attrs={"top_k": int(experts_per_token),
               "norm_topk_prob": bool(norm_topk_prob)},
    )
    helper.main_program.add_step_statistic(
        counts, EXPERT_TOKENS_COUNTER, labels={"layer": helper.name},
        index_label="expert",
        help="(token, slot) pairs routed to each expert of a routed layer")
    return out, logits, counts


def moe_aux_loss(router_logits, tokens_per_expert, balance_weight=0.01,
                 z_weight=0.001, name=None):
    """One routed layer's auxiliary cost, a float32 scalar:
    balance_weight x (E x sum_e f_e P_e: f_e the share of (token, slot)
    pairs on expert e, P_e its mean router probability) + z_weight x
    mean(logsumexp(logits)^2). The weights default to the OLMoE paper's."""
    helper = LayerHelper("moe_aux_loss", name=name)
    out = helper.create_tmp_variable(np.float32, ())
    helper.append_op(
        type="moe_aux_loss",
        inputs={"RouterLogits": [router_logits],
                "TokensPerExpert": [tokens_per_expert]},
        outputs={"Out": [out]},
        attrs={"balance_weight": float(balance_weight),
               "z_weight": float(z_weight)},
    )
    return out
