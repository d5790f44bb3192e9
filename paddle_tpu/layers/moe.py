"""Routed-expert layer DSL: a dropless top-k mixture of experts and its
auxiliary costs (ops/moe_ops.py). Beyond the 2017 reference's layer set;
the feed-forward of OLMoE / Mixtral / DeepSeek / Nemotron-H-style models.
By arguments: a softmax or a sigmoid router (`scoring`, `router_bias`,
`gate_scale`), SwiGLU or relu^2 experts (`expert_act`), all experts or one
chip's share of them (`held_experts`), a shared expert of the same kind
(`shared_expert_dim`).
"""

from __future__ import annotations

import numpy as np

from ..initializer import ConstantInitializer, XavierInitializer
from ..ops.moe_ops import bounds_rows
from ..param_attr import ParamAttr
from .helper import LayerHelper

__all__ = ["moe_ffn", "moe_aux_loss"]

EXPERT_TOKENS_COUNTER = "pt_moe_expert_tokens_total"
HELD_PAIRS_COUNTER = "pt_moe_held_pairs_total"
ROW_PATH_COUNTER = "pt_moe_row_path_total"
CHUNK_ROWS_COUNTER = "pt_moe_chunk_rows_total"


def moe_ffn(input, num_experts: int, experts_per_token: int, expert_dim: int,
            norm_topk_prob: bool = False, param_attr=None, name=None,
            scoring: str = "softmax", router_bias: bool = False,
            gate_scale: float = 1.0, expert_act: str = "swiglu",
            held_experts=None, shared_expert_dim: int = 0,
            chunk_shares=None, gate_norm_eps: float = 0.0):
    """input [B, T, d] -> (out [B, T, d], router logits [B*T, E] float32,
    tokens per expert [E] int32). Each token goes to its `experts_per_token`
    highest-scoring experts of `num_experts`; every (token, slot) pair is
    computed: no capacity, no dropped token. The router is float32.

    scoring "softmax": the gates are the chosen probabilities, not
    renormalised unless `norm_topk_prob`. "sigmoid": s = sigmoid(logits), the
    choice is the top k of s + b (`router_bias`: b [E] is `<name>.router_bias`,
    zeros, NOT trained: a buffer a load balancer would steer), the gates s of
    the chosen, over their sum with `norm_topk_prob` (over their sum +
    `gate_norm_eps` where a published layer adds one: LFM2's 1e-6).
    `gate_scale` multiplies the gates.
    expert_act "swiglu": silu(x Wg) * (x Wu) -> Wd, parameters `<name>.gate`,
    `.up` [E, d, f], `.down` [E, f, d]. "relu2": relu(x Wu)^2 -> Wd, no gate
    stack. All bias-free; `<name>.router` [d, E].
    held_experts (lo, hi): this chip's share of an expert-parallel layer.
    The router still scores all `num_experts` and a token still chooses
    among all of them; the stacks hold experts lo..hi-1 only, the pairs that
    chose one of them are computed, and a pair that chose an absent expert
    adds nothing (its chip would add it; no code stands in for that chip or
    the exchange). The shares of a layer add up to the whole layer. A share
    under half of the experts gathers, masks and combines its live rows in
    chunks of a bound (two even shares of the tokens x k pairs) in place
    of all the rows: one chunk in a step whose live pairs fit the bound,
    more, by the same arithmetic, in a step whose pairs exceed it
    (`ops/moe_ops.py:row_bound`). `chunk_shares` asks for a bound of that
    many even shares in place of two (a layer whose routers settle on both
    sides of two; None: the op as it always was).
    shared_expert_dim f_s > 0: a shared expert for every token, of the
    routed experts' kind (`expert_act`): "relu2" + relu(x Wu_s)^2 Wd_s
    (`<name>.shared_up` [d, f_s], `.shared_down`); "swiglu" + (silu(x Wg_s)
    * (x Wu_s)) Wd_s, with `<name>.shared_gate` [d, f_s] in front of them.
    param_attr may be a mapping {"router" | "up" | "down" | ...: attr}
    (`ParamAttr.derive`).

    The tokens-per-expert count (over all `num_experts`) is registered as a
    step statistic of the program (`Program.add_step_statistic`): a Trainer
    folds it on the device with the cost and publishes
    `pt_moe_expert_tokens_total{layer,expert}` at its host syncs; a share
    also publishes `pt_moe_held_pairs_total{layer,expert}`, the pairs it
    computed, by held expert (0 = `lo`), and, where its rows have a bound,
    `pt_moe_row_path_total{layer,path}`: the steps whose live pairs fitted
    one chunk of the bound (path 0) and those that needed more (path 1),
    and `pt_moe_chunk_rows_total{layer,kind}`: the chunks' live rows, the
    only ones their sums read (kind 0), and the chunks' rows (kind 1)."""
    helper = LayerHelper("moe_ffn", name=name)
    d = int(input.shape[-1])
    E, f = int(num_experts), int(expert_dim)
    lo, hi = (0, E) if held_experts is None else map(int, held_experts)
    if not 0 <= lo < hi <= E:
        raise ValueError(f"held_experts {held_experts} not within 0..{E}")
    if expert_act not in ("swiglu", "relu2"):
        raise ValueError(f"unknown expert_act {expert_act!r}")
    part = (lo, hi) != (0, E)

    def param(suffix, shape):
        # default: Glorot over ONE expert's matrix (the stock default reads
        # a 3-D shape as a convolution's and would scale by the number of
        # experts); a caller's initialiser wins
        return helper.create_parameter(
            ParamAttr.derive(param_attr, helper.name, suffix), shape,
            default_initializer=XavierInitializer(
                fan_in=shape[-2], fan_out=shape[-1]))

    inputs = {"X": [input], "RouterW": [param("router", (d, E))]}
    if expert_act == "swiglu":
        inputs["GateW"] = [param("gate", (hi - lo, d, f))]
    inputs["UpW"] = [param("up", (hi - lo, d, f))]
    inputs["DownW"] = [param("down", (hi - lo, f, d))]
    if router_bias:
        inputs["RouterBias"] = [helper.create_parameter(
            ParamAttr(name=f"{helper.name}.router_bias", trainable=False),
            (E,), default_initializer=ConstantInitializer(0.0))]
    if shared_expert_dim:
        if expert_act == "swiglu":
            inputs["SharedGateW"] = [param(
                "shared_gate", (d, int(shared_expert_dim)))]
        inputs["SharedUpW"] = [param("shared_up", (d, int(shared_expert_dim)))]
        inputs["SharedDownW"] = [param(
            "shared_down", (int(shared_expert_dim), d))]
    tokens = int(np.prod(input.shape[:-1]))
    out = helper.create_tmp_variable(input.dtype, input.shape)
    logits = helper.create_tmp_variable(np.float32, (tokens, E))
    counts = helper.create_tmp_variable(np.int32, (E,))
    outputs = {"Out": [out], "RouterLogits": [logits],
               "TokensPerExpert": [counts]}
    attrs = {"top_k": int(experts_per_token),
             "norm_topk_prob": bool(norm_topk_prob)}
    # only what differs from a softmax router over experts that are all
    # here: such a layer's op is the one it always was
    if scoring != "softmax":
        attrs["scoring"] = scoring
    if gate_scale != 1.0:
        attrs["gate_scale"] = float(gate_scale)
    if gate_norm_eps:
        attrs["gate_norm_eps"] = float(gate_norm_eps)
    if part:
        attrs["held_lo"], attrs["held_hi"] = lo, hi
        held = helper.create_tmp_variable(np.int32, (hi - lo,))
        outputs["HeldPairs"] = [held]
        if chunk_shares is not None:
            attrs["chunk_shares"] = int(chunk_shares)
        if bounds_rows((lo, hi), E, chunk_shares):
            row_path = helper.create_tmp_variable(np.int32, (2,))
            outputs["RowPath"] = [row_path]
            chunk_rows = helper.create_tmp_variable(np.int32, (2,))
            outputs["ChunkRows"] = [chunk_rows]
    helper.append_op(type="moe_ffn", inputs=inputs, outputs=outputs,
                     attrs=attrs)
    helper.main_program.add_step_statistic(
        counts, EXPERT_TOKENS_COUNTER, labels={"layer": helper.name},
        index_label="expert",
        help="(token, slot) pairs routed to each expert of a routed layer")
    if part:
        helper.main_program.add_step_statistic(
            held, HELD_PAIRS_COUNTER, labels={"layer": helper.name},
            index_label="expert",
            help="(token, slot) pairs a chip's share of a routed layer "
                 "computed, by held expert")
        if "RowPath" in outputs:
            helper.main_program.add_step_statistic(
                row_path, ROW_PATH_COUNTER, labels={"layer": helper.name},
                index_label="path",
                help="steps a chip's share of a routed layer ran in one "
                     "chunk of its bounded rows (path 0) or in more (path 1)")
            helper.main_program.add_step_statistic(
                chunk_rows, CHUNK_ROWS_COUNTER, labels={"layer": helper.name},
                index_label="kind",
                help="live rows (kind 0) among the rows of the chunks (kind "
                     "1) a chip's share of a routed layer ran in")
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
