"""LFM2-shaped decoder LM (`transformers` model_type `lfm2_moe`, Liquid AI's
LFM2-8B-A1B / LFM2-24B-A2B): gated SHORT-CONVOLUTION operators and grouped-
query attention layers in one model by `layer_types`, pre-norm residuals, and
a feed-forward that is a dense SwiGLU FFN in the first `num_dense_layers`
layers and routed experts in the others:

    h_0 = Emb[token]
    h <- h + Op_l(rms(h));   h <- h + FFN_l(rms(h));   logits = rms(h) W_head

    Op  by `layer_types[l]`:
        "conv": layers.short_conv_operator: [B | C | X] = u W_in, a causal
          depthwise convolution of `conv_kernel` taps over B * X, times C,
          W_out. No bias, no activation, no position signal.
        "full_attention": layers.multi_head_attention: `num_heads` query
          heads over `num_kv_heads` K/V heads, RMSNorm on Q and K per head
          (one scale [head size] each), THEN rotary positions, causal.
    dense W2 (silu(x W1) * (x W3)), width `dense_dim`
    E     layers.moe_ffn: a float32 sigmoid router with a choice bias (a
          buffer; `use_expert_bias`), top-k over ALL experts, gates
          renormalised over their sum + 1e-6 and scaled
          (`routed_scaling_factor`), SwiGLU experts, NO shared expert

No biases, untied head, no auxiliary cost. `held_experts` makes every routed
layer one chip's share of an expert-parallel layer.

Built from the layer DSL like `olmoe_lm`, `nemotron_h_lm`, `glm_moe_lm` and
`afmoe_lm`, so AMP, remat, Trainer and checkpointing apply unchanged.

lfm2_moe_lm: tokens [B, T] int32 -> (logits [B, T, vocab], [(router logits
[B*T, E] float32, tokens per expert [E] int32) of each routed layer]).
"""

from __future__ import annotations

import paddle_tpu.layers as layers
from ..initializer import NormalInitializer, XavierInitializer
from ..param_attr import ParamAttr

__all__ = ["lfm2_moe_lm", "LFM2_24B_LAYER_TYPES"]

CONV, ATTENTION = "conv", "full_attention"
# conv, conv, attention, conv ten times: 30 operators and 10 attention layers
LFM2_24B_LAYER_TYPES = (CONV, CONV, ATTENTION, CONV) * 10
GATE_NORM_EPS = 1e-6    # the published layer's, added to the chosen gates' sum


def lfm2_moe_lm(
    tokens,
    vocab_size: int,
    layer_types=LFM2_24B_LAYER_TYPES,
    num_dense_layers: int = 2,
    dim: int = 2048,
    num_heads: int = 32,
    num_kv_heads: int = 8,
    conv_kernel: int = 3,
    dense_dim: int = 11776,
    num_experts: int = 64,
    experts_per_token: int = 4,
    expert_dim: int = 1536,
    gate_scale: float = 1.0,
    norm_topk_prob: bool = True,
    use_expert_bias: bool = True,
    held_experts=None,
    rope_theta: float = 1e6,
    rms_eps: float = 1e-5,
    out_scale=None,
    chunk_shares: int = 3,
    name: str = "lfm2",
):
    """tokens: dense [B, T] int32 Variable. Returns (per-position logits
    [B, T, vocab_size], the routed layers' (RouterLogits, TokensPerExpert)
    in order). One layer per entry of `layer_types`; the defaults are
    LFM2-24B-A2B's published sizes (heads of dim / num_heads = 64). The
    initialisers and the float32 stream are `glm_moe_lm`'s, for its reason (a
    first step that a float32 reference can be held to): the token table
    N(0, 1), the norms' scales at one, the taps at their layer's default,
    every other matrix Glorot uniform (per expert for the stacks), and every
    matrix that WRITES to the residual stream (an operator's W_out, the
    attention's W_o, every W2) at `out_scale` times its Glorot range, by
    default 1 / sqrt(number of layers); a caller that builds a part of a
    deeper model passes that model's. Under amp a layer's bf16 output is cast
    up before it is added. `chunk_shares`: a share's routed layers work
    through their live rows in chunks of three even shares of the T x k rows
    (`layers.moe_ffn`; two is the op's own): with no shared expert a routed
    layer's only output is its held experts' and the routers settle nearer
    two even shares than glm's or trinity's do (PERF.md section 6, PR 49).
    Parameters, in order: the table; per layer the operator norm, the
    operator's (conv: in_w, conv_w, out_w; attention: wq, wk, wv, q_norm,
    k_norm, wo), the FFN norm, the FFN's (dense: w1, w3, w2; routed:
    `moe_ffn`'s); the closing norm; the head."""
    layer_types = tuple(layer_types)
    unknown = set(layer_types) - {CONV, ATTENTION}
    if unknown:
        raise ValueError(f"layer_types: {sorted(unknown)} is neither "
                         f"{CONV!r} nor {ATTENTION!r}")
    if not 0 <= num_dense_layers <= len(layer_types):
        raise ValueError(f"num_dense_layers {num_dense_layers} not within "
                         f"0..{len(layer_types)}")
    if out_scale is None:
        out_scale = len(layer_types) ** -0.5

    def scaled(fan_in=None, fan_out=None):
        return ParamAttr(initializer=XavierInitializer(
            fan_in=fan_in, fan_out=fan_out, gain=out_scale))

    def add(x, h):
        return layers.elementwise_add(x, layers.cast(h, "float32"))

    def proj(inp, layer, weight, size, act=None, attr=None):
        return layers.fc(inp, size=size, num_flatten_dims=2, act=act,
                         param_attr=ParamAttr.derive(attr, layer, weight),
                         bias_attr=False)

    def norm(x, s):
        return layers.rms_norm(x, epsilon=rms_eps, name=s,
                               param_attr=ParamAttr(name=f"{s}.w"))

    x = layers.embedding(
        tokens, size=[vocab_size, dim],
        param_attr=ParamAttr(name=f"{name}.tok_emb",
                             initializer=NormalInitializer(0.0, 1.0)))
    routers = []
    for i, kind in enumerate(layer_types):
        prefix = f"{name}.h{i}"
        h = norm(x, f"{prefix}.operator_norm")
        if kind == CONV:
            h = layers.short_conv_operator(
                h, kernel=conv_kernel, param_attr={"out_w": scaled()},
                name=f"{prefix}.conv")
        else:
            h = layers.multi_head_attention(
                h, num_heads=num_heads, num_kv_heads=num_kv_heads,
                causal=True, qk_norm="head", rotary_theta=rope_theta,
                rms_eps=rms_eps, bias_attr=False,
                param_attr={"wo": scaled()}, name=f"{prefix}.attn")
        x = add(x, h)
        h = norm(x, f"{prefix}.ffn_norm")
        if i < num_dense_layers:
            mlp = f"{prefix}.mlp"
            h = layers.elementwise_mul(
                proj(h, mlp, "w1", dense_dim, act="swish"),
                proj(h, mlp, "w3", dense_dim))
            h = proj(h, mlp, "w2", dim, attr=scaled())
        else:
            h, logits, counts = layers.moe_ffn(
                h, num_experts, experts_per_token, expert_dim,
                norm_topk_prob=norm_topk_prob, scoring="sigmoid",
                router_bias=use_expert_bias, gate_scale=gate_scale,
                gate_norm_eps=GATE_NORM_EPS if norm_topk_prob else 0.0,
                expert_act="swiglu", held_experts=held_experts,
                chunk_shares=chunk_shares,
                # Glorot over ONE expert's matrix, as the layer's default
                param_attr={"down": scaled(expert_dim, dim)},
                name=f"{prefix}.moe")
            routers.append((logits, counts))
        x = add(x, h)
    x = norm(x, f"{name}.embedding_norm")
    logits = layers.fc(x, size=vocab_size, num_flatten_dims=2,
                       param_attr=ParamAttr(name=f"{name}.out_w"),
                       bias_attr=False)
    return logits, routers
