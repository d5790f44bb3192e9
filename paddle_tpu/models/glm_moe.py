"""GLM-4.7-Flash-shaped decoder LM (`transformers` model_type
`glm4_moe_lite`; DeepSeek-V3's block): pre-RMSNorm residual layers of
multi-head LATENT attention and a feed-forward that is a dense SwiGLU FFN in
the first `first_k_dense` layers and routed experts in the others:

    h <- h + MLA(rms(h));   h <- h + FFN(rms(h));   logits = rms(h) W_head

    MLA   layers.latent_attention: a query through a `q_rank`-wide latent
          with its own RMSNorm, keys and values through ONE `kv_rank`-wide
          latent with its own RMSNorm, a `rope_dim`-wide rotary key that is
          one head shared by all query heads and bypasses the latent, heads
          of nope_dim + rope_dim for Q and K and v_dim for V
    dense W_d (silu(x W_g) * (x W_u)), width `dense_dim`
    E     layers.moe_ffn: a float32 sigmoid router with a choice bias (a
          buffer), top-k over ALL experts (n_group 1), gates renormalised
          and scaled, SwiGLU experts beside ONE SwiGLU shared expert

No biases, untied head, no auxiliary cost (the published recipe balances
load through the choice bias, which nothing steers here). `held_experts`
makes every routed layer one chip's share of an expert-parallel layer. The
multi-token-prediction module (`num_nextn_predict_layers`) is not built.

Built from the layer DSL like `olmoe_lm` and `nemotron_h_lm`, so AMP, remat,
Trainer and checkpointing apply unchanged.

glm_moe_lm: tokens [B, T] int32 -> (logits [B, T, vocab], [(router logits
[B*T, E] float32, tokens per expert [E] int32) of each routed layer]).
"""

from __future__ import annotations

import paddle_tpu.layers as layers
from ..initializer import NormalInitializer, XavierInitializer
from ..param_attr import ParamAttr

__all__ = ["glm_moe_lm"]


def glm_moe_lm(
    tokens,
    vocab_size: int,
    num_layers: int = 47,
    first_k_dense: int = 1,
    dim: int = 2048,
    num_heads: int = 20,
    q_rank: int = 768,
    kv_rank: int = 512,
    nope_dim: int = 192,
    rope_dim: int = 64,
    v_dim: int = 256,
    dense_dim: int = 10240,
    num_experts: int = 64,
    experts_per_token: int = 4,
    expert_dim: int = 1536,
    shared_expert_dim: int = 1536,
    gate_scale: float = 1.8,
    norm_topk_prob: bool = True,
    held_experts=None,
    rope_theta: float = 1e6,
    rms_eps: float = 1e-5,
    out_scale=None,
    name: str = "glm_moe",
):
    """tokens: dense [B, T] int32 Variable. Returns (per-position logits
    [B, T, vocab_size], the routed layers' (RouterLogits, TokensPerExpert)
    in order). The defaults are GLM-4.7-Flash's published sizes; attention
    is `num_heads` x 256 = 5120 wide at a hidden size of 2048. The token
    table starts from N(0, 1) as `olmoe_lm`'s does and for its reason; the
    norms' scales at one; every other matrix keeps its layer's default
    (Glorot uniform, per expert for the stacks), but for the two settings
    `nemotron_h_lm` documents, taken for its reason (a first step that a
    float32 reference can be held to; the chip's readings for the model
    without them are in PERF.md section 6, PR 37: the routers' logits ten
    times as far off, 30 rows of a layer choosing other experts where the
    reference is nowhere near a tie):

    - every matrix that writes to the residual stream (the attention's
      output projection, the dense FFN's, the experts' and the shared
      expert's down matrices) starts at `out_scale` times its Glorot range,
      by default 1 / sqrt(num_layers) (GPT-2's scheme); a caller that builds
      a part of a deeper model passes that model's;
    - the residual stream is float32 under amp too: a layer's bf16 output is
      cast up before it is added, so the stream is not rounded to 8 bits at
      every add."""
    if not 0 <= first_k_dense <= num_layers:
        raise ValueError(f"first_k_dense {first_k_dense} not within "
                         f"0..{num_layers}")
    if out_scale is None:
        out_scale = num_layers ** -0.5

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
    for i in range(num_layers):
        prefix = f"{name}.h{i}"
        h = layers.latent_attention(
            norm(x, f"{prefix}.ln_in"), num_heads=num_heads, q_rank=q_rank,
            kv_rank=kv_rank, nope_dim=nope_dim, rope_dim=rope_dim,
            v_dim=v_dim, rotary_theta=rope_theta, rms_eps=rms_eps,
            param_attr={"wo": scaled()}, name=f"{prefix}.attn")
        x = add(x, h)
        h = norm(x, f"{prefix}.ln_post")
        if i < first_k_dense:
            mlp = f"{prefix}.mlp"
            h = layers.elementwise_mul(
                proj(h, mlp, "gate", dense_dim, act="swish"),
                proj(h, mlp, "up", dense_dim))
            h = proj(h, mlp, "down", dim, attr=scaled())
        else:
            h, logits, counts = layers.moe_ffn(
                h, num_experts, experts_per_token, expert_dim,
                norm_topk_prob=norm_topk_prob, scoring="sigmoid",
                router_bias=True, gate_scale=gate_scale, expert_act="swiglu",
                held_experts=held_experts,
                shared_expert_dim=shared_expert_dim,
                # Glorot over ONE expert's matrix, as the layer's default
                param_attr={"down": scaled(expert_dim, dim),
                            "shared_down": scaled()},
                name=f"{prefix}.moe")
            routers.append((logits, counts))
        x = add(x, h)
    x = norm(x, f"{name}.ln_f")
    logits = layers.fc(x, size=vocab_size, num_flatten_dims=2,
                       param_attr=ParamAttr(name=f"{name}.out_w"),
                       bias_attr=False)
    return logits, routers
