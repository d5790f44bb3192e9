"""Trinity-shaped decoder LM (`transformers` model_type `afmoe`, Arcee's
Trinity Mini / Nano): WINDOW and GLOBAL attention layers in one model,
sandwich norms, and a feed-forward that is a dense SwiGLU FFN in the first
`num_dense_layers` layers and routed experts in the others:

    h_0 = sqrt(dim) x Emb[token]                       (`mup_enabled`)
    h <- h + n2(Attn_l(n1(h)));   h <- h + n4(FFN_l(n3(h)))
    logits = rms(h) W_head

    Attn  layers.multi_head_attention: `num_heads` query heads over
          `num_kv_heads` K/V heads of `head_dim`, RMSNorm on Q and K per
          head (one scale [head_dim] each), a sigmoid output gate from a
          fifth projection of the layer's input, and by `layer_types[l]`:
          "sliding_attention": rotary positions and a window of `window`
            keys (position i reads i - window < j <= i);
          "full_attention": NO position signal, every earlier key.
          Both kinds run the one flash kernel family (ops/flash_ops.py), a
          window layer with its `window` attribute.
    n1-n4 RMSNorm [dim]: the residual adds a NORMED branch, so the stream
          is float32 under amp with no cast (a table row and a norm's
          output are), and the scale of a matrix that writes to a branch
          (W_o, the down matrices) does not reach the stream at all
    dense W_d (silu(x W_g) * (x W_u)), width `dense_dim`
    E     layers.moe_ffn: a float32 sigmoid router with a choice bias (a
          buffer), top-k over ALL experts, gates renormalised and scaled,
          SwiGLU experts beside ONE SwiGLU shared expert

No biases, untied head, no auxiliary cost (the published recipe balances
load through the choice bias, which nothing steers here). `held_experts`
makes every routed layer one chip's share of an expert-parallel layer.

Built from the layer DSL like `olmoe_lm`, `nemotron_h_lm` and `glm_moe_lm`,
so AMP, remat, Trainer and checkpointing apply unchanged.

afmoe_lm: tokens [B, T] int32 -> (logits [B, T, vocab], [(router logits
[B*T, E] float32, tokens per expert [E] int32) of each routed layer]).
"""

from __future__ import annotations

import paddle_tpu.layers as layers
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr

__all__ = ["afmoe_lm", "TRINITY_MINI_LAYER_TYPES"]

WINDOW, GLOBAL = "sliding_attention", "full_attention"
# a global layer closes every period of four (`global_attn_every_n_layers`)
TRINITY_MINI_LAYER_TYPES = ((WINDOW,) * 3 + (GLOBAL,)) * 8


def afmoe_lm(
    tokens,
    vocab_size: int,
    layer_types=TRINITY_MINI_LAYER_TYPES,
    num_dense_layers: int = 2,
    dim: int = 2048,
    num_heads: int = 32,
    num_kv_heads: int = 4,
    head_dim: int = 128,
    window: int = 2048,
    dense_dim: int = 6144,
    num_experts: int = 128,
    experts_per_token: int = 8,
    expert_dim: int = 1024,
    shared_expert_dim: int = 1024,
    gate_scale: float = 2.826,
    norm_topk_prob: bool = True,
    held_experts=None,
    rope_theta: float = 1e4,
    rms_eps: float = 1e-5,
    chunk_shares: int = 3,
    name: str = "afmoe",
):
    """tokens: dense [B, T] int32 Variable. Returns (per-position logits
    [B, T, vocab_size], the routed layers' (RouterLogits, TokensPerExpert)
    in order). One layer per entry of `layer_types`; the defaults are
    Trinity-Mini's published sizes (attention 32 x 128 = 4096 wide at a
    hidden size of 2048). The token table starts from N(0, 1) as
    `olmoe_lm`'s does and for its reason, the norms' scales at one, every
    other matrix at its layer's default (Glorot uniform, per expert for the
    stacks). Behind the sqrt(dim) multiplier the stream starts at an rms of
    sqrt(dim) (45 at 2048) and every layer adds two unit-rms branches to it,
    so a token's own row is most of what every norm and router reads. With
    a table of N(0, 1 / dim), a stream of unit rms that every branch equals,
    the routers learn one direction common to all tokens inside 20 steps of
    Adam and half of a share's pairs land on its held experts (PERF.md
    section 6, PR 42, has the chip's readings for both). `chunk_shares`: a
    share's routed layers work through their live rows in chunks of three
    even shares of the T x k rows (`layers.moe_ffn`; two is the op's own: at
    top 8 of 128 the live rows settle on both sides of two). Parameters, in
    order: the table; per layer n1, the attention's (wq, wk, wv, q_norm,
    k_norm, wg, wo), n2, n3, the FFN's (dense: gate, up, down; routed:
    `moe_ffn`'s), n4; the final norm; the head."""
    layer_types = tuple(layer_types)
    unknown = set(layer_types) - {WINDOW, GLOBAL}
    if unknown:
        raise ValueError(f"layer_types: {sorted(unknown)} is neither "
                         f"{WINDOW!r} nor {GLOBAL!r}")
    if not 0 <= num_dense_layers <= len(layer_types):
        raise ValueError(f"num_dense_layers {num_dense_layers} not within "
                         f"0..{len(layer_types)}")

    def proj(inp, layer, weight, size, act=None):
        return layers.fc(inp, size=size, num_flatten_dims=2, act=act,
                         param_attr=ParamAttr(name=f"{layer}.{weight}"),
                         bias_attr=False)

    def norm(x, s):
        return layers.rms_norm(x, epsilon=rms_eps, name=s,
                               param_attr=ParamAttr(name=f"{s}.w"))

    x = layers.embedding(
        tokens, size=[vocab_size, dim],
        param_attr=ParamAttr(name=f"{name}.tok_emb",
                             initializer=NormalInitializer(0.0, 1.0)))
    x = layers.scale(x, scale=float(dim) ** 0.5)
    routers = []
    for i, kind in enumerate(layer_types):
        prefix = f"{name}.h{i}"
        local = kind == WINDOW
        h = layers.multi_head_attention(
            norm(x, f"{prefix}.n1"), num_heads=num_heads,
            num_kv_heads=num_kv_heads, head_dim=head_dim, causal=True,
            qk_norm="head", out_gate=True, rms_eps=rms_eps, bias_attr=False,
            rotary_theta=rope_theta if local else None,
            window=window if local else None, name=f"{prefix}.attn")
        x = layers.elementwise_add(x, norm(h, f"{prefix}.n2"))
        h = norm(x, f"{prefix}.n3")
        if i < num_dense_layers:
            mlp = f"{prefix}.mlp"
            h = layers.elementwise_mul(
                proj(h, mlp, "gate", dense_dim, act="swish"),
                proj(h, mlp, "up", dense_dim))
            h = proj(h, mlp, "down", dim)
        else:
            h, logits, counts = layers.moe_ffn(
                h, num_experts, experts_per_token, expert_dim,
                norm_topk_prob=norm_topk_prob, scoring="sigmoid",
                router_bias=True, gate_scale=gate_scale, expert_act="swiglu",
                held_experts=held_experts, chunk_shares=chunk_shares,
                shared_expert_dim=shared_expert_dim, name=f"{prefix}.moe")
            routers.append((logits, counts))
        x = layers.elementwise_add(x, norm(h, f"{prefix}.n4"))
    x = norm(x, f"{name}.ln_f")
    logits = layers.fc(x, size=vocab_size, num_flatten_dims=2,
                       param_attr=ParamAttr(name=f"{name}.out_w"),
                       bias_attr=False)
    return logits, routers
