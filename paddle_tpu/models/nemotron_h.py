"""Nemotron-H-shaped hybrid decoder LM: a stack of pre-RMSNorm residual
blocks whose kinds follow a PATTERN string, one character a block, each
block ONE mixer and nothing else:

    M   a Mamba-2 state-space mixer             (layers.mamba2_mixer)
    *   causal grouped-query attention with NO position signal: neither
        rotary nor a learned table; the state-space blocks carry order
        (layers.multi_head_attention, num_kv_heads)
    E   routed experts: a float32 sigmoid router with a choice bias, top-k
        gates renormalised and scaled, relu^2 experts without a gate
        matrix, a shared relu^2 expert beside them (layers.moe_ffn)

    x <- x + mixer_kind(rms(x, w_i));   logits = rms(x, w_f) W_head

as `nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B` publishes it (`transformers`
model_type `nemotron_h`, `hybrid_override_pattern`). No biases, untied
head. `held_experts` makes every E block one chip's share of an expert-
parallel layer (layers.moe_ffn). No auxiliary cost: the published recipe
balances load through the router's choice bias, which is a buffer here.

Built from the layer DSL like `transformer_lm` and `olmoe_lm`, so AMP,
remat, Trainer and checkpointing apply unchanged.

nemotron_h_lm: tokens [B, T] int32 -> logits [B, T, vocab].
"""

from __future__ import annotations

import paddle_tpu.layers as layers
from ..initializer import NormalInitializer, XavierInitializer
from ..param_attr import ParamAttr

__all__ = ["nemotron_h_lm"]

KINDS = "M*E"


def nemotron_h_lm(
    tokens,
    vocab_size: int,
    pattern: str,
    dim: int = 2688,
    mamba_heads: int = 64,
    mamba_head_dim: int = 64,
    n_groups: int = 8,
    state_size: int = 128,
    conv_kernel: int = 4,
    chunk: int = 128,
    num_heads: int = 32,
    num_kv_heads: int = 2,
    head_dim: int = 128,
    num_experts: int = 128,
    experts_per_token: int = 6,
    expert_dim: int = 1856,
    shared_expert_dim: int = 3712,
    gate_scale: float = 2.5,
    norm_topk_prob: bool = True,
    held_experts=None,
    out_scale=None,
    rms_eps: float = 1e-5,
    name: str = "nemotron_h",
):
    """tokens: dense [B, T] int32 Variable; `pattern`: one of "M*E" a
    block. Returns per-position logits [B, T, vocab_size]. The defaults are
    Nemotron-3-Nano-30B-A3B's published widths. The attention width is
    `num_heads` x `head_dim` (4096 at a hidden size of 2688: Q and the
    output projection are not square). The token table starts from N(0, 1)
    as `olmoe_lm`'s does and for its reason. Every other matrix keeps its
    layer's default, but for TWO DEPARTURES from the published config.json
    (`rescale_prenorm_residual` true, `residual_in_fp32` false):

    - every matrix that writes to the residual stream (the mixers' and
      attention's output projections, the experts' and the shared expert's
      down matrices) starts at `out_scale` times its layer's Glorot range,
      by default 1 / sqrt(number of blocks); a caller that builds a part of
      a deeper model passes that model's. `transformers` applies the rule
      to the parameters named `out_proj` only, the mixers'; here attention
      and the experts take it too (GPT-2's scheme);
    - the residual stream is float32 under amp too: a block's output is
      cast up before it is added. Each is about 15 % of the stream, and
      bf16's 8 bits would round 3 % of it away at every add.

    Why (PERF.md section 6, PR 32; a TPU v5e, T 8192, the first step against
    the float32 reference, two seeds as published and 24 as built): as
    published (`out_proj` alone rescaled, a bf16 stream) the router logits
    of the four routed blocks are 0.18 / 1.2-3.6 / 1.9-3.9 / 3.2-4.4 % off,
    14-640 of 8 192 tokens a block choose another expert set, the first cost
    is 0.8-1.2e-4 off and the worst dense tensor (`dt_bias`, `A_log`) reads
    0.07-0.10 of its rms; as built here the logits are 0.08 / 0.12-0.5 /
    0.16-0.6 / 0.2-0.7 % off, 2-205 tokens a block turn, the first cost is
    at most 2.1e-5 off and the worst dense tensor reads 0.008-0.036. The
    step pays for the float32 stream (the mixers' device time 167.8 ->
    182.7 ms of a 518 ms step: the casts ride in the projections' fusions)."""
    bad = set(pattern) - set(KINDS)
    if bad or not pattern:
        raise ValueError(f"pattern {pattern!r}: blocks are of {KINDS!r}")
    if out_scale is None:
        out_scale = len(pattern) ** -0.5

    def scaled(fan_in=None, fan_out=None):
        return ParamAttr(initializer=XavierInitializer(
            fan_in=fan_in, fan_out=fan_out, gain=out_scale))

    x = layers.embedding(
        tokens, size=[vocab_size, dim],
        param_attr=ParamAttr(name=f"{name}.tok_emb",
                             initializer=NormalInitializer(0.0, 1.0)))
    for i, kind in enumerate(pattern):
        prefix = f"{name}.h{i}"
        h = layers.rms_norm(x, epsilon=rms_eps, name=f"{prefix}.ln",
                            param_attr=ParamAttr(name=f"{prefix}.ln.w"))
        if kind == "M":
            h = layers.mamba2_mixer(
                h, mamba_heads, mamba_head_dim, n_groups, state_size,
                conv_kernel=conv_kernel, chunk=chunk, epsilon=rms_eps,
                param_attr={"out_w": scaled()}, name=f"{prefix}.mamba")
        elif kind == "*":
            h = layers.multi_head_attention(
                h, num_heads=num_heads, num_kv_heads=num_kv_heads,
                head_dim=head_dim, causal=True, bias_attr=False,
                param_attr={"wo": scaled()}, name=f"{prefix}.attn")
        else:
            h, _, _ = layers.moe_ffn(
                h, num_experts, experts_per_token, expert_dim,
                norm_topk_prob=norm_topk_prob, scoring="sigmoid",
                router_bias=True, gate_scale=gate_scale, expert_act="relu2",
                held_experts=held_experts,
                shared_expert_dim=shared_expert_dim,
                # Glorot over ONE expert's matrix, as the layer's default
                param_attr={"down": scaled(expert_dim, dim),
                            "shared_down": scaled()},
                name=f"{prefix}.moe")
        x = layers.elementwise_add(x, layers.cast(h, "float32"))
    x = layers.rms_norm(x, epsilon=rms_eps, name=f"{name}.ln_f",
                        param_attr=ParamAttr(name=f"{name}.ln_f.w"))
    return layers.fc(x, size=vocab_size, num_flatten_dims=2,
                     param_attr=ParamAttr(name=f"{name}.out_w"),
                     bias_attr=False)

