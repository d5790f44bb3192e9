"""OLMoE-shaped decoder LM: pre-RMSNorm blocks, QK-norm, rotary position
embedding, and a routed-expert feed-forward (dropless top-k of SwiGLU
experts, float32 softmax router) in every layer. No biases, no learned
position table, untied output head.

The block, as `allenai/OLMoE-1B-7B` publishes it (Muennighoff et al.
2024; `transformers` model_type `olmoe`):

    x = x + Wo . attn(rope(rms(h Wq)), rope(rms(h Wk)), h Wv),  h = rms(x)
    x = x + sum_{j<k} g_j (silu(h Wg[e_j]) * (h Wu[e_j])) Wd[e_j],  h = rms(x)

Built from the layer DSL like `transformer_lm`, so AMP, remat, Trainer and
checkpointing apply unchanged; attention routes through the flash
dispatcher, the experts through the grouped-matmul dispatcher
(ops/moe_ops.py).

olmoe_lm: tokens [B, T] int32 -> (logits [B, T, vocab], aux cost, a scalar).
The aux cost is the mean over layers of each routed layer's
load-balancing and router z-loss (layers.moe_aux_loss), to be added to
the caller's cross-entropy.
"""

from __future__ import annotations

import paddle_tpu.layers as layers
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr

__all__ = ["olmoe_lm"]


def _block(x, num_heads, num_experts, experts_per_token, expert_dim,
           rope_theta, rms_eps, norm_topk_prob, prefix):
    h = layers.rms_norm(x, epsilon=rms_eps, name=f"{prefix}.ln_in",
                        param_attr=ParamAttr(name=f"{prefix}.ln_in.w"))
    h = layers.multi_head_attention(
        h, num_heads=num_heads, causal=True, bias_attr=False,
        qk_norm=True, rotary_theta=rope_theta, rms_eps=rms_eps,
        name=f"{prefix}.attn")
    x = layers.elementwise_add(x, h)
    h = layers.rms_norm(x, epsilon=rms_eps, name=f"{prefix}.ln_post",
                        param_attr=ParamAttr(name=f"{prefix}.ln_post.w"))
    h, router_logits, tokens_per_expert = layers.moe_ffn(
        h, num_experts, experts_per_token, expert_dim,
        norm_topk_prob=norm_topk_prob, name=f"{prefix}.moe")
    return layers.elementwise_add(x, h), router_logits, tokens_per_expert


def olmoe_lm(
    tokens,
    vocab_size: int,
    dim: int = 2048,
    num_heads: int = 16,
    num_layers: int = 16,
    num_experts: int = 64,
    experts_per_token: int = 8,
    expert_dim: int = 1024,
    rope_theta: float = 10000.0,
    rms_eps: float = 1e-5,
    norm_topk_prob: bool = False,
    aux_balance_weight: float = 0.01,
    aux_z_weight: float = 0.001,
    name: str = "olmoe",
):
    """tokens: dense [B, T] int32 Variable. Returns (per-position logits
    [B, T, vocab_size], scalar float32 aux cost). The defaults are
    OLMoE-1B-7B's published sizes; the aux weights are the OLMoE paper's.
    The token table starts from N(0, 1), `torch.nn.Embedding`'s default,
    and that is not a caller's choice: the layer DSL's own default (Glorot
    over [vocab, dim]: std 0.006 at 50304 x 2048) leaves the table so small
    beside one attention layer's output that every position carries nearly
    the same hidden state, the causal mean of the values; rounding errors
    and routing near-ties are then common to all tokens and do not average
    out over a batch (PERF.md section 6, PR 27). Every other matrix keeps
    its layer's default (Glorot uniform, per expert for the stacks); the
    norms' scales start at one."""
    x = layers.embedding(
        tokens, size=[vocab_size, dim],
        param_attr=ParamAttr(
            name=f"{name}.tok_emb",
            initializer=NormalInitializer(0.0, 1.0)),
    )
    aux = []
    for i in range(num_layers):
        x, router_logits, tokens_per_expert = _block(
            x, num_heads, num_experts, experts_per_token, expert_dim,
            rope_theta, rms_eps, norm_topk_prob, f"{name}.h{i}")
        aux.append(layers.moe_aux_loss(
            router_logits, tokens_per_expert, aux_balance_weight,
            aux_z_weight))
    x = layers.rms_norm(x, epsilon=rms_eps, name=f"{name}.ln_f",
                        param_attr=ParamAttr(name=f"{name}.ln_f.w"))
    logits = layers.fc(x, size=vocab_size, num_flatten_dims=2,
                       param_attr=ParamAttr(name=f"{name}.out_w"),
                       bias_attr=False)
    aux_cost = aux[0]
    for a in aux[1:]:
        aux_cost = layers.elementwise_add(aux_cost, a)
    if len(aux) > 1:
        aux_cost = layers.scale(aux_cost, scale=1.0 / len(aux))
    return logits, aux_cost
