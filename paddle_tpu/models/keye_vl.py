"""Keye-VL-2.0's LANGUAGE model (`Kwai-Keye/Keye-VL-2.0-30B-A3B`, model_type
`KeyeVL2`; the Qwen3-MoE lineage's decoder with a learned sparse attention):
pre-RMSNorm blocks, every layer a grouped-query attention in which a row
attends only the `topk` keys an INDEXER scores highest for it
(`layers.sparse_attention`: per-head QK-norm, a THREE-AXIS rotary whose
positions are fed data), and every layer's feed-forward routed experts
(`layers.moe_ffn`: a float32 softmax router, top k renormalised, dropless
SwiGLU experts, no shared expert). No biases, untied head, no auxiliary cost.

    h_0 = Emb[token]                                  (a span's rows too)
    h <- h + Attn_l(rms(h, g1), p);   h <- h + MoE_l(rms(h, g2))
    logits = rms(h, g_f) W_head

    Attn  q, k, v projections; q, k <- rms_128 per head, then the rotary:
          frequency pair i of 64 turns by the position on axis 0 (temporal)
          for i < 16, 1 (height) for 16 <= i < 40, 2 (width) beyond
          (`mrope_section` [16, 24, 24]; theta 1e7), p int32 [B, 3, T] FED;
          the indexer q^I = x W^I_q [Hi x Di], k^I = x W^I_k [Di], w = x
          W^I_w [Hi] scores I(t, s) = sum_j w[t, j] relu(q^I[t, j] . k^I[s]);
          a row keeps its min(topk, t + 1) best causal keys and attends
          those only. The indexer's three matrices are FROZEN: the sets are
          discrete, so the language-model cost gives them no gradient (the
          alignment cost that trains them is not built).
    MoE   z = x W_r float32 [E]; softmax; the top k; gates p / sum(p); the
          SwiGLU experts this chip HOLDS (`held_experts`) of the E scored.

The vision tower is not built (the catalog gives no key of it); what it
leaves in the language model is: a sequence holds image SPANS whose tokens
carry grid positions (the reader of `configs/keye_vl.py` makes them).

keye_lm: tokens [B, T] int32, positions [B, 3, T] int32 -> (logits [B, T,
vocab], [(router logits [B*T, E] float32, tokens per expert [E] int32) a
layer]). Built from the layer DSL, so AMP, remat, Trainer and checkpointing
apply unchanged.
"""

from __future__ import annotations

import paddle_tpu.layers as layers
from ..initializer import NormalInitializer, XavierInitializer
from ..param_attr import ParamAttr

__all__ = ["keye_lm", "KEYE_MROPE_SECTION"]

KEYE_MROPE_SECTION = (16, 24, 24)


def keye_lm(
    tokens,
    positions,
    vocab_size: int,
    num_layers: int = 48,
    dim: int = 2048,
    num_heads: int = 32,
    num_kv_heads: int = 4,
    head_dim: int = 128,
    index_heads: int = 16,
    index_head_dim: int = 64,
    topk: int = 2048,
    num_experts: int = 128,
    experts_per_token: int = 8,
    expert_dim: int = 768,
    norm_topk_prob: bool = True,
    held_experts=None,
    rope_theta: float = 1e7,
    mrope_section=KEYE_MROPE_SECTION,
    rms_eps: float = 1e-6,
    layer_ids=None,
    name: str = "keye",
):
    """The defaults are the published sizes. `layer_ids`: the published
    indices of the layers built (a pipeline stage's part; None: 0 ..
    num_layers - 1), which name their parameters (`<name>.h<id>`).
    `held_experts` makes every layer one chip's share of an expert-parallel
    layer. The token table starts from N(0, 1) as `olmoe_lm`'s does and for
    its reason; the norms' scales at one; the matrices that WRITE to the
    stream (an attention layer's W_o, the experts' down stacks) at 1 /
    sqrt(num_layers), the published depth's, times their Glorot range
    (`rescale_prenorm_residual`, as `nemotron_h_lm` and `phi4flash_lm` start
    theirs): a branch's bf16 rounding reaches the next layers' routers
    through the float32 stream, and at the full Glorot range a later layer's
    router chose another expert than the float32 mathematics in one row of
    16 384 beyond a near-tie in one run of four (PERF.md section 6, PR 60);
    every other matrix, the frozen indexer's three among them, at its layer's
    default (Glorot uniform, per expert for the stacks). Parameters, in
    order: the table; per layer g1, the attention's (wq, wk, wv, q_norm,
    k_norm, index_wq, index_wk, index_ww, wo), g2, `moe_ffn`'s (router, gate,
    up, down); the final norm; the head."""
    ids = tuple(range(num_layers) if layer_ids is None else layer_ids)
    if len(set(ids)) != len(ids) or any(
            not 0 <= i < num_layers for i in ids):
        raise ValueError(f"layer_ids {ids}: distinct indices of the "
                         f"{num_layers} published layers")

    def writer(**fans):
        return ParamAttr(initializer=XavierInitializer(
            gain=num_layers ** -0.5, **fans))

    def add(x, branch):
        # the stream stays float32 under amp: a bf16 branch meeting it would
        # pull the SUM down to bf16 (`amp.harmonize`), the token's own row
        # with it, and every later norm and router would read it rounded
        return layers.elementwise_add(x, layers.cast(branch, "float32"))

    def norm(x, s):
        return layers.rms_norm(x, epsilon=rms_eps, name=s,
                               param_attr=ParamAttr(name=f"{s}.w"))

    x = layers.embedding(
        tokens, size=[vocab_size, dim],
        param_attr=ParamAttr(name=f"{name}.tok_emb",
                             initializer=NormalInitializer(0.0, 1.0)))
    routers = []
    for i in ids:
        prefix = f"{name}.h{i}"
        h = layers.sparse_attention(
            norm(x, f"{prefix}.ln_in"), positions, num_heads=num_heads,
            num_kv_heads=num_kv_heads, head_dim=head_dim,
            index_heads=index_heads, index_head_dim=index_head_dim,
            topk=topk, rope_sections=mrope_section, rotary_theta=rope_theta,
            rms_eps=rms_eps, param_attr={"wo": writer()},
            name=f"{prefix}.attn")
        x = add(x, h)
        h, logits, counts = layers.moe_ffn(
            norm(x, f"{prefix}.ln_post"), num_experts, experts_per_token,
            expert_dim, norm_topk_prob=norm_topk_prob, scoring="softmax",
            held_experts=held_experts,
            param_attr={"down": writer(fan_in=expert_dim, fan_out=dim)},
            name=f"{prefix}.moe")
        routers.append((logits, counts))
        x = add(x, h)
    x = norm(x, f"{name}.ln_f")
    logits = layers.fc(x, size=vocab_size, num_flatten_dims=2,
                       param_attr=ParamAttr(name=f"{name}.out_w"),
                       bias_attr=False)
    return logits, routers
