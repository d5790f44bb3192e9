"""Looped decoder LM (`transformers` model_type `ouro`, ByteDance's Ouro
1.4B / 2.6B; Zhu et al. 2025, "Scaling Latent Reasoning via Looped Language
Models"): ONE stack of layers run `turns` times with one set of weights, the
closing norm, the head and an exit gate read after every turn, and the
expected cost over the exits:

    h = Emb[token]                                  (no multiplier, no table
                                                     of positions)
    for r = 1..K:                                   (the SAME weights)
        for l = 1..L:
            h <- h + n2_l(Attn_l(n1_l(h)));  h <- h + n4_l(FFN_l(n3_l(h)))
        h = rms(h; g_f)                             (closes EVERY turn: the
                                                     normed stream is what
                                                     the next turn reads)
        z_r = h W_out;   ce_r = CE(z_r, y);   s_r = h . w_exit + b_exit

    lambda_r = sigmoid(s_r);  p_r = lambda_r prod_{j<r} (1 - lambda_j),
    p_K = prod_{j<K} (1 - lambda_j)
    cost = mean over tokens of  sum_r p_r ce_r - beta H(p)

    Attn  layers.multi_head_attention: `num_heads` heads of `head_dim`,
          causal, rotary positions over the whole head (rotate-half), no
          biases, no QK-norm; the flash kernels of ops/flash_ops.py
    FFN   W_d (silu(x W_g) * (x W_u)), width `ffn_dim`
    n1-n4 RMSNorm [dim]: the residual adds a NORMED branch (`afmoe_lm`'s
          dense layer to the letter), so the stream is float32 under amp
          with no cast

The turns are a `layers.Repeat`: one Program sub-block, traced once as the
body of a scan and rematerialised a turn at a time, all but the last (which
is differentiated where it stands: the backward pass starts from it), so K
turns hold one turn's activations and the weights' gradients are summed over
the turns by the loop's own differentiation rule. The head, its
cross-entropy and the float32 gate are INSIDE the block: one turn's [T,
vocab] logits are alive at a time and are recomputed with their turn (the
last turn's are not), which is why the labels go in here.

looped_lm: tokens [B, T] int32, labels [B, T, 1] int32 -> (cost [], per-turn
token costs [K, B, T, 1], exit probabilities [K, B, T]).
"""

from __future__ import annotations

import paddle_tpu.layers as layers
from ..initializer import (ConstantInitializer, NormalInitializer,
                           XavierInitializer)
from ..param_attr import ParamAttr

__all__ = ["looped_lm"]

HEAD_GAIN = 0.5    # the head's start, of its Glorot range (docstring below)


def looped_lm(
    tokens,
    labels,
    vocab_size: int,
    dim: int = 2048,
    num_heads: int = 16,
    head_dim: int = 128,
    num_layers: int = 48,
    ffn_dim: int = 5632,
    turns: int = 4,
    rope_theta: float = 1e6,
    rms_eps: float = 1e-6,
    exit_beta: float = 0.05,
    name: str = "looped",
):
    """tokens: dense [B, T] int32 Variable; labels [B, T, 1] int32. Returns
    (the scalar cost, the turns' token costs [turns, B, T, 1], the exit
    probabilities [turns, B, T]). The defaults are Ouro-2.6B's published
    sizes; `exit_beta` is the paper's later stage-I weight of the entropy
    term. The token table starts from N(0, 1) as `olmoe_lm`'s does and for
    its reason (beside unit-rms normed branches a Glorot table leaves every
    position the same state); the norms' scales at one; the gate's weight
    AND bias at zero, so that every token starts from the same exit
    distribution, exactly (p = 1/2, 1/4, 1/8, 1/8 at four turns): a gate
    that starts from a Glorot vector weighs every token's first gradient by
    what the matmuls' rounding did to its stream, and the first step then
    stands 2-6 times further from the float32 mathematics (PERF.md section
    6, PR 44, has the chip's readings for both); the head Glorot uniform x
    `HEAD_GAIN` (one half: the first cost's distance from the float32
    mathematics is the stream's rounding read through the head, and goes
    with the head's scale; the same section); every other matrix at its
    layer's default. Parameters, in order: the table; per layer
    n1, the attention's (wq, wk, wv, wo), n2, n3, the FFN's (gate, up,
    down), n4; the closing norm; the head; the gate's weight and bias."""

    def proj(inp, weight, size, act=None, initializer=None):
        return layers.fc(inp, size=size, num_flatten_dims=2, act=act,
                         param_attr=ParamAttr(name=weight,
                                              initializer=initializer),
                         bias_attr=False)

    def norm(x, s):
        return layers.rms_norm(x, epsilon=rms_eps, name=s,
                               param_attr=ParamAttr(name=f"{s}.w"))

    h = layers.embedding(
        tokens, size=[vocab_size, dim],
        param_attr=ParamAttr(name=f"{name}.tok_emb",
                             initializer=NormalInitializer(0.0, 1.0)))
    loop = layers.Repeat(times=turns, name=f"{name}.turns")
    with loop.block():
        x = h
        for i in range(num_layers):
            prefix = f"{name}.h{i}"
            a = layers.multi_head_attention(
                norm(x, f"{prefix}.n1"), num_heads=num_heads,
                head_dim=head_dim, causal=True, bias_attr=False,
                rotary_theta=rope_theta, rms_eps=rms_eps,
                name=f"{prefix}.attn")
            x = layers.elementwise_add(x, norm(a, f"{prefix}.n2"))
            u = norm(x, f"{prefix}.n3")
            mlp = f"{prefix}.mlp"
            m = layers.elementwise_mul(
                proj(u, f"{mlp}.gate", ffn_dim, act="swish"),
                proj(u, f"{mlp}.up", ffn_dim))
            m = proj(m, f"{mlp}.down", dim)
            x = layers.elementwise_add(x, norm(m, f"{prefix}.n4"))
        x = norm(x, f"{name}.ln_f")
        loop.update(h, x)
        logits = proj(x, f"{name}.out_w", vocab_size,
                      initializer=XavierInitializer(gain=HEAD_GAIN))
        loop.turn_output(layers.softmax_with_cross_entropy(logits, labels))
        loop.turn_output(layers.exit_gate(
            x, param_attr=ParamAttr(name=f"{name}.exit.w",
                                    initializer=ConstantInitializer(0.0)),
            bias_attr=ParamAttr(name=f"{name}.exit.b"),
            name=f"{name}.exit"))
    _, turn_costs, gate_logits = loop()
    token_cost, probs = layers.exit_expected_cost(
        turn_costs, gate_logits, beta=exit_beta, name=f"{name}.exit_cost")
    return layers.mean(token_cost), turn_costs, probs
