"""Plain reference for the LFM2-shaped decoder (`paddle_tpu.models.lfm2_moe_lm`):
the forward pass, the cost and its gradients in straightforward float32
`jax.numpy` at the highest matmul precision, no kernel, no cache, sharing only
the parameter VALUES with the code under test (and, where asked, the program's
own choice of experts, handed in). Written so that a benchmark configuration
can copy it whole as its `reference.py`
(`chipbench/configs/lfm2-24b-a2b/reference.py` is that copy;
`tests/test_chipbench_harness.py` holds the two to the same bits).

The model, as `LiquidAI/LFM2-24B-A2B` publishes it (`transformers` model_type
`lfm2_moe`; the config's keys by their own names). x [T, d], d = hidden_size;
every projection bias-free; rms_n(v, w) = v * rsqrt(mean(v^2, -1) + norm_eps)
* w over n lanes; layer l, two norms (pre-norm):

    h_0 = Emb[token]                                   (rows enter unscaled)
    h <- h + Op_l(rms_d(h, operator_norm))
    h <- h + FFN_l(rms_d(h, ffn_norm))
    logits = rms_d(h, embedding_norm) W_head   (untied here)

Op_l(u), `layer_types[l]` "conv" (K = conv_L_cache, `conv_bias` false):
    [B | C | X] = u W_in, W_in [d, 3 d], in that order;  z = B * X
    c_t = sum_{k < K} w[k] * z_{t - (K - 1) + k}, zeros before the sequence's
    start (a causal depthwise convolution, kernel [K, d], no activation)
    out = (C * c) W_out
Op_l(u), "full_attention", H = num_attention_heads, KV = num_key_value_heads,
D = d / H:
    q = u W_q [T, H, D];  k = u W_k, v = u W_v [T, KV, D]
    q <- rms_D(q, w_q), k <- rms_D(k, w_k): over each head's D lanes, ONE scale
    [D] for all query heads and one for all key heads; THEN q, k <-
    RoPE(rope_theta) over all D lanes at the token's position
    scores q k^T / sqrt(D), j <= i, query head h reads K/V head h // (H / KV),
    softmax, o = P v [T, H D];  out = o W_o
FFN_l, l < num_dense_layers: W2 (silu(v W1) * (v W3)), width intermediate_size.
FFN_l, the others: z = v W_r [T, E]; s = sigmoid(z); the CHOICE is the top
    num_experts_per_tok of s + b over ALL E (b [E] = `expert_bias`: a buffer,
    zeros, no gradient; `use_expert_bias`); gates g = routed_scaling_factor x
    s[chosen] / (sum(s[chosen]) + 1e-6) (`norm_topk_prob`); y = sum_{e chosen}
    g_e W2^e (silu(v W1^e) * (v W3^e)), moe_intermediate_size wide. No shared
    expert. Dropless.
    ONE CHIP'S SHARE: the stacks hold experts lo..hi-1 (`held_experts`) of the
    `router_experts` the router scores; a (token, slot) pair whose expert is
    absent adds nothing here (another chip adds it), in this reference and in
    the program alike.

cost = mean over tokens of -log softmax(logits)[label]. No auxiliary cost.

Assumed, where the config's keys do not say (the basis is the `transformers`
implementation of `lfm2` / `lfm2_moe`, the public description of the family):
the order [B | C | X] of the in-projection's thirds; the gate B before the
convolution and C behind it; the per-head QK-norm and its place before the
rotary; where the two norms sit and that `embedding_norm` closes the stack; the
1e-6 in the gates' sum; RoPE pairs lane i with lane i + D/2 (rotate-half, this
repo's `rotary_embedding` convention), inv_freq_i = rope_theta^(-2i/D); a zero
choice bias.

Departures from a literal transcription, none of them in the mathematics: the
convolution is K shifted multiplies; attention is mapped over (sequence, head,
block of queries), each block's [queries, T] scores with the mask as a
comparison of two `arange`s, under `jax.checkpoint` (one block's scores at a
time: T 16 384 fits); the experts are a scan over the held stack that adds each
expert's gated output for EVERY token into one accumulator (the gate is zero
where the token did not choose it); the k largest scores are picked one at a
time in a loop instead of sorted (the lowest index first among equals, as
`jax.lax.top_k`); the head and its cross-entropy run over chunks of 512 tokens.
So it fits beside the weights after a benchmark's window.

Parameters, in the program's creation order: the token table; per layer the
operator norm, then a conv layer's W_in, w [K, d], W_out or an attention
layer's W_q, W_k, W_v, w_q, w_k, W_o, then the FFN norm, then a dense layer's
W1, W3, W2 or a routed layer's W_r, the stacks W1 [held, d, f], W3, W2 [held, f,
d], b; the closing norm; W_head.

The handed choice (the routed-configuration contract of `chipbench/README.md`):
`loss_and_grads`, `loss_grads_and_routers`, `cost`, `hidden` and
`router_logits` take `choice`: a list, one per routed layer, of 0/1 masks
[tokens, router_experts] saying which experts each token's pairs go to. Where
it is given the gates are THIS file's float32 scores of those experts,
renormalised and scaled as published; gradients flow through the scores, and a
later layer's hidden state is this file's own under the handed choices.
`choice=None` is the reference's own top k. `chosen` applies the published
rule to router logits that are handed in.
"""

import math

import jax
import jax.numpy as jnp

OPERATOR = {"conv": 3, "full_attention": 6}
FFN = {"dense": 3, "routed": 5}
HEAD_CHUNK = 512
QUERY_BLOCK = 2048
GATE_NORM_EPS = 1e-6


def _rms(v, w, eps):
    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * w


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def _held(config):
    lo, hi = config.get("held_experts") or (0, _router_width(config))
    return int(lo), int(hi)


def _router_width(config):
    return int(config.get("router_experts") or config["num_experts"])


def _kinds(config):
    dense = config["num_dense_layers"]
    assert len(config["layer_types"]) == config["num_hidden_layers"]
    return ["dense" if i < dense else "routed"
            for i in range(config["num_hidden_layers"])]


# ------------------------------------------------------------ operators
def short_conv(config, u, w_in, w, w_out):
    """u [B, T, d] -> [B, T, d]: the gated short convolution, its K taps as K
    shifted multiplies (tap K - 1 reads the token itself)."""
    K, d = w.shape
    assert K == config["conv_L_cache"], (K, config["conv_L_cache"])
    T = u.shape[1]
    bcx = u @ w_in
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    z = b * x
    conv = jnp.zeros_like(z)
    for k in range(K):
        back = K - 1 - k
        conv = conv + w[k] * jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :T]
    return (c * conv) @ w_out


def rope(x, theta):
    """x [..., T, D] -> the same, position t turning the lane pair (i, i +
    D/2) by t * theta^(-2i/D)."""
    T, D = x.shape[-2:]
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def _attend(qkv):
    """One head of one sequence, q, k, v [T, D]: causal softmax in blocks of
    queries, each block against all T keys under the mask."""
    q, k, v = qkv
    T, D = q.shape
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    def one(args):
        q_b, first = args
        s = q_b @ k.T / math.sqrt(D)
        seen = (first + jnp.arange(block))[:, None] >= jnp.arange(T)[None, :]
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v

    o = jax.lax.map(jax.checkpoint(one), (
        q.reshape(T // block, block, D), jnp.arange(0, T, block)))
    return o.reshape(T, D)


def _rope_theta(config):
    return float((config.get("rope_parameters") or config)["rope_theta"])


def attention(config, u, w_q, w_k, w_v, n_q, n_k, w_o):
    """u [B, T, d] -> [B, T, d]."""
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    D, eps = config["hidden_size"] // H, config["norm_eps"]
    Bsz, T, _ = u.shape
    heads = lambda y, n: y.reshape(Bsz, T, n, D).transpose(0, 2, 1, 3)  # noqa: E731
    theta = _rope_theta(config)
    q = rope(_rms(heads(u @ w_q, H), n_q, eps), theta)       # [B, H, T, D]
    k = rope(_rms(heads(u @ w_k, KV), n_k, eps), theta)
    v = heads(u @ w_v, KV)
    k, v = (jnp.repeat(y, H // KV, axis=1) for y in (k, v))
    o = jax.lax.map(_attend, (
        q.reshape(Bsz * H, T, D), k.reshape(Bsz * H, T, D),
        v.reshape(Bsz * H, T, D)))
    o = o.reshape(Bsz, H, T, D).transpose(0, 2, 1, 3).reshape(Bsz, T, H * D)
    return o @ w_o


# ------------------------------------------------------- routed experts
def _top_k_mask(scores, top_k):
    """[N, E] -> 0/1 [N, E]: the `top_k` largest of each row, picked one at
    a time, the lowest index first among equals (as `jax.lax.top_k`)."""
    def pick(_, chosen):
        best = jnp.argmax(jnp.where(chosen > 0, -jnp.inf, scores), axis=-1)
        return chosen + jax.nn.one_hot(best, scores.shape[-1],
                                       dtype=scores.dtype)

    return jax.lax.fori_loop(0, top_k, pick, jnp.zeros_like(scores))


def router_scores(config, h, wr, b, chosen=None):
    """h [N, d] -> (logits z [N, E], gates [N, E]: the scaled, renormalised
    score of each chosen expert, zero elsewhere). `chosen` [N, E] 0/1 takes
    the place of the top k of s + b."""
    z = h @ wr
    s = jax.nn.sigmoid(z)
    if chosen is None:
        chosen = _top_k_mask(jax.lax.stop_gradient(s + b),
                             config["num_experts_per_tok"])
    gates = s * chosen
    if config["norm_topk_prob"]:
        gates = gates / (gates.sum(-1, keepdims=True) + GATE_NORM_EPS)
    return z, gates * config["routed_scaling_factor"]


def _expert(h, w1, w3, w2, gate):
    """One expert on EVERY token, times the token's gate for it (zero where
    the expert is not among the token's chosen)."""
    return _swiglu(h, w1, w3, w2) * gate[:, None]


def experts(config, h, wr, w1, w3, w2, b, chosen=None):
    """h [N, d] -> (y [N, d], router logits [N, E]): a loop over the HELD
    experts."""
    lo, hi = _held(config)
    z, gates = router_scores(config, h, wr, b, chosen)

    def add(y, expert):
        return y + jax.checkpoint(_expert)(h, *expert), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h),
                        (w1, w3, w2, gates[:, lo:hi].T))
    return y, z


# ---------------------------------------------------------------- model
def _split(config, params):
    """(table, [(operator kind, operator norm, operator's, FFN kind, FFN
    norm, FFN's)], closing norm, head)."""
    assert config["use_expert_bias"], "the published layer has a choice bias"
    params = [jnp.asarray(p, jnp.float32) for p in params]
    tok_emb, *flat = params
    layers, at = [], 0
    for op, ffn in zip(config["layer_types"], _kinds(config)):
        n_op, n_ffn = OPERATOR[op], FFN[ffn]
        layers.append((op, flat[at], flat[at + 1:at + 1 + n_op], ffn,
                       flat[at + 1 + n_op],
                       flat[at + 2 + n_op:at + 2 + n_op + n_ffn]))
        at += 2 + n_op + n_ffn
    assert len(flat) == at + 2, (len(params), at)
    return tok_emb, layers, flat[at], flat[at + 1]


def _hidden(config, params, toks, choice):
    """toks [B, T] -> (x [B, T, d] before the closing norm, each routed
    layer's router: its input h [B*T, d], its weight and its logits [B*T,
    E])."""
    tok_emb, layers, _, _ = _split(config, params)
    Bsz, T = toks.shape
    eps = config["norm_eps"]
    x = jax.lax.map(lambda t: tok_emb[t], toks)                  # [B, T, d]
    routers = []
    for op, n_op, p_op, ffn, n_ffn, p_ffn in layers:
        u = _rms(x, n_op, eps)
        x = x + (short_conv(config, u, *p_op) if op == "conv"
                 else attention(config, u, *p_op))
        h = _rms(x, n_ffn, eps)
        if ffn == "dense":
            y = _swiglu(h, *p_ffn)
        else:
            h = h.reshape(Bsz * T, -1)
            y, z = experts(
                config, h, *p_ffn,
                chosen=None if choice is None else choice[len(routers)])
            y = y.reshape(Bsz, T, -1)
            routers.append((h, p_ffn[0], z))
        x = x + y
    return x, routers


def hidden(config, params, toks, choice=None):
    """toks [B, T] -> (x [B, T, d] before the closing norm, the router logits
    of each routed layer [B*T, E]); `choice[i]` is handed to the i-th routed
    layer."""
    x, routers = _hidden(config, params, toks, choice)
    return x, [z for _, _, z in routers]


def logits(config, params, toks, choice=None):
    """[B, T, vocabulary], whole (small sizes only)."""
    _, _, w_f, w_head = _split(config, params)
    with jax.default_matmul_precision("highest"):
        x, _ = hidden(config, params, jnp.asarray(toks), choice)
        return _rms(x, w_f, config["norm_eps"]) @ w_head


def _cross_entropy_sum(config, x, labels, w_f, w_head):
    """Sum over tokens of -log softmax(rms(x) W_head)[label], in chunks."""
    N = x.shape[0]
    chunk = HEAD_CHUNK if N % HEAD_CHUNK == 0 else N

    def one(x_c, labels_c):
        logp = jax.nn.log_softmax(
            _rms(x_c, w_f, config["norm_eps"]) @ w_head, axis=-1)
        return -jnp.take_along_axis(logp, labels_c, axis=-1).sum()

    def add(total, xl):
        return total + jax.checkpoint(one)(*xl), None

    total, _ = jax.lax.scan(
        add, jnp.zeros((), jnp.float32),
        (x.reshape(N // chunk, chunk, -1), labels.reshape(N // chunk, chunk, 1)))
    return total


def _cost_and_routers(config, params, feed, choice):
    _, _, w_f, w_head = _split(config, params)
    toks, labels = jnp.asarray(feed["toks"]), jnp.asarray(feed["labels"])
    x, routers = _hidden(config, params, toks, choice)
    x = x.reshape(-1, x.shape[-1])
    return _cross_entropy_sum(config, x, labels.reshape(-1, 1), w_f,
                              w_head) / x.shape[0], routers


def cost(config, params, feed, choice=None):
    return _cost_and_routers(config, params, feed, choice)[0]


def loss_grads_and_routers(config, params, feed, choice=None):
    """(cost, gradients, each routed layer's router as this file computed it:
    input [tokens, d], weight [d, E], logits [tokens, E]), one forward pass.
    The cost is the mean next-token cross-entropy over the batch; the
    gradients are for every parameter, in the program's parameter order: what
    plain Adam is handed (no clipping, no decay). The router's bias is a
    buffer: its gradient is zero here and nothing updates it there."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    with jax.default_matmul_precision("highest"):
        (cost_, routers), grads = jax.value_and_grad(
            lambda ps: _cost_and_routers(config, ps, feed, choice),
            has_aux=True)(params)
    return cost_, grads, routers


def loss_and_grads(config, params, feed, choice=None):
    return loss_grads_and_routers(config, params, feed, choice)[:2]


def router_logits(config, params, feed, choice=None):
    """This file's own float32 routers: a list of [tokens, experts], one per
    routed layer."""
    with jax.default_matmul_precision("highest"):
        return hidden(config, params, jnp.asarray(feed["toks"]), choice)[1]


def chosen(config, params, logits):
    """The published choice on HANDED router logits (a list of float32
    [tokens, E], one per routed layer): the top k of sigmoid(z) + b, b the
    layer's choice bias among `params`. A list of 0/1 masks [tokens, E]."""
    _, layers, _, _ = _split(config, params)
    biases = [p_ffn[4] for *_, ffn, _, p_ffn in layers if ffn == "routed"]
    assert len(biases) == len(logits), (len(biases), len(logits))
    return [_top_k_mask(jax.nn.sigmoid(jnp.asarray(z, jnp.float32)) + b,
                        config["num_experts_per_tok"])
            for z, b in zip(logits, biases)]


def prepare(feed):
    """The reader's batch is already a dict of arrays."""
    return feed
