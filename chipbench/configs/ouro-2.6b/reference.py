"""Plain reference for the looped decoder LM (`paddle_tpu.models.looped_lm`;
`transformers` model_type `ouro`: ByteDance's Ouro 1.4B / 2.6B, Zhu et al.
2025, "Scaling Latent Reasoning via Looped Language Models"): the forward
pass, the cost and its gradients in float32 `jax.numpy` at the highest matmul
precision, a Python `for` over the turns on ONE set of parameters. No kernel,
no `Repeat`, no loop construct over the turns (turn r + 1 is the next
statement of the program, reading the weights turn r read), no cache. It
shares only parameter values with the code under test.

With x [B, T, d], every projection bias-free, and rms(v; g) = g * v *
rsqrt(mean(v^2, -1) + eps):

    h = Emb[tokens]                                 # no multiplier, no
                                                    # learned positions
    for r = 1..K:                                   # the SAME weights
        for l = 1..L:
            u = rms(h; g1_l)
            q, k, v = u Wq_l, u Wk_l, u Wv_l  -> [B, T, H, D]
            q, k <- rotary (rotate-half: lane i pairs with lane i + D/2,
                    inv_freq_i = theta^(-2i/D), position t), over all D
            a = causal softmax(q k^T / sqrt(D)) v  -> [B, T, H D]
            h = h + rms(a Wo_l; g2_l)               # a NORMED branch
            u = rms(h; g3_l)
            h = h + rms((silu(u Wg_l) * (u Wu_l)) Wd_l; g4_l)
        h = rms(h; g_f)                             # closes EVERY turn: the
                                                    # next turn starts here
        ce_r = -log softmax(h W_out)[label]         # one head for all turns
        s_r = h . w_exit + b_exit                   # the exit gate

    lambda_r = sigmoid(s_r)
    p_r = lambda_r * prod_{j<r} (1 - lambda_j)      for r < K
    p_K = prod_{j<K} (1 - lambda_j)                 # what is left
    cost = mean over the B T tokens of  sum_r p_r ce_r - beta * H(p),
    H(p) = - sum_r p_r log p_r

K = `total_ut_steps`, L = `num_hidden_layers`, beta = `exit_beta`.

Parameters, in the program's creation order: the token table; per layer (11
tensors) g1, Wq, Wk, Wv, Wo, g2, g3, Wg, Wu, Wd, g4; then g_f, W_out, w_exit
[d], b_exit [1].

Memory and compile time (the chip run holds this beside 2.4 GB of weights and
as much of gradients; the mathematics does not depend on any of it): WITHIN a
turn the L layers are a `jax.lax.scan` over their tensors stacked `[L, ...]`
(as first written, 32 layer applications spelled out one by one, the compile
took 150-190 s of every run: PERF.md section 6, PR 44); a layer is under
`jax.checkpoint`, so the backward pass keeps each of the K x L layer
applications' input and not its scores; attention is mapped over (sequence,
head) in blocks of `QUERY_BLOCK` query rows, each block's [rows, T] scores
whole; a turn's head and cross-entropy run over chunks of 512 tokens under
`jax.checkpoint`, so no [tokens, vocabulary] array outlives its chunk.
"""

import math

import jax
import jax.numpy as jnp

PER_LAYER = 11
HEAD_CHUNK = 512
QUERY_BLOCK = 512


def _rms(v, g, eps):
    return g * v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)


def _rotate_half(x):
    d = x.shape[-1] // 2
    return jnp.concatenate([-x[..., d:], x[..., :d]], axis=-1)


def _rope(x, theta):
    """x [B, T, H, D]: x * cos + rotate_half(x) * sin, the angles of the D/2
    frequencies repeated over both halves of the head."""
    T, D = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    return x * jnp.cos(ang) + _rotate_half(x) * jnp.sin(ang)


def _attend(qkv):
    """One head of one sequence, q, k, v [T, D]: full scores, a block of
    query rows at a time."""
    q, k, v = qkv
    T, D = q.shape
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    def rows(start_q):
        start, q_b = start_q
        s = q_b @ k.T / math.sqrt(D)                       # [block, T]
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(T)[None, :]
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v

    out = jax.lax.map(rows, (jnp.arange(0, T, block), q.reshape(-1, block, D)))
    return out.reshape(T, D)


def _layer(h, p, heads, head_dim, theta, eps):
    g1, wq, wk, wv, wo, g2, g3, wg, wu, wd, g4 = p
    B, T, _ = h.shape
    u = _rms(h, g1, eps)
    q, k, v = (t.reshape(B, T, heads, head_dim) for t in (u @ wq, u @ wk, u @ wv))
    q, k = _rope(q, theta), _rope(k, theta)
    per_head = tuple(t.transpose(0, 2, 1, 3).reshape(B * heads, T, head_dim)
                     for t in (q, k, v))
    a = jax.lax.map(jax.checkpoint(_attend), per_head)
    a = a.reshape(B, heads, T, head_dim).transpose(0, 2, 1, 3).reshape(B, T, -1)
    h = h + _rms(a @ wo, g2, eps)
    u = _rms(h, g3, eps)
    return h + _rms((jax.nn.silu(u @ wg) * (u @ wu)) @ wd, g4, eps)


def _token_costs(h, labels, w_out):
    """h [N, d], labels [N, 1] -> -log softmax(h W_out)[label] [N], in
    chunks."""
    N = h.shape[0]
    chunk = HEAD_CHUNK if N % HEAD_CHUNK == 0 else N

    def one(hl):
        h_c, labels_c = hl
        logp = jax.nn.log_softmax(h_c @ w_out, axis=-1)
        return -jnp.take_along_axis(logp, labels_c, axis=-1)[:, 0]

    return jax.lax.map(jax.checkpoint(one), (
        h.reshape(N // chunk, chunk, -1),
        labels.reshape(N // chunk, chunk, 1))).reshape(N)


def _split(config, params):
    layers = config["num_hidden_layers"]
    assert len(params) == 1 + PER_LAYER * layers + 4, len(params)
    params = [jnp.asarray(p, jnp.float32) for p in params]
    tok_emb, *rest = params
    *flat, g_f, w_out, w_exit, b_exit = rest
    return tok_emb, [flat[i * PER_LAYER:(i + 1) * PER_LAYER]
                     for i in range(layers)], g_f, w_out, w_exit, b_exit


def turns(config, params, feed):
    """-> (the turns' token costs [K, B, T], their gate logits [K, B, T], the
    stream after the last turn [B, T, d])."""
    tok_emb, layers, g_f, w_out, w_exit, b_exit = _split(config, params)
    toks, labels = jnp.asarray(feed["toks"]), jnp.asarray(feed["labels"])
    heads, head_dim = config["num_attention_heads"], config["head_dim"]
    theta, eps = float(config["rope_theta"]), config["rms_norm_eps"]
    B, T = toks.shape
    h = jax.lax.map(lambda t: tok_emb[t], toks)                  # [B, T, d]
    # the L layers' tensors side by side, [L, ...] each: the same values
    stack = [jnp.stack(ws) for ws in zip(*layers)]

    def layer(h, p):
        return jax.checkpoint(_layer, static_argnums=(2, 3, 4, 5))(
            h, p, heads, head_dim, theta, eps), None

    costs, gates = [], []
    for _ in range(config["total_ut_steps"]):       # the SAME weights
        h, _ = jax.lax.scan(layer, h, stack)        # layer 1 .. L, in order
        h = _rms(h, g_f, eps)
        costs.append(_token_costs(h.reshape(B * T, -1), labels.reshape(-1, 1),
                                  w_out).reshape(B, T))
        gates.append(h @ w_exit + b_exit)
    return jnp.stack(costs), jnp.stack(gates), h


def exit_probabilities(gates):
    """gate logits [K, ...] -> p [K, ...]: p_r = lambda_r prod_{j<r} (1 -
    lambda_j), and the last exit takes what is left."""
    lam = jax.nn.sigmoid(gates)
    p, left = [], jnp.ones_like(lam[0])
    for r in range(lam.shape[0] - 1):
        p.append(lam[r] * left)
        left = left * (1.0 - lam[r])
    p.append(left)
    return jnp.stack(p)


def expected_cost(costs, gates, beta):
    """-> (cost a token [...], exit probabilities [K, ...])."""
    p = exit_probabilities(gates)
    # 0 log 0 = 0, in the gradient too (a shut gate leaves an exit nothing)
    log_p = jnp.log(jnp.where(p > 0, p, 1.0))
    entropy = -jnp.sum(p * log_p, axis=0)
    return jnp.sum(p * costs, axis=0) - beta * entropy, p


def cost(config, params, feed):
    costs, gates, _ = turns(config, params, feed)
    return jnp.mean(expected_cost(costs, gates, config["exit_beta"])[0])


def outputs(config, params, feed):
    """(cost, the turns' token costs [K, B, T], exit probabilities [K, B,
    T]), for the tests."""
    with jax.default_matmul_precision("highest"):
        costs, gates, _ = turns(config, params, feed)
        per_token, p = expected_cost(costs, gates, config["exit_beta"])
        return jnp.mean(per_token), costs, p


def loss_and_grads(config, params, feed):
    """(cost, gradients): the mean over the batch's tokens of the expected
    cost over the exits less beta x the exit distribution's entropy; the
    gradients are for every parameter, in the program's parameter order:
    what plain Adam is handed (no clipping, no decay). Each weight's is the
    sum over the K turns that read it."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda ps: cost(config, ps, feed))(params)


def prepare(feed):
    """The reader's batch is already a dict of arrays."""
    return feed
