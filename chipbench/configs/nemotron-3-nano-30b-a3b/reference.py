"""Plain reference for nemotron-3-nano-30b-a3b: a COPY of
`tests/nemotron_h_reference.py` (everything from `import math` down to
`router_logits` is that file's text; a tier-1 test,
`tests/test_chipbench_harness.py`, holds the two to the same bits on the
CPU), with the harness's `prepare` and the handed choice (PR 36) added at the
end. Copied so that an edit in the tree cannot move the yardstick unseen. The
equations, the parameter order and the departures from a literal transcription
(the recurrence as a scan over chunks with a token-by-token scan inside,
attention mapped over heads, the experts as a scan over the held stack, the
head in chunks: so that it fits beside 2.7 GB of weights and their gradients
after the window) are in that file's docstring; `config.json` carries the
sizes under the published keys plus `router_experts` (the 128 the router
scores) and `held_experts` (the 8 this chip holds).

The handed choice. A float32 reference that makes its own top-6 choice
disagrees with a sound bf16 program wherever the sixth and seventh scores are
a rounding apart, and a whole expert (gate about 0.4) then moves in or out:
the comparison of gradients read that as an error of 0.2-0.3 of a router's
rms on one run in six (PERF.md section 6, PRs 32-36). So `loss_and_grads`,
`cost`, `hidden` and `router_logits` take `choice`: a list, one per E block,
of 0/1 masks [tokens, router_experts] saying which experts each token's pairs
go to. Where it is given the gates are THIS file's float32 scores of those
experts, renormalised and scaled as published; gradients flow through the
scores as before, and a later block's hidden state is this file's own under
the handed choices. `choice=None` is the reference's own choice, bit for bit
what the tree's file computes. `chosen` applies the published rule to router
logits that are handed in (the program's own `RouterLogits`), and
`loss_grads_and_routers` is `loss_and_grads` with this file's routers (input,
weight, logits) beside the cost, from the same forward. The functions below
`prepare` redefine the tree's of the same names with that one more argument,
because this PR may not edit the tree's file and the twin test wants its text
whole at the top: when a later PR gives `tests/nemotron_h_reference.py` the
argument, the redefinitions go.
"""

import math

import jax
import jax.numpy as jnp

PER_KIND = {"M": 9, "*": 5, "E": 7}
HEAD_CHUNK = 512


def _rms(v, w, eps):
    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * w


def _relu2(v):
    return jnp.square(jnp.maximum(v, 0.0))


def _held(config):
    lo, hi = config.get("held_experts") or (0, config["n_routed_experts"])
    return int(lo), int(hi)


def _router_width(config):
    return int(config.get("router_experts") or config["n_routed_experts"])


# ------------------------------------------------------------------ M
def _recurrence(x, dt, A, Bh, Ch, chunk):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t. x [B, T, H,
    P], dt [B, T, H], A [H], Bh / Ch [B, T, H, N] -> y [B, T, H, P]."""
    Bsz, T, H, P = x.shape
    N = Bh.shape[-1]
    pad = -T % chunk       # dt 0 behind the end: the state stands still
    seqs = [jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, Bh, Ch)]
    # time first, in chunks: [chunks, chunk, B, ...]
    seqs = tuple(jnp.moveaxis(a, 1, 0).reshape(-1, chunk, *jnp.moveaxis(
        a, 1, 0).shape[1:]) for a in seqs)

    def token(S, inp):
        x_t, dt_t, b_t, c_t = inp
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return S, jnp.sum(S * c_t[:, :, None, :], axis=-1)

    def one_chunk(S, inp):
        return jax.lax.scan(token, S, inp)

    _, y = jax.lax.scan(jax.checkpoint(one_chunk),
                        jnp.zeros((Bsz, H, P, N), jnp.float32), seqs)
    return jnp.moveaxis(y.reshape(T + pad, Bsz, H, P), 0, 1)[:, :T]


def _mamba(config, h, in_w, conv_w, conv_b, dt_bias, A_log, D, norm_w, out_w):
    """h [B, T, d] -> [B, T, d]."""
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
    d_in, K = H * P, conv_w.shape[0]
    Bsz, T, _ = h.shape
    zxd = h @ in_w
    z, xBC, dt = (zxd[..., :d_in], zxd[..., d_in:2 * d_in + 2 * G * N],
                  zxd[..., 2 * d_in + 2 * G * N:])
    padded = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + T] * conv_w[k] for k in range(K)) + conv_b
    xBC = jax.nn.silu(conv)
    x = xBC[..., :d_in].reshape(Bsz, T, H, P)
    Bm = xBC[..., d_in:d_in + G * N].reshape(Bsz, T, G, N)
    Cm = xBC[..., d_in + G * N:].reshape(Bsz, T, G, N)
    dt = jax.nn.softplus(dt + dt_bias)
    y = _recurrence(x, dt, -jnp.exp(A_log), jnp.repeat(Bm, H // G, axis=2),
                    jnp.repeat(Cm, H // G, axis=2), config["chunk_size"])
    y = (y + D[:, None] * x).reshape(Bsz, T, d_in) * jax.nn.silu(z)
    g = y.reshape(Bsz, T, G, d_in // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + config["layer_norm_epsilon"])
    return (g.reshape(Bsz, T, d_in) * norm_w) @ out_w


# ------------------------------------------------------------------ *
def _attend(qkv):
    q, k, v = qkv                      # one head of one sequence: [T, D]
    T, D = q.shape
    s = q @ k.T / math.sqrt(D)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ v


def _attention(config, h, wq, wk, wv, wo):
    Hq, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    D = config["head_dim"]
    Bsz, T, _ = h.shape

    def heads(w, n):                   # -> [B, n, T, D]
        return (h @ w).reshape(Bsz, T, n, D).transpose(0, 2, 1, 3)

    q = heads(wq, Hq)
    k, v = (jnp.repeat(heads(w, Hkv), Hq // Hkv, axis=1) for w in (wk, wv))
    a = jax.lax.map(jax.checkpoint(_attend), tuple(
        t.reshape(Bsz * Hq, T, D) for t in (q, k, v)))
    a = a.reshape(Bsz, Hq, T, D).transpose(0, 2, 1, 3).reshape(Bsz, T, Hq * D)
    return a @ wo


# ------------------------------------------------------------------ E
def _expert(h, w_up, w_down, gate):
    """One expert on EVERY token, times the token's gate for it (zero where
    the expert is not among the token's chosen)."""
    return (_relu2(h @ w_up) @ w_down) * gate[:, None]


def router_scores(config, h, wr, b):
    """h [N, d] -> (logits z [N, E], gates [N, E]: the scaled, renormalised
    score of each chosen expert, zero elsewhere)."""
    z = h @ wr
    s = jax.nn.sigmoid(z)
    biased = jax.lax.stop_gradient(s + b)

    def pick(_, chosen):     # the largest not yet chosen, one at a time
        best = jnp.argmax(jnp.where(chosen > 0, -jnp.inf, biased), axis=-1)
        return chosen + jax.nn.one_hot(best, s.shape[-1], dtype=s.dtype)

    chosen = jax.lax.fori_loop(0, config["num_experts_per_tok"], pick,
                               jnp.zeros_like(biased))
    gates = s * chosen
    if config["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    return z, gates * config["routed_scaling_factor"]


def _experts(config, h, wr, w_up, w_down, b, up_s, down_s):
    """h [N, d] -> (y [N, d], router logits [N, E])."""
    lo, hi = _held(config)
    z, gates = router_scores(config, h, wr, b)

    def add(y, expert):
        return y + jax.checkpoint(_expert)(h, *expert), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h),
                        (w_up, w_down, gates[:, lo:hi].T))
    return y + _relu2(h @ up_s) @ down_s, z


# ------------------------------------------------------------------ model
def _split(config, params):
    pattern = config["hybrid_override_pattern"]
    assert len(pattern) == config["num_hidden_layers"], pattern
    assert len(params) == 1 + sum(PER_KIND[t] for t in pattern) + 2, len(params)
    params = [jnp.asarray(p, jnp.float32) for p in params]
    tok_emb, *rest = params
    *flat, w_f, w_head = rest
    blocks, at = [], 0
    for kind in pattern:
        blocks.append((kind, flat[at:at + PER_KIND[kind]]))
        at += PER_KIND[kind]
    return tok_emb, blocks, w_f, w_head


def hidden(config, params, toks):
    """toks [B, T] -> (x [B, T, d] before the final norm, the router logits
    of each E block [B*T, E])."""
    tok_emb, blocks, _, _ = _split(config, params)
    Bsz, T = toks.shape
    eps = config["layer_norm_epsilon"]
    x = jax.lax.map(lambda t: tok_emb[t], toks)                  # [B, T, d]
    router_logits = []
    for kind, (w_ln, *p) in blocks:
        h = _rms(x, w_ln, eps)
        if kind == "M":
            y = _mamba(config, h, *p)
        elif kind == "*":
            y = _attention(config, h, *p)
        else:
            y, z = _experts(config, h.reshape(Bsz * T, -1), *p)
            y = y.reshape(Bsz, T, -1)
            router_logits.append(z)
        x = x + y
    return x, router_logits


def logits(config, params, toks):
    """[B, T, vocabulary], whole (small sizes only)."""
    _, _, w_f, w_head = _split(config, params)
    with jax.default_matmul_precision("highest"):
        x, _ = hidden(config, params, jnp.asarray(toks))
        return _rms(x, w_f, config["layer_norm_epsilon"]) @ w_head


def _cross_entropy_sum(config, x, labels, w_f, w_head):
    """Sum over tokens of -log softmax(rms(x) W_head)[label], in chunks."""
    N = x.shape[0]
    chunk = HEAD_CHUNK if N % HEAD_CHUNK == 0 else N

    def one(x_c, labels_c):
        logp = jax.nn.log_softmax(
            _rms(x_c, w_f, config["layer_norm_epsilon"]) @ w_head, axis=-1)
        return -jnp.take_along_axis(logp, labels_c, axis=-1).sum()

    def add(total, xl):
        return total + jax.checkpoint(one)(*xl), None

    total, _ = jax.lax.scan(
        add, jnp.zeros((), jnp.float32),
        (x.reshape(N // chunk, chunk, -1), labels.reshape(N // chunk, chunk, 1)))
    return total


def cost(config, params, feed):
    _, _, w_f, w_head = _split(config, params)
    toks, labels = jnp.asarray(feed["toks"]), jnp.asarray(feed["labels"])
    x, _ = hidden(config, params, toks)
    x = x.reshape(-1, x.shape[-1])
    return _cross_entropy_sum(config, x, labels.reshape(-1, 1), w_f,
                              w_head) / x.shape[0]


def loss_and_grads(config, params, feed):
    """The cost (mean next-token cross-entropy over the batch) and its
    gradient for every parameter, in the program's parameter order: what
    plain Adam is handed (no clipping, no decay). The router's bias is a
    buffer: its gradient is zero here and nothing updates it there."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda ps: cost(config, ps, feed))(params)


def router_logits(config, params, feed):
    """The reference's own float32 router: a list of [tokens, experts], one
    per E block."""
    with jax.default_matmul_precision("highest"):
        return hidden(config, params, jnp.asarray(feed["toks"]))[1]


def prepare(feed):
    """The reader's batch is already a dict of arrays."""
    return feed


# ---------------------------------------------------- the handed choice
def _top_k_mask(scores, top_k):
    """[N, E] -> 0/1 [N, E]: the `top_k` largest of each row, picked one at
    a time, the lowest index first among equals (as `jax.lax.top_k`)."""
    def pick(_, chosen):
        best = jnp.argmax(jnp.where(chosen > 0, -jnp.inf, scores), axis=-1)
        return chosen + jax.nn.one_hot(best, scores.shape[-1],
                                       dtype=scores.dtype)

    return jax.lax.fori_loop(0, top_k, pick, jnp.zeros_like(scores))


def chosen(config, params, logits):
    """The published choice on HANDED router logits (a list of float32
    [tokens, E], one per E block): the top k of sigmoid(z) + b, b the block's
    choice bias among `params`. A list of 0/1 masks [tokens, E]."""
    _, blocks, _, _ = _split(config, params)
    biases = [p[4] for kind, p in blocks if kind == "E"]
    assert len(biases) == len(logits), (len(biases), len(logits))
    return [_top_k_mask(jax.nn.sigmoid(jnp.asarray(z, jnp.float32)) + b,
                        config["num_experts_per_tok"])
            for z, b in zip(logits, biases)]


def router_scores(config, h, wr, b, chosen=None):
    """As above; `chosen` [N, E] 0/1 takes the place of the top-k of s + b."""
    z = h @ wr
    s = jax.nn.sigmoid(z)
    if chosen is None:
        chosen = _top_k_mask(jax.lax.stop_gradient(s + b),
                             config["num_experts_per_tok"])
    gates = s * chosen
    if config["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    return z, gates * config["routed_scaling_factor"]


def _experts(config, h, wr, w_up, w_down, b, up_s, down_s, chosen=None):
    """h [N, d] -> (y [N, d], router logits [N, E])."""
    lo, hi = _held(config)
    z, gates = router_scores(config, h, wr, b, chosen)

    def add(y, expert):
        return y + jax.checkpoint(_expert)(h, *expert), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h),
                        (w_up, w_down, gates[:, lo:hi].T))
    return y + _relu2(h @ up_s) @ down_s, z


def _hidden(config, params, toks, choice):
    """toks [B, T] -> (x [B, T, d] before the final norm, each E block's
    router: its input h [B*T, d], its weight and its logits [B*T, E])."""
    tok_emb, blocks, _, _ = _split(config, params)
    Bsz, T = toks.shape
    eps = config["layer_norm_epsilon"]
    x = jax.lax.map(lambda t: tok_emb[t], toks)                  # [B, T, d]
    routers = []
    for kind, (w_ln, *p) in blocks:
        h = _rms(x, w_ln, eps)
        if kind == "M":
            y = _mamba(config, h, *p)
        elif kind == "*":
            y = _attention(config, h, *p)
        else:
            h = h.reshape(Bsz * T, -1)
            y, z = _experts(
                config, h, *p,
                chosen=None if choice is None else choice[len(routers)])
            y = y.reshape(Bsz, T, -1)
            routers.append((h, p[0], z))
        x = x + y
    return x, routers


def hidden(config, params, toks, choice=None):
    """As above; `choice[i]` is handed to the i-th E block."""
    x, routers = _hidden(config, params, toks, choice)
    return x, [z for _, _, z in routers]


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
    """(cost, gradients, each E block's router as this file computed it:
    input [tokens, d], weight [d, E], logits [tokens, E]), one forward pass:
    `loss_and_grads` with what the gates were scored from beside it."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    with jax.default_matmul_precision("highest"):
        (cost_, routers), grads = jax.value_and_grad(
            lambda ps: _cost_and_routers(config, ps, feed, choice),
            has_aux=True)(params)
    return cost_, grads, routers


def loss_and_grads(config, params, feed, choice=None):
    """As above; with `choice`, under the handed experts."""
    return loss_grads_and_routers(config, params, feed, choice)[:2]


def router_logits(config, params, feed, choice=None):
    with jax.default_matmul_precision("highest"):
        return hidden(config, params, jnp.asarray(feed["toks"]), choice)[1]
