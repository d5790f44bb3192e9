"""Plain reference for the Nemotron-H-shaped hybrid decoder
(`paddle_tpu.models.nemotron_h_lm`): the forward pass, the cost and its
gradients in straightforward float32 `jax.numpy` at the highest matmul
precision, sharing only the parameter VALUES with the code under test.
Written so that a benchmark configuration can copy it from `import math`
down as its `reference.py`
(`chipbench/configs/nemotron-3-nano-30b-a3b/reference.py` is that copy;
`tests/test_chipbench_harness.py` holds the two to the same bits).

The model, as `nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16` publishes it
(`transformers` model_type `nemotron_h`; the config's keys by their own
names). x [T, d]; every projection bias-free; rms(v, w) = v * rsqrt(mean(v^2,
-1) + eps) * w; block i of kind t = pattern[i]:

    x <- x + mixer_t(rms(x, w_i));     logits = rms(x, w_f) W_head   (untied)

M, the Mamba-2 mixer (Dao & Gu 2024). H = mamba_num_heads, P = mamba_head_dim,
d_in = H P, G = n_groups, N = ssm_state_size, K = conv_kernel:
    [z | xBC | dt] = h W_in                W_in [d, d_in + (d_in + 2 G N) + H]
    xBC = silu(conv(xBC) + b_conv)         causal, depthwise, K taps:
                                           conv(u)_t = sum_k w[k] u_{t-(K-1)+k}
    x, B, C = split(xBC)                   x [T, H, P]; B, C [T, G, N]; head h
                                           reads group h // (H / G)
    dt = softplus(dt + dt_bias)  [T, H];   A = -exp(A_log)  [H]
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   per head, S [P, N], S_0 = 0
    y_t = S_t C_t + D x_t
    y = group_rms(y * silu(z), w_n)        the mean square inside each of the
                                           G groups of d_in / G channels
    out = y W_out
*, attention: q = h W_q (H_q heads x D), k, v = h W_k, h W_v (H_kv heads x D),
    causal softmax(q k^T / sqrt(D)) v, query head j reading K/V head
    j // (H_q / H_kv); out = a W_o. NO position signal: no rotary (the
    config's `rope_theta` is not read by `nemotron_h`'s attention), no
    learned table.
E, routed experts (DeepSeek-V3's router): s = sigmoid(h W_r) [T, E]; the
    CHOICE is the top k of s + b (b [E]: a buffer, zeros, no gradient;
    `n_group` 1 makes group-limited routing the identity); gates g = s[chosen]
    / sum(s[chosen]) * routed_scaling_factor; expert e: relu(h W_up[e])^2
    W_down[e], no gate matrix; shared expert relu(h W_up^s)^2 W_down^s:
    y = sum_j g_j expert_{e_j}(h) + shared(h). Dropless.
    ONE CHIP'S SHARE: the stacks hold experts lo..hi-1 of the E the router
    scores (`held_experts`); a (token, slot) pair whose expert is absent adds
    nothing here (another chip adds it), in this reference and in the program
    alike. The shared expert is whole on every chip.

cost = mean over tokens of -log softmax(logits)[label]. No auxiliary cost.

Departures from a literal transcription, none of them in the mathematics:
the recurrence runs as a `lax.scan` over chunks of `chunk_size` tokens with a
token-by-token `lax.scan` inside, each chunk under `jax.checkpoint` (the
backward keeps T / chunk states, not T); attention is mapped over (sequence,
head); the experts are a scan over the held stack that adds each expert's
gated output for EVERY token into one accumulator (the gate is zero where the
token did not choose it); the k largest scores are picked one at a time in a
loop instead of sorted; the head and its cross-entropy run over chunks of 512
tokens. So it fits beside the weights after a benchmark's window.

Parameters, in the program's creation order: the token table; per block its
norm weight, then M: W_in, conv w [K, C], conv b, dt_bias, A_log, D, w_n,
W_out (9 tensors with the norm); *: W_q, W_k, W_v, W_o (5); E: W_r, W_up
[held, d, f], W_down [held, f, d], b, W_up^s, W_down^s (7); then w_f, W_head.
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
