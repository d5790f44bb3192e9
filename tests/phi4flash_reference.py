"""Plain reference for the Phi-4-mini-flash-shaped decoder
(`paddle_tpu.models.phi4flash_lm`): the forward pass, the cost and its
gradients in straightforward float32 `jax.numpy` at the highest matmul
precision, no kernel, no chunked scan, sharing only the parameter VALUES with
the code under test. Written so that a benchmark configuration can copy it
whole as its `reference.py`
(`chipbench/configs/phi-4-mini-flash-reasoning/reference.py` is that copy;
`tests/test_chipbench_harness.py` holds the two to the same bits).

The model, as `microsoft/Phi-4-mini-flash-reasoning` publishes it (model_type
`phi4flash`; Ren et al. 2025, arXiv 2507.06607; the scan is Gu & Dao 2023, the
attention Ye et al. 2024). x [T, d], d = hidden_size; ln(v) = (v - mean) *
rsqrt(var + layer_norm_eps) * w + b; layer i (its PUBLISHED index):

    h_0 = Emb[token]                                    (rows enter unscaled)
    h <- h + Mix_i(ln(h));   h <- h + MLP(ln(h))
    logits = ln(h) Emb^T                 (the head is the table: tied, no bias)

L = the published num_hidden_layers (32). Mix_i(u):
  even i <= L / 2, a Mamba-1 mixer (d_in = mamba_expand d, N = mamba_d_state,
  K = mamba_d_conv, R = mamba_dt_rank):
    [x | z] = u W_in;  x = silu(b_conv + sum_k w[k] x_{t - (K - 1) + k}), zeros
    before the start;  [r | B | C] = x W_x;  dt = softplus(r W_dt + b_dt);
    A = -exp(A_log);  S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c]
    x_t[c] B_t[n], S_0 = 0;  y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] x_t[c];
    out = (y * silu(z)) W_out.  Layer L / 2 also hands on M = y (with the D x
    term, IN FRONT of the gate).
  even i > L / 2, a gated memory unit: out = (M * silu(u W_g)) W_o.
  odd i, differential attention, H = num_attention_heads, KV =
  num_key_value_heads, D = d / H; pair p = heads (2p, 2p + 1); query pair p
  reads K/V pair p // (H / KV):
    i <= L / 2 + 1: [q | k | v] = u W_qkv + b;  i > L / 2 + 1: q = u W_q + b
    and k, v are layer L / 2 + 1's (after its bias)
    A_1 = softmax(q_1 k_1^T / sqrt(D)), A_2 = softmax(q_2 k_2^T / sqrt(D)),
    j <= i, and j > i - sliding_window on layers i < L / 2
    o = (A_1 - lam A_2) [v_1 | v_2];  lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
    lam_init, lam_init = 0.8 - 0.6 exp(-0.3 i)
    o <- o rsqrt(mean(o^2 over the pair's 2 D lanes) + eps) w (1 - lam_init)
    out = o W_o + b_o
MLP(v): [g | y] = v W_1;  (y * silu(g)) W_2.

cost = mean over tokens of -log softmax(logits)[label].

`layer_ids` (the published indices of the layers held; absent: all L) makes the
model a part of itself: the kind of a layer, its lam_init and the boundary
follow the PUBLISHED index and depth (`published.num_hidden_layers`).

Departures from a literal transcription, none of them in the mathematics: the
convolution is K shifted multiplies; the scan is a `lax.scan` over single
tokens inside a `lax.scan` over blocks of 128 of them under `jax.checkpoint`
(the backward keeps one block's states at a time: T 8192 fits); attention is
mapped over (pair, block of queries), each block's two [queries, T] score
arrays with the masks as comparisons of two `arange`s, under
`jax.checkpoint`; every layer is under `jax.checkpoint` (the backward keeps a
layer's input and forms its inside again); the head and its cross-entropy run
over chunks of 512 tokens. So it fits beside the weights after a benchmark's
window.

Parameters, in the program's creation order: the token table; per layer the
mixing norm's w, b, then a mixer's W_in, w [K, d_in], b_conv, W_x, W_dt, b_dt,
A_log, D, W_out, or a memory unit's W_g, W_o, or an attention layer's W_qkv (or
W_q), its bias, lq1, lk1, lq2, lk2, the pair norm's w, W_o, b_o; then the MLP
norm's w, b, W_1, W_2; the closing norm's w, b.
"""

import math

import jax
import jax.numpy as jnp

MAMBA, GMU, WINDOW, FULL, CROSS = "mamba", "gmu", "window", "full", "cross"
MIXING = {MAMBA: 9, GMU: 2, WINDOW: 9, FULL: 9, CROSS: 9}
HEAD_CHUNK = 512
QUERY_BLOCK = 1024
SCAN_BLOCK = 128


def _ln(v, w, b, eps):
    mean = jnp.mean(v, -1, keepdims=True)
    var = jnp.mean((v - mean) ** 2, -1, keepdims=True)
    return (v - mean) * jax.lax.rsqrt(var + eps) * w + b


def _depth(config):
    return int((config.get("published") or {}).get(
        "num_hidden_layers", config["num_hidden_layers"]))


def held_layers(config):
    """[(published index, kind)] of the layers this configuration holds."""
    L, per = _depth(config), config["mb_per_layer"]
    half = L // 2

    def kind(i):
        if i % per == 0:
            return MAMBA if i <= half else GMU
        return WINDOW if i < half else FULL if i == half + 1 else CROSS

    ids = config.get("layer_ids") or list(range(L))
    assert len(ids) == config["num_hidden_layers"], (ids, config)
    return [(int(i), kind(int(i))) for i in ids]


# ------------------------------------------------------------ the mixers
def _scan(x, dt, A, Bm, Cm, D):
    """One sequence, token by token: x, dt [T, C]; A [C, N]; Bm, Cm [T, N]."""
    T, C = x.shape
    block = SCAN_BLOCK if T % SCAN_BLOCK == 0 else T

    def token(S, inp):
        x_t, dt_t, B_t, C_t = inp
        S = jnp.exp(dt_t[:, None] * A) * S + (dt_t * x_t)[:, None] * B_t[None]
        return S, S @ C_t + D * x_t

    def tokens(S, inp):
        return jax.lax.scan(token, S, inp)

    blocks = tuple(a.reshape(T // block, block, -1) for a in (x, dt, Bm, Cm))
    _, y = jax.lax.scan(jax.checkpoint(tokens),
                        jnp.zeros((C, A.shape[1]), jnp.float32), blocks)
    return y.reshape(T, C)


def mamba(config, u, w_in, w, b_conv, w_x, w_dt, b_dt, A_log, D, w_out):
    """u [B, T, d] -> (out [B, T, d], the scan's output y [B, T, d_in])."""
    K, d_in = w.shape
    N, R = A_log.shape[1], w_dt.shape[0]
    assert (K, N, R) == (config["mamba_d_conv"], config["mamba_d_state"],
                         config["mamba_dt_rank"]), (K, N, R)
    assert d_in == config["mamba_expand"] * config["hidden_size"]
    T = u.shape[1]
    xz = u @ w_in
    x, z = xz[..., :d_in], xz[..., d_in:]
    conv = b_conv
    for k in range(K):
        back = K - 1 - k
        conv = conv + w[k] * jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :T]
    x = jax.nn.silu(conv)
    rbc = x @ w_x
    dt = jax.nn.softplus(rbc[..., :R] @ w_dt + b_dt)
    y = jax.vmap(_scan, in_axes=(0, 0, None, 0, 0, None))(
        x, dt, -jnp.exp(A_log), rbc[..., R:R + N], rbc[..., R + N:], D)
    return (y * jax.nn.silu(z)) @ w_out, y


def gmu(u, memory, w_g, w_o):
    return (memory * jax.nn.silu(u @ w_g)) @ w_o


# -------------------------------------------------------------- attention
def _attend_pair(window, operands):
    """One query pair of one sequence: q1, q2, k1, k2 [T, D], v [T, 2 D] ->
    (A_1 v, A_2 v), each [T, 2 D], in blocks of queries."""
    q1, q2, k1, k2, v = operands
    T, D = q1.shape
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    def one(args):
        q1_b, q2_b, first = args
        ahead = (first + jnp.arange(block))[:, None] - jnp.arange(T)[None, :]
        seen = ahead >= 0
        if window:
            seen &= ahead < window
        soft = lambda q, k: jax.nn.softmax(  # noqa: E731
            jnp.where(seen, q @ k.T / math.sqrt(D), -jnp.inf), axis=-1)
        return soft(q1_b, k1) @ v, soft(q2_b, k2) @ v

    first, second = jax.lax.map(jax.checkpoint(one), (
        q1.reshape(T // block, block, D), q2.reshape(T // block, block, D),
        jnp.arange(0, T, block)))
    return first.reshape(T, 2 * D), second.reshape(T, 2 * D)


def lam_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def attention(config, i, kind, u, shared, w_q, b_q, lq1, lk1, lq2, lk2, w_n,
              w_o, b_o):
    """u [B, T, d] -> (out [B, T, d], (k, v) [B, T, KV D] after the bias)."""
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"]
    D = d // H
    Bsz, T, _ = u.shape
    qkv = u @ w_q + b_q
    if kind == CROSS:
        q, (k, v) = qkv, shared
    else:
        q, k, v = qkv[..., :d], qkv[..., d:d + KV * D], qkv[..., d + KV * D:]
    # [B, pairs, T, 2, D]: a pair's two heads side by side
    pairs = lambda y, n: y.reshape(Bsz, T, n // 2, 2, D).transpose(  # noqa: E731
        0, 2, 1, 3, 4)
    qp = pairs(q, H)
    kp, vp = (jnp.repeat(pairs(y, KV), H // KV, axis=1) for y in (k, v))
    flat = lambda y: y.reshape(Bsz * H // 2, T, -1)  # noqa: E731
    window = config["sliding_window"] if kind == WINDOW else 0
    first, second = jax.lax.map(
        lambda operands: _attend_pair(window, operands),
        (flat(qp[..., 0, :]), flat(qp[..., 1, :]), flat(kp[..., 0, :]),
         flat(kp[..., 1, :]), flat(vp.reshape(Bsz, H // 2, T, 2 * D))))
    lam = jnp.exp(lq1 @ lk1) - jnp.exp(lq2 @ lk2) + lam_init(i)
    o = first - lam * second                                 # [B H/2, T, 2 D]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + config["layer_norm_eps"])
    o = o * w_n * (1.0 - lam_init(i))
    o = o.reshape(Bsz, H // 2, T, 2 * D).transpose(0, 2, 1, 3)
    return o.reshape(Bsz, T, d) @ w_o + b_o, (k, v)


def mlp(v, w1, w2):
    gy = v @ w1
    f = w2.shape[0]
    return (gy[..., f:] * jax.nn.silu(gy[..., :f])) @ w2


# ------------------------------------------------------------------ model
def _split(config, params):
    """(table, [(published index, kind, mixing norm (w, b), mixing's, MLP
    norm (w, b), (W_1, W_2))], closing norm (w, b))."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    tok_emb, *flat = params
    layers, at = [], 0
    for i, kind in held_layers(config):
        n = MIXING[kind]
        layers.append((i, kind, flat[at:at + 2], flat[at + 2:at + 2 + n],
                       flat[at + 2 + n:at + 4 + n],
                       flat[at + 4 + n:at + 6 + n]))
        at += 6 + n
    assert len(flat) == at + 2, (len(params), at)
    return tok_emb, layers, flat[at:]


def hidden(config, params, toks):
    """toks [B, T] -> x [B, T, d] before the closing norm."""
    tok_emb, layers, _ = _split(config, params)
    eps = config["layer_norm_eps"]
    x = jax.lax.map(lambda t: tok_emb[t], toks)                  # [B, T, d]
    memory = shared = None
    half = _depth(config) // 2
    for i, kind, n_mix, p_mix, n_mlp, p_mlp in layers:

        @jax.checkpoint
        def layer(x, memory, shared, n_mix, p_mix, n_mlp, p_mlp,
                  i=i, kind=kind):
            u = _ln(x, *n_mix, eps)
            handed = None
            if kind == MAMBA:
                out, handed = mamba(config, u, *p_mix)
            elif kind == GMU:
                out = gmu(u, memory, *p_mix)
            else:
                out, handed = attention(config, i, kind, u, shared, *p_mix)
            x = x + out
            return x + mlp(_ln(x, *n_mlp, eps), *p_mlp), handed

        x, handed = layer(x, memory, shared, n_mix, p_mix, n_mlp, p_mlp)
        if kind == MAMBA and i == half:
            memory = handed
        if kind == FULL:
            shared = handed
    return x


def logits(config, params, toks):
    """[B, T, vocabulary], whole (small sizes only)."""
    tok_emb, _, n_f = _split(config, params)
    with jax.default_matmul_precision("highest"):
        x = hidden(config, params, jnp.asarray(toks))
        return _ln(x, *n_f, config["layer_norm_eps"]) @ tok_emb.T


def _cross_entropy_sum(config, x, labels, n_f, tok_emb):
    """Sum over tokens of -log softmax(ln(x) Emb^T)[label], in chunks."""
    N = x.shape[0]
    chunk = HEAD_CHUNK if N % HEAD_CHUNK == 0 else N

    def one(x_c, labels_c):
        logp = jax.nn.log_softmax(
            _ln(x_c, *n_f, config["layer_norm_eps"]) @ tok_emb.T, axis=-1)
        return -jnp.take_along_axis(logp, labels_c, axis=-1).sum()

    def add(total, xl):
        return total + jax.checkpoint(one)(*xl), None

    total, _ = jax.lax.scan(
        add, jnp.zeros((), jnp.float32),
        (x.reshape(N // chunk, chunk, -1), labels.reshape(N // chunk, chunk, 1)))
    return total


def cost(config, params, feed):
    """The mean next-token cross-entropy over the batch."""
    tok_emb, _, n_f = _split(config, params)
    toks, labels = jnp.asarray(feed["toks"]), jnp.asarray(feed["labels"])
    x = hidden(config, params, toks)
    x = x.reshape(-1, x.shape[-1])
    # the table once more, as the head: plain `jax.grad` adds its two uses up
    return _cross_entropy_sum(config, x, labels.reshape(-1, 1), n_f,
                              tok_emb) / x.shape[0]


def loss_and_grads(config, params, feed):
    """(cost, gradients for every parameter, in the program's parameter
    order): what plain Adam is handed (no clipping, no decay)."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda ps: cost(config, ps, feed))(params)


def prepare(feed):
    """The reader's batch is already a dict of arrays."""
    return feed
