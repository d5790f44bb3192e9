"""Plain reference for the Trinity-shaped decoder (`paddle_tpu.models.afmoe_lm`):
the forward pass, the cost and its gradients in straightforward float32
`jax.numpy` at the highest matmul precision, no kernel, no cache, sharing only
the parameter VALUES with the code under test (and, where asked, the program's
own choice of experts, handed in). Written so that a benchmark configuration
can copy it whole as its `reference.py`
(`chipbench/configs/trinity-mini/reference.py` is that copy;
`tests/test_chipbench_harness.py` holds the two to the same bits).

The model, as `arcee-ai/Trinity-Mini` publishes it (`transformers` model_type
`afmoe`; the config's keys by their own names). x [T, d], d = hidden_size;
every projection bias-free; rms_n(v, w) = v * rsqrt(mean(v^2, -1) +
rms_norm_eps) * w over n lanes; layer l, four norms (sandwich):

    h_0 = sqrt(d) x Emb[token]                          (`mup_enabled`)
    h <- h + rms_d(Attn_l(rms_d(h, n1)), n2)
    h <- h + rms_d(FFN_l(rms_d(h, n3)), n4)
    logits = rms_d(h, w_f) W_head   (untied)

Attn_l(x), H = num_attention_heads, KV = num_key_value_heads, D = head_dim, W =
sliding_window:
    q = x W_q [T, H, D];  k = x W_k, v = x W_v [T, KV, D];  g = x W_g [T, H D]
    q <- rms_D(q, w_q), k <- rms_D(k, w_k): over each head's D lanes, ONE scale
    [D] for all query heads and one for all key heads
    `layer_types[l]` "sliding_attention": q, k <- RoPE(rope_theta) over all D
        lanes at the token's position; position i attends to j with
        i - W < j <= i (W keys, itself among them)
    "full_attention": NO rotary (no position signal); j <= i
    scores q k^T / sqrt(D), query head h reads K/V head h // (H / KV), softmax,
    o = P v [T, H D];  out = (o * sigmoid(g)) W_o
FFN_l, l < num_dense_layers: W_d (silu(x W_g) * (x W_u)), width
    intermediate_size.
FFN_l, the others: z = x W_r [T, E]; s = sigmoid(z) (`score_func`); the CHOICE
    is the top num_experts_per_tok of s + b over ALL E (every group key is 1: no
    group limit; b [E]: a buffer, zeros, no gradient); gates g = route_scale x
    s[chosen] / (sum(s[chosen]) + 1e-20) (`route_norm`); y = sum_{e chosen} g_e
    W_d^e (silu(x W_g^e) * (x W_u^e)) + W_d^s (silu(x W_g^s) * (x W_u^s)),
    every expert and the shared expert (num_shared_experts x
    moe_intermediate_size wide) SwiGLU. Dropless.
    ONE CHIP'S SHARE: the stacks hold experts lo..hi-1 (`held_experts`) of the
    `router_experts` the router scores; a (token, slot) pair whose expert is
    absent adds nothing here (another chip adds it), in this reference and in
    the program alike. The shared expert is whole on every chip.

cost = mean over tokens of -log softmax(logits)[label]. No auxiliary cost
(`load_balance_coeff` steers the choice bias in the published trainer; it is no
term of the cost).

Assumed, where the config's keys do not say (the basis is the `transformers`
implementation of `afmoe`, the only public description of the layer): the
per-head QK-norm and its place before the rotary; the sigmoid output gate and
its fifth projection; rotary on window layers only; the four norms and where
they sit; sqrt(d) on the table's rows as the whole of `mup_enabled` in the
forward pass; the window's edge (i - j < W); RoPE pairs lane i with lane i +
D/2 (rotate-half, this repo's `rotary_embedding` convention), inv_freq_i =
rope_theta^(-2i/D); a zero choice bias.

Departures from a literal transcription, none of them in the mathematics:
attention is mapped over (sequence, head), each head's full [T, T] scores with
the mask as a comparison of two `arange`s, under `jax.checkpoint` (one head's
scores at a time: T 8192 fits); the experts are a scan over the held stack that
adds each expert's gated output for EVERY token into one accumulator (the gate
is zero where the token did not choose it); the k largest scores are picked one
at a time in a loop instead of sorted (the lowest index first among equals, as
`jax.lax.top_k`); the head and its cross-entropy run over chunks of 512 tokens.
So it fits beside the weights after a benchmark's window.

Parameters, in the program's creation order: the token table; per layer n1,
W_q, W_k, W_v, w_q, w_k, W_g, W_o, n2, n3, then a dense layer's W_g, W_u, W_d or
a routed layer's W_r, the stacks W_g [held, d, f], W_u, W_d [held, f, d], b,
W_g^s, W_u^s, W_d^s, then n4 (14 tensors a dense layer, 19 a routed one); w_f;
W_head.

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

import functools
import math

import jax
import jax.numpy as jnp

PER_KIND = {"dense": 14, "routed": 19}
HEAD_CHUNK = 512
WINDOW, GLOBAL = "sliding_attention", "full_attention"


def _rms(v, w, eps):
    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * w


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


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


# ------------------------------------------------------------ attention
def rope(x, theta):
    """x [..., T, D] -> the same, position t turning the lane pair (i, i +
    D/2) by t * theta^(-2i/D)."""
    T, D = x.shape[-2:]
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def _attend(window, qkv):
    q, k, v = qkv                      # one head of one sequence: [T, D]
    T, D = q.shape
    s = q @ k.T / math.sqrt(D)
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]      # i - j
    seen = ahead >= 0
    if window is not None:
        seen = seen & (ahead < window)
    return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v


def _attention(config, kind, x, w_q, w_k, w_v, n_q, n_k, w_g, w_o):
    """x [B, T, d] -> [B, T, d]; `kind` is the layer's entry of
    `layer_types`."""
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    D, eps = config["head_dim"], config["rms_norm_eps"]
    Bsz, T, _ = x.shape
    heads = lambda y, n: y.reshape(Bsz, T, n, D).transpose(0, 2, 1, 3)  # noqa: E731
    q = _rms(heads(x @ w_q, H), n_q, eps)                    # [B, H, T, D]
    k = _rms(heads(x @ w_k, KV), n_k, eps)
    v = heads(x @ w_v, KV)
    window = None
    if kind == WINDOW:
        q, k = rope(q, config["rope_theta"]), rope(k, config["rope_theta"])
        window = config["sliding_window"]
    else:
        assert kind == GLOBAL, kind
    k, v = (jnp.repeat(y, H // KV, axis=1) for y in (k, v))
    o = jax.lax.map(jax.checkpoint(functools.partial(_attend, window)), (
        q.reshape(Bsz * H, T, D), k.reshape(Bsz * H, T, D),
        v.reshape(Bsz * H, T, D)))
    o = o.reshape(Bsz, H, T, D).transpose(0, 2, 1, 3).reshape(Bsz, T, H * D)
    return (o * jax.nn.sigmoid(x @ w_g)) @ w_o


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
    if config["route_norm"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return z, gates * config["route_scale"]


def _expert(h, w_gate, w_up, w_down, gate):
    """One expert on EVERY token, times the token's gate for it (zero where
    the expert is not among the token's chosen)."""
    return _swiglu(h, w_gate, w_up, w_down) * gate[:, None]


def _experts(config, h, wr, w_gate, w_up, w_down, b, gate_s, up_s, down_s,
             chosen=None):
    """h [N, d] -> (y [N, d], router logits [N, E])."""
    lo, hi = _held(config)
    z, gates = router_scores(config, h, wr, b, chosen)

    def add(y, expert):
        return y + jax.checkpoint(_expert)(h, *expert), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h),
                        (w_gate, w_up, w_down, gates[:, lo:hi].T))
    return y + _swiglu(h, gate_s, up_s, down_s), z


# ---------------------------------------------------------------- model
def _split(config, params):
    kinds = _kinds(config)
    assert len(params) == 1 + sum(PER_KIND[k] for k in kinds) + 2, len(params)
    params = [jnp.asarray(p, jnp.float32) for p in params]
    tok_emb, *rest = params
    *flat, w_f, w_head = rest
    layers, at = [], 0
    for kind in kinds:
        layers.append((kind, flat[at:at + PER_KIND[kind]]))
        at += PER_KIND[kind]
    return tok_emb, layers, w_f, w_head


def _hidden(config, params, toks, choice):
    """toks [B, T] -> (x [B, T, d] before the final norm, each routed layer's
    router: its input h [B*T, d], its weight and its logits [B*T, E])."""
    tok_emb, layers, _, _ = _split(config, params)
    Bsz, T = toks.shape
    eps = config["rms_norm_eps"]
    x = jax.lax.map(lambda t: tok_emb[t], toks)                  # [B, T, d]
    if config["mup_enabled"]:
        x = x * math.sqrt(config["hidden_size"])
    routers = []
    for (kind, (n1, *p)), attends in zip(layers, config["layer_types"]):
        attn, (n2, n3, *ffn, n4) = p[:7], p[7:]
        x = x + _rms(_attention(config, attends, _rms(x, n1, eps), *attn),
                     n2, eps)
        h = _rms(x, n3, eps)
        if kind == "dense":
            y = _swiglu(h, *ffn)
        else:
            h = h.reshape(Bsz * T, -1)
            y, z = _experts(
                config, h, *ffn,
                chosen=None if choice is None else choice[len(routers)])
            y = y.reshape(Bsz, T, -1)
            routers.append((h, ffn[0], z))
        x = x + _rms(y, n4, eps)
    return x, routers


def hidden(config, params, toks, choice=None):
    """toks [B, T] -> (x [B, T, d] before the final norm, the router logits
    of each routed layer [B*T, E]); `choice[i]` is handed to the i-th routed
    layer."""
    x, routers = _hidden(config, params, toks, choice)
    return x, [z for _, _, z in routers]


def logits(config, params, toks, choice=None):
    """[B, T, vocabulary], whole (small sizes only)."""
    _, _, w_f, w_head = _split(config, params)
    with jax.default_matmul_precision("highest"):
        x, _ = hidden(config, params, jnp.asarray(toks), choice)
        return _rms(x, w_f, config["rms_norm_eps"]) @ w_head


def _cross_entropy_sum(config, x, labels, w_f, w_head):
    """Sum over tokens of -log softmax(rms(x) W_head)[label], in chunks."""
    N = x.shape[0]
    chunk = HEAD_CHUNK if N % HEAD_CHUNK == 0 else N

    def one(x_c, labels_c):
        logp = jax.nn.log_softmax(
            _rms(x_c, w_f, config["rms_norm_eps"]) @ w_head, axis=-1)
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
    biases = [p[14] for kind, p in layers if kind == "routed"]
    assert len(biases) == len(logits), (len(biases), len(logits))
    return [_top_k_mask(jax.nn.sigmoid(jnp.asarray(z, jnp.float32)) + b,
                        config["num_experts_per_tok"])
            for z, b in zip(logits, biases)]


def prepare(feed):
    """The reader's batch is already a dict of arrays."""
    return feed
