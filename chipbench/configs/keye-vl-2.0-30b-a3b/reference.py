"""Plain reference for Keye-VL-2.0's language model (`paddle_tpu.models.keye_lm`):
the forward pass, the cost and its gradients in straightforward float32
`jax.numpy` at the highest matmul precision, no kernel, no cache, sharing only
the parameter VALUES with the code under test (and, where asked, the program's
own discrete choices, handed in: the experts a token is routed to, the keys a
row keeps). Written so that a benchmark configuration can copy it whole as its
`reference.py` (`chipbench/configs/keye-vl-2.0-30b-a3b/reference.py` is that
copy; `tests/test_chipbench_keye.py` holds the two to the same bits).

The equations, from the catalog row's `config` (keys by their own names) and
`described_as` (`Kwai-Keye/Keye-VL-2.0-30B-A3B`, model_type `KeyeVL2`). Stream h
[T, d] float32, d = hidden_size 2048; eps = rms_norm_eps 1e-6; no bias anywhere;
RMSNorm_n(v; g) = v rsqrt(mean(v^2 over n lanes) + eps) g.

1. x = RMSNorm(h; g1).
2. q = x W_q [T, H, D], k = x W_k [T, KV, D], v = x W_v [T, KV, D] (H 32, KV 4,
   D 128). q <- RMSNorm_D(q; g_q), k <- RMSNorm_D(k; g_k): each head's D lanes
   on their own, one scale [D] for all query heads and one for the K/V heads.
3. Three-axis rotary. Fed p int32 [B, 3, T] (temporal, height, width).
   inv_freq_i = theta^(-2i / D), i = 0 .. D/2 - 1, theta = rope_theta 1e7. Pair
   i takes its angle from axis a(i): 0 for i < 16, 1 for 16 <= i < 40, 2 for 40
   <= i < 64 (`rope_scaling.mrope_section` [16, 24, 24], contiguous sections).
   angle(t, i) = p[a(i), t] inv_freq_i; rotate-half, lane i with lane i + D/2:
   (x_i, x_{i + D/2}) <- (x_i cos - x_{i + D/2} sin, x_{i + D/2} cos + x_i sin),
   on q and k, after the norm. A text token's three axes are equal.
4. Indexer (`sa_config`: Hi 16 heads of Di 64, one key head, `topk` 2048): q^I =
   x W^I_q [T, Hi, Di], k^I = x W^I_k [T, Di], w = x W^I_w [T, Hi];
   I(t, s) = sum_j w[t, j] relu(q^I[t, j] . k^I[s]) for s <= t.
5. Selection: S_t = the min(topk, t + 1) keys s <= t of largest I(t, s); a tie
   goes to the lower index.
6. Attention over the kept keys only: query head j reads K/V head j // (H / KV);
   a_j(t, s) = softmax over s in S_t of q_j[t] . k_{j // 8}[s] / sqrt(D); o_j[t] =
   sum over s in S_t of a_j(t, s) v_{j // 8}[s]; h <- h + concat_j(o_j) W_o. One
   sequence a row of the batch, causal over the whole of it.
7. What is trained. The sets are discrete and I enters the output nowhere else,
   so under the language-model cost W^I_q, W^I_k, W^I_w get NO gradient: the
   program freezes them (no Adam state) and this file's gradient entries for
   them are zeros, there only because the driver pairs gradients with the
   program's parameters by position.
8. Experts: x2 = RMSNorm(h; g2); z = x2 W_r float32 [T, E] (E = router_experts
   128); p = softmax(z); the top num_experts_per_tok 8 (the lower index among
   equals); gates p[chosen] / sum(p[chosen]) (`norm_topk_prob`); y = sum_e gate_e
   (silu(x2 Wg_e) * (x2 Wu_e)) Wd_e over the chosen experts THIS CHIP HOLDS
   (`held_experts` lo..hi-1 of E; the stacks hold those), experts of
   moe_intermediate_size 768; h <- h + y. No shared expert, no dense layer
   (`mlp_only_layers` [], `decoder_sparse_step` 1), no auxiliary cost.
9. Final RMSNorm, an untied head [d, vocab], mean cross-entropy in float32 over
   the chip's slice of the vocabulary.

Assumed, where the row's keys do not say (each is in `config.json` too): the
per-head QK-norm and its place before the rotary (the Qwen3-MoE lineage whose
keys these are); the Qwen2-VL form of the three-axis rotary, contiguous
sections, rotate-half; the indexer's equation is the published one of the
DeepSeek-Sparse-Attention indexer that `described_as` names, reading the
layer's normed input x, with no norm and no position signal inside it (the
family's released code adds a LayerNorm on k^I and a rotary on part of q^I
and k^I, for which the row gives no sizes) and without the positive scale 1 /
sqrt(Hi Di) (it turns no set); `q_chunk_size` and `kv_chunk_size` are tiles
and change no number; the tie rule; no auxiliary cost.

Departures from a literal transcription, none of them in the mathematics:
attention and the indexer's scores run over blocks of rows of one sequence
under `jax.checkpoint` (no [T, T] array is ever whole: T 16 384 fits beside
the weights after a benchmark's window), the mask of a block a scatter of
the kept indices; the experts are a scan over the held stack that adds each
expert's gated output for EVERY token into one accumulator (the gate is zero
where the token did not choose it); the k largest router scores are picked
one at a time in a loop (the lowest index first among equals, as
`jax.lax.top_k`); the head and its cross-entropy run over chunks of 512
tokens.

Parameters, in the program's creation order: the token table; per layer g1,
W_q, W_k, W_v, g_q, g_k, W^I_q, W^I_k, W^I_w, W_o, g2, W_r, the stacks Wg
[held, d, f], Wu, Wd [held, f, d] (15 a layer); g_f; W_head.

The handed choices (`chipbench/README.md`): `choice`, a list of 0/1 masks
[tokens, E], one a layer: the gates are THIS file's float32 probabilities of
those experts, renormalised; `kept`, a list of int32 [B*T, topk], one a layer
(-1 where a row has fewer): S_t is the handed set and everything else is this
file's own. Gradients flow through both; a later layer's hidden state is this
file's own under the handed choices. None: this file's own top k.
"""

import math

import jax
import jax.numpy as jnp

PER_LAYER = 15
HEAD_CHUNK = 512
BLOCK = 512                      # rows a block of indexer scores holds
ATTEND_BYTES = 256 * 2**20       # a block of attention scores, float32


def _rms(v, w, eps):
    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * w


def _held(config):
    lo, hi = config.get("held_experts") or (0, _router_width(config))
    return int(lo), int(hi)


def _router_width(config):
    return int(config.get("router_experts") or config["num_experts"])


def _divisor(n, cap):
    return max(r for r in range(1, max(1, min(n, cap)) + 1) if n % r == 0)


# ---------------------------------------------------------------- rotary
def mrope(x, positions, theta, sections):
    """x [B, T, heads, D], positions int32 [B, A, T] -> x turned (equation
    3): pair i by the position on the axis whose section holds it."""
    D = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    axis_of = jnp.concatenate([jnp.full((n,), a, jnp.int32)
                               for a, n in enumerate(sections)])
    assert axis_of.shape[0] == D // 2, (sections, D)
    at = positions[:, axis_of, :].transpose(0, 2, 1).astype(jnp.float32)
    ang = (at * inv_freq)[:, :, None, :]                  # [B, T, 1, D / 2]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


# -------------------------------------------------- the keys a row keeps
def _topk(config):
    return int(config["sa_config"]["topk"])


def kept(config, scores, valid):
    """The published rule (equation 5): 0/1 [rows, candidates], a row's topk
    valid candidates of largest score, the lower index among equals (all of
    them where it has no more)."""
    rows, width = scores.shape
    value, index = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf),
                                 min(_topk(config), width))
    return jnp.zeros(scores.shape, jnp.float32).at[
        jnp.arange(rows)[:, None], jnp.where(value > -jnp.inf, index, width)
    ].set(1.0, mode="drop")


def scores(config, keeper, row0, rows):
    """(I [rows, T] float32, valid [rows, T] bool) of the rows from `row0` on
    (equation 4), from what `loss_grads_routers_and_keepers` kept of a
    layer's indexer: q^I [B*T, Hi, Di], w [B*T, Hi], k^I [B, T, Di]. A block
    may straddle sequences: each row is scored against its own sequence's
    keys."""
    q_i = jax.lax.dynamic_slice_in_dim(keeper["q"], row0, rows)
    w_i = jax.lax.dynamic_slice_in_dim(keeper["w"], row0, rows)
    B, T, _ = keeper["k"].shape
    row = row0 + jnp.arange(rows)
    z = jnp.zeros((rows, T), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            z_b = jnp.einsum("rh,rht->rt", w_i, jax.nn.relu(
                jnp.einsum("rhd,td->rht", q_i, keeper["k"][b])))
            z = jnp.where((row // T == b)[:, None], z_b, z)
    return z, jnp.arange(T)[None, :] <= (row % T)[:, None]


def _attention(config, x, positions, p, handed):
    """x [B, T, d] (normed: equation 1) -> (the layer's output [B, T, d],
    its keeper). `handed`: int32 [B*T, topk], or None for the layer's own
    sets."""
    wq, wk, wv, g_q, g_k, iwq, iwk, iww, wo = p
    B, T, _ = x.shape
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    D, eps = config["head_dim"], config["rms_norm_eps"]
    sa = config["sa_config"]
    Hi = sa["indexer_num_heads"]
    assert sa["indexer_num_kv_heads"] == 1, sa
    theta = float(config["rope_theta"])
    sections = config["rope_scaling"]["mrope_section"]
    q = mrope(_rms((x @ wq).reshape(B, T, H, D), g_q, eps), positions, theta,
              sections)
    k = mrope(_rms((x @ wk).reshape(B, T, KV, D), g_k, eps), positions, theta,
              sections)
    v = (x @ wv).reshape(B, T, KV, D)
    keeper = jax.lax.stop_gradient({
        "q": (x @ iwq).reshape(B * T, Hi, -1),
        "w": (x @ iww).reshape(B * T, Hi), "k": x @ iwk})
    rows = _divisor(T, min(BLOCK, max(8, ATTEND_BYTES // (4 * H * T))))

    def block(i):        # `rows` rows of one sequence
        row0 = i * rows
        b = row0 // T
        if handed is None:
            mask = kept(config, *scores(config, keeper, row0, rows)) > 0
        else:
            index = jax.lax.dynamic_slice_in_dim(handed, row0, rows)
            mask = jnp.zeros((rows, T), bool).at[
                jnp.arange(rows)[:, None], jnp.where(index < 0, T, index)
            ].set(True, mode="drop")
        q_b = jax.lax.dynamic_slice_in_dim(q[b], row0 % T, rows)
        s = jnp.einsum("rkgd,tkd->kgrt", q_b.reshape(rows, KV, H // KV, D),
                       k[b]) / math.sqrt(D)
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgrt,tkd->rkgd", a, v[b]).reshape(rows, H * D)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(B * T // rows))
    return out.reshape(B, T, H * D) @ wo, keeper


# ------------------------------------------------------- routed experts
def _top_k_mask(scores_, top_k):
    """[N, E] -> 0/1 [N, E]: the `top_k` largest of each row, picked one at
    a time, the lowest index first among equals (as `jax.lax.top_k`)."""
    def pick(_, chosen_):
        best = jnp.argmax(jnp.where(chosen_ > 0, -jnp.inf, scores_), axis=-1)
        return chosen_ + jax.nn.one_hot(best, scores_.shape[-1],
                                        dtype=scores_.dtype)

    return jax.lax.fori_loop(0, top_k, pick, jnp.zeros_like(scores_))


def chosen(config, params, logits):
    """The published choice on HANDED router logits (a list of float32
    [tokens, E], one a layer): the top k of softmax(z). 0/1 masks."""
    assert len(logits) == config["num_hidden_layers"], len(logits)
    return [_top_k_mask(jax.nn.softmax(jnp.asarray(z, jnp.float32), axis=-1),
                        config["num_experts_per_tok"]) for z in logits]


def _expert(h, w_gate, w_up, w_down, gate):
    """One expert on EVERY token, times the token's gate for it (zero where
    the expert is not among the token's chosen)."""
    return ((jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down) * gate[:, None]


def _experts(config, h, wr, w_gate, w_up, w_down, handed=None):
    """h [N, d] -> (y [N, d], router logits [N, E]) (equation 8)."""
    lo, hi = _held(config)
    z = h @ wr
    p = jax.nn.softmax(z, axis=-1)
    if handed is None:
        handed = _top_k_mask(jax.lax.stop_gradient(p),
                             config["num_experts_per_tok"])
    gates = p * handed
    if config["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)

    def add(y, expert):
        return y + jax.checkpoint(_expert)(h, *expert), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h),
                        (w_gate, w_up, w_down, gates[:, lo:hi].T))
    return y, z


# ---------------------------------------------------------------- model
def _split(config, params):
    n = config["num_hidden_layers"]
    assert len(params) == 1 + PER_LAYER * n + 2, len(params)
    params = [jnp.asarray(p, jnp.float32) for p in params]
    tok_emb, *rest = params
    *flat, g_f, w_head = rest
    return tok_emb, [flat[i:i + PER_LAYER]
                     for i in range(0, len(flat), PER_LAYER)], g_f, w_head


def _hidden(config, params, feed, choice, handed):
    """-> (h [B, T, d] before the final norm, each layer's router (its input
    [B*T, d], weight, logits [B*T, E]), each layer's keeper)."""
    tok_emb, layers, _, _ = _split(config, params)
    toks = jnp.asarray(feed["toks"])
    positions = jnp.asarray(feed["positions"])
    B, T = toks.shape
    eps = config["rms_norm_eps"]
    h = jax.lax.map(lambda t: tok_emb[t], toks)                  # [B, T, d]
    routers, keepers = [], []
    for i, (g1, *p) in enumerate(layers):
        attn, (g2, wr, *stacks) = p[:9], p[9:]
        out, keeper = _attention(config, _rms(h, g1, eps), positions, attn,
                                 None if handed is None else handed[i])
        keepers.append(keeper)
        h = h + out
        x2 = _rms(h, g2, eps).reshape(B * T, -1)
        y, z = _experts(config, x2, wr, *stacks,
                        handed=None if choice is None else choice[i])
        routers.append((x2, wr, z))
        h = h + y.reshape(B, T, -1)
    return h, routers, keepers


def _cross_entropy_sum(config, x, labels, g_f, w_head):
    """Sum over tokens of -log softmax(rms(x) W_head)[label], in chunks."""
    N = x.shape[0]
    chunk = HEAD_CHUNK if N % HEAD_CHUNK == 0 else N

    def one(x_c, labels_c):
        logp = jax.nn.log_softmax(
            _rms(x_c, g_f, config["rms_norm_eps"]) @ w_head, axis=-1)
        return -jnp.take_along_axis(logp, labels_c, axis=-1).sum()

    def add(total, xl):
        return total + jax.checkpoint(one)(*xl), None

    total, _ = jax.lax.scan(
        add, jnp.zeros((), jnp.float32),
        (x.reshape(N // chunk, chunk, -1), labels.reshape(N // chunk, chunk, 1)))
    return total


def _cost(config, params, feed, choice, handed):
    _, _, g_f, w_head = _split(config, params)
    h, routers, keepers = _hidden(config, params, feed, choice, handed)
    h = h.reshape(-1, h.shape[-1])
    labels = jnp.asarray(feed["labels"]).reshape(-1, 1)
    return (_cross_entropy_sum(config, h, labels, g_f, w_head) / h.shape[0],
            (routers, keepers))


def hidden(config, params, feed, choice=None, kept=None):
    """The stream [B, T, d] before the final norm (small sizes; the test of
    the shares)."""
    with jax.default_matmul_precision("highest"):
        return _hidden(config, params, feed, choice, kept)[0]


def loss_grads_routers_and_keepers(config, params, feed, choice=None,
                                   kept=None):
    """(cost, gradients in the program's parameter order: what plain Adam is
    handed, zeros for the indexer's three matrices a layer, which the program
    does not train; each layer's router as this file computed it (input
    [tokens, d], weight [d, E], logits [tokens, E]); each layer's keeper:
    what `scores` reads this file's own indexer scores from). `choice`: a 0/1
    mask a layer; `kept`: an int32 [B*T, topk] a layer; None: this file's
    own."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    with jax.default_matmul_precision("highest"):
        (cost, (routers, keepers)), grads = jax.value_and_grad(
            lambda ps: _cost(config, ps, feed, choice, kept),
            has_aux=True)(params)
    return cost, grads, routers, keepers


def loss_and_grads(config, params, feed):
    return loss_grads_routers_and_keepers(config, params, feed)[:2]


def prepare(feed):
    """The reader's batch is already a dict of arrays."""
    return feed
