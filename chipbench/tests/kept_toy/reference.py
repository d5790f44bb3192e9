"""The toy's plain reference: float32 `jax.numpy`, matmuls at the highest
precision, nothing of `paddle_tpu/` and nothing of `op.py`. The entries
`drivers/train.py` asks of a program with routed ops and ops that keep k of a
row's candidates (chipbench/README.md): `prepare`, `chosen`, `kept`, `scores`,
`loss_grads_routers_and_keepers`.

    x = table[toks]                                           [B, T, d]
    where `routed`, a routed layer of the hybrid's kind:
      h = rms(x, w);  z = h Wr;  s = sigmoid(z);  the top k of s + b chosen;
      gates = s[chosen] / sum x routed_scaling_factor
      x = x + sum over the HELD experts e of gates_e relu(h Wu_e)^2 Wd_e
            + relu(h Wu_s)^2 Wd_s
    num_hidden_layers times, a learned sparse attention:
      h = rms(x, w);  q, k, v = h Wq, h Wk, h Wv              [T, H, D]
      q^I = h W^I_q [T, Hi, Di];  k^I = h W^I_k [T, Di];  w = h W^I_w [T, Hi]
      I(t, s) = sum_j w[t, j] relu(q^I[t, j] . k^I[s])
      S_t = the index_topk keys s <= t of largest I(t, s), the lowest index
            first among equals (all of them where t < index_topk)
      x = x + (softmax over s in S_t of q_t . k_s / sqrt(D)) v Wo
    cost = mean CE(rms(x, w_f) W_head, labels)

Handed `kept` (one int32 [B*T, index_topk] a layer, -1 where a row has
fewer), S_t is the handed set and everything else is this file's own. The
sets are discrete: W^I_q, W^I_k and W^I_w get no gradient.

Parameters, in the program's creation order: the table; where `routed` w, Wr
[d, E], Wu [held, d, f], Wd [held, f, d], b [E], Wu_s, Wd_s; a layer w, Wq,
Wk, Wv, Wo, W^I_q, W^I_k, W^I_w; then w_f and W_head. A block of at most 512
rows at a time: no [T, T] array is ever whole.
"""

import math

import jax
import jax.numpy as jnp

BLOCK = 512
PER_LAYER, PER_ROUTED = 8, 7


def _rms(v, w, eps):
    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * w


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _split(config, params):
    params = [jnp.asarray(p, jnp.float32) for p in params]
    table, *rest = params
    *rest, w_f, w_head = rest
    routed = rest[:PER_ROUTED] if config["routed"] else None
    rest = rest[PER_ROUTED if config["routed"] else 0:]
    assert len(rest) == PER_LAYER * config["num_hidden_layers"], len(rest)
    return table, routed, [rest[i:i + PER_LAYER]
                           for i in range(0, len(rest), PER_LAYER)], w_f, w_head


def prepare(feed):
    return feed


# ---------------------------------------------------------------- routed
def _top_k_mask(scores, top_k):
    _, index = jax.lax.top_k(scores, top_k)
    return jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], index].set(1.0)


def chosen(config, params, logits):
    """The published choice on HANDED router logits ([tokens, E] float32,
    one a routed layer): the top k of sigmoid(z) + b. 0/1 masks."""
    _, routed, _, _, _ = _split(config, params)
    (z,) = logits
    return [_top_k_mask(jax.nn.sigmoid(jnp.asarray(z, jnp.float32)) + routed[4],
                        config["num_experts_per_tok"])]


def _routed(config, x, p, handed):
    w, wr, w_up, w_down, b, up_s, down_s = p
    lo, hi = config["held_experts"]
    h = _rms(x, w, config["rms_norm_eps"]).reshape(-1, x.shape[-1])
    z = h @ wr
    s = jax.nn.sigmoid(z)
    if handed is None:
        handed = _top_k_mask(jax.lax.stop_gradient(s + b),
                             config["num_experts_per_tok"])
    gates = s * handed
    if config["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    gates = gates * config["routed_scaling_factor"]
    y = _relu2(h @ up_s) @ down_s
    for e in range(hi - lo):
        y = y + gates[:, lo + e, None] * (_relu2(h @ w_up[e]) @ w_down[e])
    return x + y.reshape(x.shape), (h, wr, z)


# ------------------------------------------------- the keys a row keeps
def kept(config, scores, valid):
    """The published rule: 0/1 [rows, candidates], a row's index_topk valid
    candidates of largest score (all of them where it has no more)."""
    value, index = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf),
                                 config["index_topk"])
    rows, width = scores.shape
    return jnp.zeros(scores.shape, jnp.float32).at[
        jnp.arange(rows)[:, None], jnp.where(value > -jnp.inf, index, width)
    ].set(1.0, mode="drop")


def scores(config, keeper, row0, rows):
    """(I [rows, T] float32, valid [rows, T] bool) of the rows from `row0`
    on, from what `loss_grads_routers_and_keepers` kept of a layer's indexer:
    q^I [B*T, Hi, Di], w [B*T, Hi], k^I [B, T, Di]. A block may straddle
    sequences: each row is scored against its own sequence's keys."""
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


def _attention(config, x, p, handed):
    """x [B, T, d] -> (x + the layer's output, its keeper). `handed`: int32
    [B*T, index_topk], or None for the layer's own top index_topk."""
    w, wq, wk, wv, wo, iwq, iwk, iww = p
    B, T, d = x.shape
    heads, index_heads = config["num_attention_heads"], config["index_n_heads"]
    h = _rms(x, w, config["rms_norm_eps"])
    q, key, v = ((h @ m).reshape(B, T, heads, -1) for m in (wq, wk, wv))
    keeper = jax.lax.stop_gradient({
        "q": (h @ iwq).reshape(B * T, index_heads, -1),
        "w": (h @ iww).reshape(B * T, index_heads), "k": h @ iwk})
    rows = max(r for r in range(1, min(T, BLOCK) + 1) if T % r == 0)

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
        s = jnp.einsum("rhd,thd->hrt", q_b, key[b]) / math.sqrt(q.shape[-1])
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("hrt,thd->rhd", a, v[b]).reshape(rows, -1)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(B * T // rows))
    return x + out.reshape(B, T, -1) @ wo, keeper


def _cost(config, params, feed, choice, handed):
    table, routed, layers, w_f, w_head = _split(config, params)
    toks, labels = jnp.asarray(feed["toks"]), jnp.asarray(feed["labels"])
    x = table[toks]
    routers, keepers = [], []
    if routed is not None:
        x, router = _routed(config, x, routed,
                            None if choice is None else choice[0])
        routers.append(router)
    for i, p in enumerate(layers):
        x, keeper = _attention(config, x, p,
                               None if handed is None else handed[i])
        keepers.append(keeper)
    logp = jax.nn.log_softmax(
        _rms(x, w_f, config["rms_norm_eps"]) @ w_head, axis=-1)
    cost = -jnp.take_along_axis(logp, labels.reshape(*toks.shape, 1), -1)
    return cost.mean(), (routers, keepers)


def loss_grads_routers_and_keepers(config, params, feed, choice=None,
                                   kept=None):
    """(cost, gradients in the program's parameter order, each routed
    layer's router as this file computed it (input [tokens, d], weight
    [d, E], logits [tokens, E]), each choosing layer's keeper: what `scores`
    reads this file's own indexer scores from). `choice`: a 0/1 mask a routed
    layer, `kept`: an int32 [B*T, index_topk] a choosing layer; None: this
    file's own top k."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    with jax.default_matmul_precision("highest"):
        (cost, (routers, keepers)), grads = jax.value_and_grad(
            lambda ps: _cost(config, ps, feed, choice, kept),
            has_aux=True)(params)
    return cost, grads, routers, keepers


def loss_and_grads(config, params, feed):
    return loss_grads_routers_and_keepers(config, params, feed)[:2]
