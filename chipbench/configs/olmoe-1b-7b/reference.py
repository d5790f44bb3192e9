"""Plain reference for olmoe-1b-7b: a COPY of `tests/olmoe_reference.py`
(everything from `import math` down to `router_logits` is that file's text;
a tier-1 test, `tests/test_chipbench_harness.py`, holds the two to the same
bits on the CPU), with the harness's `prepare` and the handed choice (PR 36)
added at the end. Copied so that an edit in the tree cannot move the
yardstick unseen.

The handed choice. A float32 reference that makes its own top-8 choice
disagrees with a sound bf16 program wherever the eighth and ninth
probabilities are a rounding apart, and the comparison of gradients then
reads the moved pair as an error (PERF.md section 6, PRs 26-36). So
`loss_and_grads`, `cost`, `hidden` and `router_logits` take `choice`: a list,
one per layer, of 0/1 masks [tokens, E] saying which experts each token's
pairs go to. Where it is given the gates are THIS file's float32
probabilities of those experts (renormalised where `norm_topk_prob`), and the
balance cost's shares f_e are counted over them; gradients flow through the
probabilities as before. `choice=None` is the reference's own choice, bit for
bit what the tree's file computes. `chosen` applies the published rule to
router logits that are handed in (the program's own `RouterLogits`), and
`loss_grads_and_routers` is `loss_and_grads` with this file's routers (input,
weight, logits) beside the cost, from the same forward. The functions below
`prepare` redefine the tree's of the same names with that one more argument,
because this PR may not edit the tree's file and the twin test wants its text
whole at the top: when a later PR gives `tests/olmoe_reference.py` the
argument, the redefinitions go.

The layer, as `allenai/OLMoE-1B-7B-0125-Instruct` publishes it
(`transformers` model_type `olmoe`; Muennighoff et al. 2024). With x
[T, d], every projection bias-free, and rms(v, w) = v * rsqrt(mean(v^2, -1)
+ eps) * w:

    h  = rms(x, w_in)                                   # input_layernorm
    q  = rms(h Wq, w_qn);  k = rms(h Wk, w_kn);  v = h Wv
                          # QK-norm over the whole d, BEFORE the split into heads
    q, k -> [T, H, D]; rotary on q and k: rotate-half convention,
                          # inv_freq_i = theta^(-2i/D), position t
    a  = causal softmax(q k^T / sqrt(D)) v  -> [T, d];   x = x + a Wo
    h  = rms(x, w_post)                                 # post_attention_layernorm
    z  = h Wr            [T, E], float32                # router logits
    p  = softmax(z, -1);  (g, e) = top_k(p)             # gates NOT renormalised
                                                        # (norm_topk_prob false)
    y  = sum_{j<k} g_j * ( silu(h Wg[e_j]) * (h Wu[e_j]) ) Wd[e_j]
    x  = x + y
    logits = rms(x, w_f) W_head                         # untied head

    cost = mean CE(logits, labels) + mean over layers of
           (balance_weight * L_balance + z_weight * L_z)
    L_balance = E * sum_e f_e * P_e,  f_e = share of the tokens x k (token,
                slot) pairs routed to e (a count: no gradient), P_e = mean
                over tokens of p[:, e]. (`transformers` counts f_e per slot,
                which is k times this; the weight is an assumption either
                way, see config.json.)
    L_z = mean over tokens of logsumexp(z)^2

Tokens are the whole batch's, B x T of them: the two auxiliary costs are
products of means and do not split by sequence. Every (token, slot) pair
is computed: no capacity, no dropped token.

Parameters, in the program's creation order: the token table; per layer
(12 tensors) w_in, Wq, Wk, Wv, w_qn, w_kn, Wo, w_post, Wr, Wg [E, d, f],
Wu [E, d, f], Wd [E, f, d]; then w_f and W_head.

Memory (the chip run holds this beside 7.5 GB of weights and Adam state,
and `correct` wants every book of the memory peak to stand, right after
this ran, under what the steps then reach; the mathematics does not depend
on any of it). Scratch: attention is mapped over (sequence, head), the
experts are a scan over the stacked weights that adds each expert's masked
output into one accumulator, the head and its cross-entropy run over chunks
of 512 tokens; each body is `jax.checkpoint`ed, so the backward pass keeps
the bodies' inputs and not [64, tokens, d] of partial sums or [tokens,
vocabulary] of logits. Code (the runtime books a loaded program among the
live arrays, and a float32 matmul at `highest` is six bf16 passes of
code: 52 MB as first written, against the 41 MB a step adds to the live
state; PERF.md section 6, PR 27): matmuls that share an input are one
matmul (Wq, Wk, Wv as one einsum over the stacked three; Wg and Wu side
by side as one [d, 2f] matrix: stacked, XLA kept 1.07 GB of transposed
copies of the two expert stacks), Q and K share one norm and one
rotation, the token table is read one sequence at a time (a gather of
8 192 rows has a sort in its gradient, one of 4 096 has not), and the k
largest probabilities are picked one at a time in a loop instead of
sorted: 36 MB at two sequences.
"""

import math

import jax
import jax.numpy as jnp

PER_LAYER = 12
HEAD_CHUNK = 512


def _rms(v, w, eps):
    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * w


def _rotate_half(x):
    d = x.shape[-1] // 2
    return jnp.concatenate([-x[..., d:], x[..., :d]], axis=-1)


def _rope(x, theta):
    """x [B, T, H, D]: x * cos + rotate_half(x) * sin, the angles of the
    D/2 frequencies repeated over both halves of the head."""
    T, D = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    return x * jnp.cos(ang) + _rotate_half(x) * jnp.sin(ang)


def _attend(qkv):
    q, k, v = qkv                      # one head of one sequence: [T, D]
    T, D = q.shape
    s = q @ k.T / math.sqrt(D)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ v


def _expert(h, wg, wu, wd, gate):
    """One expert on EVERY token, times the token's gate for it (zero
    where the expert is not among the token's top k)."""
    f = wg.shape[1]
    gu = h @ jnp.concatenate([wg, wu], axis=1)       # one matmul, [N, 2f]
    return ((jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ wd) * gate[:, None]


def _routed_ffn(h, wr, wg, wu, wd, top_k, norm_topk_prob):
    """h [N, d] -> (y [N, d], router logits z [N, E], probabilities p
    [N, E], chosen [N, E]: 1 for the token's top k experts, else 0)."""
    z = h @ wr
    p = jax.nn.softmax(z, axis=-1)

    def pick(_, chosen):     # the largest not yet chosen, one at a time
        best = jnp.argmax(jnp.where(chosen > 0, -jnp.inf, p), axis=-1)
        return chosen + jax.nn.one_hot(best, p.shape[-1], dtype=p.dtype)

    chosen = jax.lax.fori_loop(
        0, top_k, pick, jnp.zeros_like(jax.lax.stop_gradient(p)))   # [N, E]
    gates = p * chosen
    if norm_topk_prob:
        gates = gates / gates.sum(-1, keepdims=True)

    def add(y, expert):
        return y + jax.checkpoint(_expert)(h, *expert), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (wg, wu, wd, gates.T))
    return y, z, p, chosen


def _aux(z, p, chosen, balance_weight, z_weight):
    E = z.shape[-1]
    share = jax.lax.stop_gradient(chosen.sum(0) / chosen.sum())
    balance = E * jnp.sum(share * p.mean(0))
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(z, axis=-1)))
    return balance_weight * balance + z_weight * z_loss


def _sizes(config):
    return (config["num_hidden_layers"], config["num_attention_heads"],
            config["num_experts_per_tok"], config["rms_norm_eps"],
            float(config["rope_theta"]), bool(config["norm_topk_prob"]))


def _split(config, params):
    layers = config["num_hidden_layers"]
    assert len(params) == 1 + PER_LAYER * layers + 2, len(params)
    params = [jnp.asarray(p, jnp.float32) for p in params]
    tok_emb, *rest = params
    *blocks, w_f, w_head = rest
    return tok_emb, [blocks[i * PER_LAYER:(i + 1) * PER_LAYER]
                     for i in range(layers)], w_f, w_head


def hidden(config, params, toks):
    """toks [B, T] -> (x [B*T, d] before the final norm, the router logits
    of each layer [B*T, E], the auxiliary cost)."""
    _, heads, top_k, eps, theta, norm_topk = _sizes(config)
    tok_emb, blocks, _, _ = _split(config, params)
    B, T = toks.shape
    x = jax.lax.map(lambda t: tok_emb[t], toks).reshape(B * T, -1)    # [N, d]
    d = x.shape[-1]
    router_logits, aux = [], 0.0
    for (w_in, wq, wk, wv, w_qn, w_kn, wo, w_post, wr, wg, wu, wd) in blocks:
        h = _rms(x, w_in, eps)
        qkv = jnp.einsum("nd,sde->sne", h, jnp.stack([wq, wk, wv]))
        qk = _rms(qkv[:2], jnp.stack([w_qn, w_kn])[:, None, :], eps)
        qk = _rope(qk.reshape(2 * B, T, heads, d // heads), theta)
        v = qkv[2].reshape(B, T, heads, d // heads)
        q, k = qk[:B], qk[B:]
        per_head = [t.transpose(0, 2, 1, 3).reshape(B * heads, T, d // heads)
                    for t in (q, k, v)]
        a = jax.lax.map(jax.checkpoint(_attend), tuple(per_head))
        a = a.reshape(B, heads, T, d // heads).transpose(0, 2, 1, 3)
        x = x + a.reshape(B * T, d) @ wo
        h = _rms(x, w_post, eps)
        y, z, p, chosen = _routed_ffn(h, wr, wg, wu, wd, top_k, norm_topk)
        x = x + y
        router_logits.append(z)
        aux = aux + _aux(z, p, chosen, config["aux_balance_weight"],
                         config["aux_z_weight"])
    return x, router_logits, aux / len(blocks)


def logits(config, params, toks):
    """[B, T, vocabulary], whole (small sizes only)."""
    _, _, w_f, w_head = _split(config, params)
    with jax.default_matmul_precision("highest"):
        x, _, _ = hidden(config, params, jnp.asarray(toks))
        out = _rms(x, w_f, config["rms_norm_eps"]) @ w_head
    return out.reshape(*toks.shape, -1)


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


def cost(config, params, feed):
    _, _, w_f, w_head = _split(config, params)
    toks, labels = jnp.asarray(feed["toks"]), jnp.asarray(feed["labels"])
    x, _, aux = hidden(config, params, toks)
    ce = _cross_entropy_sum(config, x, labels.reshape(-1, 1), w_f, w_head)
    return ce / x.shape[0] + aux


def loss_and_grads(config, params, feed):
    """The cost (mean next-token cross-entropy over the batch plus the
    auxiliary costs) and its gradient for every parameter, in the program's
    parameter order: what plain Adam is handed (no clipping, no decay)."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda ps: cost(config, ps, feed))(params)


def router_logits(config, params, feed):
    """The reference's own float32 router: a list of [tokens, experts], one
    per routed layer."""
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
    [tokens, E], one per layer): the top k of softmax(z). A list of 0/1
    masks [tokens, E]."""
    assert len(logits) == config["num_hidden_layers"], len(logits)
    return [_top_k_mask(jax.nn.softmax(jnp.asarray(z, jnp.float32), axis=-1),
                        config["num_experts_per_tok"]) for z in logits]


def _routed_ffn(h, wr, wg, wu, wd, top_k, norm_topk_prob, chosen=None):
    """As above; `chosen` [N, E] 0/1 takes the place of the top k of p."""
    z = h @ wr
    p = jax.nn.softmax(z, axis=-1)
    if chosen is None:
        chosen = _top_k_mask(jax.lax.stop_gradient(p), top_k)
    gates = p * chosen
    if norm_topk_prob:
        gates = gates / gates.sum(-1, keepdims=True)

    def add(y, expert):
        return y + jax.checkpoint(_expert)(h, *expert), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (wg, wu, wd, gates.T))
    return y, z, p, chosen


def _hidden(config, params, toks, choice):
    """toks [B, T] -> (x [B*T, d] before the final norm, each layer's
    router: its input h [B*T, d], its weight and its logits [B*T, E]; the
    auxiliary cost)."""
    _, heads, top_k, eps, theta, norm_topk = _sizes(config)
    tok_emb, blocks, _, _ = _split(config, params)
    B, T = toks.shape
    x = jax.lax.map(lambda t: tok_emb[t], toks).reshape(B * T, -1)    # [N, d]
    d = x.shape[-1]
    routers, aux = [], 0.0
    for (w_in, wq, wk, wv, w_qn, w_kn, wo, w_post, wr, wg, wu, wd) in blocks:
        h = _rms(x, w_in, eps)
        qkv = jnp.einsum("nd,sde->sne", h, jnp.stack([wq, wk, wv]))
        qk = _rms(qkv[:2], jnp.stack([w_qn, w_kn])[:, None, :], eps)
        qk = _rope(qk.reshape(2 * B, T, heads, d // heads), theta)
        v = qkv[2].reshape(B, T, heads, d // heads)
        q, k = qk[:B], qk[B:]
        per_head = [t.transpose(0, 2, 1, 3).reshape(B * heads, T, d // heads)
                    for t in (q, k, v)]
        a = jax.lax.map(jax.checkpoint(_attend), tuple(per_head))
        a = a.reshape(B, heads, T, d // heads).transpose(0, 2, 1, 3)
        x = x + a.reshape(B * T, d) @ wo
        h = _rms(x, w_post, eps)
        y, z, p, picked = _routed_ffn(
            h, wr, wg, wu, wd, top_k, norm_topk,
            None if choice is None else choice[len(routers)])
        x = x + y
        routers.append((h, wr, z))
        aux = aux + _aux(z, p, picked, config["aux_balance_weight"],
                         config["aux_z_weight"])
    return x, routers, aux / len(blocks)


def hidden(config, params, toks, choice=None):
    """As above; `choice[i]` is handed to the i-th layer."""
    x, routers, aux = _hidden(config, params, toks, choice)
    return x, [z for _, _, z in routers], aux


def _cost_and_routers(config, params, feed, choice):
    _, _, w_f, w_head = _split(config, params)
    toks, labels = jnp.asarray(feed["toks"]), jnp.asarray(feed["labels"])
    x, routers, aux = _hidden(config, params, toks, choice)
    ce = _cross_entropy_sum(config, x, labels.reshape(-1, 1), w_f, w_head)
    return ce / x.shape[0] + aux, routers


def cost(config, params, feed, choice=None):
    return _cost_and_routers(config, params, feed, choice)[0]


def loss_grads_and_routers(config, params, feed, choice=None):
    """(cost, gradients, each layer's router as this file computed it: input
    [tokens, d], weight [d, E], logits [tokens, E]), one forward pass:
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
