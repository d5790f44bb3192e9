"""Plain reference for gpt2-small: the forward pass, the loss and its
gradients for a pre-LN GPT-2-shaped decoder as `paddle_tpu.models.transformer_lm` defines
it, in straightforward `jax.numpy`, float32, matmuls at the highest
precision; no kernels, no AMP. Independent of the code under test: it
shares only the parameter values, taken in the program's creation order.

Per layer (16 tensors): ln1 w, b; wq, bq, wk, bk, wv, bv, wo, bo; ln2 w, b;
ffn_in w, b; ffn_out w, b. Before them the token and position tables,
after them ln_f w, b and the (untied, bias-free) output head.
Departures from the published GPT-2 are the model builder's and are
listed in config.json.

The twelve layers run as one `lax.scan` over their stacked weights, not as
an unrolled loop: the mathematics and, on the CPU, every bit of the result
are the same, but the unrolled program was 400 MiB of code, which the chip
books among its live arrays: it set the run's `peak_bytes_in_use` (2.41 GB
against the steps' 2.23) and so 1.7 % of `peak_hbm_gib` measured this file
(PR 26; the scanned program is 40 MiB and compiles in half the time).
"""

import math

import jax
import jax.numpy as jnp


def _ln(x, w, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _layer(heads, causal, x, layer):
    (l1w, l1b, wq, bq, wk, bk, wv, bv, wo, bo,
     l2w, l2b, w_in, b_in, w_out, b_out) = layer
    T = x.shape[0]
    h = _ln(x, l1w, l1b)
    d = wq.shape[1] // heads
    q, k, v = ((h @ w + b).reshape(T, heads, d)
               for w, b in ((wq, bq), (wk, bk), (wv, bv)))
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v).reshape(T, heads * d)
    x = x + a @ wo + bo
    h = _ln(x, l2w, l2b)
    return x + jax.nn.gelu(h @ w_in + b_in, approximate=True) @ w_out + b_out


def _one_sequence(params, heads, toks, labels):
    tok_emb, pos_emb, *rest = params
    *layers, lnf_w, lnf_b, out_w = rest
    T = toks.shape[0]
    x = tok_emb[toks] + pos_emb[:T]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    # the layers are alike: one layer's code over the stacked weights
    stacked = [jnp.stack(layers[i::16]) for i in range(16)]
    x, _ = jax.lax.scan(
        lambda x, layer: (_layer(heads, causal, x, layer), None), x, stacked)
    logits = _ln(x, lnf_w, lnf_b) @ out_w
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels, axis=-1).mean()


def prepare(feed):
    """The reader's batch is already a dict of arrays."""
    return feed


def loss_and_grads(config, params, feed):
    """Mean next-token cross-entropy over the batch and its gradient for
    every parameter (what plain Adam is handed: no clipping, no decay).
    One sequence at a time, summed in a scan, so that the float32
    activations of one sequence are all it holds."""
    params = [jnp.asarray(p, jnp.float32) for p in params]
    assert len(params) == 2 + 16 * config["n_layer"] + 3, len(params)
    one = jax.value_and_grad(
        lambda ps, toks, labels: _one_sequence(ps, config["n_head"], toks, labels))

    def add(total, tl):
        return jax.tree.map(jnp.add, total, one(params, *tl)), None

    zero = (jnp.zeros((), jnp.float32), [jnp.zeros_like(p) for p in params])
    toks, labels = jnp.asarray(feed["toks"]), jnp.asarray(feed["labels"])
    with jax.default_matmul_precision("highest"):
        (cost, grads), _ = jax.lax.scan(add, zero, (toks, labels))
    n = toks.shape[0]
    return cost / n, [g / n for g in grads]
