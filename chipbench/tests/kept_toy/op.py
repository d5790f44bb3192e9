"""The toy's "system": a learned sparse attention in plain `jax.numpy`, every
matmul's inputs rounded to bf16 and accumulated in float32, as bf16 AMP does
(the indexer's too: its published form runs in fp8).

    q, k, v = h Wq, h Wk, h Wv            [T, H, D] each (no rotary, no norm)
    q^I = g W^I_q [T, Hi, Di];  k^I = g W^I_k [T, Di];  w = g W^I_w [T, Hi]
    I(t, s) = sum_j w[t, j] relu(q^I[t, j] . k^I[s])
    S_t = the k keys s <= t of largest I(t, s) (all of them where t < k)
    out_t = (softmax over s in S_t of q_t . k_s / sqrt(D)) v  Wo

g is h unless another `index_input` is given. The sets are discrete, so the
indexer's three matrices get no gradient. A block of rows at a time, so that
no [T, T] array is whole. Returns (out [B, T, d], chosen int32 [B*T, k]: a
row's kept keys by index, -1 where it has fewer than k).

`fault` breaks it the ways `drivers/train.py` has to see: "k_minus_1" keeps
one key fewer; "future_key" keeps key t + 1 in place of the row's best;
"negated" keeps the top k of -I; "chosen_not_used" attends the right keys
and gives out the top k of -I as `chosen`. (An indexer that reads another
layer's input is the caller's fault: `index_input`.)
"""

import math

import jax
import jax.numpy as jnp

BLOCK = 512
FAULTS = ("k_minus_1", "future_key", "negated", "chosen_not_used")


def _mm(a, b):
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _bf16(*arrays):
    return tuple(a.astype(jnp.bfloat16) for a in arrays)


def _top(z, valid, k):
    """int32 [R, k]: each row's k valid candidates of largest z, -1 behind
    the valid ones."""
    value, index = jax.lax.top_k(jnp.where(valid, z, -jnp.inf), k)
    return jnp.where(value > -jnp.inf, index, -1).astype(jnp.int32)


def kept_attention(h, weights, k, heads, index_heads, index_input=None,
                   fault=None):
    wq, wk, wv, wo, iwq, iwk, iww = weights
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"kept_attention: no fault {fault!r} among {FAULTS}")
    B, T, _ = h.shape
    g = h if index_input is None else index_input
    q, key, v = (_mm(h, w).reshape(B, T, heads, -1) for w in (wq, wk, wv))
    q_i = _mm(g, iwq).reshape(B, T, index_heads, -1)
    k_i, w_i = _mm(g, iwk), _mm(g, iww)
    rows = max(r for r in range(1, min(T, BLOCK) + 1) if T % r == 0)

    def block(i):
        b, t0 = i // (T // rows), (i % (T // rows)) * rows

        def mine(a):
            return jax.lax.dynamic_slice_in_dim(a[b], t0, rows)

        t = t0 + jnp.arange(rows)
        valid = jnp.arange(T)[None, :] <= t[:, None]
        z = jnp.einsum("rh,rht->rt", mine(w_i), jax.nn.relu(jnp.einsum(
            "rhd,td->rht", *_bf16(mine(q_i), k_i[b]),
            preferred_element_type=jnp.float32)))
        attended = _top(-z if fault == "negated" else z, valid, k)
        if fault == "k_minus_1":
            attended = attended.at[:, k - 1].set(-1)
        if fault == "future_key":
            attended = attended.at[:, 0].set(jnp.minimum(t + 1, T - 1))
        given = _top(-z, valid, k) if fault == "chosen_not_used" else attended
        mask = jnp.zeros((rows, T), bool).at[
            jnp.arange(rows)[:, None], jnp.where(attended < 0, T, attended)
        ].set(True, mode="drop")
        s = jnp.einsum("rhd,thd->hrt", *_bf16(mine(q), key[b]),
                       preferred_element_type=jnp.float32)
        p = jax.nn.softmax(jnp.where(mask, s / math.sqrt(q.shape[-1]),
                                     -jnp.inf), axis=-1)
        out = jnp.einsum("hrt,thd->rhd", *_bf16(p, v[b]),
                         preferred_element_type=jnp.float32)
        return out.reshape(rows, -1), given

    out, chosen = jax.lax.map(jax.checkpoint(block),
                              jnp.arange(B * (T // rows)))
    return _mm(out.reshape(B, T, -1), wo), chosen.reshape(B * T, k)
