"""The seven wrong programs the Keye-VL comparison has to catch (ISSUE 60):
each is `paddle_tpu` with one function replaced, so that the Program, the
weights and the reference stay what they are and only the arithmetic under
test is wrong. `tests/test_keye_vl.py` applies each at the small size and
holds it to the number that has to catch it, computed by the benchmark's own
driver (`chipbench/drivers/train.py:kept_numbers_by_layer`) or by the
gradient comparison; on the chip the same replacements run under
`chipbench/run.py` at the published widths (PERF.md section 6, PR 60, has what
caught each).

    k_minus_1            a row keeps topk - 1 keys       (`kept_sets_off_rule`)
    future_key           a row keeps key t + 1 among its topk: the attention's
                         causal mask drops it again, `Chosen` says what was
                         selected                        (`kept_sets_off_rule`)
    indexer_other_input  the indexer's queries and weights are the PREVIOUS
                         token's: it scores another input
                                                   (`kept_turned_not_near_tie`)
    negated              the rows keep the topk of -I
                                                   (`kept_turned_not_near_tie`)
    attends_every_key    `Chosen` is right and the attention runs over every
                         causal key                                (the tensors)
    axes_swapped         the rotary reads the height axis where the width
                         sections are and the reverse: only the image spans'
                         tokens can show it                        (the tensors)
    bf16_router          the router's input and weight are rounded to bf16
"""

import contextlib

import jax.numpy as jnp

CONTROLS = ("k_minus_1", "future_key", "indexer_other_input", "negated",
            "attends_every_key", "axes_swapped", "bf16_router")


@contextlib.contextmanager
def applied(name: str):
    """`paddle_tpu` with the control `name` in place."""
    from paddle_tpu.ops import moe_ops, qk_ops, sparse_attention_ops as sp

    if name not in CONTROLS:
        raise ValueError(f"unknown control {name!r}: one of {CONTROLS}")
    patches = []
    if name == "k_minus_1":
        right = sp.keep_bits

        def wrong(q_i, k_i, w_i, topk, *rest, **kw):
            return right(q_i, k_i, w_i, topk - 1, *rest, **kw)
        patches.append((sp, "keep_bits", wrong))
    elif name == "future_key":
        right = sp.select_by_count

        def wrong(z, valid, k):
            # the first key a row may not keep is key t + 1
            nxt = jnp.roll(valid, 1, axis=1) & ~valid
            return right(jnp.where(nxt, jnp.inf, z), valid | nxt, k)
        patches.append((sp, "select_by_count", wrong))
    elif name in ("indexer_other_input", "negated"):
        right = sp.index_scores

        if name == "negated":
            def wrong(q_i, k_i, w_i):
                return -right(q_i, k_i, w_i)
        else:
            def wrong(q_i, k_i, w_i):
                return right(jnp.roll(q_i, 1, axis=0), k_i,
                             jnp.roll(w_i, 1, axis=0))
        patches.append((sp, "index_scores", wrong))
    elif name == "attends_every_key":
        right = sp.sparse_attention

        def wrong(q, k, v, bits):
            return right(q, k, v, jnp.full_like(bits, -1))
        patches.append((sp, "sparse_attention", wrong))
    elif name == "axes_swapped":
        right = qk_ops.fed_tables

        def wrong(positions, sections, theta, R):
            return right(positions[:, jnp.asarray([0, 2, 1]), :], sections,
                         theta, R)
        patches.append((qk_ops, "fed_tables", wrong))
    else:
        right = moe_ops.route

        def wrong(x, router_w, *args, **kw):
            return right(x.astype(jnp.bfloat16).astype(jnp.float32),
                         router_w.astype(jnp.bfloat16).astype(jnp.float32),
                         *args, **kw)
        patches.append((moe_ops, "route", wrong))
    was = [(module, attr, getattr(module, attr)) for module, attr, _ in patches]
    for module, attr, fn in patches:
        setattr(module, attr, fn)
    try:
        yield
    finally:
        for module, attr, fn in was:
            setattr(module, attr, fn)
