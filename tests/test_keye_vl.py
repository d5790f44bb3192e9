"""Keye-VL-2.0's language model: each mechanism's op against a written-out form
before the model (the fed-position rotary against a loop over tokens and
pairs, its kernels interpreted; the sparse attention's plain form against a
token loop, its selection's two forms against each other, its backward
against `jax.grad` of the loop, `Chosen`'s contract, the attention kernels
under the keep operand interpreted against the plain form), then the whole
model through `Executor` against `tests/keye_vl_reference.py` on seeded
weights, gradient by gradient, the shares of an expert-parallel layer against
the uncut reference, the published parameter count, the image spans'
positions, the config through `Trainer`, and seven wrong programs
(`tests/keye_vl_controls.py`) that the comparison has to catch. CPU: the
attention takes its plain form; `tests/test_tpu_compile.py` compiles the step
for a described v5e.

Tolerances. float32 on the CPU at the highest matmul precision, both sides:
the cost within 2e-4 of itself and EVERY trainable gradient within 2e-4 of
its rms (what float32 sums in another order leave; a gradient that is
missing, doubled or handed to the wrong parameter reads ~1). The discrete
choices (a token's experts, a row's kept keys) are the PROGRAM's, handed to
the reference as the benchmark's driver hands them, so a near-tie cannot turn
the comparison; that the program's sets are the reference's own up to such
ties is held on its own by the driver's numbers.
"""

import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.core.backward import append_backward
from paddle_tpu.ops import flash_ops, qk_ops
from paddle_tpu.ops import sparse_attention_ops as sp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(__file__))
import keye_vl_controls  # noqa: E402
import keye_vl_reference as ref  # noqa: E402


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


keye_config = _load(os.path.join(ROOT, "configs", "keye_vl.py"),
                    "keye_vl_config_under_test")

SMALL = dict(
    vocab_size=96, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6, rope_theta=1e7,
    rope_scaling={"mrope_section": [2, 3, 3]},
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 2,
               "indexer_num_kv_heads": 1, "topk": 16},
    num_experts=4, router_experts=8, held_experts=[0, 4],
    num_experts_per_tok=2, norm_topk_prob=True, moe_intermediate_size=32,
    num_hidden_layers=2)
B, T, GRID = 2, 96, 4
ATTN = ("wq", "wk", "wv", "q_norm", "k_norm", "wo")
INDEXER = ("index_wq", "index_wk", "index_ww")
TRAINED = ["tok_emb"] + [
    f"h{i}.{n}" for i in range(SMALL["num_hidden_layers"])
    for n in (["ln_in.w"] + [f"attn.{a}" for a in ATTN]
              + ["ln_post.w", "moe.router", "moe.gate", "moe.up", "moe.down"])
] + ["ln_f.w", "out_w"]


def _rng(seed=0):
    return np.random.RandomState(seed)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


# ------------------------------------------------- the fed-position rotary ---
def _rotary_loop(x, positions, sections, theta):
    """Equation 3 a token, a head and a pair at a time, float64."""
    x = np.asarray(x, np.float64)
    Bn, Tn, H, D = x.shape
    axis_of = np.repeat(np.arange(len(sections)), sections)
    out = np.zeros_like(x)
    for b in range(Bn):
        for t in range(Tn):
            for i in range(D // 2):
                ang = float(positions[b, axis_of[i], t]) * theta ** (-2 * i / D)
                a, c = x[b, t, :, i], x[b, t, :, i + D // 2]
                out[b, t, :, i] = a * math.cos(ang) - c * math.sin(ang)
                out[b, t, :, i + D // 2] = c * math.cos(ang) + a * math.sin(ang)
    return out


def _positions(seed=3, batch=B, seqlen=T, grid=GRID, spans=4):
    r = _rng(seed)
    return np.stack([keye_config.span_positions(
        seqlen, keye_config.span_starts(r, seqlen, spans, grid), grid)
        for _ in range(batch)])


def test_fed_rotary_matches_a_loop_over_tokens_and_pairs():
    x = _rng(1).randn(2, 24, 3, 16).astype(np.float32)
    pos = _positions(batch=2, seqlen=24, grid=2, spans=2)
    assert (pos[:, 1] != pos[:, 2]).any()          # unequal axes
    tables = qk_ops.fed_tables(jnp.asarray(pos), (2, 3, 3), 1e4, 16)
    got = qk_ops.qk_assemble(jnp.asarray(x), None, 0.0, False, 1e4, 16,
                             jnp.float32, True, tables)
    assert np.abs(np.asarray(got) - _rotary_loop(x, pos, (2, 3, 3), 1e4)
                  ).max() < 2e-5


def test_three_equal_axes_are_the_old_rotary_bit_for_bit():
    x = jnp.asarray(_rng(2).randn(2, 64, 4, 128), jnp.float32)
    scale = jnp.asarray(_rng(3).rand(1, 128) + 0.5, jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (2, 3, 64))
    tables = qk_ops.fed_tables(pos, (16, 24, 24), 1e7, 128)
    for s in (None, scale):
        old = qk_ops.qk_assemble(x, s, 1e-6, False, 1e7, 128, jnp.float32)
        fed = qk_ops.qk_assemble(x, s, 1e-6, False, 1e7, 128, jnp.float32,
                                 True, tables)
        assert bool((old == fed).all())
    one = qk_ops.fed_tables(pos[:, :1], (64,), 1e7, 128)     # A = 1
    assert all(bool((a == b).all()) for a, b in zip(one, tables))


def test_fed_rotary_gradient_is_the_turn_by_the_negated_angle():
    x = jnp.asarray(_rng(4).randn(2, 24, 3, 16), jnp.float32)
    scale = jnp.asarray(_rng(5).rand(1, 16) + 0.5, jnp.float32)
    g = jnp.asarray(_rng(6).randn(2, 24, 3, 16), jnp.float32)
    tables = qk_ops.fed_tables(jnp.asarray(_positions(
        batch=2, seqlen=24, grid=2, spans=2)), (2, 3, 3), 1e4, 16)

    def rule(x, s):
        return (qk_ops.qk_assemble(x, s, 1e-6, False, 1e4, 16, jnp.float32,
                                   True, tables) * g).sum()

    def autodiff(x, s):
        return (qk_ops._assemble(x, s, 1e-6, False, 1e4, 16, jnp.float32,
                                 tables) * g).sum()

    for got, want in zip(jax.grad(rule, (0, 1))(x, scale),
                         jax.grad(autodiff, (0, 1))(x, scale)):
        assert _rel(got, want) < 1e-5


def test_fed_rotary_kernels_interpreted_match_the_xla_form():
    x = jnp.asarray(_rng(7).randn(2, 64, 4, 128), jnp.float32)
    scale = jnp.asarray(_rng(8).rand(1, 128) + 0.5, jnp.float32)
    g = jnp.asarray(_rng(9).randn(2, 64, 4, 128), jnp.float32)
    pos = jnp.asarray(_positions(batch=2, seqlen=64, grid=4, spans=2))
    tables = qk_ops.fed_tables(pos, (16, 24, 24), 1e7, 128)
    got = qk_ops._kernel_fwd(x, scale, 1e-6, False, 1e7, 128, jnp.float32,
                             interpret=True, tables=tables)
    want = qk_ops._assemble(x, scale, 1e-6, False, 1e7, 128, jnp.float32,
                            tables)
    assert _rel(got, want) < 1e-5
    for got, want in zip(
            qk_ops._kernel_bwd(x, scale, g, 1e-6, False, 1e7, 128,
                               interpret=True, tables=tables),
            qk_ops._assemble_bwd(x, scale, g, 1e-6, False, 1e7, 128, tables)):
        assert _rel(got, want) < 1e-5


def test_rotary_layer_refuses_positions_that_do_not_fit_its_sections():
    pt.reset()
    x = pt.layers.data("x", shape=[8, 64], dtype=np.float32)
    p = pt.layers.data("p", shape=[3, 8], dtype=np.int32)
    with pytest.raises(ValueError, match="sections"):
        pt.layers.rotary_embedding(x, 4, positions=p, sections=[2, 3, 4])
    with pytest.raises(ValueError, match="sections without positions"):
        pt.layers.rotary_embedding(x, 4, sections=[2, 3, 3])
    pt.layers.rotary_embedding(x, 4, positions=p, sections=[2, 3, 3])


# ---------------------------------------------------- the sparse attention ---
def _operands(Tn, H=4, KV=2, D=16, Hi=2, Di=8, ties=True, seed=0, batch=2):
    r = _rng(seed)
    f = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)  # noqa: E731
    q, k, v = f(batch, Tn, H, D), f(batch, Tn, KV, D), f(batch, Tn, KV, D)
    q_i, k_i, w_i = f(batch, Tn, Hi, Di), f(batch, Tn, Di), f(batch, Tn, Hi)
    if ties:        # every third key the same: equal scores in every row
        k_i = k_i.at[:, ::3].set(k_i[:, :1])
    return q, k, v, q_i, k_i, w_i


def _token_loop(q, k, v, q_i, k_i, w_i, topk, xp=np):
    """Equations 4-6 a row at a time: (out [B, T, H, D], the kept sets as
    lists). `xp` numpy in float64, or `jax.numpy` so that `jax.grad` sees
    it."""
    if xp is np:
        q, k, v, q_i, k_i, w_i = (np.asarray(a, np.float64)
                                  for a in (q, k, v, q_i, k_i, w_i))
    Bn, Tn, H, D = q.shape
    G = H // k.shape[2]
    outs, sets = [], []
    for b in range(Bn):
        for t in range(Tn):
            z = xp.einsum("h,hs->s", w_i[b, t], xp.maximum(
                xp.einsum("hd,sd->hs", q_i[b, t], k_i[b, :t + 1]), 0.0))
            scores = [float(x) for x in np.asarray(z, np.float32)]
            order = sorted(range(t + 1), key=lambda s: (-scores[s], s))
            kept = sorted(order[:min(topk, t + 1)])
            sets.append(kept)
            kk = xp.repeat(k[b][np.asarray(kept)], G, axis=1)   # [n, H, D]
            vv = xp.repeat(v[b][np.asarray(kept)], G, axis=1)
            s = xp.einsum("hd,nhd->hn", q[b, t], kk) / math.sqrt(D)
            a = xp.exp(s - s.max(-1, keepdims=True))
            outs.append(xp.einsum("hn,nhd->hd", a / a.sum(-1, keepdims=True),
                                  vv))
    return xp.stack(outs).reshape(Bn, Tn, H, D), sets


@pytest.mark.parametrize("Tn", [96, 130])
def test_plain_sparse_attention_matches_a_token_loop(Tn):
    """T 96 and 130 (no multiple of anything), topk 16: rows with fewer than
    topk keys, ties (every third indexer key equal)."""
    q, k, v, q_i, k_i, w_i = _operands(Tn)
    want, sets = _token_loop(q, k, v, q_i, k_i, w_i, 16)
    bits = sp.keep_bits(q_i, k_i, w_i, 16)
    assert bits.shape == (2, Tn, 128) and bits.dtype == jnp.int32
    kept = np.asarray(sp.unpack_bits(bits.reshape(2 * Tn, -1), Tn))
    assert [list(np.nonzero(row)[0]) for row in kept] == sets
    assert _rel(sp.attend_plain(q, k, v, bits), want) < 1e-5


def test_chosen_lists_a_rows_kept_keys_and_minus_one_behind_them():
    q, k, v, q_i, k_i, w_i = _operands(96)
    bits = sp.keep_bits(q_i, k_i, w_i, 16)
    chosen = np.asarray(sp.chosen_from_bits(bits, 96, 16))
    assert chosen.shape == (2 * 96, 16) and chosen.dtype == np.int32
    _, sets = _token_loop(q, k, v, q_i, k_i, w_i, 16)
    for row, kept in zip(chosen, sets):
        assert list(row[:len(kept)]) == kept and (row[len(kept):] == -1).all()
    wide = np.asarray(sp.chosen_from_bits(bits, 96, 200))   # topk beyond T
    assert wide.shape == (192, 200) and (wide[:, 96:] == -1).all()
    assert (wide[95, :16] == chosen[95]).all() and (wide[95, 16:] == -1).all()


def test_plain_sparse_attention_backward_is_the_loops():
    q, k, v, q_i, k_i, w_i = _operands(9, batch=1)
    bits = sp.keep_bits(q_i, k_i, w_i, 6)
    g = jnp.asarray(_rng(3).randn(*q.shape), jnp.float32)
    got = jax.grad(lambda *a: (sp.attend_plain(*a, bits) * g).sum(),
                   (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_token_loop(*a, q_i, k_i, w_i, 6, xp=jnp)[0]
                                * g).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert _rel(a, b) < 1e-5


def _select_top_k(z, valid, k):
    """The selection as a sort: `lax.top_k` of a tile (the lower index among
    equals) and a scatter. The second witness of `select_by_count`."""
    R, T = z.shape
    value, index = jax.lax.top_k(jnp.where(valid, z, -jnp.inf), min(k, T))
    return jnp.zeros((R, T), bool).at[
        jnp.arange(R)[:, None], jnp.where(value > -jnp.inf, index, T)
    ].set(True, mode="drop")


def test_selection_by_count_is_the_top_k_on_a_tile_of_equal_scores():
    z = jnp.asarray(np.round(_rng(4).randn(64, 700) * 2) / 2, jnp.float32)
    z = z.at[5].set(0.0).at[6].set(-0.0)            # a row of one value
    valid = jnp.arange(700)[None, :] <= (jnp.arange(64) * 11)[:, None]
    for k in (1, 16, 300):
        assert bool((sp.select_by_count(z, valid, k)
                     == _select_top_k(z, valid, k)).all())


def _keep_bits_whole(q_i, k_i, w_i, topk):
    """`keep_bits` as PR 60 wrote it: every tile scored and counted against
    the WHOLE sequence's keys. The witness of the grouped form."""
    Bn, Tn = q_i.shape[:2]
    rows = sp._rows(Tn)
    per_seq = Tn // rows

    def tile(i):
        b, t0 = i // per_seq, (i % per_seq) * rows

        def mine(a):
            return jax.lax.dynamic_slice_in_dim(a[b], t0, rows)

        z = sp.index_scores(mine(q_i), k_i[b], mine(w_i))
        with jax.named_scope("select"):
            valid = jnp.arange(Tn)[None, :] <= (t0 + jnp.arange(rows))[:, None]
            return sp.pack_bits(sp.select_by_count(z, valid, topk))

    bits = jax.lax.map(tile, jnp.arange(Bn * per_seq))
    return bits.reshape(Bn, Tn, -1)


def _exact_operands(Tn, dtype, seed=0, batch=2, Hi=16, Di=8):
    """Indexer operands whose every score is exact in float32 in whatever
    order a backend sums the products and the heads: small integers and
    halves (exact in bf16 too). A handful of distinct keys, so that most of a
    row's scores are ties, across every prefix's end."""
    r = _rng(seed)
    q_i = r.randint(-2, 3, (batch, Tn, Hi, Di))
    k_i = r.randint(-2, 3, (batch, 5, Di))[:, r.randint(0, 5, Tn)]
    w_i = r.randint(-2, 3, (batch, Tn, Hi)) / 2
    return tuple(jnp.asarray(a, dtype) for a in (q_i, k_i, w_i))


def _primitives(jaxpr):
    """Every primitive's name in a jaxpr, inner jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(inner)


# (tiles of 32 rows, topk, dtype): 1, 3, 8 and 9 tiles; topk under a tile's
# rows (no group is free), at the first group's end, between two groups' ends
# (the free tiles are a group of their own), at T and beyond it
GROUPED = [(1, 8, "float32"), (1, 32, "bfloat16"), (3, 16, "bfloat16"),
           (3, 32, "float32"), (3, 40, "bfloat16"), (8, 8, "float32"),
           (8, 100, "bfloat16"), (9, 20, "bfloat16"), (9, 64, "float32"),
           (9, 1000, "float32")]


@pytest.mark.parametrize("tiles,topk,dtype", GROUPED)
def test_grouped_keep_bits_are_the_whole_sequence_forms(monkeypatch, tiles,
                                                        topk, dtype):
    """A tile scored and counted against its group's causal prefix keeps what
    it kept against the whole sequence, to the bit, ties included. Under one
    `jit` a case (eagerly every group's map compiles alone)."""
    monkeypatch.setattr(sp, "BLOCK", 32)
    Tn = 32 * tiles
    operands = _exact_operands(Tn, dtype, seed=tiles + topk)
    got, want = jax.jit(lambda *a: (
        sp.keep_bits(*a, topk), _keep_bits_whole(*a, topk)))(*operands)
    assert got.shape == (2, Tn, sp.keep_lanes(Tn)) and got.dtype == jnp.int32
    assert bool((got == want).all())
    kept = np.asarray(sp.unpack_bits(got.reshape(2 * Tn, -1), Tn)).sum(1)
    assert (kept == np.minimum(topk, np.tile(np.arange(Tn), 2) + 1)).all()


def test_groups_follow_the_shapes_and_a_free_group_is_not_scored(monkeypatch):
    """The groups' ends by hand at the cell's shapes and at the edges; a
    group whose prefix is at most topk keys traces neither the scores nor a
    count (no `dot_general`, no `while` but the map's own), and a sequence of
    one tile is one map over the whole sequence, as before."""
    assert sp._groups(32, 512, 2048) == [(4 * i, 4 * i + 4) for i in range(8)]
    assert sp._groups(32, 512, 1024) == [(0, 2), (2, 4)] + [
        (4 * i, 4 * i + 4) for i in range(1, 8)]
    assert sp._groups(32, 512, 100) == [(4 * i, 4 * i + 4) for i in range(8)]
    assert sp._groups(9, 32, 96) == [(0, 3), (3, 4), (4, 6), (6, 8), (8, 9)]
    assert sp._groups(1, 96, 16) == [(0, 1)] == sp._groups(1, 96, 500)
    assert sp._groups(32, 512, 1 << 20) == [(0, 32)]

    def count(Tn, topk):
        names = list(_primitives(jax.make_jaxpr(
            lambda *a: sp.keep_bits(*a, topk))(
                *_exact_operands(Tn, "float32")).jaxpr))
        return names.count("dot_general"), names.count("while") \
            + names.count("scan")

    monkeypatch.setattr(sp, "BLOCK", 32)
    assert count(96, 8)[0] == 3 * count(32, 8)[0] > 0   # three, all scored
    assert count(96, 1000) == (0, 1)    # T <= topk: the causal mask's bits
    assert count(96, 32)[0] == 2 * count(32, 8)[0]    # the first is free
    monkeypatch.setattr(sp, "BLOCK", 512)
    assert count(96, 16) == count(32, 8)              # one tile: one map


@pytest.fixture(scope="module")
def two_tiles():
    """(the indexer's operands of two tiles of 32 rows, their right bits at
    topk 32: the first tile free, the second scored)."""
    operands = _operands(64, Hi=4, ties=False)[3:]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sp, "BLOCK", 32)
        return operands, jax.jit(lambda *a: sp.keep_bits(*a, 32))(*operands)


@pytest.mark.parametrize("control", keye_vl_controls.CONTROLS[:4])
def test_the_controls_still_turn_the_grouped_bits(monkeypatch, two_tiles,
                                                  control):
    """Each control that replaces a function of `sparse_attention_ops` is
    reached through the module by the grouped form too: the bits change."""
    monkeypatch.setattr(sp, "BLOCK", 32)
    operands, right = two_tiles
    with keye_vl_controls.applied(control):
        wrong = jax.jit(lambda *a: sp.keep_bits(*a, 32))(*operands)
    assert wrong.shape == right.shape and not bool((right == wrong).all())


def test_scored_pairs_are_static_arithmetic_counted_once(monkeypatch):
    """`pt_sparse_keep_scored_pairs` by hand at the cell's shapes and at a
    small one, its ratio to the causal pairs, and an op traced twice counted
    once."""
    import types

    # T 16 384 in tiles of 512, topk 2 048: eight groups of 2 048 rows, the
    # first free, group g scored against 2 048 g keys
    assert sp.scored_pairs(16384, 2048) == 2048 * 2048 * sum(range(2, 9))
    assert sp.scored_pairs(16384, 2048) / (16384 * 16385 // 2) \
        == pytest.approx(1.0937, abs=1e-4)
    assert sp.scored_pairs(16384, 16384) == 0
    assert sp.scored_pairs(96, 16) == 96 * 96       # one tile, as before
    monkeypatch.setattr(sp, "BLOCK", 32)
    # nine tiles of 32, topk 96: tiles 0-2 free, then ends at 4, 6, 8, 9
    by_hand = 32 * (1 * 128 + 2 * 192 + 2 * 256 + 1 * 288)
    assert sp.scored_pairs(288, 96) == by_hand
    monkeypatch.setattr(sp, "_scored", {})
    ctx = types.SimpleNamespace(op=types.SimpleNamespace(
        outputs={"Keep": ["a.attn.keep"]}))
    for Tn, topk in ((288, 96), (288, 96), (96, 8)):
        sp._count_scored(ctx, 2, Tn, topk)
    assert len(sp._scored) == 2
    families = {f[0]: f[3][0][1] for f in sp._families()}
    assert families["pt_sparse_keep_scored_pairs"] == 2 * (
        by_hand + 32 * (32 + 64 + 96))


def test_attention_kernels_under_the_keep_operand_match_the_plain_form():
    """The packed flash kernels, interpreted, 4-over-2 heads of 128 at T 1024
    (two 512-row blocks: a crossed block's strips and a bare block, each
    under its bits), forward and the fused backward."""
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, q_i, k_i, w_i = _operands(1024, D=128, ties=False, batch=1)
    bits = sp.keep_bits(q_i, k_i, w_i, 200)
    pack = lambda x: x.reshape(1, 1024, -1)  # noqa: E731

    def kernels(q, k, v):
        return flash_ops._packed_attention(pack(q), pack(k), pack(v), 4, True,
                                           0, False, bits).reshape(q.shape)

    g = jnp.asarray(_rng(5).randn(*q.shape), jnp.float32)
    with pltpu.force_tpu_interpret_mode():
        got = kernels(q, k, v)
        got_grads = jax.grad(lambda *a: (kernels(*a) * g).sum(),
                             (0, 1, 2))(q, k, v)
    assert _rel(got, sp.attend_plain(q, k, v, bits)) < 1e-5
    want = jax.grad(lambda *a: (sp.attend_plain(*a, bits) * g).sum(),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got_grads, want):
        assert _rel(a, b) < 1e-5
    # all bits set is plain causal attention
    with pltpu.force_tpu_interpret_mode():
        dense = flash_ops._packed_attention(
            pack(q), pack(k), pack(v), 4, True, 0, False,
            jnp.full_like(bits, -1))
        causal = flash_ops._packed_attention(pack(q), pack(k), pack(v), 4,
                                             True)
    assert _rel(dense, causal) < 1e-6


def test_dispatch_is_counted_and_the_pairs_are_static_arithmetic():
    assert sp.kept_pairs(16384, 2048) == 31_458_304
    assert sp.kept_pairs(2048, 2048) == 2048 * 2049 // 2
    q = jnp.zeros((1, 16384, 32, 128), jnp.bfloat16)
    assert not sp.kernels_eligible(q, q[:, :, :4])       # the CPU


def test_pair_counts_have_one_source_whatever_program_reuses_a_name(
        monkeypatch):
    """A second program that reuses an op's names at another T is another
    record, an op traced again is the same one, and both gauges' families
    (`pt_sparse_attention_*`, `pt_flash_attention_pairs{path=sparse_*}`) are
    sums over the one dictionary."""
    import types

    monkeypatch.setattr(sp, "_traced", {})
    monkeypatch.setattr(flash_ops, "_pairs", {})
    ctx = types.SimpleNamespace(op=types.SimpleNamespace(
        outputs={"Out": ["a.attn.out"]}))
    for Tn in (96, 96, 130):
        sp._count(ctx, "plain", jnp.zeros((2, Tn, 4, 16)), 16,
                  jnp.zeros((2, Tn, 128), jnp.int32))
    kept = 2 * (sp.kept_pairs(96, 16) + sp.kept_pairs(130, 16))
    assert len(sp._traced) == 2
    families = {f[0]: f[3][0][1] for f in sp._families()}
    assert families["pt_sparse_attention_kept_pairs"] == kept
    assert families["pt_sparse_attention_causal_pairs"] == 2 * (
        96 * 97 // 2 + 130 * 131 // 2)
    assert flash_ops._pairs == {
        ("sparse_plain", "kept"): 4 * kept,
        ("sparse_plain", "computed"): 2 * 4 * (96 * 96 + 130 * 130)}


# ------------------------------------------------------------- the model ---
def _build(amp=False, cfg=SMALL, held=None, layer_ids=None):
    pt.reset()
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = 11
    toks = pt.layers.data("toks", shape=[T], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[T, 1], dtype=np.int32)
    positions = pt.layers.data("positions", shape=[3, T], dtype=np.int32)
    sa = cfg["sa_config"]
    logits, routers = models.keye_lm(
        toks, positions, vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"], layer_ids=layer_ids,
        dim=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
        num_experts=cfg["router_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_dim=cfg["moe_intermediate_size"],
        held_experts=tuple(cfg["held_experts"] if held is None else held),
        rope_theta=cfg["rope_theta"],
        mrope_section=tuple(cfg["rope_scaling"]["mrope_section"]),
        rms_eps=cfg["rms_norm_eps"])
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    if amp:
        main.set_amp("bfloat16")
    block = main.global_block()
    chosen = [block.var(op.outputs["Chosen"][0]) for op in block.ops
              if op.type == "sparse_keep"]
    return main, startup, logits, loss, [z for z, _ in routers], chosen


def _feed(seed=5):
    start = _rng(seed).randint(0, 64, (B, 1))
    seq = (start + np.arange(T + 1)) % 64
    return {"toks": seq[:, :-1].astype(np.int32),
            "labels": seq[:, 1:, None].astype(np.int32),
            "positions": _positions(seed)}


def _moved_weights(main, seed=9):
    """Seeded weights moved off their start (ones and zeros too, so that
    every tensor's gradient tells), in the program's order."""
    scope, r = pt.global_scope(), _rng(seed)
    params = main.parameters()
    for p in params:
        scope.set(p.name, jnp.asarray(
            np.asarray(scope.get(p.name))
            + 0.05 * r.randn(*p.shape).astype(np.float32)))
    return params, [np.asarray(scope.get(p.name)) for p in params]


def _first_step(amp=False, cfg=SMALL, grads=True):
    """The program's cost, every gradient and its discrete choices on seeded
    weights, beside the reference's under those choices and the driver's
    numbers of the kept sets. `grads` False: the forward pass alone (the
    kept sets' numbers need no more)."""
    main, startup, logits, loss, router_logits, chosen = _build(amp, cfg)
    pairs = append_backward(loss) if grads else []
    exe = pt.Executor()
    exe.run(startup)
    params, values = _moved_weights(main)
    feed = _feed()
    n, m = len(router_logits), len(chosen)
    got = exe.run(main, feed=feed, fetch_list=(
        [loss] + router_logits + chosen + [g for _, g in pairs]))
    z, sets, grads = got[1:1 + n], got[1 + n:1 + n + m], got[1 + n + m:]
    handed = dict(choice=ref.chosen(cfg, values, z),
                  kept=[jnp.asarray(s) for s in sets])
    if grads:
        want_cost, want_grads, _, keepers = jax.jit(
            lambda v: ref.loss_grads_routers_and_keepers(
                cfg, v, feed, **handed))(values)
    else:
        with jax.default_matmul_precision("highest"):
            want_cost, (_, keepers) = jax.jit(lambda v: ref._cost(
                cfg, v, feed, handed["choice"], handed["kept"]))(values)
        want_grads = [np.zeros(())] * len(params)
    by_name = {p.name: g for p, g in zip(params, want_grads)}
    driver = _load(os.path.join(ROOT, "chipbench", "drivers", "train.py"),
                   "chipbench_train_driver_for_keye")
    with jax.default_matmul_precision("highest"):
        held = jax.device_get(driver.kept_numbers_by_layer(
            ref, cfg, keepers, [jnp.asarray(s) for s in sets]))
    return {"names": [p.name for p in params], "cost": float(got[0]),
            "want_cost": float(want_cost),
            "trained": [p.name for p, _ in pairs],
            "errs": {p.name: _rel(g, by_name[p.name])
                     for (p, _), g in zip(pairs, grads)},
            "indexer_reference_grads": [
                float(np.abs(np.asarray(by_name[nm])).max())
                for nm in by_name if ".index_w" in nm],
            "kept": [{k: float(v) for k, v in layer.items()}
                     for layer in held]}


@pytest.fixture(scope="module")
def float32_step():
    with jax.default_matmul_precision("highest"):
        return _first_step(False)


def test_float32_model_matches_the_reference_cost(float32_step):
    r = float32_step
    assert [n.split(".", 1)[1] for n in r["trained"]] == TRAINED
    per_layer = 1 + len(ATTN) + len(INDEXER) + 5
    assert len(r["names"]) == 1 + 2 * per_layer + 2 == len(TRAINED) + 6
    assert abs(r["cost"] - r["want_cost"]) < 2e-4 * abs(r["want_cost"])
    for layer in r["kept"]:         # the program's sets ARE the reference's
        assert layer["sets_off_rule"] == 0 == layer["turned_not_near_tie"]


@pytest.mark.parametrize("at", range(len(TRAINED)), ids=TRAINED)
def test_float32_gradient_matches_the_reference(float32_step, at):
    name = float32_step["trained"][at]
    assert float32_step["errs"][name] < 2e-4, (name,
                                               float32_step["errs"][name])


def test_the_indexer_is_frozen_and_gets_no_gradient(float32_step):
    r = float32_step
    frozen = [n for n in r["names"] if n not in r["trained"]]
    assert [n.split(".")[-1] for n in frozen] == list(INDEXER) * 2
    assert r["indexer_reference_grads"] == [0.0] * 6
    main, *_ = _build()
    pt.optimizer.Adam(learning_rate=1e-3).minimize(
        main.global_block().var([op for op in main.global_block().ops
                                 if op.type == "mean"][-1].outputs["Out"][0]))
    state = [v.name for v in main.persistables()]
    assert not [n for n in state if ".index_w" in n and "moment" in n]
    assert [n for n in state if "attn.wq" in n and "moment" in n]


def test_the_scored_pairs_reach_the_registry(float32_step):
    """A traced model's `sparse_keep` ops publish their gauge beside the
    attention ops' three: at one tile a sequence every row scores every key,
    2 T / (T + 1) of the causal pairs."""
    from paddle_tpu.obs import metrics

    series = {}
    for line in metrics.registry().render().splitlines():
        if line.startswith("pt_sparse_"):
            name, value = line.rsplit(" ", 1)
            series[name] = float(value)
    scored = series["pt_sparse_keep_scored_pairs"]
    assert scored >= 2 * 2 * T * T          # two layers, batch 2
    assert 1.0 < scored / series["pt_sparse_attention_causal_pairs"] <= 2.0


@pytest.mark.parametrize("control", keye_vl_controls.CONTROLS)
def test_the_comparison_tells_a_wrong_program(control):
    """Each wrong program of `tests/keye_vl_controls.py` by the number named
    there; float32 on the CPU, where the right program reads 0, 0 and under
    2e-4."""
    by_sets = control in keye_vl_controls.CONTROLS[:4]
    with jax.default_matmul_precision("highest"), \
            keye_vl_controls.applied(control):
        r = _first_step(False, grads=not by_sets)
    off = sum(layer["sets_off_rule"] for layer in r["kept"])
    turned = sum(layer["turned_not_near_tie"] for layer in r["kept"])
    worst = max(r["errs"].values(), default=0.0)
    if control in ("k_minus_1", "future_key"):
        assert off > 0
    elif control in ("indexer_other_input", "negated"):
        assert off == 0 and turned > 0
    else:
        # the first layer's sets are right (its indexer read the right
        # input); a later layer's indexer reads a stream the wrong layer
        # wrote, and may turn
        assert off == 0 == r["kept"][0]["turned_not_near_tie"]
        # ten times what the right program is held to; the dense attention
        # far more (the swapped axes move the image spans' tokens alone,
        # a quarter of the rows by at most three grid steps: 0.018 here)
        assert worst > (0.05 if control == "attends_every_key" else 2e-3), \
            worst


def test_bf16_amp_model_stays_near_the_reference():
    r = _first_step(True)
    assert abs(r["cost"] - r["want_cost"]) < 2e-3 * abs(r["want_cost"])
    assert sorted(r["errs"].values())[len(r["errs"]) // 2] < 0.05
    for layer in r["kept"]:
        assert layer["sets_off_rule"] == 0 == layer["turned_not_near_tie"]


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Eight shares of one layer (an expert a chip of 8 at this size), the
    same weights: what every chip computes alike (the attention's output,
    the stream) counted once, the shares' expert parts add up to what the
    uncut reference gives for the whole layer. The router's and the rows'
    choices are the uncut reference's own, handed to every share as the
    program's are."""
    cfg = dict(SMALL, num_hidden_layers=1, held_experts=[0, 8], num_experts=8)
    feed, r = _feed(), _rng(21)
    E, d, f = 8, cfg["hidden_size"], cfg["moe_intermediate_size"]
    main, startup, *_ = _build(cfg=cfg)
    pt.Executor().run(startup)
    _, whole = _moved_weights(main)
    hidden = lambda c, w: np.asarray(jax.jit(  # noqa: E731
        lambda w: ref.hidden(c, w, feed))(w))
    h_whole = hidden(cfg, whole)
    # the stream in front of the experts: the layer without any expert
    none = [np.zeros_like(w) if w.shape[-2:] in ((d, f), (f, d))
            and w.ndim == 3 else w for w in whole]
    h_attn = hidden(cfg, none)
    total = np.zeros_like(h_whole)
    for e in range(E):
        share_cfg = dict(cfg, held_experts=[e, e + 1], num_experts=1)
        share = [w[e:e + 1] if w.ndim == 3 else w for w in whole]
        total += hidden(share_cfg, share) - h_attn
    assert _rel(h_attn + total, h_whole) < 1e-5
    # and the PROGRAM's share is the reference's share
    main, startup, logits, *_ = _build(cfg=cfg, held=(2, 4))
    exe = pt.Executor()
    exe.run(startup)
    scope = pt.global_scope()
    for p, w in zip(main.parameters(), whole):
        scope.set(p.name, jnp.asarray(w[2:4] if w.ndim == 3 else w))
    got_cost, = exe.run(main, feed=feed, fetch_list=[
        main.global_block().var([op for op in main.global_block().ops
                                 if op.type == "mean"][-1].outputs["Out"][0])])
    want_cost, _ = ref.loss_and_grads(
        dict(cfg, held_experts=[2, 4], num_experts=2),
        [w[2:4] if w.ndim == 3 else w for w in whole], feed)
    assert abs(float(got_cost) - float(want_cost)) < 2e-4 * float(want_cost)


def _count(cfg, layers, experts, vocab, frozen=True):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    KV, D = cfg["num_key_value_heads"], cfg["head_dim"]
    sa = cfg["sa_config"]
    indexer = d * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                   + sa["indexer_head_dim"] + sa["indexer_num_heads"])
    layer = (experts * 3 * d * cfg["moe_intermediate_size"]
             + d * (H * D + 2 * KV * D) + H * D * d + 2 * D
             + d * cfg["router_experts"] + 2 * d)
    return layers * (layer + (indexer if frozen else 0)) + 2 * vocab * d + d


def test_parameter_counts_by_shapes():
    """30 640 650 240 whole, 465 390 592 held here (456 346 624 trained): by
    the shapes the PROGRAM declares at the published sizes (nothing is
    allocated), against the arithmetic."""
    import json

    with open(os.path.join(ROOT, "chipbench", "configs",
                           "keye-vl-2.0-30b-a3b", "config.json")) as f:
        config = json.load(f)
    assert _count(config, 48, 128, 151936) == 30_640_650_240
    assert _count(config, 48, 128, 151936, frozen=False) == 30_532_122_624
    assert _count(config, 4, 16, 18992) == 465_390_592
    model = _load(os.path.join(ROOT, "chipbench", "configs",
                               "keye-vl-2.0-30b-a3b", "model.py"),
                  "keye_benchmark_model")
    pt.reset()
    model.get_model(config, {"batch": 1, "seqlen": 16384, "image_spans": 4,
                             "image_grid": 32}, 7)
    params = pt.default_main_program().parameters()
    held = sum(int(np.prod(p.shape)) for p in params)
    trained = sum(int(np.prod(p.shape)) for p in params if p.trainable)
    assert (held, trained) == (465_390_592, 456_346_624)


def test_image_span_positions_follow_the_rule_by_hand():
    pos = keye_config.span_positions(20, [2, 9], 2)
    assert pos.tolist() == [
        [0, 1, 2, 2, 2, 2, 4, 5, 6, 7, 7, 7, 7, 9, 10, 11, 12, 13, 14, 15],
        [0, 1, 2, 2, 3, 3, 4, 5, 6, 7, 7, 8, 8, 9, 10, 11, 12, 13, 14, 15],
        [0, 1, 2, 3, 2, 3, 4, 5, 6, 7, 8, 7, 8, 9, 10, 11, 12, 13, 14, 15]]
    starts = keye_config.span_starts(_rng(1), 16384, 4, 32)
    assert len(starts) == 4 and all(
        b - a >= 1024 for a, b in zip(starts, starts[1:]))
    pos = keye_config.span_positions(16384, starts, 32)
    s = starts[0]
    assert (pos[:, :s] == np.arange(s)).all()              # text: equal axes
    assert (pos[0, s:s + 1024] == s).all()
    assert pos[1, s + 33] == s + 1 and pos[2, s + 33] == s + 1
    assert (pos[:, s + 1024] == s + 32).all()               # the next text
    assert ((pos[1] != pos[2]).sum(), (pos[0] != pos[1]).sum()) == (
        4 * (1024 - 32), 4 * (1024 - 32))
    with pytest.raises(ValueError, match="apart"):
        keye_config.span_positions(20, [2, 4], 2)
    # the benchmark's copy is this one
    model = _load(os.path.join(ROOT, "chipbench", "configs",
                               "keye-vl-2.0-30b-a3b", "model.py"),
                  "keye_benchmark_model_positions")
    assert (model.span_positions(16384, starts, 32) == pos).all()


def test_config_trains_through_trainer():
    from paddle_tpu.trainer import Trainer

    pt.reset()
    model = keye_config.get_model(
        layers=2, published_layers=4, dim=64, heads=4, kv_heads=2,
        head_dim=16, index_heads=2, index_head_dim=8, topk=16, experts=8,
        held_experts=(0, 4), experts_per_token=2, expert_dim=32,
        mrope_section=(2, 3, 3), seqlen=96, vocab=256, batch=2,
        image_spans=4, image_grid=4, steps=12, amp="bfloat16")
    costs = []
    Trainer(model["cost"]).train(
        model["reader"], num_passes=1, event_handler=lambda e: costs.append(
            float(e.cost)) if hasattr(e, "cost") and e.cost is not None
        else None)
    assert len(costs) >= 2 and np.isfinite(costs).all()
    assert min(costs[-3:]) < costs[0]
    from paddle_tpu.obs import metrics

    text = metrics.registry().render()
    assert 'pt_sparse_attention_dispatch_total{path="plain"}' in text
    assert 'pt_rotary_fed_positions_total{axes="3"}' in text
    assert "pt_sparse_attention_saved_choice_bytes" in text
