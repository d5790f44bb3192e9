"""Attention decoder / beam-search layer DSL.

Reference: the v2 book's `simple_attention` + `recurrent_group` decoder
(trainer_config_helpers/networks.py) driven by RecurrentGradientMachine
(gserver/gradientmachines/RecurrentGradientMachine.h:307,309), and Fluid's
beam_search / beam_search_decode ops. Training and generation share
parameters by NAME (pass the same `name` to both) — the scope keeps the
trained values, generation programs pick them up like the reference's
generation config reusing the trained model.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..initializer import XavierInitializer
from ..param_attr import ParamAttr
from .helper import LayerHelper

__all__ = ["attention_gru_decoder", "attention_gru_beam_search",
           "multi_head_attention", "latent_attention",
           "differential_attention", "sparse_attention"]


def _in_front_of_kernel(helper, emit, var):
    """Marks the op that just made `var` (a norm or a rotary between a Q or K
    projection and the attention kernel) with what it emits: "kernel" for the
    LAST op in front of the kernel, whose only reader rounds it to the
    kernel's input dtype, so the op emits that; "float32" for a norm whose
    reader is a rotary. The ops' kernels (ops/nn_ops.py: `QK_EMIT_ATTR`) then
    keep no float32 array of the projection's shape. Set here, by the layer
    that knows the reader; a norm or a rotary a user builds has no mark and
    lowers as it always did."""
    from ..ops.nn_ops import QK_EMIT_ATTR

    op = helper.block.ops[-1]
    assert var.name in op.output_names(), (op, var)
    op.attrs[QK_EMIT_ATTR] = emit
    return var


def multi_head_attention(
    query,
    key=None,
    value=None,
    num_heads: int = 8,
    causal: bool = True,
    param_attr=None,
    bias_attr=None,
    name=None,
    qk_norm=False,
    rotary_theta: Optional[float] = None,
    rms_eps: float = 1e-5,
    num_kv_heads: Optional[int] = None,
    head_dim: Optional[int] = None,
    window: Optional[int] = None,
    out_gate: bool = False,
    positions=None,
    rope_sections=None,
):
    """Transformer multi-head attention over dense [B, T, E] inputs
    (self-attention when key/value are None). Beyond the 2017 reference's
    layer set — the modern long-context workhorse; compute routes through
    the flash-attention dispatcher (ops/flash_ops.py: fused O(T)-memory
    Pallas kernel on TPU, jnp reference elsewhere). Q/K/V/O projections
    are `fc` layers so AMP/sharding apply as everywhere else.

    qk_norm: RMSNorm (learned scale, `rms_eps`) on the Q and K projections
    before the rotary. True: over the WHOLE projection width, heads x D
    lanes at once, with a scale [heads x D] (OLMoE's form; NOT the per-head
    form most QK-normed models use). "head": over each head's D lanes on
    its own, one scale [D] shared by all query heads and one by all K/V
    heads (the per-head form: `afmoe`, and the Qwen3 / OLMo-2 lineage's).
    False: none. Anything else raises.
    rotary_theta: rotary position embedding of that base on Q and K, after
    the norm. None: no position signal (the causal prefix alone tells two
    occurrences of an id apart).
    num_kv_heads: fewer K/V heads than query heads (grouped-query
    attention): the K and V projections are [E, num_kv_heads x D] and query
    head j reads K/V head j // (num_heads / num_kv_heads). None or
    `num_heads`: the ops of plain multi-head attention, unchanged.
    head_dim: a head size D other than E / num_heads: Q is [E, num_heads x
    D] and the output projection [num_heads x D, E] (Nemotron-H: 32 x 128
    at E 2688). The kernel reads the K/V heads from the shapes, so the op
    carries no attribute for them.
    window: W > 0, causal self-attention only: position i attends to the W
    keys i - W < j <= i (itself among them), handed to the `flash_attention`
    op as its `window` attribute; a layer without one is a global layer.
    out_gate: a fifth projection W_g [E, num_heads x D] of the layer's INPUT
    (`query`); the kernels' output is multiplied by sigmoid(query W_g)
    before the output projection (`afmoe`'s gated attention): an `fc` with
    a sigmoid and an `elementwise_mul`.
    positions, rope_sections: the rotary's positions as fed data, int32 [B,
    A, T], and the frequency pairs an axis turns (`rotary_embedding`'s
    `positions` and `sections`; only with `rotary_theta`).
    All of these off by default, and then the ops appended are exactly
    those of a layer without them.
    param_attr may be a mapping {"wq" | "wk" | "wv" | "wg" | "wo": attr}
    (`ParamAttr.derive`). Parameters, in order: wq, wk, wv, the two norms'
    scales, wg, wo."""
    from .nn import elementwise_mul, fc, rms_norm, rotary_embedding

    is_cross = key is not None or value is not None
    if is_cross and causal:
        # a square start-aligned causal mask is meaningless when Tq != Tk;
        # silent acceptance would make encoder-decoder models quietly
        # ignore most of the source sequence
        raise ValueError(
            "causal=True is only valid for self-attention; pass "
            "causal=False for cross-attention"
        )
    if qk_norm not in (False, True, "head"):
        raise ValueError(f"qk_norm {qk_norm!r}: False, True (over the whole "
                         f"projection) or 'head' (over each head)")
    if window is not None and (window <= 0 or not causal):
        raise ValueError(f"window {window}: a positive number of keys, and "
                         f"only with causal=True")
    key = query if key is None else key
    value = query if value is None else value
    helper = LayerHelper("multi_head_attention", name=name)
    E = int(query.shape[-1])
    if head_dim is None and E % num_heads:
        raise ValueError(f"hidden dim {E} not divisible by {num_heads} heads")
    D = E // num_heads if head_dim is None else int(head_dim)
    kv_heads = num_heads if num_kv_heads is None else int(num_kv_heads)
    if num_heads % kv_heads:
        raise ValueError(f"{num_heads} query heads do not share {kv_heads} "
                         f"K/V heads evenly")
    E_q, E_kv = num_heads * D, kv_heads * D

    def _derive(attr, s):
        # distinct per-projection names; ParamAttr.derive prevents
        # wq/wk/wv/wo collapsing into ONE shared parameter
        return ParamAttr.derive(attr, helper.name, s)

    proj = lambda x, s, size: fc(x, size=size, num_flatten_dims=2,
                                 param_attr=_derive(param_attr, s),
                                 bias_attr=_derive(bias_attr, f"{s}_b"))
    q, k, v = (proj(query, "wq", E_q), proj(key, "wk", E_kv),
               proj(value, "wv", E_kv))
    if qk_norm:
        # the norms' scales start at one whatever initialiser the caller
        # gave the projections: only the derived name is taken over
        group = D if qk_norm == "head" else None
        emit = "float32" if rotary_theta else "kernel"
        q = _in_front_of_kernel(helper, emit, rms_norm(
            q, epsilon=rms_eps, name=f"{helper.name}.q_norm",
            param_attr=_derive(param_attr, "q_norm").name, group=group))
        k = _in_front_of_kernel(helper, emit, rms_norm(
            k, epsilon=rms_eps, name=f"{helper.name}.k_norm",
            param_attr=_derive(param_attr, "k_norm").name, group=group))
    if positions is not None and not rotary_theta:
        raise ValueError("positions are the rotary's: pass rotary_theta")
    if rotary_theta:
        fed = {} if positions is None else {
            "positions": positions, "sections": rope_sections}
        q = _in_front_of_kernel(helper, "kernel", rotary_embedding(
            q, num_heads, rotary_theta, **fed))
        k = _in_front_of_kernel(helper, "kernel", rotary_embedding(
            k, kv_heads, rotary_theta, **fed))
    out = helper.create_tmp_variable(query.dtype,
                                     tuple(query.shape[:-1]) + (E_q,))
    attrs = {"num_heads": num_heads, "causal": causal}
    if window:
        attrs["window"] = int(window)
    helper.append_op(
        type="flash_attention",
        inputs={"Q": [q], "K": [k], "V": [v]},
        outputs={"Out": [out]},
        attrs=attrs,
    )
    if out_gate:
        out = elementwise_mul(out, fc(
            query, size=E_q, num_flatten_dims=2, act="sigmoid",
            param_attr=_derive(param_attr, "wg"), bias_attr=False))
    return fc(out, size=E, num_flatten_dims=2,
              param_attr=_derive(param_attr, "wo"),
              bias_attr=_derive(bias_attr, "wo_b"))


def latent_attention(
    x,
    num_heads: int,
    q_rank: int,
    kv_rank: int,
    nope_dim: int,
    rope_dim: int,
    v_dim: int,
    rotary_theta: float = 10000.0,
    rms_eps: float = 1e-5,
    param_attr=None,
    name=None,
):
    """Causal multi-head LATENT self-attention (DeepSeek-V2's MLA, as
    `transformers`' `deepseek_v3` / `glm4_moe_lite` attention computes it in
    training) over a dense [B, T, E] input, in the EXPANDED form: keys and
    values are materialised per head and the packed flash kernels run at a
    head of `nope_dim + rope_dim` lanes. No biases.

        c_q  = rms(x Wq_a)                 Wq_a  [E, q_rank]
        q    = c_q Wq_b                    Wq_b  [q_rank, H x (nope + rope)]
        [c_kv | k_r] = x Wkv_a             Wkv_a [E, kv_rank + rope]
        [k_n | v]    = rms(c_kv) Wkv_b     Wkv_b [kv_rank, H x (nope + Dv)]
        q_r, k_r <- rotary(theta) over their `rope` lanes; k_r is ONE head,
        not normed, laid beside every head's k_n: k_h = [k_n_h | k_r]
        out  = attention(q, k, v) Wo       Wo    [H x Dv, E]

    scores q k^T / sqrt(nope + rope), causal. The kernels take one head
    size for Q, K and V, so `v_dim` has to equal `nope_dim + rope_dim` (256
    = 192 + 64 at GLM-4.7-Flash; a narrower V would be padded by a caller
    that needs it). Built from `fc`, `rms_norm`, `rotary_embedding(...,
    rotary_dim=)`, the op `latent_kv_expand` and the `flash_attention` op,
    so AMP and sharding apply as everywhere else; each part is an op of its
    own scope (`mul` x 5, `rms_norm` x 2, `rotary_embedding` x 2, `split`,
    `latent_kv_expand`, `flash_attention`). The absorbed decode form and a
    cache that stores the latent are not built (ROADMAP Queue 2 A4).
    param_attr may be a mapping {"wq_a" | "wq_b" | "wkv_a" | "wkv_b" | "wo":
    attr} (`ParamAttr.derive`). Parameters, in order: wq_a, q_norm, wq_b,
    wkv_a, kv_norm, wkv_b, wo."""
    from .nn import fc, rms_norm, rotary_embedding, split

    helper = LayerHelper("latent_attention", name=name)
    E = int(x.shape[-1])
    D = int(nope_dim) + int(rope_dim)
    if int(v_dim) != D:
        raise ValueError(f"v_dim {v_dim} must equal nope_dim + rope_dim = {D}:"
                         f" the attention kernels take one head size")

    def _derive(s):
        return ParamAttr.derive(param_attr, helper.name, s)

    def proj(inp, s, size):
        return fc(inp, size=size, num_flatten_dims=2, param_attr=_derive(s),
                  bias_attr=False)

    def norm(inp, s):
        # scales start at one whatever initialiser the caller gave the
        # projections: only the derived name is taken over
        return rms_norm(inp, epsilon=rms_eps, name=f"{helper.name}.{s}",
                        param_attr=_derive(s).name)

    q = proj(norm(proj(x, "wq_a", int(q_rank)), "q_norm"), "wq_b",
             num_heads * D)
    q = _in_front_of_kernel(helper, "kernel", rotary_embedding(
        q, num_heads, rotary_theta, rotary_dim=rope_dim))
    c_kv, k_rope = split(proj(x, "wkv_a", int(kv_rank) + int(rope_dim)),
                         [int(kv_rank), int(rope_dim)], dim=2)
    kv = proj(norm(c_kv, "kv_norm"), "wkv_b", num_heads * (int(nope_dim) + D))
    # `latent_kv_expand` lays k_rope beside every head in kv's dtype: the
    # kernel's
    k_rope = _in_front_of_kernel(helper, "kernel", rotary_embedding(
        k_rope, 1, rotary_theta))
    packed = tuple(x.shape[:-1]) + (num_heads * D,)    # Q, K, V and the output
    k = helper.create_tmp_variable(kv.dtype, packed)
    v = helper.create_tmp_variable(kv.dtype, packed)
    helper.append_op(
        type="latent_kv_expand",
        inputs={"KV": [kv], "KRope": [k_rope]},
        outputs={"K": [k], "V": [v]},
        attrs={"num_heads": num_heads, "nope_dim": int(nope_dim)},
    )
    out = helper.create_tmp_variable(x.dtype, packed)
    helper.append_op(
        type="flash_attention",
        inputs={"Q": [q], "K": [k], "V": [v]},
        outputs={"Out": [out]},
        attrs={"num_heads": num_heads, "causal": True},
    )
    return proj(out, "wo", E)


def differential_attention(
    query,
    num_heads: int,
    num_kv_heads: int,
    depth: int,
    window: Optional[int] = None,
    shared_kv=None,
    return_kv: bool = False,
    rms_eps: float = 1e-5,
    lam_std: float = 0.1,
    param_attr=None,
    name=None,
):
    """Causal DIFFERENTIAL attention (Ye et al. 2024) over a dense [B, T, E]
    input, heads of D = E / num_heads: the heads form PAIRS (2p, 2p + 1), a
    query pair p reads K/V pair p // (num_heads / num_kv_heads), and a pair
    is two softmaxes over one value twice a head wide:

        [q | k | v] = u W_qkv + b     W_qkv [E, (num_heads + 2 num_kv_heads) D]
        A_1 = softmax(q_1 k_1^T / sqrt(D)),  A_2 = softmax(q_2 k_2^T / sqrt(D))
        o   = (A_1 - lam A_2) [v_1 | v_2]                        (2 D lanes)
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init
        lam_init = 0.8 - 0.6 exp(-0.3 depth)
        o  <- rms_{2D}(o, w) (1 - lam_init);      out = o W_o + b_o

    under the causal mask, and under `window` W also j > i - W. `depth`: the
    layer's index in its model (it sets lam_init). lq1, lk1, lq2, lk2 [D]
    are learned, N(0, `lam_std`) at the start (0.1 as published), shared by
    the layer's pairs; w [2 D] starts at one.
    shared_kv=(k, v): the layer projects q alone (W_q [E, E] with its bias)
    and reads these keys and values, another layer's [B, T, num_kv_heads D]
    (cross attention inside one sequence, so Tq == Tk and the mask stays
    causal; anything else raises). return_kv: returns (out, (k, v)), this
    layer's keys and values after their bias, for such readers.
    ONE `flash_attention` op a layer, in its pair form (attr `head_pairs`):
    heads 2p and 2p + 1 are neighbours in the packed projections, so at heads
    of 64 lane block p of q is `[q_1 | q_2]`, of k `[k_1 | k_2]` and of v the
    pair's value `[v_1 | v_2]`, whole, and the kernels keep each softmax's P V
    over the block's 128 lanes as that head's output (two outputs, each
    softmax computed once; K/V pair g read by its group's query pairs through
    the index map, nothing repeated or split in HBM). Eligibility, the window
    bound, the dispatch counters and the mesh rule are every attention
    layer's; `diff_combine` behind the launch takes the two outputs. The ops'
    scopes carry `<name>.qkv`, `<name>.kernels`, `<name>.combine`,
    `<name>.out_proj`. param_attr may be a mapping {"wqkv" | "wq" | "wo":
    attr}. Parameters, in order: wqkv (or wq) and its bias, lq1, lk1, lq2,
    lk2, the norm's scale, wo and its bias."""
    import math

    from ..initializer import ConstantInitializer, NormalInitializer
    from .nn import fc, split

    helper = LayerHelper("differential_attention", name=name)
    E = int(query.shape[-1])
    if E % num_heads or num_heads % 2 or num_kv_heads % 2:
        raise ValueError(f"{num_heads} query and {num_kv_heads} K/V heads "
                         f"over {E} lanes do not form pairs")
    if num_heads % num_kv_heads:
        raise ValueError(f"{num_heads} query heads do not share "
                         f"{num_kv_heads} K/V heads evenly")
    if window is not None and window <= 0:
        raise ValueError(f"window {window}: a positive number of keys")
    D = E // num_heads
    E_kv = num_kv_heads * D
    n = helper.name

    def _derive(s):
        return ParamAttr.derive(param_attr, n, s)

    if shared_kv is None:
        qkv = fc(query, size=E + 2 * E_kv, num_flatten_dims=2,
                 param_attr=_derive("wqkv"), bias_attr=_derive("wqkv_b"),
                 name=f"{n}.qkv")
        q, k, v = split(qkv, [E, E_kv, E_kv], dim=2)
    else:
        k, v = shared_kv
        if (tuple(k.shape) != tuple(v.shape) or int(k.shape[-1]) != E_kv
                or tuple(k.shape[:-1]) != tuple(query.shape[:-1])):
            raise ValueError(
                f"shared_kv {tuple(k.shape)}, {tuple(v.shape)}: another "
                f"layer's keys and values over the SAME sequence, "
                f"{tuple(query.shape[:-1]) + (E_kv,)}")
        q = fc(query, size=E, num_flatten_dims=2, param_attr=_derive("wq"),
               bias_attr=_derive("wq_b"), name=f"{n}.qkv")
    # ONE launch: a pair's heads are neighbours in the packed projections,
    # so lane block p of q is [q_1 | q_2], of k [k_1 | k_2] and of v the pair's
    # value, whole; the op's two outputs are A_1 [v_1 | v_2], A_2 [v_1 | v_2]
    attrs = {"num_heads": num_heads, "causal": True, "head_pairs": True}
    if window:
        attrs["window"] = int(window)
    kernels = LayerHelper("flash_attention", name=f"{n}.kernels")
    launched = [kernels.create_tmp_variable(query.dtype, tuple(q.shape))
                for _ in range(2)]
    kernels.append_op(type="flash_attention",
                      inputs={"Q": [q], "K": [k], "V": [v]},
                      outputs={"Out": [launched[0]], "Out2": [launched[1]]},
                      attrs=attrs)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * depth)
    vectors = [helper.create_parameter(
        _derive(s), (D,), default_initializer=NormalInitializer(0.0, lam_std))
        for s in ("lq1", "lk1", "lq2", "lk2")]
    norm_w = helper.create_parameter(
        _derive("subln").name, (2 * D,),
        default_initializer=ConstantInitializer(1.0))
    combine = LayerHelper("diff_combine", name=f"{n}.combine")
    out = combine.create_tmp_variable(query.dtype, tuple(query.shape))
    slots = ("First", "Second", "LamQ1", "LamK1", "LamQ2", "LamK2")
    combine.append_op(
        type="diff_combine",
        inputs={**{s: [x] for s, x in zip(slots, launched + vectors)},
                "NormW": [norm_w]},
        outputs={"Out": [out]},
        attrs={"head_dim": D, "lam_init": lam_init, "epsilon": rms_eps,
               "launches": 1})
    out = fc(out, size=E, num_flatten_dims=2, param_attr=_derive("wo"),
             bias_attr=_derive("wo_b"), name=f"{n}.out_proj")
    return (out, (k, v)) if return_kv else out


def sparse_attention(
    query,
    positions=None,
    num_heads: int = 32,
    num_kv_heads: int = 4,
    head_dim: int = 128,
    index_heads: int = 16,
    index_head_dim: int = 64,
    topk: int = 2048,
    rope_sections=None,
    rotary_theta: float = 1e7,
    rms_eps: float = 1e-6,
    param_attr=None,
    name=None,
):
    """Causal LEARNED SPARSE self-attention (the DeepSeek-Sparse-Attention
    form) over a dense [B, T, E] input: grouped-query attention with a
    per-head QK-norm and a rotary, in which a row attends only the `topk`
    keys an INDEXER scores highest for it. No biases.

        q = x W_q [T, H, D];  k = x W_k, v = x W_v [T, KV, D]
        q <- rms_D(q, g_q), k <- rms_D(k, g_k): per head, one scale [D] each
        q, k <- rotary(theta) at the token's position: `positions` int32
            [B, A, T] as fed data with `rope_sections` (a three-axis rotary:
            `rotary_embedding`), or 0..T-1 without
        q^I = x W^I_q [T, Hi, Di];  k^I = x W^I_k [T, Di];  w = x W^I_w [T, Hi]
        I(t, s) = sum_j w[t, j] relu(q^I[t, j] . k^I[s])         s <= t
        S_t = the min(topk, t + 1) keys s <= t of largest I(t, s), the lower
              index among equals
        o_j[t] = softmax over s in S_t of (q_j[t] . k_{j // g}[s] / sqrt(D))
                 applied to v_{j // g}[s];   out = concat_j(o_j) W_o

    The indexer reads the layer's input x as it comes (no norm and no
    position signal of its own). The sets are discrete and I enters the
    output nowhere else, so the indexer's three matrices get NO gradient
    under a cost of the output: they are `trainable=False` (no optimizer
    state), and a job that trains through this layer continues training with
    the indexer frozen (the alignment cost by which the published recipe
    trains it is not built: ROADMAP Queue 2 A).
    Projections are `fc` ops; the norm and rotary are the launch every
    attention layer has (`qk_assemble`); then `sparse_keep` (the scores in
    tiles, the selection, written as one bit a (row, key); its second output
    `Chosen` int32 [B x T, topk], a row's kept keys by index and -1 behind
    them, costs a program that does not fetch it nothing) and
    `sparse_attention` (the packed flash kernels under that keep operand on
    the chip, the plain form elsewhere: ops/sparse_attention_ops.py). The
    ops' scopes carry `<name>.qkv`, `<name>.q_norm`, `.k_norm`, `.q_rope`,
    `.k_rope`, `<name>.indexer` (its three projections; the scores are the
    inner scope `indexer` of `sparse_keep`), `<name>.select`, `<name>.kernels`,
    `<name>.out_proj`. param_attr may be a mapping {"wq" | "wk" | "wv" |
    "wo" | "index_wq" | "index_wk" | "index_ww": attr}. Parameters, in order:
    wq, wk, wv, the two norms' scales, index_wq, index_wk, index_ww, wo."""
    from ..ops.sparse_attention_ops import keep_lanes
    from .nn import fc, rms_norm, rotary_embedding

    helper = LayerHelper("sparse_attention", name=name)
    n = helper.name
    E = int(query.shape[-1])
    H, KV, D = int(num_heads), int(num_kv_heads), int(head_dim)
    Hi, Di, topk = int(index_heads), int(index_head_dim), int(topk)
    if H % KV:
        raise ValueError(f"{H} query heads do not share {KV} K/V heads "
                         f"evenly")
    if topk <= 0:
        raise ValueError(f"topk {topk}: a positive number of keys")
    B, T = int(query.shape[0]), int(query.shape[1])

    def _derive(s, trainable=True):
        attr = ParamAttr.derive(param_attr, n, s)
        if not trainable:
            attr.trainable = False
        return attr

    def proj(s, size, scope, trainable=True):
        return fc(query, size=size, num_flatten_dims=2, bias_attr=False,
                  param_attr=_derive(s, trainable), name=f"{n}.{scope}")

    q, k, v = proj("wq", H * D, "qkv"), proj("wk", KV * D, "qkv"), \
        proj("wv", KV * D, "qkv")
    fed = {} if positions is None else {"positions": positions,
                                        "sections": rope_sections}
    turned = []
    for x, heads, s in ((q, H, "q_norm"), (k, KV, "k_norm")):
        x = _in_front_of_kernel(helper, "float32", rms_norm(
            x, epsilon=rms_eps, name=f"{n}.{s}",
            param_attr=_derive(s).name, group=D))
        turned.append(_in_front_of_kernel(helper, "kernel", rotary_embedding(
            x, heads, rotary_theta, name=f"{n}.{s[0]}_rope", **fed)))
    q, k = turned
    # the sets are discrete: the indexer gets no gradient, and is frozen
    index = [proj(s, size, "indexer", trainable=False) for s, size in (
        ("index_wq", Hi * Di), ("index_wk", Di), ("index_ww", Hi))]
    select = LayerHelper("sparse_keep", name=f"{n}.select")
    keep = select.create_tmp_variable(np.int32, (B, T, keep_lanes(T)))
    chosen = select.create_tmp_variable(
        np.int32, (B * T if B >= 0 else -1, topk))
    select.append_op(
        type="sparse_keep",
        inputs=dict(zip(("IndexQ", "IndexK", "IndexW"),
                        ([x] for x in index))),
        outputs={"Keep": [keep], "Chosen": [chosen]},
        attrs={"index_heads": Hi, "topk": topk})
    kernels = LayerHelper("sparse_attention", name=f"{n}.kernels")
    out = kernels.create_tmp_variable(query.dtype, (B, T, H * D))
    kernels.append_op(
        type="sparse_attention",
        inputs={"Q": [q], "K": [k], "V": [v], "Keep": [keep]},
        outputs={"Out": [out]},
        attrs={"num_heads": H, "topk": topk})
    return fc(out, size=E, num_flatten_dims=2, bias_attr=False,
              param_attr=_derive("wo"), name=f"{n}.out_proj")


def _decoder_params(helper, ctx_dim, emb_dim, hidden, att_size):
    """Create (or re-bind by name) the shared decoder parameter set."""
    n = helper.name
    xav = XavierInitializer()
    p = lambda suffix, shape: helper.create_parameter(
        ParamAttr(name=f"{n}.{suffix}"), shape, default_initializer=xav
    )
    return {
        "WaEnc": p("wa_enc", (ctx_dim, att_size)),
        "WaDec": p("wa_dec", (hidden, att_size)),
        "Va": p("va", (att_size,)),
        "Wx": p("wx", (emb_dim + ctx_dim, 3 * hidden)),
        "Wh": p("wh", (hidden, 3 * hidden)),
        "Bias": helper.create_parameter(
            ParamAttr(name=f"{n}.b"), (3 * hidden,), is_bias=True
        ),
    }


def attention_gru_decoder(
    enc_state,
    trg_emb,
    boot_state,
    size: int,
    att_size: Optional[int] = None,
    src_max_len: Optional[int] = None,
    trg_max_len: Optional[int] = None,
    name=None,
):
    """Teacher-forced attention GRU decoder returning per-target-token

    hidden states (lod aligned with trg_emb). `size` = decoder hidden H;
    enc_state is the [.., C] encoder LoD output; boot_state [B, H]."""
    helper = LayerHelper("att_gru_decoder", name=name)
    ctx_dim = int(enc_state.shape[-1])
    emb_dim = int(trg_emb.shape[-1])
    att_size = att_size or size
    params = _decoder_params(helper, ctx_dim, emb_dim, size, att_size)
    out = helper.create_tmp_variable(trg_emb.dtype, (-1, size), lod_level=1)
    helper.append_op(
        type="attention_gru_decoder",
        inputs={
            "EncState": [enc_state],
            "TrgEmb": [trg_emb],
            "H0": [boot_state],
            **{k: [v] for k, v in params.items()},
        },
        outputs={"Hidden": [out]},
        attrs={"src_max_len": src_max_len, "trg_max_len": trg_max_len},
    )
    return out


def attention_gru_beam_search(
    enc_state,
    boot_state,
    embedding_param,
    out_w_param,
    out_b_param,
    size: int,
    att_size: Optional[int] = None,
    beam_size: int = 4,
    max_len: int = 32,
    bos_id: int = 0,
    eos_id: int = 1,
    src_max_len: Optional[int] = None,
    length_normalize: bool = False,
    name=None,
):
    """Beam-search generation with the decoder named `name` (share with the

    training-time attention_gru_decoder). embedding_param / out_w_param /
    out_b_param are the target embedding table [V, E] and output projection
    [H, V], [V] — pass the Variables (or names) used at training time.
    Returns (ids [B,K,T] int32, scores [B,K], lengths [B,K] int32)."""
    helper = LayerHelper("att_gru_decoder", name=name)
    ctx_dim = int(enc_state.shape[-1])
    gb = helper.main_program.global_block()

    def as_var(v):
        """Bind a trained parameter by name: from this program if declared,
        else re-declare it with the shape found in the global scope (the
        fresh-generation-program case)."""
        if not isinstance(v, str):
            return v
        if gb.has_var(v):
            return gb.var(v)
        from ..core.executor import global_scope

        scope = global_scope()
        if scope.has(v):
            val = scope.get(v)
            return helper.create_parameter(
                ParamAttr(name=v), tuple(val.shape), dtype=np.dtype(str(val.dtype))
            )
        raise KeyError(
            f"parameter {v!r} is neither declared in this program nor "
            f"present in the global scope — train it first or pass a Variable"
        )

    emb_v, w_out, b_out = map(as_var, (embedding_param, out_w_param, out_b_param))
    emb_dim = int(emb_v.shape[-1])
    att_size = att_size or size
    params = _decoder_params(helper, ctx_dim, emb_dim, size, att_size)
    ids = helper.create_tmp_variable(np.int32, (-1, beam_size, max_len))
    scores = helper.create_tmp_variable(enc_state.dtype, (-1, beam_size))
    lengths = helper.create_tmp_variable(np.int32, (-1, beam_size))
    helper.append_op(
        type="attention_gru_beam_search",
        inputs={
            "EncState": [enc_state],
            "H0": [boot_state],
            "Embedding": [emb_v],
            "WOut": [w_out],
            "BOut": [b_out],
            **{k: [v] for k, v in params.items()},
        },
        outputs={"Ids": [ids], "Scores": [scores], "Lengths": [lengths]},
        attrs={
            "beam_size": beam_size,
            "max_len": max_len,
            "bos_id": bos_id,
            "eos_id": eos_id,
            "src_max_len": src_max_len,
            "length_normalize": length_normalize,
        },
    )
    return ids, scores, lengths
