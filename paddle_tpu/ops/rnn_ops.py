"""Recurrent op kernels: LSTM / GRU / simple RNN over ragged batches.

Reference: paddle/operators/lstm_op.cc + operators/math/lstm_compute (the
fused cell math), cuda/include/hl_gpu_lstm.cuh / hl_lstm.h:42
(hl_lstm_parallel_forward — the hand-fused per-timestep CUDA kernels), and
Gen-1 gserver/layers/LstmLayer.cpp / GatedRecurrentLayer.cpp.

TPU design: the reference reorders ragged sequences into per-timestep
dense batches (sequence2batch) and launches one fused kernel per step.
Here the same layout transform happens once (LoDArray.to_batch), then a
single `lax.scan` carries (h, c) across timesteps — XLA fuses the gate
matmul + elementwise into one MXU-friendly loop body, which is exactly
what hl_lstm_parallel_forward hand-wrote. Padding steps are masked so the
carry freezes past each sequence's end (no-padding semantics preserved).

Gate layout in the packed 4H weight/bias: [i, f, g(candidate), o]; GRU
packed 3H: [u(update), r(reset), c(candidate)].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.lod import LoDArray
from ..core.registry import register_op
from ..flags import FLAGS
from . import mesh_dispatch, pallas_kernels
from .activation_ops import _ACTIVATIONS


def _act(name):
    if name == "identity" or name is None:
        return lambda v: v
    fn = _ACTIVATIONS[name]
    return lambda v: fn(v, {})


def lstm_scan(
    x_tbh,  # [T, B, 4H] projected input
    mask,  # [T, B]
    w_rec,  # [H, 4H]
    bias,  # [4H] or None
    w_peephole=None,  # [3H] (Wic, Wfc, Woc) or None
    h0=None,
    c0=None,
    gate_act="sigmoid",
    cell_act="tanh",
    cand_act="tanh",
    reverse=False,
):
    """Core masked LSTM scan. Returns (h_seq [T,B,H], (h_T, c_T))."""
    T, B, H4 = x_tbh.shape
    H = H4 // 4
    ga, ca, da = _act(gate_act), _act(cell_act), _act(cand_act)
    # uniform compute dtype: under amp the projected input arrives bf16
    # while weights/bias/boot-state are f32 masters — cast them down so
    # the scan carry dtype is stable (bf16 keeps the recurrence HBM-light;
    # the recurrent matmul still accumulates f32 on the MXU below)
    dt = x_tbh.dtype
    w_rec = w_rec.astype(dt)
    bias = None if bias is None else bias.astype(dt)
    w_peephole = None if w_peephole is None else w_peephole.astype(dt)
    h0 = jnp.zeros((B, H), dt) if h0 is None else h0.astype(dt)
    c0 = jnp.zeros((B, H), dt) if c0 is None else c0.astype(dt)
    if reverse:
        x_tbh = x_tbh[::-1]
        mask = mask[::-1]
    if w_peephole is not None:
        w_ic, w_fc, w_oc = jnp.split(w_peephole, 3)
    else:
        w_ic = w_fc = w_oc = None

    def step(carry, inp):
        h_prev, c_prev = carry
        x_t, m_t = inp
        gates = x_t + jnp.dot(
            h_prev, w_rec, preferred_element_type=jnp.float32
        ).astype(x_t.dtype)
        if bias is not None:
            gates = gates + bias
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        if w_ic is not None:
            i = i + c_prev * w_ic
            f = f + c_prev * w_fc
        i, f = ga(i), ga(f)
        c = f * c_prev + i * da(g)
        if w_oc is not None:
            o = o + c * w_oc
        o = ga(o)
        h = o * ca(c)
        m = m_t[:, None].astype(x_t.dtype)
        h = m * h + (1 - m) * h_prev
        c = m * c + (1 - m) * c_prev
        return (h, c), h

    (h_T, c_T), h_seq = jax.lax.scan(step, (h0, c0), (x_tbh, mask))
    if reverse:
        h_seq = h_seq[::-1]
    return h_seq, (h_T, c_T)


def gru_cell(xp, h_prev, w_rec, ga, da):
    """One GRU step on a pre-projected (and biased) input xp [..., 3H].

    w_rec packs [H, 2H] update/reset + [H, H] candidate as [H, 3H].
    Reference: operators/math/detail/gru_kernel.h:62 gru_finalOutput —
    h = (1-u)*h_prev + u*c. Shared by gru_scan and the attention decoder."""
    H = h_prev.shape[-1]
    w_ur, w_c = w_rec[:, : 2 * H], w_rec[:, 2 * H :]
    x_ur, x_c = xp[..., : 2 * H], xp[..., 2 * H :]
    ur = ga(
        x_ur
        + jnp.dot(h_prev, w_ur, preferred_element_type=jnp.float32).astype(xp.dtype)
    )
    u, r = ur[..., :H], ur[..., H:]
    c = da(
        x_c
        + jnp.dot(r * h_prev, w_c, preferred_element_type=jnp.float32).astype(
            xp.dtype
        )
    )
    return (1 - u) * h_prev + u * c


def gru_scan(
    x_tbh,  # [T, B, 3H]
    mask,  # [T, B]
    w_rec,  # [H, 2H] for update/reset + [H, H] candidate packed as [H, 3H]
    bias,  # [3H] or None
    h0=None,
    gate_act="sigmoid",
    cand_act="tanh",
    reverse=False,
):
    """Masked GRU scan (reference: operators/gru_op.cc, hl_gpu_gru.cuh)."""
    T, B, H3 = x_tbh.shape
    H = H3 // 3
    ga, da = _act(gate_act), _act(cand_act)
    dt = x_tbh.dtype  # uniform carry dtype under amp (see lstm_scan)
    w_rec = w_rec.astype(dt)
    bias = None if bias is None else bias.astype(dt)
    h0 = jnp.zeros((B, H), dt) if h0 is None else h0.astype(dt)
    if reverse:
        x_tbh = x_tbh[::-1]
        mask = mask[::-1]
    def step(h_prev, inp):
        x_t, m_t = inp
        if bias is not None:
            x_t = x_t + bias
        h = gru_cell(x_t, h_prev, w_rec, ga, da)
        m = m_t[:, None].astype(x_t.dtype)
        h = m * h + (1 - m) * h_prev
        return h, h

    h_T, h_seq = jax.lax.scan(step, h0, (x_tbh, mask))
    if reverse:
        h_seq = h_seq[::-1]
    return h_seq, h_T


def stacked_lstm2_scan(x_tbh, mask, w1, b1, wx2, w2, b2):
    """Two stacked LSTM layers in ONE masked scan: layer 2's input
    projection (h1 @ wx2) runs inside the step, so the sequential step
    count is T instead of 2T. Measured ≈1.2× on the recurrence at
    dispatch-floor-bound cells (exp_lstm_smallcell: old link, rounds
    <= 5, not re-measured on this chip; record in git history). Standard gates only (sigmoid/tanh,
    no peepholes, forward)."""
    T, B, H4 = x_tbh.shape
    H = H4 // 4
    dt = x_tbh.dtype
    w1, wx2, w2 = (w.astype(dt) for w in (w1, wx2, w2))
    b1 = None if b1 is None else b1.astype(dt)
    b2 = None if b2 is None else b2.astype(dt)
    z = jnp.zeros((B, H), dt)

    def cell(x_t, h_prev, c_prev, w, b, m):
        gates = x_t + jnp.dot(
            h_prev, w, preferred_element_type=jnp.float32).astype(dt)
        if b is not None:
            gates = gates + b
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = (jax.nn.sigmoid(v) for v in (i, f, o))
        c = f * c_prev + i * jnp.tanh(g)
        h = o * jnp.tanh(c)
        return m * h + (1 - m) * h_prev, m * c + (1 - m) * c_prev

    def step(carry, inp):
        h1, c1, h2, c2 = carry
        x_t, m_t = inp
        m = m_t[:, None].astype(dt)
        h1, c1 = cell(x_t, h1, c1, w1, b1, m)
        xp2 = jnp.dot(h1, wx2,
                      preferred_element_type=jnp.float32).astype(dt)
        h2, c2 = cell(xp2, h2, c2, w2, b2, m)
        return (h1, c1, h2, c2), h2

    (_, _, h2_T, c2_T), h2_seq = jax.lax.scan(
        step, (z, z, z, z), (x_tbh, mask))
    return h2_seq, (h2_T, c2_T)


@register_op("stacked_lstm2")
def stacked_lstm2_kernel(ctx):
    """Two stacked LSTM layers with the inter-layer projection absorbed
    (the hot structure of benchmark/paddle/rnn/rnn.py). Trace-time
    dispatch: where the per-layer fused Pallas kernel is eligible it
    wins more than layer-packing (each layer's whole sequence is one
    kernel), so the op runs two fused layers with a batched inter-layer
    matmul; otherwise the single stacked scan halves the sequential
    step count of the two-scan formulation."""
    x: LoDArray = ctx.input("Input")  # [*, 4H] pre-projected layer 1
    w1, wx2, w2 = (ctx.input(k) for k in ("Weight1", "WX2", "Weight2"))
    b1 = ctx.input("Bias1") if ctx.has_input("Bias1") else None
    b2 = ctx.input("Bias2") if ctx.has_input("Bias2") else None
    max_len = ctx.attr("max_len") or x.capacity
    x_tb, mask = x.to_batch(max_len=max_len)
    B, H = x_tb.shape[1], w1.shape[0]
    if FLAGS.use_fused_rnn and pallas_kernels.lstm_supported(
            mesh_dispatch.local_batch(B), H, "sigmoid", "tanh", "tanh",
            None, itemsize=x_tb.dtype.itemsize):
        h1_seq, _ = pallas_kernels.lstm_fused(x_tb, mask, w1, bias=b1)
        xp2 = jnp.dot(h1_seq, wx2.astype(h1_seq.dtype),
                      preferred_element_type=jnp.float32
                      ).astype(h1_seq.dtype)
        h2_seq, _ = pallas_kernels.lstm_fused(xp2, mask, w2, bias=b2)
    else:
        h2_seq, _ = stacked_lstm2_scan(x_tb, mask, w1, b1, wx2, w2, b2)
    ctx.set_output("Hidden", LoDArray.from_batch(h2_seq, mask, x))


@register_op("stacked_lstm")
def stacked_lstm_kernel(ctx):
    """N-layer book-structure stacked LSTM (reference: fluid book
    understand_sentiment stacked_lstm_net, stacked_num layers) as ONE
    op. Default formulation: layer by layer — each layer a fused Pallas
    kernel where eligible (else a masked scan), with the inter-layer
    concat-fc as a BATCHED matmul over the full [T, B, ·] sequence.

    Measured (exp_stacked_book: old link, rounds <= 5, not re-measured
    on this chip; record in git history): at the book's dispatch-bound
    hid=128 a single-scan formulation did not separate from the noise
    floor, and at hid=512 it was neutral (1.01x).
    The layer-by-layer form stands on the structural argument: the
    book's [4H, 4H] concat-fc runs as ONE [T*B, 4H] batched matmul per
    layer here, where a stacked_lstm2-style single scan would run it as
    T sequential [B, 4H] matmuls. (stacked_lstm2's pure stack won its
    trade 1.25-1.46x because its inter-layer op is the thin [H, 4H]
    projection.)

    Inputs: Input (layer-1 [*, 4H] projection, LoDArray), Weights (n of
    [H, 4H]), WAs (n-1 of [4H, 4H]: fc_prev half of the inter-layer
    fc), WBs (n-1 of [H, 4H]: lstm_prev half), Biases (n of [4H],
    optional), FcBiases (n-1 of [4H], optional).
    Outputs: FcOut and Hidden — the book pools both streams."""
    x: LoDArray = ctx.input("Input")
    ws = ctx.inputs("Weights")
    was = ctx.inputs("WAs")
    wbs = ctx.inputs("WBs")
    n = len(ws)
    bs = ctx.inputs("Biases") if ctx.has_input("Biases") else [None] * n
    fbs = (ctx.inputs("FcBiases") if ctx.has_input("FcBiases")
           else [None] * (n - 1))
    max_len = ctx.attr("max_len") or x.capacity
    x_tb, mask = x.to_batch(max_len=max_len)
    B, H = x_tb.shape[1], ws[0].shape[0]
    dt = x_tb.dtype
    fused = FLAGS.use_fused_rnn and pallas_kernels.lstm_supported(
        mesh_dispatch.local_batch(B), H, "sigmoid", "tanh", "tanh",
        None, itemsize=x_tb.dtype.itemsize)
    fc_seq = x_tb
    h_seq = None
    for i in range(n):
        if i > 0:
            fc_seq = (jnp.dot(fc_seq, was[i - 1].astype(dt),
                              preferred_element_type=jnp.float32)
                      + jnp.dot(h_seq, wbs[i - 1].astype(dt),
                                preferred_element_type=jnp.float32)
                      ).astype(dt)
            if fbs[i - 1] is not None:
                fc_seq = fc_seq + fbs[i - 1].astype(dt)
        if fused:
            h_seq, _ = pallas_kernels.lstm_fused(fc_seq, mask, ws[i],
                                                 bias=bs[i])
        else:
            h_seq, _ = lstm_scan(
                fc_seq, mask, ws[i].astype(dt),
                None if bs[i] is None else bs[i].astype(dt))
    ctx.set_output("FcOut", LoDArray.from_batch(fc_seq, mask, x))
    ctx.set_output("Hidden", LoDArray.from_batch(h_seq, mask, x))


@register_op("dynamic_lstm")
def dynamic_lstm_kernel(ctx):
    """Reference: paddle/operators/lstm_op.cc / fluid layers nn.py:227.

    Input is the pre-projected [*, 4H] LoDArray (the x @ W_x fc happens in
    the preceding layer, matching the reference API)."""
    x: LoDArray = ctx.input("Input")
    w = ctx.input("Weight")  # [H, 4H]
    b = ctx.input("Bias") if ctx.has_input("Bias") else None
    use_peep = ctx.attr("use_peepholes", False)
    peep = None
    if b is not None and use_peep:
        b, peep = b[: w.shape[1]], b[w.shape[1] :]
    max_len = ctx.attr("max_len") or x.capacity
    x_tb, mask = x.to_batch(max_len=max_len)
    gate_act = ctx.attr("gate_activation", "sigmoid")
    cell_act = ctx.attr("cell_activation", "tanh")
    cand_act = ctx.attr("candidate_activation", "tanh")
    reverse = ctx.attr("is_reverse", False)
    B, H = x_tb.shape[1], w.shape[0]
    if FLAGS.use_fused_rnn and pallas_kernels.lstm_supported(
        mesh_dispatch.local_batch(B), H, gate_act, cell_act, cand_act,
        peep, itemsize=x_tb.dtype.itemsize,
    ):
        h_seq, (h_T, c_T) = pallas_kernels.lstm_fused(
            x_tb, mask, w, bias=b, reverse=reverse
        )
    else:
        h_seq, (h_T, c_T) = lstm_scan(
            x_tb,
            mask,
            w,
            b,
            w_peephole=peep,
            gate_act=gate_act,
            cell_act=cell_act,
            cand_act=cand_act,
            reverse=reverse,
        )
    ctx.set_output("Hidden", LoDArray.from_batch(h_seq, mask, x))
    if ctx.has_output("LastH"):
        ctx.set_output("LastH", h_T)
    if ctx.has_output("LastC"):
        ctx.set_output("LastC", c_T)


@register_op("dynamic_gru")
def dynamic_gru_kernel(ctx):
    """Reference: paddle/operators/gru_op.cc / Gen-1 GatedRecurrentLayer."""
    x: LoDArray = ctx.input("Input")
    w = ctx.input("Weight")  # [H, 3H]
    b = ctx.input("Bias") if ctx.has_input("Bias") else None
    max_len = ctx.attr("max_len") or x.capacity
    x_tb, mask = x.to_batch(max_len=max_len)
    gate_act = ctx.attr("gate_activation", "sigmoid")
    cand_act = ctx.attr("candidate_activation", "tanh")
    reverse = ctx.attr("is_reverse", False)
    B, H = x_tb.shape[1], w.shape[0]
    if FLAGS.use_fused_rnn and pallas_kernels.gru_supported(
        mesh_dispatch.local_batch(B), H, gate_act, cand_act,
        itemsize=x_tb.dtype.itemsize
    ):
        h_seq, h_T = pallas_kernels.gru_fused(
            x_tb, mask, w, bias=b, reverse=reverse
        )
    else:
        h_seq, h_T = gru_scan(
            x_tb,
            mask,
            w,
            b,
            gate_act=gate_act,
            cand_act=cand_act,
            reverse=reverse,
        )
    ctx.set_output("Hidden", LoDArray.from_batch(h_seq, mask, x))
    if ctx.has_output("LastH"):
        ctx.set_output("LastH", h_T)


@register_op("simple_rnn")
def simple_rnn_kernel(ctx):
    """Gen-1 RecurrentLayer.cpp: h_t = act(x_t + h_{t-1} @ W)."""
    x: LoDArray = ctx.input("Input")
    w = ctx.input("Weight")  # [H, H]
    b = ctx.input("Bias") if ctx.has_input("Bias") else None
    act = _act(ctx.attr("activation", "tanh"))
    max_len = ctx.attr("max_len") or x.capacity
    x_tb, mask = x.to_batch(max_len=max_len)

    def step(h_prev, inp):
        x_t, m_t = inp
        h = x_t + jnp.dot(h_prev, w, preferred_element_type=jnp.float32).astype(
            x_t.dtype
        )
        if b is not None:
            h = h + b
        h = act(h)
        m = m_t[:, None].astype(x_t.dtype)
        h = m * h + (1 - m) * h_prev
        return h, h

    B, H = x_tb.shape[1], w.shape[0]
    h0 = jnp.zeros((B, H), x_tb.dtype)
    h_T, h_seq = jax.lax.scan(step, h0, (x_tb, mask))
    ctx.set_output("Hidden", LoDArray.from_batch(h_seq, mask, x))
    if ctx.has_output("LastH"):
        ctx.set_output("LastH", h_T)
