"""Fused Bahdanau attention decoder (Pallas) — the NMT hot path.

Reference philosophy: the reference's answer to a hot recurrent cell was a
hand-written fused kernel (cuda/include/hl_lstm.h:42, hl_gpu_gru.cuh);
its RecurrentGradientMachine ran the book's `simple_attention` decoder
(trainer_config_helpers/networks.py) frame by frame. Here the analogous
hot loop is the attention-GRU decoder scan: 51% of the NMT step
(nmt_breakdown: old link, rounds <= 5, not re-measured on this chip;
record in git history), dominated by materializing
`tanh(enc_proj + dec_proj)` [B, S, A] to HBM every timestep — ~6.6 MB
written + read per step forward, and the default scan VJP additionally
saves that tensor per step (~330 MB of residuals) and accumulates a
[B, S, A] enc_proj gradient through the reverse-scan carry (~26 MB of
traffic per step).

TPU design — three small Pallas kernels around one custom-VJP scan:

  fwd (per step, grid over batch tiles): score+softmax+context entirely
      in VMEM — tanh(ep+dp)·v, masked softmax over S, alpha-weighted
      context — never materializing [B, S, A]. Emits ctx and alpha
      (alpha is [B, S]: tiny; it is the only per-step residual beyond
      the h/ctx vectors).
  bwd step (per reverse step): recomputes the tanh tile-locally and
      produces d(dec_proj) and d(scores) — the two step-local gradients
      the sequential dh chain needs. d(enc_proj) is NOT accumulated here.
  bwd phase-2 (once, grid (batch tiles, T)): re-walks all steps with a
      VMEM accumulator to produce d(enc_proj), folding the dv reduction
      in — the [B, S, A]-sized gradient is written exactly once.

The GRU cell's backward is hand-derived batched XLA (gates recomputed
from the saved h/ctx sequences in batched MXU matmuls — same recipe as
the fused GRU kernel, pallas_kernels.py); only the dh carry is
sequential. enc_proj enters as a differentiated INPUT, so the enc-side
projection (enc @ WaEnc) and its gradients stay in ordinary XLA outside
the boundary.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# trace-time dispatch counters: which formulation each (re)trace of the
# fused decoder actually engaged. Tests reset this and assert the bench
# geometry takes the fused path — a silent fallback to the scan (e.g. a
# config off the eligibility grid) must fail loudly, not just run slow.
dispatch_stats = {"fused_calls": 0, "scan_fwd": 0, "scan_bwd": 0}


def reset_dispatch_stats():
    for k in dispatch_stats:
        dispatch_stats[k] = 0
    _decoder_fn.cache_clear()  # custom-VJP fns re-trace → counters fire


def _bblk(B: int, Sp: int, A: int, C: int, itemsize: int) -> int:
    """Batch tile shared by ALL the attention kernels (fwd, bwd-step,
    phase-2 use one eligibility so a config never runs fused forward and
    then fails to tile the backward), 0 where the fused decoder is off:
    tune/space.py's rule for the family (`bahdanau_default`, legality
    `bahdanau_blk_legal`), or what a sweep forced."""
    from ..tune import space

    if B <= 0:  # mesh-local batch that the dp axis does not divide
        return 0
    cfg = space.pick("bahdanau_attention",
                     {"B": B, "Sp": Sp, "A": A, "C": C},
                     space.ITEMSIZE_DTYPE[itemsize])
    return int(cfg["bblk"]) if cfg else 0


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _backend_ok() -> bool:
    from .pallas_kernels import backend_ok

    return backend_ok("fused_attention_interpret")


def _pad_s(s: int) -> int:
    from ..tune.space import pad_s  # one padding rule, shared with the sweep

    return pad_s(s)


def fused_decoder_eligible(B: int, S: int, A: int, C: int, dtype) -> bool:
    from ..flags import FLAGS

    if not FLAGS.use_fused_attention or not _backend_ok():
        return False
    sp = _pad_s(S)
    item = jnp.dtype(dtype).itemsize
    return (
        dtype in (jnp.bfloat16, jnp.float32)
        and A % 128 == 0
        and C % 128 == 0
        and _bblk(B, sp, A, C, item) > 0
    )


# ---------------------------------------------------------------- kernels --
def _attn_fwd_kernel(ep_ref, enc_ref, dp_ref, v_ref, mask_ref,
                     ctx_ref, alpha_ref):
    ep = ep_ref[:].astype(jnp.float32)          # [b, Sp, A]
    dp = dp_ref[:].astype(jnp.float32)          # [b, A]
    t = jnp.tanh(ep + dp[:, None, :])
    scores = jnp.sum(t * v_ref[0].astype(jnp.float32)[None, None, :], -1)
    scores = jnp.where(mask_ref[:] > 0, scores, -1e9)   # [b, Sp]
    m = jnp.max(scores, -1, keepdims=True)
    e = jnp.exp(scores - m)
    alpha = e / jnp.sum(e, -1, keepdims=True)
    alpha_ref[:] = alpha
    enc = enc_ref[:]                             # [b, Sp, C]
    ctx = jax.lax.dot_general(
        alpha[:, None, :].astype(enc.dtype), enc,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )                                            # [b, 1, C]
    ctx_ref[:] = ctx[:, 0, :].astype(ctx_ref.dtype)


def _attn_bwd_kernel(ep_ref, enc_ref, dp_ref, v_ref, mask_ref,
                     dctx_ref, alpha_ref, ddp_ref, dsc_ref):
    enc = enc_ref[:]                             # [b, Sp, C]
    dctx = dctx_ref[:]                           # [b, C]
    # dalpha[b,s] = sum_c dctx[b,c] * enc[b,s,c]
    dalpha = jax.lax.dot_general(
        dctx[:, None, :], enc, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )[:, 0, :]                                   # [b, Sp]
    alpha = alpha_ref[:]                         # [b, Sp] f32
    tot = jnp.sum(alpha * dalpha, -1, keepdims=True)
    dsc = alpha * (dalpha - tot)
    dsc = jnp.where(mask_ref[:] > 0, dsc, 0.0)
    dsc_ref[:] = dsc
    ep = ep_ref[:].astype(jnp.float32)
    dp = dp_ref[:].astype(jnp.float32)
    t = jnp.tanh(ep + dp[:, None, :])
    omt2 = (1.0 - t * t)                         # [b, Sp, A]
    # ddp[b,a] = sum_s dsc[b,s] * (1-t^2)[b,s,a] * v[a]
    ddp = jax.lax.dot_general(
        dsc[:, None, :].astype(omt2.dtype), omt2,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )[:, 0, :] * v_ref[0].astype(jnp.float32)[None, :]
    ddp_ref[:] = ddp.astype(ddp_ref.dtype)


def _attn_phase2_kernel(ep_ref, dp_ref, dsc_ref, v_ref,
                        dep_ref, dv_ref, dep_acc, dv_acc):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(jnp.logical_and(b == 0, t == 0))
    def _():
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(t == 0)
    def _():
        dep_acc[:] = jnp.zeros_like(dep_acc)

    ep = ep_ref[:].astype(jnp.float32)           # [b, Sp, A]
    dp = dp_ref[:].astype(jnp.float32)           # [1, b, A]
    th = jnp.tanh(ep + dp[0][:, None, :])
    dsc = dsc_ref[:][0]                          # [b, Sp] f32
    dep_t = dsc[:, :, None] * (1.0 - th * th) \
        * v_ref[0].astype(jnp.float32)[None, None, :]

    # accumulate in the f32 scratch (an io-dtype read-modify-write over
    # ~T steps loses low-order gradient bits under bf16 AMP); cast to
    # the io dtype exactly once on the final t
    dep_acc[:] = dep_acc[:] + dep_t
    # dv[a] += sum_{b,s} tanh[b,s,a] * dsc[b,s]
    dv_acc[:] = dv_acc[:] + jnp.sum(
        th * dsc[:, :, None], axis=(0, 1), keepdims=False
    )[None, :]

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        dep_ref[:] = dep_acc[:].astype(dep_ref.dtype)

    @pl.when(jnp.logical_and(b == pl.num_programs(0) - 1,
                             t == pl.num_programs(1) - 1))
    def _():
        dv_ref[:] = dv_acc[:]


# ------------------------------------------------------------ kernel calls --
def _attn_fwd(ep, enc, dp, v, maskf, interpret):
    B, Sp, A = ep.shape
    C = enc.shape[-1]
    blk = _bblk(B, Sp, A, C, ep.dtype.itemsize)
    nb = B // blk
    ctx, alpha = pl.pallas_call(
        _attn_fwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((blk, Sp, A), lambda b: (b, 0, 0)),
            pl.BlockSpec((blk, Sp, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((blk, A), lambda b: (b, 0)),
            pl.BlockSpec((1, A), lambda b: (0, 0)),
            pl.BlockSpec((blk, Sp), lambda b: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((blk, C), lambda b: (b, 0)),
            pl.BlockSpec((blk, Sp), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, C), enc.dtype),
            jax.ShapeDtypeStruct((B, Sp), jnp.float32),
        ],
        interpret=interpret,
    )(ep, enc, dp, v.reshape(1, -1), maskf)
    return ctx, alpha


def _attn_bwd_step(ep, enc, dp, v, maskf, dctx, alpha, interpret):
    B, Sp, A = ep.shape
    C = enc.shape[-1]
    blk = _bblk(B, Sp, A, C, ep.dtype.itemsize)
    nb = B // blk
    ddp, dsc = pl.pallas_call(
        _attn_bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((blk, Sp, A), lambda b: (b, 0, 0)),
            pl.BlockSpec((blk, Sp, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((blk, A), lambda b: (b, 0)),
            pl.BlockSpec((1, A), lambda b: (0, 0)),
            pl.BlockSpec((blk, Sp), lambda b: (b, 0)),
            pl.BlockSpec((blk, C), lambda b: (b, 0)),
            pl.BlockSpec((blk, Sp), lambda b: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((blk, A), lambda b: (b, 0)),
            pl.BlockSpec((blk, Sp), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, A), ep.dtype),
            jax.ShapeDtypeStruct((B, Sp), jnp.float32),
        ],
        interpret=interpret,
    )(ep, enc, dp, v.reshape(1, -1), maskf, dctx, alpha)
    return ddp, dsc


def _attn_phase2(ep, dp_seq, dsc_seq, v, C, interpret):
    B, Sp, A = ep.shape
    T = dp_seq.shape[0]
    # same blk as the fwd/bwd kernels (the shared _bblk cost model
    # covers phase-2's accumulator, so this cannot return 0 here)
    blk = _bblk(B, Sp, A, C, ep.dtype.itemsize)
    nb = B // blk
    dep, dv = pl.pallas_call(
        _attn_phase2_kernel,
        grid=(nb, T),
        in_specs=[
            pl.BlockSpec((blk, Sp, A), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, blk, A), lambda b, t: (t, b, 0)),
            pl.BlockSpec((1, blk, Sp), lambda b, t: (t, b, 0)),
            pl.BlockSpec((1, A), lambda b, t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((blk, Sp, A), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, A), lambda b, t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, A), ep.dtype),
            jax.ShapeDtypeStruct((1, A), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((blk, Sp, A), jnp.float32),
                        pltpu.VMEM((1, A), jnp.float32)],
        interpret=interpret,
    )(ep, dp_seq, dsc_seq, v.reshape(1, -1))
    return dep, dv[0]


# -------------------------------------------------- the decoder, custom VJP --
def _gru_fwd_step(xp, h_prev, wh, H):
    w_ur, w_c = wh[:, : 2 * H], wh[:, 2 * H:]
    ur = jax.nn.sigmoid(
        xp[..., : 2 * H]
        + jnp.dot(h_prev, w_ur).astype(xp.dtype))
    u, r = ur[..., :H], ur[..., H:]
    c = jnp.tanh(
        xp[..., 2 * H:]
        + jnp.dot(r * h_prev, w_c).astype(xp.dtype))
    return (1 - u) * h_prev + u * c


@functools.lru_cache(maxsize=None)
def _decoder_fn(interpret: bool):
    """custom-VJP'd teacher-forcing decoder over padded-S operands.

    (enc, ep, maskf [B,Sp], trg [T,B,E], tmask [T,B], h0,
     wa_dec [H,A], v [A], wx [(E+C),3H], wh [H,3H], bias [3H]) -> h_seq.

    Under a dp mesh the call runs inside shard_map (mesh_dispatch
    policy): operands are then per-shard and the weight cotangents are
    per-shard partial sums over the local batch, returned as they are —
    shard_map's transpose sums the cotangents of replicated inputs
    (see ops/pallas_kernels._lstm_core).
    """

    def forward(enc, ep, maskf, trg, tmask, h0, wa_dec, v, wx, wh, bias):
        H = h0.shape[-1]
        dispatch_stats["scan_fwd"] += 1

        def step(h_prev, inp):
            x_t, m_t = inp
            dp = jnp.dot(h_prev, wa_dec).astype(h_prev.dtype)
            ctx, alpha = _attn_fwd(ep, enc, dp, v, maskf, interpret)
            xin = jnp.concatenate([x_t, ctx.astype(x_t.dtype)], -1)
            xp = jnp.dot(xin, wx).astype(x_t.dtype) + bias
            h = _gru_fwd_step(xp, h_prev, wh, H)
            m = m_t[:, None].astype(h.dtype)
            h = m * h + (1 - m) * h_prev
            return h, (h, alpha, ctx)

        _, (h_seq, alpha_seq, ctx_seq) = jax.lax.scan(
            step, h0, (trg, tmask))
        return h_seq, alpha_seq, ctx_seq

    @jax.custom_vjp
    def f(enc, ep, maskf, trg, tmask, h0, wa_dec, v, wx, wh, bias):
        h_seq, _, _ = forward(enc, ep, maskf, trg, tmask, h0, wa_dec, v,
                              wx, wh, bias)
        return h_seq

    def fwd(enc, ep, maskf, trg, tmask, h0, wa_dec, v, wx, wh, bias):
        h_seq, alpha_seq, ctx_seq = forward(
            enc, ep, maskf, trg, tmask, h0, wa_dec, v, wx, wh, bias)
        res = (enc, ep, maskf, trg, tmask, h0, wa_dec, v, wx, wh, bias,
               h_seq, alpha_seq, ctx_seq)
        return h_seq, res

    def bwd(res, g_seq):
        (enc, ep, maskf, trg, tmask, h0, wa_dec, v, wx, wh, bias,
         h_seq, alpha_seq, ctx_seq) = res
        H = h_seq.shape[-1]
        E = trg.shape[-1]
        dt = h_seq.dtype
        g_seq = g_seq.astype(dt)
        # ---- batched recompute of every gate (MXU, no sequential dep) --
        hp_seq = jnp.concatenate([h0[None], h_seq[:-1]], 0)   # h_{t-1}
        dp_seq = jnp.dot(hp_seq, wa_dec).astype(dt)           # [T,B,A]
        xin_seq = jnp.concatenate([trg, ctx_seq.astype(dt)], -1)
        xp_seq = jnp.dot(xin_seq, wx).astype(dt) + bias
        w_ur, w_c = wh[:, : 2 * H], wh[:, 2 * H:]
        ur_seq = jax.nn.sigmoid(
            xp_seq[..., : 2 * H] + jnp.dot(hp_seq, w_ur).astype(dt))
        u_seq, r_seq = ur_seq[..., :H], ur_seq[..., H:]
        rh_seq = r_seq * hp_seq
        c_seq = jnp.tanh(
            xp_seq[..., 2 * H:] + jnp.dot(rh_seq, w_c).astype(dt))

        dispatch_stats["scan_bwd"] += 1

        def back_step(dh_carry, inp):
            g_t, m_t, hp, u, r, c, dp, alpha = inp
            dh = dh_carry + g_t
            m = m_t[:, None].astype(dt)
            dh_cell = dh * m
            dh_prev = dh * (1 - m)
            # GRU cell backward (h = (1-u) hp + u c)
            du = dh_cell * (c - hp)
            dc = dh_cell * u
            dh_prev = dh_prev + dh_cell * (1 - u)
            dpre_c = dc * (1 - c * c)
            drh = jnp.dot(dpre_c, w_c.T).astype(dt)
            dr = drh * hp
            dh_prev = dh_prev + drh * r
            dpre_u = du * u * (1 - u)
            dpre_r = dr * r * (1 - r)
            dur = jnp.concatenate([dpre_u, dpre_r], -1)
            dh_prev = dh_prev + jnp.dot(dur, w_ur.T).astype(dt)
            dxp = jnp.concatenate([dur, dpre_c], -1)      # [B,3H]
            dctx = jnp.dot(dxp, wx[E:].T).astype(dt)
            # attention backward, step-local outputs only
            ddp, dsc = _attn_bwd_step(ep, enc, dp, v, maskf, dctx,
                                      alpha, interpret)
            dh_prev = dh_prev + jnp.dot(ddp, wa_dec.T).astype(dt)
            return dh_prev, (dxp, dctx, dsc, ddp)

        dh0, (dxp_seq, dctx_seq, dsc_seq, ddp_seq) = jax.lax.scan(
            back_step,
            jnp.zeros_like(h0),
            (g_seq, tmask, hp_seq, u_seq, r_seq, c_seq, dp_seq,
             alpha_seq),
            reverse=True,
        )
        # the [B,Sp,A]-sized gradient, written exactly once
        dep, dv = _attn_phase2(ep, dp_seq, dsc_seq, v, enc.shape[-1],
                               interpret)
        # ---- dx + batched parameter grads ------------------------------
        dx_seq = jnp.einsum("tbg,eg->tbe", dxp_seq, wx[:E]).astype(dt)
        dwx = jnp.einsum("tbi,tbg->ig", xin_seq, dxp_seq)
        dbias = jnp.sum(dxp_seq, (0, 1))
        dw_ur = jnp.einsum("tbh,tbg->hg", hp_seq, dxp_seq[..., : 2 * H])
        dw_c = jnp.einsum("tbh,tbg->hg", rh_seq, dxp_seq[..., 2 * H:])
        dwh = jnp.concatenate([dw_ur, dw_c], -1)
        dwa_dec = jnp.einsum("tbh,tba->ha", hp_seq, ddp_seq)
        denc = jnp.einsum("tbs,tbc->bsc", alpha_seq.astype(dt),
                          dctx_seq).astype(enc.dtype)
        dv = dv.astype(jnp.float32)
        return (denc, dep, jnp.zeros_like(maskf), dx_seq,
                jnp.zeros_like(tmask), dh0, dwa_dec.astype(wa_dec.dtype),
                dv.astype(v.dtype), dwx.astype(wx.dtype),
                dwh.astype(wh.dtype), dbias)

    f.defvjp(fwd, bwd)
    return f


def fused_attention_decoder(enc_b, enc_proj, enc_mask, trg_b, trg_mask,
                            h0, wa_dec, v_att, wx, wh, bias):
    """Public entry: unpadded [B, S, ·] operands; pads S for the kernels.

    enc_mask is bool [B, S]; trg_mask float [T, B]; bias may be None.
    Returns h_seq [T, B, H].
    """
    B, S, A = enc_proj.shape
    sp = _pad_s(S)
    pad = [(0, 0), (0, sp - S), (0, 0)]
    ep = jnp.pad(enc_proj, pad)
    enc = jnp.pad(enc_b, pad)
    maskf = jnp.pad(enc_mask.astype(jnp.float32), [(0, 0), (0, sp - S)])
    if bias is None:
        bias = jnp.zeros((wx.shape[1],), trg_b.dtype)
    dispatch_stats["fused_calls"] += 1
    from . import mesh_dispatch

    f = _decoder_fn(_interpret())
    # mesh policy (ops/mesh_dispatch.py): the kernels run per-shard
    # under shard_map — batch-sharded operands, replicated weights
    call = mesh_dispatch.shard_batch(
        f, (0, 0, 0, 1, 1, 0, None, None, None, None, None), ((1, 3),))
    return call(enc, ep, maskf, trg_b, trg_mask.astype(jnp.float32),
                h0, wa_dec, v_att, wx, wh, bias)
