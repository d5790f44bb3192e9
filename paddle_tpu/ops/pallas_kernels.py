"""Pallas fused recurrent kernels.

Reference: the hand-written fused CUDA recurrences —
`hl_lstm_parallel_forward` (cuda/include/hl_lstm.h:42, hl_gpu_lstm.cuh) and
the GRU equivalents (hl_gpu_gru.cuh) — which keep the recurrent state in
registers/shared memory and run the whole sequence in one kernel launch.

TPU design: one pallas_call with `grid=(T,)`; the TPU grid runs
sequentially, so the hidden/cell state lives in VMEM scratch across grid
steps while each timestep's pre-projected input block is pipelined in from
HBM automatically by the BlockSpec machinery (double-buffered DMA). The
per-step h @ W_rec hits the MXU; all gate math fuses on the VPU; the only
HBM traffic is the x block in and the h block out — the same
bandwidth-optimality argument as the reference's fused kernels.

Training: `pallas_call` has no automatic VJP, so the fused forward is
wrapped in `jax.custom_vjp` whose backward re-runs the plain `lax.scan`
formulation under `jax.vjp` (rematerialized backward — same FLOPs as a
saved-activation backward plus one forward, no extra HBM residency).

Eligibility (else callers fall back to the scan): sigmoid/tanh gates, no
peepholes, B multiple of 8, H multiple of 128 (f32 tile constraints).
Non-TPU backends run the kernel in interpret mode (tests on CPU exercise
the same code path).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mesh_dispatch


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def backend_ok(interpret_flag: str) -> bool:
    """Shared dispatch gate for every fused-kernel family (RNN,
    attention): interpret mode exists for tests; production dispatch must
    not send CPU/GPU users through the pure-Python interpreter when the
    XLA formulation is sitting right there. `interpret_flag` names that
    family's test-override flag."""
    from ..flags import FLAGS

    return jax.default_backend() == "tpu" or getattr(FLAGS, interpret_flag)


def _backend_ok() -> bool:
    return backend_ok("fused_rnn_interpret")


# The backward kernel's VMEM working set must fit the 16M scoped budget;
# the model below reproduces every measured compile outcome: LSTM bf16
# H=1280 B=128 → 18.7M predicted vs 18.75M in the observed train-graph
# overflow; GRU f32 H=1280 B=128 → 25.6M vs observed 25.0M overflow;
# LSTM bf16 H=1280 B=256 → 24.2M vs the microbench fused_error row;
# GRU bf16 H=1280 B=128 → 14.7M, compiles and wins 1.88x
# (rnn_kernel_microbench: old link, rounds <= 5, not re-measured on this
# chip; record in git history). The budget keeps a 1M safety
# margin below the hardware's 16M: LSTM bf16 H=1280 B=64 models at 15.9M
# and was observed BOTH compiling (152k tok/s) and overflowing by 824K
# on different compiles of the same graph — borderline configs flip with
# the compiler's scratch scheduling, so they stay on the scan.
_VMEM_BUDGET = 15 * 1024 * 1024

# The model above counts blocks and carries; it does not count the f32
# gate temporaries the compiler keeps per step (they grow with B*4H), and
# libtpu 0.0.34 refuses configs the model admits at its 16M DEFAULT
# scoped-VMEM cap: LSTM backward bf16 H=512 at B=256 (16.6M — a cell of
# the reference's published grid), B=384 (21.9M), B=512 (18.0M); the
# forward at H=1280 B=128 (17.5M, its resident [H,4H] weight alone is
# 13.1M). The chip has 128M of VMEM, so every kernel of this family
# raises the cap instead of narrowing the windows; the largest need
# among the eligible configs is 22M (tests/test_tpu_compile.py compiles
# them for a described v5e).
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024)


def _bwd_vmem_bytes(B: int, H: int, G: int, itemsize: int,
                    dw_max_h: int) -> int:
    """G = gates per cell (4 LSTM, 3 GRU); itemsize = io dtype bytes;
    dw_max_h = that cell's fused-dW threshold (the model must track the
    kernel's actual fuse decision)."""
    weight_block = G * H * H * itemsize
    io_blocks = 2 * (G + 3) * B * H * itemsize  # double-buffered streams
    carries = 3 * B * H * itemsize
    dw_acc = 4 * G * H * H if H <= dw_max_h else 0  # f32 accumulator
    return weight_block + io_blocks + carries + dw_acc


def _tuned_fused(kind: str, B: int, H: int, itemsize: int) -> bool:
    """The fused-vs-scan choice at (B, H, dtype): tune/space.py's rule
    for the family (tile alignment, the VMEM model above and the
    measured H window: `_rnn_default`), or what a sweep forced. The
    fused-RNN kernels have no free tile parameter: their knob is the
    dispatch itself."""
    from ..tune import space

    return bool(space.pick(f"fused_{kind}", {"B": B, "H": H},
                           space.ITEMSIZE_DTYPE[itemsize])["fused"])


def lstm_supported(B: int, H: int, gate_act, cell_act, cand_act, peep,
                   itemsize: int = 2) -> bool:
    return (peep is None
            and gate_act == "sigmoid"
            and cell_act == "tanh"
            and cand_act == "tanh"
            and _backend_ok()
            and _tuned_fused("lstm", B, H, itemsize))


def gru_supported(B: int, H: int, gate_act, cand_act,
                  itemsize: int = 2) -> bool:
    return (gate_act == "sigmoid"
            and cand_act == "tanh"
            and _backend_ok()
            and _tuned_fused("gru", B, H, itemsize))


# ------------------------------------------------------------------ LSTM ---
def _lstm_kernel(
    x_ref, m_ref, w_ref, h_seq_ref, c_seq_ref, hT_ref, cT_ref, h_s, c_s
):
    """One timestep per grid step; h/c persist in VMEM scratch. c_seq is

    emitted as a residual for the hand-written backward kernel."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_s[:] = jnp.zeros_like(h_s)
        c_s[:] = jnp.zeros_like(c_s)

    h_prev = h_s[:]
    c_prev = c_s[:].astype(jnp.float32)
    # gate math in f32 on the VPU regardless of io dtype (also works
    # around Mosaic's refusal to broadcast an f32 scalar into a bf16
    # vector inside sigmoid); the MXU matmul accumulates f32 anyway
    gates = x_ref[0].astype(jnp.float32) + jnp.dot(
        h_prev, w_ref[:], preferred_element_type=jnp.float32
    )
    H = h_prev.shape[-1]
    i = jax.nn.sigmoid(gates[:, :H])
    f = jax.nn.sigmoid(gates[:, H : 2 * H])
    g = jnp.tanh(gates[:, 2 * H : 3 * H])
    o = jax.nn.sigmoid(gates[:, 3 * H :])
    c = f * c_prev + i * g
    h = o * jnp.tanh(c)
    m = m_ref[0, 0][:, None]
    h = m * h + (1 - m) * h_prev.astype(jnp.float32)
    c = m * c + (1 - m) * c_prev
    dt = h_s.dtype
    h_s[:] = h.astype(dt)
    c_s[:] = c.astype(dt)
    h_seq_ref[0] = h.astype(dt)
    c_seq_ref[0] = c.astype(dt)

    @pl.when(t == pl.num_programs(0) - 1)
    def _():
        hT_ref[:] = h.astype(dt)
        cT_ref[:] = c.astype(dt)


def _lstm_pallas_raw(x_tbh, mask, w_rec):
    T, B, H4 = x_tbh.shape
    H = H4 // 4
    dt = x_tbh.dtype
    return pl.pallas_call(
        _lstm_kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H4), lambda t: (t, 0, 0)),
            # mask rides as [T, 1, B]: a (1, 1, B) block satisfies the
            # (sublane, lane) tiling rule for any B (dims equal the array's)
            pl.BlockSpec((1, 1, B), lambda t: (t, 0, 0)),
            pl.BlockSpec((H, H4), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, B, H), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, B, H), lambda t: (t, 0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H), dt),
            jax.ShapeDtypeStruct((T, B, H), dt),
            jax.ShapeDtypeStruct((B, H), dt),
            jax.ShapeDtypeStruct((B, H), dt),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), dt),
            pltpu.VMEM((B, H), dt),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
    )(x_tbh, mask.astype(jnp.float32).reshape(T, 1, B), w_rec)


def _lstm_bwd_kernel(
    gates_ref,  # (1, B, 4H) pre-activation gates at t
    cprev_ref,  # (1, B, H) c_{t-1}
    hprev_ref,  # (1, B, H) h_{t-1}
    dh_seq_ref,  # (1, B, H) output cotangent at t
    m_ref,  # (1, 1, B)
    w_ref,  # (H, 4H)
    dhT_ref,  # (B, H) cotangent of final h
    dcT_ref,  # (B, H) cotangent of final c
    dx_ref,  # out (1, B, 4H)
    dw_ref,  # out (H, 4H) — absent when accumulate_dw=False
    dh_s,  # scratch (B, H): dL/dh_t carry
    dc_s,  # scratch (B, H): dL/dc_t carry
    dw_s,  # scratch (H, 4H) f32 accumulator — absent when accumulate_dw=False
    *,
    accumulate_dw: bool = True,
):
    """Reverse-time step: t = T-1-s via the index maps. Gates are

    recomputed OUTSIDE in one batched matmul (h_seq is saved, so gate
    pre-activations have no sequential dependency); only the dh/dc carry
    is sequential here.

    accumulate_dw=False drops the in-VMEM [H, 4H] f32 dW accumulator (16H²
    bytes — past H=640 it evicts everything else); dW is then one batched
    einsum over the emitted dgates OUTSIDE the kernel, which only costs one
    extra HBM read of dx. That lifts the eligibility window to the
    reference's largest published config (H=1280,
    /root/reference/benchmark/README.md:129-136)."""
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _():
        dh_s[:] = dhT_ref[:]
        dc_s[:] = dcT_ref[:]
        if accumulate_dw:
            dw_s[:] = jnp.zeros_like(dw_s)

    # all gate/cotangent math in f32 (see _lstm_kernel's dtype note)
    gates = gates_ref[0].astype(jnp.float32)
    H = dh_s.shape[-1]
    i = jax.nn.sigmoid(gates[:, :H])
    f = jax.nn.sigmoid(gates[:, H : 2 * H])
    g = jnp.tanh(gates[:, 2 * H : 3 * H])
    o = jax.nn.sigmoid(gates[:, 3 * H :])
    c_prev = cprev_ref[0].astype(jnp.float32)
    h_prev = hprev_ref[0]
    m = m_ref[0, 0][:, None]

    c_raw = f * c_prev + i * g
    tc = jnp.tanh(c_raw)

    dh_total = dh_seq_ref[0].astype(jnp.float32) + dh_s[:].astype(jnp.float32)
    dc_total = dc_s[:].astype(jnp.float32)
    dh_raw = m * dh_total
    dc_raw = m * dc_total + dh_raw * o * (1 - tc * tc)
    do_a = dh_raw * tc * o * (1 - o)
    di_a = dc_raw * g * i * (1 - i)
    df_a = dc_raw * c_prev * f * (1 - f)
    dg_a = dc_raw * i * (1 - g * g)
    dgates = jnp.concatenate([di_a, df_a, dg_a, do_a], axis=1)

    dt = dx_ref.dtype
    dx_ref[0] = dgates.astype(dt)
    dh_s[:] = (
        jnp.dot(
            dgates.astype(dt), w_ref[:].T,
            preferred_element_type=jnp.float32,
        )
        + (1 - m) * dh_total
    ).astype(dh_s.dtype)
    dc_s[:] = (dc_raw * f + (1 - m) * dc_total).astype(dc_s.dtype)
    if accumulate_dw:
        dw_s[:] = dw_s[:] + jnp.dot(
            h_prev.T, dgates.astype(dt), preferred_element_type=jnp.float32
        )

        @pl.when(s == pl.num_programs(0) - 1)
        def _():
            dw_ref[:] = dw_s[:].astype(dw_ref.dtype)


def _lstm_bwd_kernel_nodw(
    gates_ref, cprev_ref, hprev_ref, dh_seq_ref, m_ref, w_ref, dhT_ref,
    dcT_ref, dx_ref, dh_s, dc_s,
):
    """Positional-signature adapter: without the dW output/scratch, pallas
    hands the kernel one fewer ref in each group."""
    _lstm_bwd_kernel(
        gates_ref, cprev_ref, hprev_ref, dh_seq_ref, m_ref, w_ref, dhT_ref,
        dcT_ref, dx_ref, None, dh_s, dc_s, None, accumulate_dw=False,
    )


# past this hidden size the [H, 4H] f32 dW accumulator (16H² bytes) no
# longer fits VMEM next to the weight and io blocks; switch to the outer
# batched-einsum dW (see _lstm_bwd_kernel docstring)
_LSTM_FUSED_DW_MAX_H = 640


def _lstm_bwd_pallas(x_tbh, mask, w_rec, h_seq, c_seq, dh_seq, dhT, dcT):
    T, B, H4 = x_tbh.shape
    H = H4 // 4
    dt = x_tbh.dtype
    zeros = jnp.zeros((1, B, H), dt)
    h_prev_seq = jnp.concatenate([zeros, h_seq[:-1]], axis=0)
    c_prev_seq = jnp.concatenate([zeros, c_seq[:-1]], axis=0)
    # all gate pre-activations in ONE batched matmul — no recurrence
    gates_pre = x_tbh + jnp.einsum(
        "tbh,hk->tbk", h_prev_seq, w_rec,
        preferred_element_type=jnp.float32,
    ).astype(dt)
    fuse_dw = H <= _LSTM_FUSED_DW_MAX_H
    rev = lambda t: (T - 1 - t, 0, 0)  # noqa: E731 — reverse-time index map
    out_specs = [pl.BlockSpec((1, B, H4), rev)]
    out_shape = [jax.ShapeDtypeStruct((T, B, H4), dt)]
    scratch = [pltpu.VMEM((B, H), dt), pltpu.VMEM((B, H), dt)]
    if fuse_dw:
        out_specs.append(pl.BlockSpec((H, H4), lambda t: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((H, H4), dt))
        scratch.append(pltpu.VMEM((H, H4), jnp.float32))
    outs = pl.pallas_call(
        _lstm_bwd_kernel if fuse_dw else _lstm_bwd_kernel_nodw,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H4), rev),
            pl.BlockSpec((1, B, H), rev),
            pl.BlockSpec((1, B, H), rev),
            pl.BlockSpec((1, B, H), rev),
            pl.BlockSpec((1, 1, B), rev),
            pl.BlockSpec((H, H4), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
    )(
        gates_pre,
        c_prev_seq,
        h_prev_seq,
        dh_seq,
        mask.astype(jnp.float32).reshape(T, 1, B),
        w_rec,
        dhT,
        dcT,
    )
    if fuse_dw:
        dx, dw = outs
    else:
        (dx,) = outs if isinstance(outs, (list, tuple)) else (outs,)
        dw = jnp.einsum(
            "tbh,tbk->hk", h_prev_seq, dx,
            preferred_element_type=jnp.float32,
        ).astype(dt)
    return dx, dw


def lstm_fused(x_tbh, mask, w_rec, bias=None, reverse=False):
    """Fused LSTM over the whole sequence (zero-boot, sigmoid/tanh).

    Mirrors lstm_scan's signature subset: optional pre-gate bias and
    time reversal (flip in, flip the emitted sequence back). Under an
    active mesh the call is shard_map'd over the dp axis (mesh_dispatch
    policy): batch-sharded x/mask, replicated weight, per-shard kernel
    at the local batch; shard_map's transpose sums the per-shard dW."""
    if bias is not None:
        # master-weight bias casts DOWN to the activation dtype (amp):
        # promoting x to f32 here would double the whole sequence's HBM
        # traffic through the kernel
        x_tbh = x_tbh + bias.astype(x_tbh.dtype)
    # f32 master weight likewise meets the activation dtype at the kernel
    # boundary; the cast's transpose restores an f32 dW for the optimizer
    w_rec = w_rec.astype(x_tbh.dtype)
    # outputs (h_seq [T,B,H], (h_T [B,H], c_T [B,H]))
    call = mesh_dispatch.shard_batch(
        _lstm_core, (1, 1, None), ((1, 3), (0, 2), (0, 2)),
        out_tree=_RNN_LSTM_OUT_TREE)
    if reverse:
        h_seq, last = call(x_tbh[::-1], mask[::-1], w_rec)
        return h_seq[::-1], last
    return call(x_tbh, mask, w_rec)


_RNN_LSTM_OUT_TREE = jax.tree.structure((0, (0, 0)))
_RNN_GRU_OUT_TREE = jax.tree.structure((0, 0))


@jax.custom_vjp
def _lstm_core(x_tbh, mask, w_rec):
    """custom-VJP fused LSTM. Under a dp mesh it runs inside shard_map
    and its weight cotangent is a per-shard partial sum; the backward
    returns it AS IS — shard_map's transpose psums the cotangent of a
    replicated input over the mesh axis, with check_vma off too (jax
    0.9.0; see mesh_dispatch). An explicit psum here counts it dp times
    over."""
    h_seq, _c_seq, h_T, c_T = _lstm_pallas_raw(x_tbh, mask, w_rec)
    return h_seq, (h_T, c_T)


def _lstm_core_fwd(x_tbh, mask, w_rec):
    h_seq, c_seq, h_T, c_T = _lstm_pallas_raw(x_tbh, mask, w_rec)
    return (h_seq, (h_T, c_T)), (x_tbh, mask, w_rec, h_seq, c_seq)


def _lstm_core_bwd(res, ct):
    x_tbh, mask, w_rec, h_seq, c_seq = res
    dh_seq, (dhT, dcT) = ct
    dx, dw = _lstm_bwd_pallas(
        x_tbh, mask, w_rec, h_seq, c_seq, dh_seq, dhT, dcT
    )
    return dx, None, dw


_lstm_core.defvjp(_lstm_core_fwd, _lstm_core_bwd)


# ------------------------------------------------------------------- GRU ---
def _gru_kernel(x_ref, m_ref, w_ref, h_seq_ref, hT_ref, h_s):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_s[:] = jnp.zeros_like(h_s)

    h_prev = h_s[:]
    H = h_prev.shape[-1]
    xp = x_ref[0].astype(jnp.float32)  # gate math in f32 (see _lstm_kernel)
    w_ur = w_ref[:, : 2 * H]
    w_c = w_ref[:, 2 * H :]
    ur = jax.nn.sigmoid(
        xp[:, : 2 * H]
        + jnp.dot(h_prev, w_ur, preferred_element_type=jnp.float32)
    )
    u, r = ur[:, :H], ur[:, H:]
    c = jnp.tanh(
        xp[:, 2 * H :]
        + jnp.dot(
            (r * h_prev.astype(jnp.float32)).astype(h_prev.dtype), w_c,
            preferred_element_type=jnp.float32,
        )
    )
    h = (1 - u) * h_prev.astype(jnp.float32) + u * c
    m = m_ref[0, 0][:, None]
    h = m * h + (1 - m) * h_prev.astype(jnp.float32)
    dt = h_s.dtype
    h_s[:] = h.astype(dt)
    h_seq_ref[0] = h.astype(dt)

    @pl.when(t == pl.num_programs(0) - 1)
    def _():
        hT_ref[:] = h.astype(dt)


def _gru_pallas_raw(x_tbh, mask, w_rec):
    T, B, H3 = x_tbh.shape
    H = H3 // 3
    dt = x_tbh.dtype
    return pl.pallas_call(
        _gru_kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H3), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, 1, B), lambda t: (t, 0, 0)),
            pl.BlockSpec((H, H3), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, B, H), lambda t: (t, 0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H), dt),
            jax.ShapeDtypeStruct((B, H), dt),
        ],
        scratch_shapes=[pltpu.VMEM((B, H), dt)],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
    )(x_tbh, mask.astype(jnp.float32).reshape(T, 1, B), w_rec)


def _gru_bwd_kernel(
    ur_pre_ref,  # (1, B, 2H) update/reset pre-activations at t
    c_pre_ref,  # (1, B, H) candidate pre-activation at t
    hprev_ref,  # (1, B, H) h_{t-1}
    dh_seq_ref,  # (1, B, H) output cotangent at t
    m_ref,  # (1, 1, B)
    w_ref,  # (H, 3H) = [W_u | W_r | W_c]
    dhT_ref,  # (B, H) cotangent of final h
    dx_ref,  # out (1, B, 3H)
    dw_ref,  # out (H, 3H) — absent when accumulate_dw=False
    dh_s,  # scratch (B, H): dL/dh_t carry
    dw_s,  # scratch (H, 3H) f32 accumulator — absent when accumulate_dw=False
    *,
    accumulate_dw: bool = True,
):
    """Reverse-time GRU step (t = T-1-s via the index maps), replacing the
    round-2 scan-replay VJP. Forward (gru_cell):
        u = σ(xu + h@Wu);  r = σ(xr + h@Wr);  c = tanh(xc + (r·h)@Wc)
        h' = (1-u)·h + u·c, masked h' = m·h' + (1-m)·h
    The pre-activations have no sequential dependency (h_seq is saved) so
    they are recomputed OUTSIDE in batched matmuls; only the dh carry is
    sequential. Reference counterpart: hl_gpu_gru.cuh backward."""
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _():
        dh_s[:] = dhT_ref[:]
        if accumulate_dw:
            dw_s[:] = jnp.zeros_like(dw_s)

    H = dh_s.shape[-1]
    ur = jax.nn.sigmoid(ur_pre_ref[0].astype(jnp.float32))
    u, r = ur[:, :H], ur[:, H:]
    c = jnp.tanh(c_pre_ref[0].astype(jnp.float32))
    h_prev = hprev_ref[0]
    h_prev32 = h_prev.astype(jnp.float32)
    m = m_ref[0, 0][:, None]

    dh_total = dh_seq_ref[0].astype(jnp.float32) + dh_s[:].astype(jnp.float32)
    dh_raw = m * dh_total
    dc_act = dh_raw * u
    du_act = dh_raw * (c - h_prev32)
    dh_prev = (1 - m) * dh_total + dh_raw * (1 - u)

    dc_pre = dc_act * (1 - c * c)
    dt = dx_ref.dtype
    w_c = w_ref[:, 2 * H:]
    drh = jnp.dot(
        dc_pre.astype(dt), w_c.T, preferred_element_type=jnp.float32
    )  # cotangent of (r·h_prev)
    dr_act = drh * h_prev32
    dh_prev = dh_prev + drh * r

    du_pre = du_act * u * (1 - u)
    dr_pre = dr_act * r * (1 - r)
    dur = jnp.concatenate([du_pre, dr_pre], axis=1)
    w_ur = w_ref[:, : 2 * H]
    dh_prev = dh_prev + jnp.dot(
        dur.astype(dt), w_ur.T, preferred_element_type=jnp.float32
    )

    dx_ref[0] = jnp.concatenate([du_pre, dr_pre, dc_pre], axis=1).astype(dt)
    dh_s[:] = dh_prev.astype(dh_s.dtype)
    if accumulate_dw:
        rh = (r * h_prev32).astype(dt)
        dw_s[:, : 2 * H] = dw_s[:, : 2 * H] + jnp.dot(
            h_prev.T, dur.astype(dt), preferred_element_type=jnp.float32
        )
        dw_s[:, 2 * H:] = dw_s[:, 2 * H:] + jnp.dot(
            rh.T, dc_pre.astype(dt), preferred_element_type=jnp.float32
        )

        @pl.when(s == pl.num_programs(0) - 1)
        def _():
            dw_ref[:] = dw_s[:].astype(dw_ref.dtype)


def _gru_bwd_kernel_nodw(
    ur_pre_ref, c_pre_ref, hprev_ref, dh_seq_ref, m_ref, w_ref, dhT_ref,
    dx_ref, dh_s,
):
    _gru_bwd_kernel(
        ur_pre_ref, c_pre_ref, hprev_ref, dh_seq_ref, m_ref, w_ref, dhT_ref,
        dx_ref, None, dh_s, None, accumulate_dw=False,
    )


_GRU_FUSED_DW_MAX_H = 640  # 12H² f32 accumulator bytes vs ~16 MB VMEM


def _gru_bwd_pallas(x_tbh, mask, w_rec, h_seq, dh_seq, dhT):
    T, B, H3 = x_tbh.shape
    H = H3 // 3
    dt = x_tbh.dtype
    zeros = jnp.zeros((1, B, H), dt)
    h_prev_seq = jnp.concatenate([zeros, h_seq[:-1]], axis=0)
    # batched pre-activation recompute (no recurrence): u/r first, then the
    # candidate path through r·h_prev
    ur_pre = x_tbh[:, :, : 2 * H] + jnp.einsum(
        "tbh,hk->tbk", h_prev_seq, w_rec[:, : 2 * H],
        preferred_element_type=jnp.float32,
    ).astype(dt)
    r_seq = jax.nn.sigmoid(ur_pre[:, :, H:].astype(jnp.float32))
    rh_seq = (r_seq * h_prev_seq.astype(jnp.float32)).astype(dt)
    c_pre = x_tbh[:, :, 2 * H:] + jnp.einsum(
        "tbh,hk->tbk", rh_seq, w_rec[:, 2 * H:],
        preferred_element_type=jnp.float32,
    ).astype(dt)
    fuse_dw = H <= _GRU_FUSED_DW_MAX_H
    rev = lambda t: (T - 1 - t, 0, 0)  # noqa: E731 — reverse-time index map
    out_specs = [pl.BlockSpec((1, B, H3), rev)]
    out_shape = [jax.ShapeDtypeStruct((T, B, H3), dt)]
    scratch = [pltpu.VMEM((B, H), dt)]
    if fuse_dw:
        out_specs.append(pl.BlockSpec((H, H3), lambda t: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((H, H3), dt))
        scratch.append(pltpu.VMEM((H, H3), jnp.float32))
    outs = pl.pallas_call(
        _gru_bwd_kernel if fuse_dw else _gru_bwd_kernel_nodw,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, 2 * H), rev),
            pl.BlockSpec((1, B, H), rev),
            pl.BlockSpec((1, B, H), rev),
            pl.BlockSpec((1, B, H), rev),
            pl.BlockSpec((1, 1, B), rev),
            pl.BlockSpec((H, H3), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
    )(
        ur_pre,
        c_pre,
        h_prev_seq,
        dh_seq,
        mask.astype(jnp.float32).reshape(T, 1, B),
        w_rec,
        dhT,
    )
    if fuse_dw:
        dx, dw = outs
    else:
        (dx,) = outs if isinstance(outs, (list, tuple)) else (outs,)
        dw_ur = jnp.einsum(
            "tbh,tbk->hk", h_prev_seq, dx[:, :, : 2 * H],
            preferred_element_type=jnp.float32,
        )
        dw_c = jnp.einsum(
            "tbh,tbk->hk", rh_seq, dx[:, :, 2 * H:],
            preferred_element_type=jnp.float32,
        )
        dw = jnp.concatenate([dw_ur, dw_c], axis=1).astype(dt)
    return dx, dw


def gru_fused(x_tbh, mask, w_rec, bias=None, reverse=False):
    """Fused GRU over the whole sequence (zero-boot, sigmoid/tanh).

    Mesh policy as lstm_fused: shard_map'd over dp when a mesh is
    active."""
    if bias is not None:
        x_tbh = x_tbh + bias.astype(x_tbh.dtype)  # see lstm_fused
    w_rec = w_rec.astype(x_tbh.dtype)
    call = mesh_dispatch.shard_batch(
        _gru_core, (1, 1, None), ((1, 3), (0, 2)),
        out_tree=_RNN_GRU_OUT_TREE)
    if reverse:
        h_seq, h_T = call(x_tbh[::-1], mask[::-1], w_rec)
        return h_seq[::-1], h_T
    return call(x_tbh, mask, w_rec)


@jax.custom_vjp
def _gru_core(x_tbh, mask, w_rec):
    """custom-VJP fused GRU; see _lstm_core for the mesh contract."""
    h_seq, h_T = _gru_pallas_raw(x_tbh, mask, w_rec)
    return h_seq, h_T


def _gru_core_fwd(x_tbh, mask, w_rec):
    h_seq, h_T = _gru_pallas_raw(x_tbh, mask, w_rec)
    return (h_seq, h_T), (x_tbh, mask, w_rec, h_seq)


def _gru_core_bwd(res, ct):
    x_tbh, mask, w_rec, h_seq = res
    dh_seq, dhT = ct
    dx, dw = _gru_bwd_pallas(x_tbh, mask, w_rec, h_seq, dh_seq, dhT)
    return dx, None, dw


_gru_core.defvjp(_gru_core_fwd, _gru_core_bwd)
