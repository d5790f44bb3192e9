"""Main-path Pallas kernels compile for a described TPU v5e, at real widths.

No chip is attached under test: the TPU compiler that ships with the
install compiles for a topology that is only described
(`topologies.get_topology_desc`). This catches what interpret mode cannot
— a block the Mosaic tiling refuses, a kernel past its VMEM budget — at
no chip time. It says nothing about results or speed.

Rules this file keeps (on-chip-measurement guide §2): the topology is
described inside a module-scoped fixture that skips when it cannot be,
never at import and never in a parametrize argument; every compile
happens in the test's own process; all such tests live in this one file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BF16, F32, I8 = jnp.bfloat16, jnp.float32, jnp.int8
T, B = 100, 128  # bench LSTM sequence length / batch


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_mode(monkeypatch):
    """What the chip would decide: interpret mode off, fused dispatch
    eligible (`jax.default_backend()` still says cpu during these
    compiles), and the matmul precision the chip process runs with — the
    default, not conftest's CPU-oracle "highest", which Mosaic refuses
    on bf16 operands. The persistent compile cache is off around them:
    an entry written for a described chip cannot be read back without
    one."""
    from jax.experimental.compilation_cache import compilation_cache

    from paddle_tpu.ops import pallas_kernels, quant_kernels

    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_kernels, "_backend_ok", lambda: True)
    monkeypatch.setattr(quant_kernels, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with jax.default_matmul_precision("default"):
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# ---- case builders: each returns (fn, [(shape, dtype), ...]) ------------
def _lstm_fwd(H, B=B):
    from paddle_tpu.ops import pallas_kernels as pk

    return pk._lstm_pallas_raw, [
        ((T, B, 4 * H), BF16), ((T, B), F32), ((H, 4 * H), BF16)]


def _lstm_bwd(hb):
    from paddle_tpu.ops import pallas_kernels as pk

    H, B = hb
    seq, last = ((T, B, H), BF16), ((B, H), BF16)
    return pk._lstm_bwd_pallas, [
        ((T, B, 4 * H), BF16), ((T, B), F32), ((H, 4 * H), BF16),
        seq, seq, seq, last, last]


def _largest_gru_h():
    from paddle_tpu.ops import pallas_kernels as pk

    return max(h for h in range(128, 4096 + 1, 128)
               if pk.gru_supported(B, h, "sigmoid", "tanh", itemsize=2))


def _gru_fwd(H):
    from paddle_tpu.ops import pallas_kernels as pk

    H = H or _largest_gru_h()
    return pk._gru_pallas_raw, [
        ((T, B, 3 * H), BF16), ((T, B), F32), ((H, 3 * H), BF16)]


def _gru_bwd(H):
    from paddle_tpu.ops import pallas_kernels as pk

    H = H or _largest_gru_h()
    seq = ((T, B, H), BF16)
    return pk._gru_bwd_pallas, [
        ((T, B, 3 * H), BF16), ((T, B), F32), ((H, 3 * H), BF16),
        seq, seq, ((B, H), BF16)]


# NMT bench decoder attention: batch 128, source length 50 (padded by the
# kernels' own rule), attention width 512, bidirectional context 1024
_ATT = dict(B=128, S=50, A=512, C=1024, T=50)


def _bahdanau(which):
    from paddle_tpu.ops import bahdanau_kernels as bk

    b, a, c, t = _ATT["B"], _ATT["A"], _ATT["C"], _ATT["T"]
    sp = bk._pad_s(_ATT["S"])
    ep, enc = ((b, sp, a), BF16), ((b, sp, c), BF16)
    dp, v, maskf = ((b, a), BF16), ((a,), BF16), ((b, sp), F32)
    if which == "fwd":
        return (lambda *xs: bk._attn_fwd(*xs, interpret=False),
                [ep, enc, dp, v, maskf])
    if which == "bwd_step":
        return (lambda *xs: bk._attn_bwd_step(*xs, interpret=False),
                [ep, enc, dp, v, maskf, ((b, c), BF16), ((b, sp), F32)])
    return (lambda ep_, dps, dscs, v_: bk._attn_phase2(
                ep_, dps, dscs, v_, c, interpret=False),
            [ep, ((t, b, a), BF16), ((t, b, sp), F32), v])


def _flash(case):
    from paddle_tpu.ops import flash_ops

    # the packed kernels at the benchmark's shapes: gpt2-small's
    # [12, 1024, 768] with 12 heads (two 64-wide heads a lane block) and
    # olmoe-1b-7b's [2, 4096, 2048] with 16 heads (one 128-wide head a block)
    shape, heads, with_bwd = case
    qkv = [(shape, BF16)] * 3

    def fwd(q, k, v):
        return flash_ops._packed_attention(q, k, v, heads, True)

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(F32).sum(), (0, 1, 2))(
            q, k, v)

    return (fwd_bwd if with_bwd else fwd), qkv


def _flash_gqa(case):
    from paddle_tpu.ops import flash_ops

    # Nemotron-3-Nano's attention at the cell's T 8192: 32 query heads x 128
    # over 2 K/V heads, Q packed [1, 8192, 4096] and K, V [1, 8192, 256]: the
    # index maps hand 16 query lane blocks the same K/V block, the fused
    # backward holds dQ for the whole sequence, and dK / dV leave the kernel
    # once a query head in float32
    (B, T, heads, kv_heads, D) = case
    specs = [((B, T, heads * D), BF16)] + [((B, T, kv_heads * D), BF16)] * 2

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: flash_ops._packed_attention(
            *a, heads, True).astype(F32).sum(), (0, 1, 2))(q, k, v)

    return fwd_bwd, specs


def _flash_pair(case):
    from paddle_tpu.ops import flash_ops

    # a Phi-4-mini-flash differential-attention layer's ONE launch at the
    # cell's shape: 40 query heads of 64 in 20 pairs over 10 K/V pairs, Q
    # packed [1, 8192, 2560] and K, V [1, 8192, 1280]. A pair is a lane block
    # (`[q_1 | q_2]`, `[k_1 | k_2]`, the value `[v_1 | v_2]` whole), two query
    # pairs read a K/V pair through the index map, two outputs of the block's
    # width, and the fused backward holds dQ's [8192, 128] float32 beside two
    # O and two dO blocks
    window, with_bwd = case
    specs = [((1, 8192, 40 * 64), BF16)] + [((1, 8192, 20 * 64), BF16)] * 2

    def fwd(q, k, v):
        return flash_ops._packed_attention(q, k, v, 40, True, window, True)

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: sum(o.astype(F32).sum() for o in fwd(*a)),
                        (0, 1, 2))(q, k, v)

    return (fwd_bwd if with_bwd else fwd), specs


def _flash_keep(with_bwd):
    from paddle_tpu.ops import flash_ops

    # a Keye-VL sparse-attention layer's launch at the cell's shape: 32 query
    # heads x 128 over 4 K/V heads, T 16 384, under the KEEP operand: int32
    # [1, 16384, 512], a bit a (row, key), whose [block_q, 128] block of words
    # opens into a k block's key tiles by a shift a tile; the fused backward
    # holds dQ's [16384, 128] float32 beside it
    T = 16384
    specs = [((1, T, 4096), BF16)] + [((1, T, 512), BF16)] * 2 + [
        ((1, T, 128 * (T // 4096)), jnp.int32)]

    def fwd(q, k, v, keep):
        return flash_ops._packed_attention(q, k, v, 32, True, 0, False, keep)

    def fwd_bwd(q, k, v, keep):
        return jax.grad(lambda q, k, v: fwd(q, k, v, keep).astype(F32).sum(),
                        (0, 1, 2))(q, k, v)

    return (fwd_bwd if with_bwd else fwd), specs


def _qk_fed(with_bwd):
    from paddle_tpu.ops import qk_ops

    # the per-head norm and the three-axis rotary of a Keye-VL layer's Q in
    # one launch, the cos and sin tables of the FED positions as operands
    T = 16384
    specs = [((1, T, 32, 128), BF16), ((1, 128), F32), ((1, 3, T), jnp.int32)]

    def fwd(x, scale, positions):
        tables = qk_ops.fed_tables(positions, (16, 24, 24), 1e7, 128)
        return qk_ops._kernel_fwd(x, scale, 1e-6, False, 1e7, 128, BF16,
                                  tables=tables)

    def fwd_bwd(x, scale, positions):
        tables = qk_ops.fed_tables(positions, (16, 24, 24), 1e7, 128)
        return qk_ops._kernel_bwd(x, scale, x, 1e-6, False, 1e7, 128,
                                  tables=tables)

    return (fwd_bwd if with_bwd else fwd), specs


def _short_conv(case):
    from paddle_tpu.ops import short_conv_ops

    # an LFM2 operator's mix at the cell's shape: [1, 16384, 3 x 2048] bf16,
    # 3 taps: blocks of 256 whole rows with 16 halo rows before (and, in the
    # backward, behind), 512-lane tiles, chunks of 16 rows rolled along the
    # sublanes
    (B, T, d, K), with_bwd = case
    specs = [((B, T, 3 * d), BF16), ((K, d), F32)]

    def fwd(bcx, w):
        return short_conv_ops._kernel_fwd(bcx, w)

    def fwd_bwd(bcx, w):
        return short_conv_ops._kernel_bwd(bcx, w, fwd(bcx, w))

    return (fwd_bwd if with_bwd else fwd), specs


def _conv_silu(with_bwd):
    from paddle_tpu.ops import ssm_ops

    # a Nemotron mixer's conv, bias and silu at the cell's shape: the packed
    # [1, 8192, 6144] bf16 the in-projection writes, 4 taps: blocks of 1024
    # rows x 512 lanes (the lane tile a grid axis) with 16 halo rows before
    # and, in the backward, behind; the backward reads the cotangent as the
    # scan's backward kernel hands it, dx | dB | dC as three operands
    specs = [((1, 8192, 6144), BF16), ((4, 6144), F32), ((6144,), F32)]
    parts = (4096, 1024, 1024)
    assert ssm_ops._shapes_conv_ok(jax.ShapeDtypeStruct(*specs[0]),
                                   jax.ShapeDtypeStruct(*specs[1]))

    def fwd(x, w, b):
        return ssm_ops._conv_silu_kernels(x, w, b, parts)

    def fwd_bwd(*args):
        return jax.grad(lambda *a: fwd(*a).astype(F32).sum(), (0, 1, 2))(*args)

    return (fwd_bwd if with_bwd else fwd), specs


def _ssd_scan(case):
    from paddle_tpu.ops import ssm_ops

    # Nemotron-3-Nano's Mamba-2 scan at the cell's shape: T 8192 in 64
    # chunks of 128, 64 heads x 64 in 8 groups, state 128: the forward
    # kernel (a group's [512, 128] float32 state in VMEM across the chunk
    # axis; x, B, C as lane ranges of the packed [1, 8192, 6144]) and, with
    # `with_bwd`, the differentiated forward (it also writes the state each
    # chunk starts from) and the backward kernel with its own copies of dx,
    # dB, dC into the packed cotangent. `gated`: what the mixer runs, the
    # gated group norm as the forward's epilogue (z's [128, 512] block and
    # the norm weight's lanes in, bf16 out) and the backward's prologue (y
    # formed again from the [Q, Q] blocks; dz and the weight's cotangent out)
    with_bwd, gated = case
    geom = ssm_ops.ScanGeometry(H=64, P=64, G=8, N=128, Q=128)
    assert ssm_ops._shapes_scan_ok(geom, 8192, BF16)
    specs = [((1, 8192, 64 * 64 + 2 * 8 * 128), BF16), ((1, 8192, 64), F32),
             ((64,), F32), ((64,), F32)]
    if gated:
        specs += [((1, 8192, 64 * 64), BF16), ((64 * 64,), F32)]

    def fwd(xBC, dt, A, D, *gate):
        return ssm_ops._ssd_kernels(xBC, dt, A, D, geom, gate,
                                    1e-5 if gate else None)

    def fwd_bwd(*args):
        return jax.grad(lambda *a: fwd(*a).astype(F32).sum(),
                        tuple(range(len(args))))(*args)

    return (fwd_bwd if with_bwd else fwd), specs


def _selective_scan(with_bwd):
    from paddle_tpu.ops import ssm_ops

    # a Phi-4-mini-flash mixer's selective scan at the cell's shape: T 8192
    # in 64 chunks of 128, 5120 channels in 10 tiles of 512 lanes, 16 states
    # in the sublanes: the forward kernel (every tile's [16, 512] float32
    # state in one VMEM scratch across the chunk axis, B and C spread over
    # 128 lanes) and, with `with_bwd`, the backward kernel (a chunk's 128
    # states formed again into a 4 MB scratch, dB and dC summed over the
    # tiles in place)
    specs = [((1, 8192, 5120), BF16), ((1, 8192, 5120), F32),
             ((5120, 16), F32), ((1, 8192, 16), BF16), ((1, 8192, 16), BF16),
             ((5120,), F32)]
    assert ssm_ops._shapes_selective_ok(jax.ShapeDtypeStruct(*specs[0]),
                                        jax.ShapeDtypeStruct(*specs[2]))

    def fwd(*args):
        return ssm_ops._sel_kernel_forward(*args)[0]

    def fwd_bwd(*args):
        y, starts = ssm_ops._sel_kernel_forward(*args)
        return ssm_ops._sel_kernel_backward(*args, starts, y)

    return (fwd_bwd if with_bwd else fwd), specs


def _gmm_share(kn):
    from paddle_tpu.ops import moe_ops

    # Nemotron's routed layer as one chip of 16 holds it: 8 192 tokens x
    # top-6 rows, 8 held experts whose group sizes sum to FEWER than the
    # rows; the expert width 1856 padded to 1920 (15 lane tiles)
    k, n = kn
    assert moe_ops._shapes_gmm_ok(jax.ShapeDtypeStruct((49152, k), BF16),
                                  jax.ShapeDtypeStruct((8, k, n), BF16))
    specs = [((49152, k), BF16), ((8, k, n), BF16), ((8,), jnp.int32)]

    def fwd_bwd(lhs, rhs, sizes):
        out, vjp = jax.vjp(
            lambda a, b: moe_ops._gmm_kernel(a, b, sizes), lhs, rhs)
        return (out, *vjp(out))

    return fwd_bwd, specs


def _gmm(kn):
    from paddle_tpu.ops import moe_ops

    # OLMoE-1B-7B's routed layer at batch 1 x T 4096: 4096 tokens x top-8
    # rows over 64 experts; (2048, 1024) is gate / up, (1024, 2048) down.
    # Forward and the custom VJP's two backward kernels, at the
    # dispatcher's own tiling
    k, n = kn
    specs = [((32768, k), BF16), ((64, k, n), BF16), ((64,), jnp.int32)]

    def fwd_bwd(lhs, rhs, sizes):
        out, vjp = jax.vjp(
            lambda a, b: moe_ops._gmm_kernel(a, b, sizes), lhs, rhs)
        return (out, *vjp(out))

    return fwd_bwd, specs


def _quant(mkn):
    from paddle_tpu.ops import quant_kernels as qk
    from paddle_tpu.tune import space

    m, k, n = mkn
    cfg = space.quant_matmul_default(dict(M=m, K=k, N=n, dtype="int8"))
    assert cfg is not None, f"no legal int8 tile for {mkn}"
    return (lambda x, w: qk._quant_matmul_pallas(
                x, w, int(cfg["block_m"]), int(cfg["block_n"])),
            [((m, k), I8), ((k, n), I8)])


CASES = [
    ("lstm_fwd_h512", _lstm_fwd, 512),
    ("lstm_bwd_h512", _lstm_bwd, (512, 128)),
    ("lstm_fwd_h1280", _lstm_fwd, 1280),
    ("lstm_bwd_h1280", _lstm_bwd, (1280, 128)),
    # past the compiler's 16M default scoped-VMEM cap (the kernels raise
    # it): batch 256 is a cell of the reference's published LSTM grid,
    # 512 is chip_smoke's one-chip reference for dp4, 384 the largest
    # need (21.9M) among the configs lstm_supported admits
    ("lstm_bwd_h512_b256", _lstm_bwd, (512, 256)),
    ("lstm_bwd_h512_b384", _lstm_bwd, (512, 384)),
    ("lstm_bwd_h512_b512", _lstm_bwd, (512, 512)),
    ("gru_fwd_h512", _gru_fwd, 512),
    ("gru_bwd_h512", _gru_bwd, 512),
    ("gru_fwd_largest_h", _gru_fwd, None),
    ("gru_bwd_largest_h", _gru_bwd, None),
    ("bahdanau_fwd", _bahdanau, "fwd"),
    ("bahdanau_bwd_step", _bahdanau, "bwd_step"),
    ("bahdanau_phase2", _bahdanau, "phase2"),
    ("flash_fwd_t1024", _flash, ((12, 1024, 768), 12, False)),
    ("flash_fwd_bwd_t1024", _flash, ((12, 1024, 768), 12, True)),
    ("flash_fwd_bwd_olmoe_t4096", _flash, ((2, 4096, 2048), 16, True)),
    ("gmm_fwd_bwd_olmoe_gate_up", _gmm, (2048, 1024)),
    ("gmm_fwd_bwd_olmoe_down", _gmm, (1024, 2048)),
    ("flash_fwd_bwd_nemotron_gqa_t8192", _flash_gqa, (1, 8192, 32, 2, 128)),
    # LFM2's attention layer at the cell's T 16 384: 32 heads of 64 (K and V
    # repeated to them), blocks of 1024, the fused backward with dQ's
    # [16384, 128] float32 accumulator in VMEM
    ("flash_fwd_bwd_lfm2_d64_t16384", _flash, ((1, 16384, 2048), 32, True)),
    ("flash_pair_fwd_phi4_t8192", _flash_pair, (0, False)),
    ("flash_pair_fwd_bwd_phi4_t8192", _flash_pair, (0, True)),
    ("flash_pair_fwd_phi4_window512_t8192", _flash_pair, (512, False)),
    ("flash_pair_fwd_bwd_phi4_window512_t8192", _flash_pair, (512, True)),
    ("flash_keep_operand_fwd_bwd_keye_t16384", _flash_keep, True),
    ("qk_assemble_fed_positions_bwd_keye_t16384", _qk_fed, True),
    ("short_conv_fwd_lfm2_t16384", _short_conv, ((1, 16384, 2048, 3), False)),
    ("short_conv_fwd_bwd_lfm2_t16384", _short_conv,
     ((1, 16384, 2048, 3), True)),
    ("gmm_fwd_bwd_nemotron_share_up", _gmm_share, (2688, 1920)),
    ("gmm_fwd_bwd_nemotron_share_down", _gmm_share, (1920, 2688)),
    ("ssd_scan_fwd_nemotron_t8192", _ssd_scan, (False, False)),
    ("ssd_scan_fwd_bwd_nemotron_t8192", _ssd_scan, (True, False)),
    ("ssd_scan_gated_fwd_nemotron_t8192", _ssd_scan, (False, True)),
    ("ssd_scan_gated_fwd_bwd_nemotron_t8192", _ssd_scan, (True, True)),
    ("causal_conv_silu_fwd_nemotron_t8192", _conv_silu, False),
    ("causal_conv_silu_fwd_bwd_nemotron_t8192", _conv_silu, True),
    ("selective_scan_fwd_phi4_t8192", _selective_scan, False),
    ("selective_scan_fwd_bwd_phi4_t8192", _selective_scan, True),
    # ResNet-50 head at a full serving bucket, and the small probe shape
    ("quant_matmul_64x2048x1000", _quant, (64, 2048, 1000)),
    ("quant_matmul_8x512x512", _quant, (8, 512, 512)),
    # the served int8 MLP's three sites (bench serving_quant: batch 8,
    # 512 -> 1024 -> 1024 -> 128)
    ("quant_matmul_mlp_fc1", _quant, (8, 512, 1024)),
    ("quant_matmul_mlp_fc2", _quant, (8, 1024, 1024)),
    ("quant_matmul_mlp_fc3", _quant, (8, 1024, 128)),
]


@pytest.mark.parametrize("build,arg", [c[1:] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, compiled_mode, build, arg):
    fn, specs = build(arg)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_plain_and_differentiated_attention_stay_two_launches(
        one_chip, compiled_mode):
    """This function calls the op twice itself: plainly, as an inference
    program launches it, and under `jax.grad`. The plain attention forward
    writes no statistics, so it is another kernel than the differentiated
    forward and XLA keeps both beside the fused backward: 3. (A training
    step holds the forward once since PR 33, whatever its launches look
    like: `test_step_program_holds_each_forward_kernel_launch_once`. As ONE
    identical launch XLA merged the two, and with them a whole doubled
    forward: gpt2-small's step 139.8 -> 110.6 ms on the chip, PR 28.)"""
    from paddle_tpu.ops import flash_ops

    def both(q, k, v):
        att = lambda *a: flash_ops._packed_attention(*a, 12, True)  # noqa: E731
        grads = jax.grad(lambda *a: att(*a).astype(F32).sum(), (0, 1, 2))(
            q, k, v)
        return att(q, k, v), grads

    args = [jax.ShapeDtypeStruct((2, 1024, 768), BF16, sharding=one_chip)] * 3
    text = jax.jit(both).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


# The step programs of the configurations the benchmark held before PR 32, as
# `chipbench/configs/<c>/model.py` builds them (batch 1 x T 1024: the kernels'
# window), lowered for the described chip with the dispatchers steered as the
# chip would decide: sha256 of the traced program's jaxpr (the Pallas kernels'
# bodies and index maps among it) and of the StableHLO text with the kernels'
# serialized bodies left out (they carry file paths and line numbers). PR 32
# added fewer K/V heads, held experts, a sigmoid router and a shared expert
# to code these programs run through; with the old arguments they had to stay
# what they were. PR 33 MEANT to change them (the forward ops are traced once,
# under differentiation: `core/executor.py:_run_autodiff`) and replaced all
# four digests (PERF.md section 6); the programs without an autodiff op it
# had to leave alone: INFERENCE_PROGRAMS, whose digests its parent gives too.
# A PR that MEANS to change one of these programs replaces its digests here
# and says so in PERF.md. PR 46 meant to change the flash kernels' bodies (a
# crossed block in two strips): the jaxprs' digests are its, the StableHLO
# digests without the kernels' bodies are the parent's still. PR 47 MEANT to
# change olmoe's three (its Q and K go from projection to kernel through
# `ops/qk_ops.py:qk_assemble`, a `custom_vjp` and two Pallas launches each
# way, where a float32 norm and rotary stood; the inference program holds the
# forward launch): olmoe's two step digests and its inference digest are PR
# 47's. gpt2-small has no norm and no rotary on Q or K: its three stand. PR 51
# meant to change the share cells' step programs (a chunk's sums are gathers
# through the sort's inverse: `ops/moe_ops.py:_sum_of_slots`); no digest of
# theirs is kept here (their tests below hold their memory and their counts),
# and `moe_ffn(held=None)` is olmoe's path: these four digests stand.
STEP_PROGRAMS = {
    "gpt2-small": (
        "67175aee1ee0d338d3262d309294bef3754189380ee3398ca6e60179ed6e9e39",
        "730695f9a2bfef705cb0288c6c24bdb03987e0b842152fd2df2cdf96bf441588"),
    "olmoe-1b-7b": (
        "62d4d5538466371e865e60712357427b92fcb0cc6e24b1ef6658f5240ffcac0a",
        "1a621231e022e9207a3e9e0fc5276fa2f7cba9391aca5f43cc59aa485bf88889"),
}
# the same models' `clone(for_test=True)` (no autodiff op, no optimizer op):
# sha256 of the StableHLO text, kernels' bodies left out
INFERENCE_PROGRAMS = {
    "gpt2-small":
        "b6d9e75d434f2cb52b064a04313fe4d1f325ac2825cd95307a4349a7f2a90fc0",
    "olmoe-1b-7b":
        "3c22c84744aba79bc786baee9683cfd403be5314505e7b7fca3886bcae2e8288",
}
# Pallas launches in the traced training step, by kernel: each forward launch
# once (the parent held it twice: 36 and 15 launches)
STEP_LAUNCHES = {
    "gpt2-small": {"flash_attention_fwd": 12, "flash_attention_bwd": 12},
    "olmoe-1b-7b": {"flash_attention_fwd": 1, "flash_attention_bwd": 1,
                    "grouped_matmul": 9,
                    # Q's and K's norm and rotary, one launch each way (PR 47)
                    "qk_assemble_fwd": 2, "qk_assemble_bwd": 2},
}


def _digest(text, kernel_bodies=True):
    """sha256 of a program's text, of StableHLO with the kernels' serialized
    bodies left out."""
    import hashlib

    if not kernel_bodies:
        text = re.sub(r'backend_config = "(?:[^"\\]|\\.)*"',
                      'backend_config = "..."', text)
    return hashlib.sha256(text.encode()).hexdigest()


def _load_module(path):
    import importlib.util

    spec = importlib.util.spec_from_file_location("step_program_model", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _benchmark_model(name, batch, seqlen):
    """A benchmark configuration's model as its cell builds it."""
    import json

    folder = os.path.join(ROOT, "chipbench", "configs", name)
    with open(os.path.join(folder, "config.json")) as f:
        config = json.load(f)
    return _load_module(os.path.join(folder, "model.py")).get_model(
        config, {"batch": batch, "seqlen": seqlen}, 7)


def _step_program(build, batch, seqlen, one_chip, monkeypatch, for_test=False,
                  more_feed=None):
    """(raw step, its arguments as shapes on the described chip) of the
    training step of the model `build()` makes, or of its `for_test` clone.
    `more_feed`: {name: (dims, dtype)} fed beside `toks` and `labels`."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.core import executor as ex
    from paddle_tpu.ops import (flash_ops, moe_ops, qk_ops, short_conv_ops,
                                ssm_ops)

    monkeypatch.setattr(ssm_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(short_conv_ops, "kernels_eligible",
                        short_conv_ops._shapes_ok)
    monkeypatch.setattr(qk_ops, "kernels_eligible", qk_ops._shapes_ok)
    monkeypatch.setattr(
        flash_ops, "flash_eligible", lambda q, k=None, window=0: (
            flash_ops._shapes_flash_ok(q, q if k is None else k, window)
            and flash_ops._prefers_flash(q, q if k is None else k)))
    monkeypatch.setattr(moe_ops, "gmm_eligible", moe_ops._shapes_gmm_ok)
    pt.reset()
    model = build()
    prog, startup = pt.default_main_program(), pt.default_startup_program()
    if for_test:
        prog = prog.clone(for_test=True)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), np.dtype(dtype),
                                    sharding=one_chip)

    made = {n for op in startup.global_block().ops
            for names in op.outputs.values() for n in names}
    state = {v.name: shape(v.shape, v.dtype) for v in prog.persistables()
             if v.name in made}
    rebound = ex.rebound_persistables(prog)
    donated = {n: v for n, v in state.items() if n in rebound}
    kept = {n: v for n, v in state.items() if n not in rebound}
    feed = {"toks": shape((batch, seqlen), np.int32),
            "labels": shape((batch, seqlen, 1), np.int32)}
    feed.update({n: shape(*v) for n, v in (more_feed or {}).items()})
    fetch = [model["cost"].name] + [s["var"] for s in prog.step_statistics]
    return (pt.Executor()._raw_step(prog, fetch),
            (donated, kept, feed, shape((), np.uint32)))


@pytest.mark.parametrize("name", sorted(STEP_PROGRAMS))
def test_step_programs_of_the_older_configurations_did_not_change(
        one_chip, compiled_mode, monkeypatch, name):
    raw, args = _step_program(lambda: _benchmark_model(name, 1, 1024), 1, 1024,
                              one_chip, monkeypatch)
    jaxpr = str(jax.make_jaxpr(raw)(*args))
    assert "pallas_call" in jaxpr       # the kernels are in what is hashed
    text = jax.jit(raw, donate_argnums=(0,)).lower(*args).as_text()
    assert (_digest(jaxpr), _digest(text, kernel_bodies=False)) \
        == STEP_PROGRAMS[name]


@pytest.mark.parametrize("name", sorted(INFERENCE_PROGRAMS))
def test_program_without_an_autodiff_op_lowers_to_the_parents_text(
        one_chip, compiled_mode, monkeypatch, name):
    raw, args = _step_program(lambda: _benchmark_model(name, 1, 1024), 1, 1024,
                              one_chip, monkeypatch, for_test=True)
    text = jax.jit(raw, donate_argnums=(0,)).lower(*args).as_text()
    assert "tpu_custom_call" in text
    assert _digest(text, kernel_bodies=False) == INFERENCE_PROGRAMS[name]


def _launches(jaxpr):
    """Pallas launches of a traced program by kernel name, those inside
    called sub-programs (custom_vjp rules, checkpoints, loops) counted as
    often as they are called."""
    import collections

    found = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[str(eqn.params["name"] or "grouped_matmul")] += 1
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _launches(sub)
    return found


@pytest.mark.parametrize("name", sorted(STEP_LAUNCHES))
def test_step_program_holds_each_forward_kernel_launch_once(
        one_chip, compiled_mode, monkeypatch, name):
    """Two thirds of the launches the parent of PR 33 traced: per attention
    layer one forward and one fused backward (it held a second, plain
    forward), per routed layer 3 grouped matmuls forward and 6 backward (it
    held 3 more forward)."""
    raw, args = _step_program(lambda: _benchmark_model(name, 1, 1024), 1, 1024,
                              one_chip, monkeypatch)
    assert dict(_launches(jax.make_jaxpr(raw)(*args).jaxpr)) \
        == STEP_LAUNCHES[name]


# `pt_qk_assemble_dispatch_total{op,emit}` of one traced training step of each
# benchmark configuration (batch 1 x T 1024): the norms and rotaries an
# attention layer marked in front of its kernel, one increment an op traced.
# olmoe: one layer, Q and K each a whole-width norm in front of a rotary.
# trinity: five layers, the four window layers as olmoe's (per head), the
# global layer's two norms the last ops in front of its kernel. glm: five
# latent layers, Q's partial rotary and the one-head `k_rope`'s. ouro: eight
# layers' Q and K rotaries in `Repeat`'s body, which is traced once. gpt2-small
# and the hybrid have neither op on Q or K.
QK_ASSEMBLE_COUNTS = {
    "gpt2-small": {},
    "nemotron-3-nano-30b-a3b": {},
    "olmoe-1b-7b": {("rms_norm", "float32"): 2,
                    ("rotary_embedding", "kernel"): 2},
    "trinity-mini": {("rms_norm", "float32"): 8, ("rms_norm", "kernel"): 2,
                     ("rotary_embedding", "kernel"): 8},
    "glm-4.7-flash": {("rotary_embedding", "kernel"): 10},
    "ouro-2.6b": {("rotary_embedding", "kernel"): 16},
}


@pytest.mark.parametrize("name", sorted(QK_ASSEMBLE_COUNTS))
def test_qk_assemble_counts_what_a_layer_marked(one_chip, compiled_mode,
                                                monkeypatch, name):
    from paddle_tpu.obs import metrics

    def counts():
        reg = metrics.registry()
        return {(op, emit): reg.counter_value(
            "pt_qk_assemble_dispatch_total", labels={"op": op, "emit": emit})
            for op in ("rms_norm", "rotary_embedding")
            for emit in ("kernel", "float32")}

    raw, args = _step_program(lambda: _benchmark_model(name, 1, 1024), 1, 1024,
                              one_chip, monkeypatch)
    before = counts()
    jaxpr = jax.make_jaxpr(raw)(*args)
    traced = {k: int(n - before[k]) for k, n in counts().items()
              if n != before[k]}
    assert traced == QK_ASSEMBLE_COUNTS[name]
    # one launch forward and one backward for each op that emits to a kernel
    # on whole lane tiles turned whole (glm's partial turn: XLA's), and none
    # for a norm the rotary behind it absorbed
    launches = _launches(jaxpr.jaxpr)
    last = sum(n for (_, emit), n in traced.items() if emit == "kernel")
    want = 0 if name == "glm-4.7-flash" else last
    if name == "ouro-2.6b":
        # the body forward in the loop, for the last turn and run again;
        # backward for the last turn and in the loop (PR 56)
        assert (launches["qk_assemble_fwd"], launches["qk_assemble_bwd"]) \
            == (3 * want, 2 * want)
    else:
        assert (launches.get("qk_assemble_fwd", 0),
                launches.get("qk_assemble_bwd", 0)) == (want, want)


def test_nemotron_step_program_fits_one_chip(one_chip, compiled_mode,
                                             monkeypatch):
    """The hybrid's whole step at the size `configs/nemotron_h.py` trains
    (batch 1 x T 8192, 667 M parameters) compiles for the described v5e: it
    fits 15.75 GiB without `Program.remat_policy` (the compiler refuses a
    program that does not), the attention and grouped-matmul kernels are in
    it, and no `ragged-dot` is (XLA's own grouped kernel, which takes the
    job where the width padding fails to hand it to megablox, carries no
    scope for a trace's readers)."""
    from paddle_tpu.obs import metrics

    config = _load_module(os.path.join(ROOT, "configs", "nemotron_h.py"))
    raw, args = _step_program(config.get_model, 1, 8192, one_chip,
                              monkeypatch)
    counted = lambda: [metrics.registry().counter_value(  # noqa: E731
        "pt_ssm_conv_dispatch_total", labels={"path": p})
        for p in ("pallas", "xla")]
    before = counted()
    lowered = jax.jit(raw, donate_argnums=(0,)).lower(*args)
    # the step's one trace counts each of the four mixers' convs once
    assert [b - a for a, b in zip(before, counted())] == [4, 0]
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "flash_attention_bwd" in text and "ragged-dot" not in text
    # the four mixers' scans are the kernels: forward, the checkpoint's
    # second forward (it writes the chunk states) and backward, every one
    # under the op's inner `scan` scope, where `ssm.scan_ms` finds it; and
    # around them no layout copy of a [chunks x groups, heads, P, N] float32
    # array is left under a mixer's scope (XLA's einsums had twelve)
    kernels = re.findall(
        r'custom-call\(.*custom_call_target="tpu_custom_call".*'
        r'op_name="([^"]*ssd_scan_(?:fwd|bwd)[^"]*)"', text)
    assert len(kernels) == 12, kernels
    assert all("mamba2_mixer." in name and "/scan/" in name
               for name in kernels), kernels
    assert sum("transpose(jvp(" in name for name in kernels) == 8
    assert not re.findall(
        r"= f32\[512,8,64,128\]\S* copy\(.*op_name=\"[^\"]*mamba2_mixer",
        text)
    # the gated norm is the kernels' epilogue: y never reaches HBM, so the
    # float32 [T, H P] y is nowhere relaid for XLA's norm (the parent held
    # twelve `copy` of float32 [1024,8,8,512], with no op name, and twelve
    # `reshape` of float32 [1,8192,4096] under `gate_norm`), the forward
    # kernels write bf16 and no row is left under a `gate_norm` scope
    assert not re.findall(
        r"= f32\[(?:1024,8,8,512|1,8192,4096|8192,8,512)\]\S* "
        r"(?:copy|reshape)\(", text)
    assert "/gate_norm/" not in text
    assert len(re.findall(
        r"= \(bf16\[1,8192,4096\]\S*, f32\[1,64,4096,128\]\S*\) "
        r"custom-call\(.*ssd_scan_fwd", text)) == 8
    # ... and the conv, its bias and its silu are one kernel a direction
    # (PR 50): forward, the checkpoint's second forward and backward, every
    # one under the op's inner `conv` scope, where `ssm.conv_ms` finds it.
    # The backward reads the scan backward's dx, dB and dC as they are (the
    # slices of their concatenation fold into the three arrays), and no
    # float32 array of the packed [1, 8192, 6144] is left anywhere in the
    # step: the pad, the shifted windows, the sum kept for silu's derivative
    # and the transposed taps' padded cotangent all went
    convs = re.findall(
        r'custom-call\(.*custom_call_target="tpu_custom_call".*'
        r'op_name="([^"]*causal_conv_silu_(?:fwd|bwd)[^"]*)"', text)
    assert len(convs) == 12, convs
    assert all("mamba2_mixer." in name and "/conv/" in name
               for name in convs), convs
    assert sum("transpose(jvp(" in name for name in convs) == 8
    assert "f32[1,8192,6144]" not in text
    assert not re.findall(r"= bf16\[1,8192,6144\]\S* (?:concatenate|fusion)\(",
                          text)
    assert dict(_launches(jax.make_jaxpr(raw)(*args).jaxpr)) == {
        "flash_attention_fwd": 1, "flash_attention_bwd": 1,
        "grouped_matmul": 32, "ssd_scan_fwd": 8, "ssd_scan_bwd": 4,
        "causal_conv_silu_fwd": 8, "causal_conv_silu_bwd": 4}
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 7.9e9          # 12 B a parameter
    # the parent's books read 7.454 + 4.687 GiB here and the chip 12.215
    # (4.73 G of program); this program's read 7.454 + 4.872 and the chip
    # 4.80 G of program: XLA's memory scheduler hands the two programs
    # different orders (PERF.md section 6, PR 43)
    print("nemotron step: arguments %.3f GiB, temporaries %.3f GiB" % (
        memory.argument_size_in_bytes / 2**30,
        memory.temp_size_in_bytes / 2**30))
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 12.4 * 2**30)


@pytest.mark.parametrize("where,chunk,what,path,conv", [
    ("tpu", 128, "mixer", "pallas_chunked_gated", "xla"),
    ("tpu", 128, "scan", "pallas_chunked", None),
    ("cpu", 128, "mixer", "xla_chunked", "xla"),
    ("tpu_mesh", 128, "mixer", "xla_chunked", "xla"),
    ("tpu", 16, "mixer", "xla_chunked", "xla"),
    ("tpu", 128, "mixer_amp", "pallas_chunked_gated", "pallas"),
    ("cpu", 128, "mixer_amp", "xla_chunked", "xla"),
    ("tpu_mesh", 128, "mixer_amp", "xla_chunked", "xla")])
def test_scan_dispatch_counts_the_path_it_chose(monkeypatch, where, chunk,
                                                what, path, conv):
    """`pt_ssm_scan_dispatch_total{path}`: for an eligible shape where the
    backend is the TPU (steered: nothing is lowered here, the op is only
    traced) the mixer takes the kernels with the gated norm as their
    epilogue and `ssd_chunked_scan` alone the kernels without it; the
    einsums (and XLA's norm) on the CPU, under an active mesh (a bare
    `pallas_call` cannot be partitioned) and at a chunk the kernels do not
    take. `pt_ssm_conv_dispatch_total{path}` beside it: a mixer's conv, bias
    and silu take their kernels (`pallas`) on the TPU for a bf16 projection
    (`mixer_amp`) of whole row blocks, and XLA's form (`xla`) in float32, at
    256 tokens, on the CPU and under a mesh. One increment an op traced,
    under one label; no flag and no attribute chooses."""
    import contextlib

    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.obs import metrics
    from paddle_tpu.ops import mesh_dispatch, ssm_ops

    if where != "cpu":
        monkeypatch.setattr(ssm_ops, "_on_tpu", lambda: True)
    H, P, G, N, d = 4, 64, 2, 128, 64
    T_ = 256
    if what == "mixer_amp":     # one row block of one 512-lane tile
        G, T_ = 1, 1024
    width = 2 * H * P + 2 * G * N + H
    if what != "scan":
        shapes = [(1, T_, d), (d, width), (4, H * P + 2 * G * N),
                  (H * P + 2 * G * N,), (H,), (H,), (H,), (H * P,),
                  (H * P, d)]
        # under amp the stream and the two projection matrices are bf16
        low = (0, 1, 8) if what == "mixer_amp" else ()
        dtypes = [BF16 if at in low else F32 for at in range(len(shapes))]
        traced = lambda *a: ssm_ops.mamba2_mixer(  # noqa: E731
            *a, num_heads=H, head_dim=P, n_groups=G, state_size=N, eps=1e-5,
            chunk=chunk)
        want = (1, T_, d)
    else:
        shapes = [(1, 256, H, P), (1, 256, H), (H,), (1, 256, G, N),
                  (1, 256, G, N), (H,)]
        dtypes = [F32] * len(shapes)
        traced = lambda *a: ssm_ops.ssd_chunked_scan(  # noqa: E731
            *a, chunk=chunk)
        want = (1, 256, H, P)
    families = {
        "pt_ssm_scan_dispatch_total": (
            "pallas_chunked_gated", "pallas_chunked", "xla_chunked"),
        "pt_ssm_conv_dispatch_total": ("pallas", "xla")}
    count = lambda: {  # noqa: E731
        (family, p): metrics.registry().counter_value(
            family, labels={"path": p})
        for family, paths in families.items() for p in paths}
    before = count()
    mesh = mesh_dispatch.active_mesh(
        Mesh(np.array(jax.devices()[:1]), ("dp",)), "dp") \
        if where == "tpu_mesh" else contextlib.nullcontext()
    with mesh:
        out = jax.eval_shape(traced, *[jax.ShapeDtypeStruct(s, dt)
                                       for s, dt in zip(shapes, dtypes)])
    assert out.shape == want
    after = count()
    assert {k: after[k] - before[k] for k in after} == {
        (family, p): int(p == chosen)
        for family, chosen in zip(families, (path, conv))
        for p in families[family]}


def test_glm_moe_step_program_fits_one_chip(one_chip, compiled_mode,
                                            monkeypatch):
    """The GLM-4.7-Flash share's whole step at the size `configs/glm_moe.py`
    trains (batch 1 x T 8192, 591 M parameters, five latent-attention layers
    at 20 heads x 256) compiles for the described v5e: it fits 15.75 GiB
    without `Program.remat_policy` (under 15.0 GiB by the compiler's own
    books, which settles T 8192 against 4096 before any chip time), the
    attention kernels at head size 256 (the fused backward with its
    [8192, 256] float32 dQ accumulator in VMEM) and the grouped-matmul
    kernels are in it, and no `ragged-dot` is."""
    config = _load_module(os.path.join(ROOT, "configs", "glm_moe.py"))
    raw, args = _step_program(config.get_model, 1, 8192, one_chip,
                              monkeypatch)
    launches = dict(_launches(jax.make_jaxpr(raw)(*args).jaxpr))
    assert launches["flash_attention_fwd"] == 5
    assert launches["flash_attention_bwd"] == 5
    compiled = jax.jit(raw, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert "flash_attention_bwd" in text and "ragged-dot" not in text
    assert "gmm" in text
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 7.0e9          # 12 B a parameter
    print("glm step: arguments %.3f GiB, temporaries %.3f GiB" % (
        memory.argument_size_in_bytes / 2**30,
        memory.temp_size_in_bytes / 2**30))
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.0 * 2**30)


def test_looped_lm_step_program_fits_one_chip(one_chip, compiled_mode,
                                              monkeypatch):
    """The ouro-2.6b cell's whole step (batch 1 x T 4096, 8 layers of Ouro-2.6B
    run four times through `layers.Repeat`, 612 M parameters) compiles for the
    described v5e under 15.0 GiB by `memory_analysis()` (arguments 6.845 +
    temporaries 7.827, which overstate: the compiler's `program HBM usage` line
    says 6.06 G beside the arguments, and the chip reads that to 0.01), and
    holds the loop as `while`s (a forward and a backward one, of K - 1 turns
    each) with the LAST turn beside them, differentiated where it stands (PR
    56: the backward loop ran it again from its carry, as it runs the others).
    So the jaxpr calls the flash kernels 3 L times forward and 2 L backward:
    the stack once in the forward loop's body, once for the last turn with
    its residuals kept and once more in the backward loop's body, which runs a
    turn again; the transposed stack for the last turn and once in the
    backward loop's body. Not K L times either way. The temporaries stay
    within 0.1 GiB of the 7.877 that the lowering which ran all K turns again
    printed here: the backward loop's gradient sums START from the last
    turn's, so there is one set of them, and the last turn's residuals are
    dead before the loop begins."""
    layers_, turns = 8, 4
    raw, args = _step_program(lambda: _benchmark_model("ouro-2.6b", 1, 4096),
                              1, 4096, one_chip, monkeypatch)
    jaxpr = jax.make_jaxpr(raw)(*args)
    launches = dict(_launches(jaxpr.jaxpr))
    # a layer's Q and K rotaries ride with its attention kernels (PR 47)
    assert launches == {"flash_attention_fwd": 3 * layers_,
                        "flash_attention_bwd": 2 * layers_,
                        "qk_assemble_fwd": 6 * layers_,
                        "qk_assemble_bwd": 4 * layers_}
    assert str(jaxpr).count(f"length={turns - 1}") >= 2      # the two scans
    compiled = jax.jit(raw, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"\bwhile\(", text)) == 2
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*', text)
    forward = [c for c in calls if "flash_attention_fwd" in c]
    backward = [c for c in calls if "flash_attention_bwd" in c]
    assert (len(forward), len(backward)) == (3 * layers_, 2 * layers_)
    assert max(len(forward), len(backward)) < turns * layers_
    # the forward that the backward loop runs again says so in its path,
    # where `repeat.recompute_ms` looks for it: one stack's kernels
    assert len([c for c in forward if "/rematted_computation/" in c
                and "transpose(jvp(repeat." in c]) == layers_
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 7.3e9          # 12 B a parameter
    print("looped step: arguments %.3f GiB, temporaries %.3f GiB" % (
        memory.argument_size_in_bytes / 2**30,
        memory.temp_size_in_bytes / 2**30))
    assert memory.temp_size_in_bytes < (7.877 + 0.1) * 2**30
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.0 * 2**30)


def test_lfm2_step_program_fits_one_chip(one_chip, compiled_mode,
                                         monkeypatch):
    """The lfm2-24b-a2b cell's whole step (batch 1 x T 16 384, 486 M
    parameters: published layers 1-5 of LFM2-24B-A2B, conv | attention, conv,
    conv, conv, the first dense, 8 of 64 experts held) compiles for the
    described v5e with arguments + temporaries under 14.5 GiB by the
    compiler's own books (which settles T 16 384 against 8192 before any chip
    time), ONE forward and one fused-backward attention launch at 32 heads of
    64 over 16 k keys, the grouped-matmul kernels in it and no `ragged-dot`,
    and no float32 array of the operators' [T, 6144] projection."""
    raw, args = _step_program(
        lambda: _benchmark_model("lfm2-24b-a2b", 1, 16384), 1, 16384,
        one_chip, monkeypatch)
    launches = dict(_launches(jax.make_jaxpr(raw)(*args).jaxpr))
    # 16 384 query rows of a 128-lane block: dQ for the whole sequence still
    # fits the fused backward's VMEM (`_FUSED_BWD_MAX_ELEMENTS`)
    assert {n: c for n, c in launches.items() if n.startswith("flash")} == {
        "flash_attention_fwd": 1, "flash_attention_bwd": 1}
    # every operator's mix is one launch a direction
    assert launches["gated_short_conv_fwd"] == 4
    assert launches["gated_short_conv_bwd"] == 4
    compiled = jax.jit(raw, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert "flash_attention_bwd" in text and "ragged-dot" not in text
    assert "gmm" in text
    assert "f32[1,16384,6144]" not in text
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 5.8e9          # 12 B a parameter
    print("lfm2 step: arguments %.3f GiB, temporaries %.3f GiB" % (
        memory.argument_size_in_bytes / 2**30,
        memory.temp_size_in_bytes / 2**30))
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 14.5 * 2**30)


def test_keye_step_program_fits_one_chip(one_chip, compiled_mode,
                                         monkeypatch):
    """The keye-vl-2.0-30b-a3b cell's whole step (batch 1 x T 16 384, 465 M
    parameters of which 456 M are trained: four layers of learned sparse
    attention over 16 of 128 experts, an eighth of the vocabulary) compiles
    for the described v5e with arguments + temporaries under 13 GiB by the
    compiler's own books (which settles T 16 384 before any chip time), four
    forward and four fused-backward attention launches under the keep operand
    (and no other: the plain form is not in the program), eight norm-and-rotary
    launches a direction with the fed tables, the selection by counting (no
    sort in the program), the grouped-matmul kernels in it, and NO array of
    [T, T] elements of any type: the largest the indexer writes is a tile's
    [512, 16, 16384] scores."""
    T = 16384
    raw, args = _step_program(
        lambda: _load_module(os.path.join(
            ROOT, "chipbench", "configs", "keye-vl-2.0-30b-a3b", "model.py")
        ).get_model(
            __import__("json").load(open(os.path.join(
                ROOT, "chipbench", "configs", "keye-vl-2.0-30b-a3b",
                "config.json"))),
            {"batch": 1, "seqlen": T, "image_spans": 4, "image_grid": 32}, 7),
        1, T, one_chip, monkeypatch,
        more_feed={"positions": ((1, 3, T), "int32")})
    launches = dict(_launches(jax.make_jaxpr(raw)(*args).jaxpr))
    assert {n: c for n, c in launches.items()
            if n.startswith(("flash", "qk_"))} == {
        "flash_attention_fwd": 4, "flash_attention_bwd": 4,
        "qk_assemble_fwd": 8, "qk_assemble_bwd": 8}
    compiled = jax.jit(raw, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert "gmm" in text and "ragged-dot" not in text
    assert not [line for line in text.splitlines()      # the router sorts
                if " sort(" in line and "sparse_keep" in line]
    assert not re.findall(r"\b[a-z]+\d*\[[\d,]*16384,16384[\d,]*\]", text)
    biggest = max(n for _, dtype, n, body, _ in _written_arrays(text)
                  if not body and dtype in ("f32", "bf16"))
    assert biggest <= T * 18992                 # the head's logits
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 5.4e9          # 12 B a trained one
    print("keye step: arguments %.3f GiB, temporaries %.3f GiB" % (
        memory.argument_size_in_bytes / 2**30,
        memory.temp_size_in_bytes / 2**30))
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 13.0 * 2**30)


def test_phi4_step_program_fits_one_chip(one_chip, compiled_mode,
                                         monkeypatch):
    """The phi-4-mini-flash-reasoning cell's whole step (batch 1 x T 8192,
    697 M parameters: published layers 0, 1, 16, 17, 18, 19 of
    Phi-4-mini-flash-reasoning) compiles for the described v5e with arguments
    + temporaries under 14.5 GiB by the compiler's own books (which settles
    T 8192 against 4096 before any chip time: ISSUE 57's rule), three
    forward and three backward attention launches at 40 heads of 64 in pairs
    (ONE a layer since PR 58, where four at 20 heads ran), each mixer's scan
    forward twice (once again inside its checkpoint) and backward once, no
    array of [T, d_in, N] shape, and neither K nor V repeated to the query
    heads in front of a kernel. The HBM reading is printed (`-s`)."""
    raw, args = _step_program(
        lambda: _benchmark_model("phi-4-mini-flash-reasoning", 1, 8192), 1,
        8192, one_chip, monkeypatch)
    launches = dict(_launches(jax.make_jaxpr(raw)(*args).jaxpr))
    assert launches == {
        "flash_attention_fwd": 3, "flash_attention_bwd": 3,
        "selective_scan_fwd": 4, "selective_scan_bwd": 2,
        "causal_conv_silu_fwd": 4, "causal_conv_silu_bwd": 2}
    compiled = jax.jit(raw, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert "selective_scan_bwd" in text and "flash_attention_bwd" in text
    for shape in ("[1,8192,5120,16]", "[1,8192,16,5120]", "[8192,5120,16]",
                  "[8192,16,5120]"):
        assert shape not in text, shape
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 8.3e9          # 12 B a parameter
    print("phi4 step: arguments %.3f GiB, temporaries %.3f GiB" % (
        memory.argument_size_in_bytes / 2**30,
        memory.temp_size_in_bytes / 2**30))
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 14.5 * 2**30)


_AFMOE_STEP = {}


def _afmoe_step(one_chip, monkeypatch):
    """Trinity's step at the size `configs/afmoe.py` trains, traced, lowered
    and compiled for the described v5e ONCE for the tests below (one worker
    runs this file): its launches, texts and memory, and the scopes of the
    norms and rotaries between each layer's `q_proj` and its kernel."""
    if _AFMOE_STEP:
        return _AFMOE_STEP
    import paddle_tpu as pt
    from paddle_tpu.core.executor import _op_scope

    config = _load_module(os.path.join(ROOT, "configs", "afmoe.py"))
    raw, args = _step_program(config.get_model, 1, 8192, one_chip,
                              monkeypatch)
    ops = pt.default_main_program().global_block().ops
    made_by = {n: op for op in ops for n in op.output_names()}
    scopes = []
    for kernel in (op for op in ops if op.type == "flash_attention"):
        op = made_by[kernel.inputs["Q"][0]]
        while op.type in ("rotary_embedding", "rms_norm"):
            scopes.append(_op_scope(op))
            op = made_by[op.inputs["X"][0]]
        assert op.type == "mul"
    lowered = jax.jit(raw, donate_argnums=(0,)).lower(*args)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    _AFMOE_STEP.update(
        launches=dict(_launches(jax.make_jaxpr(raw)(*args).jaxpr)),
        lowered=lowered.as_text(), compiled=compiled.as_text(),
        arguments=memory.argument_size_in_bytes,
        temporaries=memory.temp_size_in_bytes, q_scopes=scopes)
    return _AFMOE_STEP


def test_afmoe_step_program_fits_one_chip(one_chip, compiled_mode,
                                          monkeypatch):
    """The Trinity-Mini share's whole step at the size `configs/afmoe.py`
    trains (batch 1 x T 8192, 705 M parameters: a dense layer and four routed
    ones of 16 held experts, 32-over-4 heads of 128, layers window, window,
    global, window, window) compiles for the described v5e: under 15.0 GiB by
    the compiler's own books (which settles the 8-chip share against the
    16-chip one before any chip time), five forward and five fused backward
    attention launches, the four window layers' kernels ANOTHER kernel body
    than the global layer's (the lower diagonal is in it), the grouped-matmul
    kernels in it and no `ragged-dot`."""
    step = _afmoe_step(one_chip, monkeypatch)
    assert step["launches"]["flash_attention_fwd"] == 5
    assert step["launches"]["flash_attention_bwd"] == 5
    # by kernel name, the distinct serialized bodies in the lowered text (a
    # jitted launch is lowered once however many layers call it): the global
    # layer's and the window layers'
    bodies = {}
    for body, name in re.findall(
            r'backend_config = "((?:[^"\\]|\\.)*)"[^\n]*?'
            r'kernel_name = "(flash_attention_\w+)"', step["lowered"]):
        bodies.setdefault(name, set()).add(body)
    assert {n: len(b) for n, b in bodies.items()} == {
        "flash_attention_fwd": 2, "flash_attention_bwd": 2}
    text = step["compiled"]
    assert "flash_attention_bwd" in text and "ragged-dot" not in text
    assert "gmm" in text
    assert step["arguments"] > 8.4e9          # 12 B a parameter
    print("afmoe step: arguments %.3f GiB, temporaries %.3f GiB" % (
        step["arguments"] / 2**30, step["temporaries"] / 2**30))
    assert step["arguments"] + step["temporaries"] < 15.0 * 2**30


def test_afmoe_step_writes_no_float32_array_of_qs_size_in_front_of_a_kernel(
        one_chip, compiled_mode, monkeypatch):
    """Between a layer's `q_proj` `mul` and its `flash_attention` (the scopes
    of the norm and the rotary the layer marked, forward and transposed) the
    compiled step writes nothing of Q's size, 8192 x 4096, in float32: the
    parent wrote four such arrays a layer forward (the dot's own output, the
    norm's, the rotary's halves) and as many backward. What is written there
    is the two kernels' bf16: ten launches of a Q or K each way."""
    step = _afmoe_step(one_chip, monkeypatch)
    scopes = step["q_scopes"]
    assert sorted(s.split(".")[0] for s in scopes) == \
        ["rms_norm"] * 5 + ["rotary_embedding"] * 4
    assert (step["launches"]["qk_assemble_fwd"],
            step["launches"]["qk_assemble_bwd"]) == (10, 10)
    under = [a for a in _written_arrays(step["compiled"])
             if a[2] == 8192 * 4096 and not a[3]
             and any(s + ")" in a[4] or s + "/" in a[4] for s in scopes)]
    # the reading has teeth: each Q's kernel writes under its last op's scope,
    # forward and backward, in bf16
    assert sum(a[:2] == ("custom-call", "bf16") for a in under) == 10
    assert not [a for a in under if a[1] == "f32"], under
    assert not [a for a in under if a[0] == "copy"], under


def test_a_share_under_a_half_runs_its_kernels_on_a_chunk_of_its_rows(
        one_chip, compiled_mode, monkeypatch):
    """The hybrid's routed layer as one chip of 16 holds it (T 8192, top 6,
    d 2688, f 1856, 8 of 128 experts), forward and backward: every grouped
    matmul runs at m = 6 144 rows, a chunk, inside one of the two loops
    over chunks, and as often as the whole-rows share launched them (2
    forward; 2 again, 2 `gmm` and 2 `tgmm` backward), so the program holds
    no second copy of the kernels; no float array of 49 152 rows exists
    anywhere; no d-wide rows are scattered, forward or backward (PR 51): the
    rows move by gathers alone, of a chunk's 6144 rows or of one of a
    token's slots (half of the 8192 tokens at a time: 49 152 pairs are
    eight chunks), each inside a chunk's loop; and the step compiles
    for the chip with the loops and the kernels in it and no conditional."""
    from paddle_tpu.ops import moe_ops

    monkeypatch.setattr(moe_ops, "gmm_eligible", moe_ops._shapes_gmm_ok)
    T, k, d, f, E, held = 8192, 6, 2688, 1856, 128, 8
    assert moe_ops.row_bound(T * k, (0, held), E, 256) == 6144

    def step(x, wr, up, down):
        def cost(x, wr, up, down):
            out, _, _, pairs, path, _ = moe_ops.moe_ffn(
                x, wr, None, up, down, k, True, scoring="sigmoid",
                gate_scale=2.5, held=(0, held))
            return out.astype(F32).sum(), (pairs, path)

        return jax.value_and_grad(cost, (0, 1, 2, 3), has_aux=True)(
            x, wr, up, down)

    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in (
        ((T, d), F32), ((d, E), F32), ((held, d, f), BF16),
        ((held, f, d), BF16))]
    kernels, whole_rows, moved = [], [], []

    def walk(jaxpr, loops):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("gather", "scatter-add", "scatter"):
                moved.extend(
                    (eqn.primitive.name, loops, v.aval.shape[0])
                    for v in (*eqn.invars[2:], *eqn.outvars)
                    if v.aval.ndim == 2 and v.aval.shape[1] == d)
            if eqn.primitive.name == "pallas_call":
                kernels.append((loops, {
                    v.aval.shape[0] for v in eqn.invars
                    if v.aval.ndim == 2 and v.aval.shape[0] in (6144, T * k)}))
            whole_rows.extend(
                (eqn.primitive.name, v.aval) for v in eqn.outvars
                if getattr(v.aval, "ndim", 0) == 2
                and v.aval.shape[0] == T * k
                and jnp.issubdtype(v.aval.dtype, jnp.floating))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, loops + (eqn.primitive.name == "while"))

    walk(jax.make_jaxpr(step)(*args).jaxpr, 0)
    assert kernels == [(1, {6144})] * 8
    assert not whole_rows, whole_rows
    assert {m[0] for m in moved} == {"gather"}, moved
    assert {m[1:] for m in moved} == {(1, 6144), (1, T // 2)}, moved
    text = jax.jit(step).lower(*args).compile().as_text()
    assert len(re.findall(r" while\(", text)) >= 2     # forward, backward
    assert " conditional(" not in text
    assert "tpu_custom_call" in text and "ragged-dot" not in text
    assert not re.findall(r"= f32\[\d+,%d\]\S* scatter\(" % d, text)


def _written_arrays(hlo_text):
    """(opcode, dtype, elements, in_fusion_body, op_name) of every array that
    an instruction of the optimized HLO produces. What an instruction of a
    fusion's body produces stays in registers; the rest is written out."""
    bodies = set(re.findall(r" fusion\(.*calls=%([\w.\-]+)", hlo_text))
    out, body = [], False
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            body = head.group(1) in bodies
            continue
        inst = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (.*)", line)
        if not inst:
            continue
        # the opcode is the first lower-case word before a "(" that follows
        # a space: tiling annotations (`:T(8,128)`, `S(1)`) never do
        opcode = re.search(r"(?:^|\s)([a-z][\w\-]*)\(", inst.group(1))
        if opcode.group(1) in ("parameter", "tuple", "get-tuple-element",
                               "bitcast"):
            continue
        op_name = re.search(r'op_name="([^"]*)"', inst.group(1))
        for dtype, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]",
                                      inst.group(1)[:opcode.start()]):
            elements = math.prod(int(d) for d in dims.split(",") if d)
            out.append((opcode.group(1), dtype, elements, body,
                        op_name.group(1) if op_name else ""))
    return out


def test_gpt2_head_writes_no_float32_logits(one_chip, compiled_mode):
    """gpt2-small's output head at its real widths, as `layers.fc` and the
    cost op build it under bf16 AMP: [12, 1024, 768] x [768, 50257], the
    cost, its mean, the gradients to the activations and the weight. The
    gain of the cost op's custom_vjp over flattened logits rests on what
    this pins: no float32 array of tokens x V elements is written out, no
    array of that size is copied in any dtype, and the step's temporaries
    stay under the plain log_softmax formulation's (the op before PR 31,
    inline here as the yardstick). The weight's own [768, 50257] is free
    to be laid out as XLA likes."""
    from paddle_tpu.core.program import Operator
    from paddle_tpu.core.registry import OpContext
    from paddle_tpu.ops.nn_ops import softmax_with_cross_entropy_kernel

    Bt, Tt, E, V = 12, 1024, 768, 50257

    def cost_op(logits, label):
        op = Operator("softmax_with_cross_entropy",
                      {"Logits": ["x"], "Label": ["l"]},
                      {"Softmax": ["s"], "Loss": ["y"]}, {})
        env = {"x": logits, "l": label}
        softmax_with_cross_entropy_kernel(OpContext(op, env))
        return env["y"]

    def plain(logits, label):
        logp = jax.nn.log_softmax(logits.astype(F32), axis=-1)
        lbl = jnp.clip(label[..., 0], 0, V - 1)
        return -jnp.take_along_axis(logp, lbl[..., None], axis=-1)

    def head(cost):
        def loss(h, w, label):
            logits = h.reshape(Bt * Tt, E) @ w.astype(BF16)
            return jnp.mean(cost(logits.reshape(Bt, Tt, V), label))
        return jax.value_and_grad(loss, (0, 1))

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
            (((Bt, Tt, E), BF16), ((E, V), F32), ((Bt, Tt, 1), jnp.int32))]
    new = jax.jit(head(cost_op)).lower(*args).compile()
    old = jax.jit(head(plain)).lower(*args).compile()

    def big(compiled):
        return [a for a in _written_arrays(compiled.as_text())
                if a[2] == Bt * Tt * V]

    # the reading has teeth: the yardstick does write float32 logits
    assert any(d == "f32" and not body for _, d, _, body, _ in big(old))
    assert not [a for a in big(new) if a[1] == "f32" and not a[3]], big(new)
    assert not [a for a in big(new) if a[0] == "copy"], big(new)
    assert (new.memory_analysis().temp_size_in_bytes
            < old.memory_analysis().temp_size_in_bytes)


def test_largest_gru_h_is_the_published_maximum(compiled_mode):
    """The `largest_h` cases above must be compiling the reference's
    largest published hidden size, not a window that quietly shrank."""
    assert _largest_gru_h() == 1280
