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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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
    """A step program holds the forward ops twice (`Executor` traces them and
    `_run_autodiff` traces them again). The plain attention forward writes no
    statistics, so it is another kernel than the differentiated forward and
    XLA keeps both; as ONE identical jitted launch it merges them, and with
    them the whole doubled forward (gpt2-small's step 139.8 -> 110.6 ms on the
    chip, PR 28), which shrinks olmoe-1b-7b's step program under the plain
    reference's and fails the benchmark's memory rule. Whoever repairs that
    rule flips this count to 2 (ROADMAP Queue 1 item 3b)."""
    from paddle_tpu.ops import flash_ops

    def both(q, k, v):
        att = lambda *a: flash_ops._packed_attention(*a, 12, True)  # noqa: E731
        grads = jax.grad(lambda *a: att(*a).astype(F32).sum(), (0, 1, 2))(
            q, k, v)
        return att(q, k, v), grads

    args = [jax.ShapeDtypeStruct((2, 1024, 768), BF16, sharding=one_chip)] * 3
    text = jax.jit(both).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def _written_arrays(hlo_text):
    """(opcode, dtype, elements, in_fusion_body) of every array that an
    instruction of the optimized HLO produces. What an instruction of a
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
        for dtype, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]",
                                      inst.group(1)[:opcode.start()]):
            elements = math.prod(int(d) for d in dims.split(",") if d)
            out.append((opcode.group(1), dtype, elements, body))
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
    assert any(d == "f32" and not body for _, d, _, body in big(old))
    assert not [a for a in big(new) if a[1] == "f32" and not a[3]], big(new)
    assert not [a for a in big(new) if a[0] == "copy"], big(new)
    assert (new.memory_analysis().temp_size_in_bytes
            < old.memory_analysis().temp_size_in_bytes)


def test_largest_gru_h_is_the_published_maximum(compiled_mode):
    """The `largest_h` cases above must be compiling the reference's
    largest published hidden size, not a window that quietly shrank."""
    assert _largest_gru_h() == 1280
