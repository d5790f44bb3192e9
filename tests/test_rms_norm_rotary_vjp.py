"""What stands between a Q or K projection and the attention kernel (PR 47):
the norm and the rotary an attention layer marks keep no float32 array of the
projection's shape, round ONCE, at the kernel's input, to the bits the plain
float32 forms (the ops before PR 47, inline here) give when they are rounded
there; their backwards are rules of their own (`ops/qk_ops.py:qk_assemble`)
and agree with autodiff of the plain forms. An op without the layer's mark is
the op it always was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import amp
from paddle_tpu.core import registry
from paddle_tpu.ops import nn_ops, qk_ops

F32, BF16 = jnp.float32, jnp.bfloat16
EPS = 1e-5


# ---- the plain float32 forms: what the ops were before PR 47 --------------
def plain_norm(x, scale, group=None):
    """float32 out of any input, per run of `group` lanes or the whole axis."""
    shape = x.shape
    if group is not None:
        x = x.reshape(shape[:-1] + (-1, group))
    x32 = x.astype(F32)
    out = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                              + EPS) * scale
    return out.reshape(shape)


def plain_rotary(x, heads, theta, rotary_dim=None):
    """[B, T, E] in, float32 out: slices and a concatenate."""
    B, T, E = x.shape
    D = E // heads
    R = D if rotary_dim is None else rotary_dim
    inv_freq = theta ** (-jnp.arange(0, R, 2, dtype=F32) / R)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x32 = x.reshape(B, T, heads, D).astype(F32)
    x1, x2 = x32[..., D - R: D - R // 2], x32[..., D - R // 2:]
    parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if R < D:
        parts.insert(0, x32[..., : D - R])
    return jnp.concatenate(parts, axis=-1).reshape(B, T, E)


def _ulp(dtype):
    return float(jnp.finfo(dtype).eps)


def _close(got, want, dtype):
    """Within 1e-5 of the largest value in float32, one ulp of it in bf16."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    tol = 1e-5 if dtype == F32 else _ulp(BF16)
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0), (
        np.abs(got - want).max(), np.abs(want).max())


def _rand(key, shape, dtype=F32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, F32).astype(dtype)


# ---- the ops, run as the Executor runs them -------------------------------
def _attention_ops(amp_dtype, **kw):
    """(ops in front of the kernel, the kernel's op, env with the parameters
    and the feed bound) of one attention layer of 4 heads of 8 over E 32."""
    pt.reset()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = pt.layers.data("x", shape=[16, 32], dtype=np.float32)
        pt.layers.multi_head_attention(x, num_heads=4, name="att",
                                       bias_attr=False, **kw)
    prog.random_seed = startup.random_seed = 3
    pt.Executor().run(startup)
    scope = pt.global_scope()
    env = {p.name: jnp.asarray(scope.get(p.name)) for p in prog.parameters()}
    for n in env:
        if n.endswith("_norm"):
            env[n] = 1.0 + 0.3 * _rand(7, env[n].shape)
    env["x"] = _rand(1, (2, 16, 32))
    if amp_dtype:
        env[amp.AMP_KEY] = amp_dtype
    block = prog.global_block()
    at = [o.type for o in block.ops].index("flash_attention")
    return block, block.ops[:at], block.ops[at], env


def _run(block, ops, env):
    for op in ops:
        registry.get_kernel(op.type)(registry.OpContext(op, env, block=block))
    return env


@pytest.mark.parametrize("amp_dtype", [None, "bfloat16"])
@pytest.mark.parametrize("kw", [
    dict(qk_norm=True, rotary_theta=1e4),                       # OLMoE's
    dict(qk_norm="head", rotary_theta=1e4, num_kv_heads=1, head_dim=8),
    dict(qk_norm="head", num_kv_heads=2, head_dim=8),           # a global layer
    dict(rotary_theta=1e4),                                     # Ouro's
], ids=["whole_norm_rotary", "head_norm_rotary_one_kv", "head_norm", "rotary"])
def test_kernel_inputs_are_the_plain_forms_rounded_once(kw, amp_dtype):
    """Q and K as the `flash_attention` op receives them: the dtype its own
    cast would give them (so the cast is a no-op) and the bits of the plain
    float32 norm and rotary of the projections, rounded once."""
    block, front, kernel, env = _attention_ops(amp_dtype, **kw)
    _run(block, front, env)
    low = BF16 if amp_dtype else F32
    projections = [o.outputs["Out"][0] for o in front if o.type == "mul"]
    for slot, proj in (("Q", projections[0]), ("K", projections[1])):
        got = env[kernel.inputs[slot][0]]
        want = env[proj]
        assert want.dtype == low
        heads = want.shape[-1] // 8
        if kw.get("qk_norm"):
            scale = env[f"att.{slot.lower()}_norm"]
            want = plain_norm(want, scale,
                              8 if kw["qk_norm"] == "head" else None)
        if kw.get("rotary_theta"):
            want = plain_rotary(want, heads, 1e4)
        assert got.dtype == low
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want.astype(low), np.float32))


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("group", [None, 128], ids=["whole", "group128"])
@pytest.mark.parametrize("turn", [False, True], ids=["norm", "norm_rotary"])
def test_marked_norm_matches_the_plain_form_and_its_gradients(dtype, group,
                                                              turn):
    """`qk_assemble` with a norm (and the rotary behind it): the value to the
    bit, dx and dScale against `jax.grad` of the plain forms."""
    B, T, H, D = 2, 8, 2, 128
    x = _rand(0, (B, T, H * D), dtype)
    scale = 1.0 + 0.3 * _rand(1, (D if group else H * D,))
    weight = _rand(2, (B, T, H * D))
    theta = 1e4 if turn else None

    def new(x, scale):
        out = qk_ops.qk_assemble(x.reshape(B, T, H, D), scale.reshape(-1, D),
                                 EPS, group is None, theta, D, dtype)
        return out.reshape(B, T, H * D)

    def plain(x, scale):
        out = plain_norm(x, scale, group)
        return (plain_rotary(out, H, theta) if turn else out).astype(dtype)

    np.testing.assert_array_equal(np.asarray(new(x, scale), np.float32),
                                  np.asarray(plain(x, scale), np.float32))
    loss = lambda f: lambda x, s: jnp.sum(f(x, s).astype(F32) * weight)  # noqa: E731
    got = jax.grad(loss(new), (0, 1))(x, scale)
    # the yardstick is differentiated in float32 throughout
    want = jax.grad(lambda x, s: jnp.sum(
        (plain_rotary(plain_norm(x, s, group), H, theta) if turn
         else plain_norm(x, s, group)) * weight), (0, 1))(x.astype(F32), scale)
    assert got[0].dtype == dtype and got[1].dtype == F32
    _close(got[0], want[0], dtype)
    _close(got[1], want[1], F32 if dtype == F32 else BF16)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads,rotary_dim", [(4, None), (2, 64), (1, None)],
                         ids=["whole_head", "rotary_dim64", "one_kv_head"])
def test_rotary_is_the_plain_form_and_its_backward_the_turn_back(
        dtype, heads, rotary_dim):
    B, T, D = 2, 8, 128
    x = _rand(3, (B, T, heads * D), dtype)
    weight = _rand(4, (B, T, heads * D))

    def new(x):
        return nn_ops.rotary(x.reshape(B, T, heads, D), 1e4,
                             rotary_dim).reshape(x.shape)

    got = new(x)
    assert got.dtype == dtype
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(plain_rotary(x, heads, 1e4, rotary_dim).astype(dtype),
                   np.float32))
    dx = jax.grad(lambda x: jnp.sum(new(x).astype(F32) * weight))(x)
    want = jax.grad(lambda x: jnp.sum(
        plain_rotary(x, heads, 1e4, rotary_dim) * weight))(x.astype(F32))
    assert dx.dtype == dtype
    _close(dx, want, dtype)
    if rotary_dim:      # the lanes in front pass their cotangent through
        front = np.asarray(dx, np.float32).reshape(B, T, heads, D)[..., :64]
        np.testing.assert_array_equal(front, np.asarray(
            weight.astype(dtype), np.float32).reshape(B, T, heads, D)[..., :64])


def test_rotary_differentiates_inside_a_checkpointed_scan():
    """Ouro's use: the rule inside `jax.lax.scan` under `jax.checkpoint`
    (`layers.Repeat`'s scanned, rematerialised body)."""
    B, T, H, D = 1, 8, 2, 16
    x = _rand(5, (B, T, H * D))

    def body(rot):
        @jax.checkpoint
        def step(h, _):
            return jnp.tanh(rot(h)), None
        return lambda x: jnp.sum(jax.lax.scan(step, x, None, length=3)[0] ** 2)

    new = body(lambda h: nn_ops.rotary(h.reshape(B, T, H, D), 1e4)
               .reshape(h.shape))
    plain = body(lambda h: plain_rotary(h, H, 1e4))
    _close(jax.grad(new)(x), jax.grad(plain)(x), F32)


def _one_op(op_type, x, attrs, amp_dtype, scale=None):
    op = pt.core.program.Operator(
        op_type, {"X": ["x"], **({"Scale": ["s"]} if scale is not None else {})},
        {("Y" if op_type == "rms_norm" else "Out"): ["y"]}, attrs)
    env = {"x": x, "s": scale}
    if amp_dtype:
        env[amp.AMP_KEY] = amp_dtype
    registry.get_kernel(op_type)(registry.OpContext(op, env))
    return env["y"]


@pytest.mark.parametrize("group", [None, 8])
def test_an_unmarked_norm_returns_float32_whatever_reads_it(group):
    """A stream norm, a latent norm, a closing norm, a user's: float32 out of
    bf16 under AMP (a router may read it), and the value the marked one
    rounds."""
    x, scale = _rand(6, (2, 4, 16), BF16), 1.0 + 0.3 * _rand(7, (group or 16,))
    attrs = {"epsilon": EPS, **({"group": group} if group else {})}
    plain = _one_op("rms_norm", x, attrs, "bfloat16", scale)
    assert plain.dtype == F32
    np.testing.assert_array_equal(plain, plain_norm(x, scale, group))
    last = _one_op("rms_norm", x, {**attrs, nn_ops.QK_EMIT_ATTR: "kernel"},
                   "bfloat16", scale)
    assert last.dtype == BF16
    np.testing.assert_array_equal(np.asarray(last, np.float32),
                                  np.asarray(plain.astype(BF16), np.float32))
    # a norm in front of a rotary, and any marked op without AMP: float32
    for attrs_, amp_dtype in (({nn_ops.QK_EMIT_ATTR: "float32"}, "bfloat16"),
                              ({nn_ops.QK_EMIT_ATTR: "kernel"}, None)):
        out = _one_op("rms_norm", x, {**attrs, **attrs_}, amp_dtype, scale)
        assert out.dtype == F32
        np.testing.assert_array_equal(out, plain)
    with pytest.raises(ValueError, match="qk_emit"):
        _one_op("rms_norm", x, {**attrs, nn_ops.QK_EMIT_ATTR: "bf16"}, None,
                scale)


def test_an_unmarked_rotary_keeps_its_inputs_dtype():
    for dtype in (F32, BF16):
        x = _rand(8, (1, 4, 16), dtype)
        attrs = {"num_heads": 2, "theta": 1e4}
        assert _one_op("rotary_embedding", x, attrs, "bfloat16").dtype == dtype
    last = _one_op("rotary_embedding", _rand(8, (1, 4, 16)),
                   {**attrs, nn_ops.QK_EMIT_ATTR: "kernel"}, "bfloat16")
    assert last.dtype == BF16


# ---- the kernels, interpreted ---------------------------------------------
@pytest.mark.parametrize("case", [
    dict(H=8, norm=True, whole=False, turn=True, scales=1),     # trinity's Q
    dict(H=1, norm=True, whole=False, turn=True, scales=1),     # one K/V head
    dict(H=4, norm=True, whole=True, turn=True, scales=4),      # OLMoE's
    dict(H=2, norm=True, whole=False, turn=False, scales=1),    # a global layer
    dict(H=2, norm=False, whole=False, turn=True, scales=0),    # Ouro's
], ids=["head_norm_rotary", "one_kv_head", "whole_norm_rotary", "head_norm",
        "rotary"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_kernels_give_the_xla_formulations_values(case, dtype):
    """`qk_assemble_fwd` / `qk_assemble_bwd` in interpret mode on two
    sequences of two row blocks against the XLA formulation of the same
    rule: what the chip runs for heads of whole lane tiles."""
    B, T, H, D = 2, 128, case["H"], 128
    x = _rand(9, (B, T, H, D), dtype)
    g = _rand(10, (B, T, H, D), dtype)
    scale = 1.0 + 0.3 * _rand(11, (case["scales"], D)) if case["norm"] else None
    theta = 1e4 if case["turn"] else None
    assert qk_ops._shapes_ok(x, theta, D)
    args = (EPS, case["whole"], theta, D)
    want = qk_ops._assemble(x, scale, *args, dtype)
    got = qk_ops._kernel_fwd(x, scale, *args, dtype, interpret=True)
    _close(got, want, dtype)
    want = qk_ops._assemble_bwd(x, scale, g, *args)
    got = qk_ops._kernel_bwd(x, scale, g, *args, interpret=True)
    _close(got[0], want[0].astype(dtype), dtype)
    if case["norm"]:
        assert got[1].shape == scale.shape and got[1].dtype == F32
        _close(got[1], want[1], F32)
    else:
        assert got[1] is None


def test_kernels_take_whole_lane_tiles_turned_whole_only():
    ok = lambda shape, theta, R: qk_ops._shapes_ok(  # noqa: E731
        jax.ShapeDtypeStruct(shape, BF16), theta, R)
    assert ok((1, 8192, 32, 128), 1e4, 128) and ok((2, 4096, 16, 128), None, 0)
    assert not ok((1, 8192, 20, 256), 1e4, 64)      # glm's partial turn
    assert not ok((1, 1024, 12, 64), 1e4, 64)       # half a lane tile
    assert not ok((1, 100, 4, 128), 1e4, 128)       # rows no block divides
    assert not qk_ops.kernels_eligible(             # never on this backend
        jax.ShapeDtypeStruct((1, 8192, 32, 128), BF16), 1e4, 128)
