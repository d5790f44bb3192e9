"""softmax_with_cross_entropy: the hard-label path is a custom_vjp over
logits flattened to [rows, V] (ops/nn_ops.py). Every case compares the op
with the plain formulation `-log_softmax(x.astype(f32))[label]`
differentiated by jax.grad: same values in float32, the same up to one
rounding of the logits' dtype in bf16."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.lod import LoDArray
from paddle_tpu.core.program import Operator
from paddle_tpu.core.registry import OpContext
from paddle_tpu.ops.nn_ops import softmax_with_cross_entropy_kernel

V = 1031  # 50 257 scaled down: not a multiple of 128
TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-6),
       jnp.bfloat16: dict(rtol=1e-2, atol=1e-6)}


def _op(logits, label, soft_label=False):
    """(Loss, Softmax) of one op run on the given values."""
    op = Operator("softmax_with_cross_entropy",
                  {"Logits": ["x"], "Label": ["l"]},
                  {"Softmax": ["s"], "Loss": ["y"]},
                  {"soft_label": soft_label})
    env = {"x": logits, "l": label}
    softmax_with_cross_entropy_kernel(OpContext(op, env))
    return env["y"], env["s"]


def _plain(logits, label):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    lbl = label[..., 0] if label.ndim == logits.ndim else label
    lbl = jnp.clip(lbl.astype(jnp.int32), 0, logits.shape[-1] - 1)
    return -jnp.take_along_axis(logp, lbl[..., None], axis=-1)


def _weighted(fn, w):
    """A scalar of the loss with a different cotangent for every row."""
    return lambda x, lab: jnp.sum(fn(x, lab) * w)


def _counts():
    from paddle_tpu.obs import metrics

    reg = metrics.registry()
    return {p: reg.counter_value("pt_cost_op_dispatch_total",
                                 labels={"path": p})
            for p in ("rows", "soft_label")}


@pytest.mark.parametrize("label_trailing_one", [True, False],
                         ids=["label_n1", "label_n"])
@pytest.mark.parametrize("lead", [(24,), (3, 8)], ids=["NV", "BTV"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_hard_label_loss_and_gradient_match_plain(dtype, lead,
                                                  label_trailing_one):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*lead, V) * 3.0, dtype)
    lab = rng.randint(0, V, lead + ((1,) if label_trailing_one else ()))
    lab = jnp.asarray(lab, jnp.int32)
    w = jnp.asarray(rng.rand(*lead, 1) + 0.5, jnp.float32)

    loss, _ = _op(x, lab)
    want = _plain(x, lab)
    assert loss.shape == lead + (1,) and loss.dtype == jnp.float32
    np.testing.assert_allclose(loss, want, rtol=2e-5, atol=2e-6)

    got = jax.grad(_weighted(lambda *a: _op(*a)[0], w))(x, lab)
    ref = jax.grad(_weighted(_plain, w))(x, lab)
    assert got.dtype == x.dtype and got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_out_of_range_label_clips():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(4, V), jnp.float32)
    lab = jnp.asarray([[-3], [0], [V + 7], [V - 1]], jnp.int32)
    clipped = jnp.asarray([[0], [0], [V - 1], [V - 1]], jnp.int32)
    np.testing.assert_allclose(_op(x, lab)[0], _plain(x, clipped), rtol=2e-5)
    np.testing.assert_allclose(
        jax.grad(lambda x: _op(x, lab)[0].sum())(x),
        jax.grad(lambda x: _plain(x, clipped).sum())(x),
        rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_ragged_padding_slots_have_zero_loss_and_gradient(dtype):
    rng = np.random.RandomState(2)
    lens = (5, 2, 4)
    seqs = [rng.randn(n, V).astype(np.float32) * 2.0 for n in lens]
    logits = LoDArray.from_sequences(seqs, bucket=16)
    logits = logits.with_data(jnp.asarray(logits.data, dtype))
    labels = LoDArray.from_sequences(
        [rng.randint(0, V, (n, 1)).astype(np.int32) for n in lens], bucket=16)
    real = np.asarray(logits.token_mask)
    assert real.sum() == sum(lens) and (~real).sum() == 16 - sum(lens)

    loss, softmax = _op(logits, labels)
    assert isinstance(loss, LoDArray) and isinstance(softmax, LoDArray)
    assert loss.data.shape == (16, 1)
    want = _plain(logits.data, labels.data)
    np.testing.assert_allclose(loss.data[real], want[real], rtol=2e-5)
    assert not np.asarray(loss.data[~real]).any()

    got = jax.grad(lambda d: _op(logits.with_data(d), labels)[0].data.sum())(
        logits.data)
    ref = jax.grad(lambda d: _plain(d, labels.data).sum())(logits.data)
    np.testing.assert_allclose(np.asarray(got, np.float32)[real],
                               np.asarray(ref, np.float32)[real], **TOL[dtype])
    assert not np.asarray(got, np.float32)[~real].any()


def test_soft_label_values_and_both_gradients():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(6, V), jnp.float32)
    soft = jax.nn.softmax(jnp.asarray(rng.randn(6, V), jnp.float32))

    def plain(x, soft):
        return -jnp.sum(soft * jax.nn.log_softmax(x, axis=-1), axis=-1,
                        keepdims=True)

    before = _counts()
    loss, _ = _op(x, soft, soft_label=True)
    after = _counts()
    assert after["soft_label"] - before["soft_label"] == 1
    assert after["rows"] == before["rows"]
    assert loss.shape == (6, 1)
    np.testing.assert_allclose(loss, plain(x, soft), rtol=1e-6)
    got = jax.grad(lambda *a: _op(*a, soft_label=True)[0].sum(), (0, 1))(
        x, soft)
    ref = jax.grad(lambda *a: plain(*a).sum(), (0, 1))(x, soft)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(g, r, rtol=1e-6, atol=1e-7)


def test_large_logits_stay_finite():
    """|x| ~ 80 in bf16: exp(80) overflows nothing because the row max is
    subtracted forward and the log-sum-exp backward."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.choice([-80.0, 80.0], (4, V)), jnp.bfloat16)
    x = x.at[3].set(80.0)
    lab = jnp.asarray([0, 5, V - 1, 17], jnp.int32)
    loss, _ = _op(x, lab)
    grad = jax.grad(lambda x: _op(x, lab)[0].sum())(x)
    assert np.isfinite(np.asarray(loss)).all()
    assert np.isfinite(np.asarray(grad, np.float32)).all()
    np.testing.assert_allclose(loss, _plain(x, lab), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(loss[3, 0], np.log(V), rtol=1e-6)


def test_program_fetches_softmax_and_counts_each_traced_op():
    """Through the Program: the Softmax output, fetched, equals
    jax.nn.softmax of the float32 logits; the counter grows by one for
    each op traced, and a compiled program counts nothing more."""
    rng = np.random.RandomState(5)
    x = pt.layers.data("x", shape=[7, V])
    lab = pt.layers.data("lab", shape=[7, 1], dtype=np.int32)
    loss = pt.layers.softmax_with_cross_entropy(x, lab)
    op = pt.default_main_program().global_block().ops[-1]
    assert op.type == "softmax_with_cross_entropy"
    assert set(op.inputs) == {"Logits", "Label"}
    assert set(op.outputs) == {"Softmax", "Loss"}
    softmax = op.outputs["Softmax"][0]
    feed = {"x": (rng.randn(2, 7, V) * 2.0).astype(np.float32),
            "lab": rng.randint(0, V, (2, 7, 1)).astype(np.int32)}
    exe = pt.Executor()
    before = _counts()
    got_loss, got_softmax = exe.run(feed=feed, fetch_list=[loss, softmax])
    mid = _counts()
    exe.run(feed=feed, fetch_list=[loss, softmax])
    after = _counts()
    assert mid["rows"] - before["rows"] == 1
    assert after == mid
    assert mid["soft_label"] == before["soft_label"]
    np.testing.assert_allclose(
        got_softmax, jax.nn.softmax(jnp.asarray(feed["x"]), axis=-1),
        rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(
        got_loss, _plain(jnp.asarray(feed["x"]), jnp.asarray(feed["lab"])),
        rtol=2e-5, atol=2e-6)
