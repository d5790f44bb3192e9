"""The forward ops of a training program are traced once (PR 33): the ops in
front of an `autodiff` op run inside its differentiation, and `env` takes
what they bound from that one trace (`core/executor.py:_run_autodiff`).

(a) counts of traces, by a spy kernel and by the ops' own dispatch counters;
(b) a program with dropout, batch norm, a `sparse_update` table beside a
    dense one, a routed layer's step statistic and a `remat_policy`: every
    value the step hands out equals what the parent's TWO traces give on the
    same seed. Those two traces are rebuilt here from `_BlockRunner` (the
    plain forward for the values, `jax.grad` over the same ops for the
    gradients: the parent's `run_ops` + `_run_autodiff` to the letter), and
    the cost the parent's tree printed for this program is recorded beside;
(c) what a block with two autodiff ops means; what stays static.
The lowered step programs' kernel counts are in `tests/test_tpu_compile.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core import registry
from paddle_tpu.core.executor import _BlockRunner
from paddle_tpu.core.program import grad_var_name
from paddle_tpu.core.sparse import SelectedRows

B, T, D, V = 8, 6, 16, 50


def _spy(monkeypatch, op_type):
    """Count the traces of `op_type`'s kernel."""
    calls = []
    kernel = registry.get_kernel(op_type)

    def counted(ctx):
        calls.append(ctx.op.type)
        return kernel(ctx)

    monkeypatch.setitem(registry._KERNELS, op_type, counted)
    return calls


def _classifier(train, sparse=False, remat=None):
    pt.reset()
    ids = pt.layers.data("ids", shape=[T], dtype=np.int32)
    label = pt.layers.data("label", shape=[1], dtype=np.int32)
    emb = pt.layers.embedding(ids, size=(V, D), is_sparse=sparse)
    hidden = pt.layers.fc(pt.layers.reduce_mean(emb, dim=1), size=D,
                          act="tanh")
    logits = pt.layers.fc(hidden, size=4)
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, label))
    if train:
        pt.optimizer.SGD(0.1).minimize(loss)
    if remat:
        pt.memory_optimize(policy=remat)
    rng = np.random.RandomState(0)
    feed = {"ids": rng.randint(0, V, (B, T)).astype(np.int32),
            "label": rng.randint(0, 4, (B, 1)).astype(np.int32)}
    return loss, feed


# what traces the cost op's kernel how often in one compile: the training
# program once (it took two); a sparse_update table adds the abstract
# discovery pass (`jax.eval_shape`: no device time), where it took three;
# `jax.checkpoint` traces its function once; without an autodiff op, once
TRACES = {
    "train": (dict(train=True), 1),
    "train_sparse_table": (dict(train=True, sparse=True), 2),
    "train_remat_full": (dict(train=True, remat="full"), 1),
    "train_remat_dots_sparse_table": (
        dict(train=True, sparse=True, remat="dots"), 2),
    "inference": (dict(train=False), 1),
}


@pytest.mark.parametrize("case", sorted(TRACES))
def test_forward_op_is_traced_once_a_compile(monkeypatch, case):
    from paddle_tpu.obs import metrics

    kwargs, want = TRACES[case]
    loss, feed = _classifier(**kwargs)
    calls = _spy(monkeypatch, "softmax_with_cross_entropy")
    muls = _spy(monkeypatch, "mul")
    count = lambda: metrics.registry().counter_value(  # noqa: E731
        "pt_cost_op_dispatch_total", labels={"path": "rows"})
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    before = count()
    first = exe.run(feed=feed, fetch_list=[loss])[0]
    assert len(calls) == want and len(muls) == 2 * want
    # the op's own witness: one increment an op traced
    assert count() - before == want
    second = exe.run(feed=feed, fetch_list=[loss])[0]   # compiled: no trace
    assert len(calls) == want and count() - before == want
    assert np.isfinite(first) and (second < first if kwargs["train"]
                                   else second == first)


def test_attention_op_is_dispatched_once_in_a_training_step():
    from paddle_tpu.obs import metrics

    pt.reset()
    x = pt.layers.data("x", shape=[16, 32], dtype="float32")
    out = pt.layers.multi_head_attention(x, num_heads=4, causal=True)
    loss = pt.layers.mean(out)
    pt.optimizer.SGD(0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    count = lambda: sum(metrics.registry().counter_value(  # noqa: E731
        "pt_flash_attention_dispatch_total", labels={"path": p})
        for p in ("packed", "xla"))
    before = count()
    exe.run(feed={"x": np.random.RandomState(0).randn(2, 16, 32).astype(
        np.float32)}, fetch_list=[loss])
    assert count() - before == 1


# ------------------------------------------------------------------ (b) ---
def _mixed_program():
    """Dropout, batch norm (train mode: it rebinds its running statistics),
    a sparse_update table beside a dense one, a routed layer (its
    tokens-per-expert vector is a step statistic), a `custom_vjp` cost op,
    under `remat_policy` "dots"."""
    pt.reset()
    ids = pt.layers.data("ids", shape=[T], dtype=np.int32)
    label = pt.layers.data("label", shape=[1], dtype=np.int32)
    sparse = pt.layers.embedding(ids, size=(V, D), is_sparse=True,
                                 param_attr=pt.ParamAttr(name="table_sparse"))
    dense = pt.layers.embedding(ids, size=(V, D),
                                param_attr=pt.ParamAttr(name="table_dense"))
    tokens = pt.layers.elementwise_add(sparse, dense)            # [B, T, D]
    routed, _, counts = pt.layers.moe_ffn(
        tokens, num_experts=4, experts_per_token=2, expert_dim=8, name="moe")
    pooled = pt.layers.reduce_mean(pt.layers.elementwise_add(tokens, routed),
                                   dim=1)
    normed = pt.layers.batch_norm(pt.layers.fc(pooled, size=D))
    dropped = pt.layers.dropout(normed, dropout_prob=0.25)
    logits = pt.layers.fc(dropped, size=4)
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, label))
    pt.optimizer.SGD(0.5).minimize(loss)
    pt.memory_optimize(policy="dots")
    prog, startup = pt.default_main_program(), pt.default_startup_program()
    prog.random_seed = startup.random_seed = 11
    rng = np.random.RandomState(3)
    feed = {"ids": rng.randint(0, V, (B, T)).astype(np.int32),
            "label": rng.randint(0, 4, (B, 1)).astype(np.int32)}
    return prog, startup, feed, {"loss": loss, "dropped": dropped,
                                 "counts": counts, "normed": normed}


def _two_traces(prog, state, feed, seed, names):
    """What the parent's step computed: the forward ops run plainly into
    `env` (the values), then traced again inside `jax.grad` (the
    gradients). The sparse table is differentiated densely here."""
    block = prog.global_block()
    ops = block.ops
    ad = next(i for i, op in enumerate(ops) if op.type == "autodiff")
    params = list(ops[ad].attrs["params"])
    loss_name = ops[ad].inputs["Loss"][0]
    runner = _BlockRunner(prog)

    @jax.jit
    def both(state, feed, seed):
        entry = {**state, **feed, "@RNG@": jax.random.PRNGKey(seed),
                 "@RNG_COUNTER@": 0, "@AMP@": prog.amp_dtype}
        env = dict(entry)
        runner.run_ops(ops[:ad], env, dict(entry), block)

        def loss_of(pvals):
            env2 = {**entry, **pvals}
            runner.run_ops(ops[:ad], env2, dict(entry), block)
            return jnp.reshape(env2[loss_name], ())

        grads = jax.grad(loss_of)({p: entry[p] for p in params})
        return ({n: env[n] for n in names}, grads,
                np.int32(env["@RNG_COUNTER@"]))

    return both(state, feed, seed)


# the cost and the sum of the dropped activations that the PARENT's tree
# (commit 2065a8a, its two traces) printed for `_mixed_program` on this
# seed and feed, on the CPU backend
PARENT_COST = 1.7000272274017334
PARENT_DROPPED_SUM = -2.1289410176686943


def test_step_hands_out_what_the_two_traces_gave():
    prog, startup, feed, v = _mixed_program()
    exe = pt.Executor()
    exe.run(startup)
    scope = pt.global_scope()
    state = {p.name: np.asarray(scope.get(p.name))
             for p in prog.persistables() if scope.has(p.name)}
    bn_stats = sorted(n for n in state if "batch_norm" in n
                      and ("mean" in n or "variance" in n))
    assert len(bn_stats) == 2, sorted(state)
    ad = next(op for op in prog.global_block().ops if op.type == "autodiff")
    params = list(ad.attrs["params"])
    forward = [v["loss"].name, v["dropped"].name, v["normed"].name,
               v["counts"].name] + bn_stats
    want, want_grads, want_counter = _two_traces(
        prog, {n: jnp.asarray(a) for n, a in state.items()},
        {n: jnp.asarray(a) for n, a in feed.items()}, jnp.uint32(11), forward)
    assert int(want_counter) == 1       # one dropout op drew one key

    stat = prog.step_statistics[0]["var"]
    assert stat == v["counts"].name
    fetch = forward[:4] + [grad_var_name(p) for p in params]
    got = exe.run(prog, feed=feed, fetch_list=fetch, return_numpy=False)
    got = dict(zip(fetch, got))

    # the routed layer, the cost op: custom_vjp ops (their `fwd` rule now
    # makes the value): 1e-6; everything in front of them: to the bit
    np.testing.assert_allclose(got[v["loss"].name], want[v["loss"].name],
                               rtol=1e-6)
    np.testing.assert_allclose(got[v["loss"].name], PARENT_COST, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(got[v["dropped"].name], np.float64).sum(),
        PARENT_DROPPED_SUM, rtol=1e-5)
    for name in (v["dropped"].name, v["normed"].name):
        np.testing.assert_allclose(got[name], want[name], rtol=1e-6,
                                   atol=1e-7)
        assert np.asarray(got[name]).any()
    np.testing.assert_array_equal(got[stat], want[stat])
    assert np.asarray(got[stat]).dtype == np.int32
    assert int(np.asarray(got[stat]).sum()) == B * T * 2
    # the statistics batch norm rebound in the forward reach the scope from
    # the one trace, and they moved
    for n in bn_stats:
        np.testing.assert_allclose(scope.get(n), want[n], rtol=1e-6,
                                   atol=1e-7)
        assert not np.array_equal(np.asarray(scope.get(n)), state[n])
    for p in params:
        g = got[grad_var_name(p)]
        if p == "table_sparse":
            assert isinstance(g, SelectedRows)
            g = g.to_dense()
        np.testing.assert_allclose(g, want_grads[p], rtol=1e-5, atol=1e-7,
                                   err_msg=p)
        assert np.asarray(g).any(), p
    # and SGD applied them: the parameters moved by -0.5 x gradient
    for p in ("table_dense", "table_sparse"):
        np.testing.assert_allclose(
            np.asarray(scope.get(p)), state[p] - 0.5 * np.asarray(
                want_grads[p]), rtol=1e-5, atol=1e-6)


def test_values_without_a_custom_vjp_are_the_plain_forwards_to_the_bit():
    """No custom_vjp op, no remat: the differentiated forward's primal
    values ARE the plain forward's computation."""
    pt.reset()
    x = pt.layers.data("x", shape=[D], dtype="float32")
    y = pt.layers.data("y", shape=[1], dtype="float32")
    hidden = pt.layers.dropout(
        pt.layers.batch_norm(pt.layers.fc(x, size=D, act="relu")), 0.5)
    pred = pt.layers.fc(hidden, size=1)
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.SGD(0.1).minimize(loss)
    prog, startup = pt.default_main_program(), pt.default_startup_program()
    prog.random_seed = startup.random_seed = 5
    rng = np.random.RandomState(1)
    feed = {"x": rng.randn(B, D).astype(np.float32),
            "y": rng.randn(B, 1).astype(np.float32)}
    exe = pt.Executor()
    exe.run(startup)
    scope = pt.global_scope()
    state = {p.name: jnp.asarray(np.asarray(scope.get(p.name)))
             for p in prog.persistables() if scope.has(p.name)}
    ad = next(op for op in prog.global_block().ops if op.type == "autodiff")
    names = [loss.name, hidden.name, pred.name]
    want, want_grads, _ = _two_traces(
        prog, state, {n: jnp.asarray(a) for n, a in feed.items()},
        jnp.uint32(5), names)
    fetch = names + [grad_var_name(p) for p in ad.attrs["params"]]
    got = dict(zip(fetch, exe.run(prog, feed=feed, fetch_list=fetch)))
    for n in names:
        np.testing.assert_array_equal(got[n], want[n], err_msg=n)
    for p in ad.attrs["params"]:
        np.testing.assert_array_equal(got[grad_var_name(p)], want_grads[p],
                                      err_msg=p)


# ------------------------------------------------------------------ (c) ---
def test_static_values_leave_the_forward_by_the_side_channel():
    """The dropout ops advance `@RNG_COUNTER@`, a Python integer: the ops
    behind the autodiff op see the value the forward left, not a tracer."""
    pt.reset()
    x = pt.layers.data("x", shape=[D], dtype="float32")
    h = pt.layers.dropout(pt.layers.dropout(pt.layers.fc(x, size=D), 0.3),
                          0.3)
    loss = pt.layers.mean(h)
    pt.optimizer.SGD(0.1).minimize(loss)
    prog = pt.default_main_program()
    runner = _BlockRunner(prog)
    pt.Executor().run(pt.default_startup_program())
    scope = pt.global_scope()
    seen = {}

    def step(state, x):
        env = {**state, "x": x, "@RNG@": jax.random.PRNGKey(0),
               "@RNG_COUNTER@": 0, "@AMP@": None}
        runner.run_block(0, env)
        seen.update(counter=env["@RNG_COUNTER@"], amp=env["@AMP@"],
                    tape="@SPARSE_TAPE@" in env)
        return env[loss.name]

    jax.jit(step)({p.name: scope.get(p.name) for p in prog.persistables()},
                  jnp.ones((B, D), jnp.float32))
    assert seen == {"counter": 2, "amp": None, "tape": False}
    assert type(seen["counter"]) is int


def test_a_value_that_mixes_arrays_with_static_leaves_is_refused(monkeypatch):
    """No op binds one today. An array beside a Python number in one value
    could leave the differentiated forward neither as its output (the number
    would come back an array) nor by the side channel (the array is a tracer
    of the differentiation, dead once it returns): refused by name."""
    kernel = registry.get_kernel("mean")

    def mixed(ctx):
        kernel(ctx)
        ctx.env["@MIXED@"] = (ctx.env[ctx.op.outputs["Out"][0]], 3)

    monkeypatch.setitem(registry._KERNELS, "mean", mixed)
    loss, feed = _classifier(train=True)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    with pytest.raises(TypeError, match="'@MIXED@' is bound to a pytree that "
                                        "mixes arrays with static leaves"):
        exe.run(pt.default_main_program(), feed=feed, fetch_list=[loss])


def test_a_second_autodiff_op_differentiates_everything_in_front_of_it():
    """No model, demo or test of the repo builds two autodiff ops in one
    block; the meaning is kept all the same: each differentiates the ops
    in front of it, the first among them for the second (a gradient of a
    gradient), and every op is traced once."""
    from paddle_tpu.core.backward import append_backward

    pt.reset()
    x = pt.layers.data("x", shape=[3], dtype="float32")
    y = pt.layers.fc(x, size=1, bias_attr=False,
                     param_attr=pt.ParamAttr(name="w2ad"))
    loss = pt.layers.mean(pt.layers.elementwise_mul(
        pt.layers.elementwise_mul(y, y), y))                 # mean((xw)^3)
    (_, g), = append_backward(loss, parameter_list=["w2ad"])
    penalty = pt.layers.reduce_sum(pt.layers.elementwise_mul(g, g))  # |dL/dw|^2
    block = pt.default_main_program().global_block()
    block.append_op(
        type="autodiff", inputs={"Loss": [penalty]},
        outputs={"Grads": [block.create_var("w2ad@GRAD2", (3, 1),
                                            "float32")]},
        attrs={"params": ["w2ad"]})
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    xs = np.random.RandomState(0).randn(5, 3).astype(np.float32)
    w0 = np.asarray(pt.global_scope().get("w2ad"))

    def l(w):
        return jnp.mean((xs @ w) ** 3)

    def p(w):
        return jnp.sum(jax.grad(l)(w) ** 2)

    got_loss, got_pen, got_g = exe.run(feed={"x": xs},
                                       fetch_list=[loss, penalty, g])
    np.testing.assert_allclose(got_loss, l(w0), rtol=1e-5)
    np.testing.assert_allclose(got_pen, p(w0), rtol=1e-5)
    # the second autodiff op rebinds the same gradient name: d penalty / dw
    np.testing.assert_allclose(got_g, jax.grad(p)(w0), rtol=1e-4, atol=1e-6)
