"""`layers.Repeat`: one Program sub-block run K times with one set of
weights, as a scan whose turns are rematerialised one at a time, all but the
last, which is differentiated where it stands. Held to the same network
written K times by shared `ParamAttr` names (values, every gradient, one Adam
step), with `remat` on and off, and to `scan(checkpoint(turn))`, the lowering
that ran all K turns again, to the bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.param_attr import ParamAttr

B, D = 3, 8


def _body(h, dropout=0.0, prefix=""):
    """One turn: a normed tanh layer added to the stream, parameters by
    name; gives (the next stream, a per-row value of it)."""
    u = pt.layers.rms_norm(h, name=f"{prefix}n", param_attr=ParamAttr(name=f"{prefix}n.w"))
    u = pt.layers.fc(u, size=D, act="tanh", param_attr=ParamAttr(name=f"{prefix}w"),
                     bias_attr=ParamAttr(name=f"{prefix}b"))
    if dropout:
        u = pt.layers.dropout(u, dropout_prob=dropout)
    h2 = pt.layers.elementwise_add(h, u)
    return h2, pt.layers.reduce_sum(pt.layers.elementwise_mul(h2, h2), dim=-1)


def _looped(times, remat=True, dropout=0.0):
    x = pt.layers.data("x", shape=[D], dtype=np.float32)
    loop = pt.layers.Repeat(times=times, remat=remat)
    with loop.block():
        h2, row = _body(x, dropout)
        loop.update(x, h2)
        loop.turn_output(row)
    h_fin, rows = loop()
    return x, h_fin, rows


def _written_out(times, prefixes=None):
    """The same network, the body `times` times in the global block; with
    `prefixes` each turn has its own weights (the untied network)."""
    x = pt.layers.data("x", shape=[D], dtype=np.float32)
    h, rows = x, []
    for t in range(times):
        h, row = _body(h, prefix=prefixes[t] if prefixes else "")
        rows.append(row)
    return x, h, rows


def _cost(h_fin, rows):
    """Reads the final carry and every turn's output, weighted by turn."""
    total = pt.layers.reduce_sum(h_fin)
    for t, row in enumerate(rows):
        total = pt.layers.elementwise_add(
            total, pt.layers.scale(pt.layers.reduce_sum(row), scale=0.1 * (t + 1)))
    return total


def _split_rows(rows, times):
    return [pt.layers.reshape(r, [B]) for r in pt.layers.split(rows, times, dim=0)]


def _programs(build):
    pt.reset()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        out = build()
    prog.random_seed = startup.random_seed = 3
    return prog, startup, out


def _x(seed=0):
    return np.random.RandomState(seed).randn(B, D).astype(np.float32)


def _values_and_grads(kind, times):
    """(final stream, stacked rows [K, B], {parameter: gradient}) of the cost
    through one `Executor.run`."""
    def build():
        if kind == "written_out":
            _, h, rows = _written_out(times)
            cost = _cost(h, rows)
            stacked = pt.layers.concat(
                [pt.layers.reshape(r, [1, B]) for r in rows], axis=0)
        else:
            _, h, stacked = _looped(times, remat=kind == "remat")
            cost = _cost(h, _split_rows(stacked, times))
        return h, stacked, cost, pt.append_backward(cost)

    prog, startup, (h, stacked, cost, pairs) = _programs(build)
    exe = pt.Executor()
    exe.run(startup)
    got = exe.run(prog, feed={"x": _x()},
                  fetch_list=[h, stacked, cost] + [g for _, g in pairs])
    return got[0], got[1], got[2], {p.name: g for (p, _), g in zip(pairs, got[3:])}


@pytest.mark.parametrize("times", [1, 3])
@pytest.mark.parametrize("kind", ["remat", "no_remat"])
def test_repeat_is_the_network_written_k_times(kind, times):
    """Forward and every gradient to 1e-6 in float32; the stacked outputs
    come in turn order; at K = 1 the loop is the body alone."""
    h, rows, cost, grads = _values_and_grads(kind, times)
    want_h, want_rows, want_cost, want = _values_and_grads("written_out", times)
    assert rows.shape == (times, B)
    np.testing.assert_allclose(h, want_h, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(rows, want_rows, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(cost, want_cost, rtol=1e-6)
    assert sorted(grads) == sorted(want) == ["b", "n.w", "w"]
    for name in want:
        assert np.abs(want[name]).max() > 1e-3
        np.testing.assert_allclose(grads[name], want[name], rtol=1e-6,
                                   atol=1e-6, err_msg=name)


def test_remat_on_and_off_give_the_same_bits():
    on, off = (_values_and_grads(kind, 3) for kind in ("remat", "no_remat"))
    for a, b in zip(on[:3], off[:3]):
        assert np.array_equal(a, b)
    for name in on[3]:
        assert np.array_equal(on[3][name], off[3][name]), name


def test_a_parameter_made_in_the_body_exists_once():
    prog, startup, _ = _programs(lambda: _looped(4))
    assert [p.name for p in prog.parameters()] == ["n.w", "w", "b"]
    made = [n for op in startup.global_block().ops for n in op.output_names()]
    assert sorted(made) == ["b", "n.w", "w"]
    ops = [op for b in prog.blocks for op in b.ops if op.type == "repeat"]
    assert len(ops) == 1 and ops[0].attrs["times"] == 4
    assert ops[0].attrs["remat"] is True
    assert prog.blocks[ops[0].attrs["sub_block"]].parent_idx == 0


def _adam_step(build):
    """One Adam step from seeded weights: ({parameter: value after the
    step}, {parameter: value before})."""
    prog, startup, cost = _programs(build)
    exe = pt.Executor()
    exe.run(startup)
    scope = pt.global_scope()
    names = [p.name for p in prog.parameters()]
    before = {n: np.array(scope.get(n)) for n in names}
    exe.run(prog, feed={"x": _x()}, fetch_list=[cost])
    return {n: np.array(scope.get(n)) for n in names}, before


@pytest.mark.parametrize("kind", ["remat", "no_remat"])
def test_one_adam_step_on_a_weight_read_k_times(kind):
    """Adam on a weight the loop reads K times = Adam on the SUM of the K
    per-turn gradients of the untied network (each turn its own copy of the
    weights, all K copies equal): ROADMAP Queue 2 A item 13's test."""
    K, lr = 3, 1e-2

    def tied():
        _, h, rows = _looped(K, remat=kind == "remat")
        cost = _cost(h, _split_rows(rows, K))
        pt.optimizer.Adam(learning_rate=lr).minimize(cost)
        return cost

    after, before = _adam_step(tied)

    prefixes = [f"t{t}." for t in range(K)]

    def untied():
        _, h, rows = _written_out(K, prefixes)
        cost = _cost(h, rows)
        return cost, pt.append_backward(cost)

    prog, startup, (cost, pairs) = _programs(untied)
    exe = pt.Executor()
    exe.run(startup)
    scope = pt.global_scope()
    for pre in prefixes:                       # every copy = the tied weights
        for n, v in before.items():
            scope.set(pre + n, v)
    grads = exe.run(prog, feed={"x": _x()}, fetch_list=[g for _, g in pairs])
    per_turn = {p.name: g for (p, _), g in zip(pairs, grads)}
    for n in before:
        g = sum(per_turn[pre + n] for pre in prefixes)
        assert all(np.abs(per_turn[pre + n]).max() > 1e-4 for pre in prefixes)
        # the first step of Adam from zero moments: m / (1 - b1) = g and
        # v / (1 - b2) = g^2
        want = before[n] - lr * g / (np.abs(g) + 1e-8)
        np.testing.assert_allclose(after[n], want, rtol=2e-5, atol=2e-6,
                                   err_msg=n)


def test_dropout_in_the_body_draws_anew_each_turn():
    def build():
        x = pt.layers.data("x", shape=[D], dtype=np.float32)
        loop = pt.layers.Repeat(times=4)
        with loop.block():
            loop.turn_output(pt.layers.dropout(x, dropout_prob=0.5))
        return loop()[0]

    prog, _, kept = _programs(build)
    masks = pt.Executor().run(
        prog, feed={"x": np.ones((64, D), np.float32)}, fetch_list=[kept])[0] != 0
    assert masks.shape == (4, 64, D)
    for a in range(4):
        assert 0.3 < masks[a].mean() < 0.7
        for b in range(a):
            assert (masks[a] != masks[b]).mean() > 0.3


def test_clone_for_test_runs_the_loop():
    def build():
        _, h, rows = _looped(3, dropout=0.5)
        cost = _cost(h, _split_rows(rows, 3))
        pt.optimizer.Adam(learning_rate=1e-2).minimize(cost)
        return h

    prog, startup, h = _programs(build)
    test_prog = prog.clone(for_test=True)
    assert not [op for b in test_prog.blocks for op in b.ops
                if op.type in ("autodiff", "adam")]
    exe = pt.Executor()
    exe.run(startup)
    a, = exe.run(test_prog, feed={"x": _x()}, fetch_list=[h.name])
    b, = exe.run(test_prog, feed={"x": _x()}, fetch_list=[h.name])
    assert a.shape == (B, D) and np.array_equal(a, b)   # dropout is off


@pytest.mark.parametrize("kind,rerun,carries", [("remat", 2, 2),
                                                 ("no_remat", 0, 3)])
def test_the_loop_is_counted_and_says_what_it_keeps(kind, rerun, carries):
    from paddle_tpu.obs import metrics

    _values_and_grads(kind, 3)
    reg = metrics.registry()
    assert reg.counter_value(
        "pt_repeat_dispatch_total",
        labels={"remat": str(kind == "remat").lower()}) >= 1
    text = reg.render()
    assert "pt_repeat_turns 3" in text
    assert f"pt_repeat_rerun_turns {rerun}" in text
    # the carries [B, D] of the turns the loop runs again (with remat: all
    # but the last) and the stacked rows [3, B], float32
    assert f"pt_repeat_saved_bytes {carries * B * D * 4 + 3 * B * 4}" in text


def _runs(jaxpr, primitive):
    """How often a traced program executes `primitive`: a scan's body as
    often as the scan is long, a called sub-program as often as it is
    called."""
    total = 0
    for eqn in jaxpr.eqns:
        total += eqn.primitive.name == primitive
        inner = sum(_runs(sub, primitive)
                    for sub in jax.core.jaxprs_in_params(eqn.params))
        total += inner * eqn.params.get("length", 1) \
            if eqn.primitive.name == "scan" else inner
    return total


def _step_jaxpr(times, remat=True):
    def build():
        _, h, rows = _looped(times, remat=remat)
        cost = _cost(h, _split_rows(rows, times))
        pt.append_backward(cost)
        return cost

    prog, startup, cost = _programs(build)
    exe = pt.Executor()
    exe.run(startup)
    state = {p.name: pt.global_scope().get(p.name) for p in prog.parameters()}
    raw = exe._raw_step(prog, [cost.name])
    return jax.make_jaxpr(raw)({}, state, {"x": _x()}, np.uint32(1))


@pytest.mark.parametrize("times", [1, 2, 4])
def test_a_step_runs_the_turn_2k_minus_1_times(times):
    """The body's one `tanh` (its derivative reads the forward's result, so
    every `tanh` in the step is a forward run of a turn): K forward, K - 1
    again in the backward pass, where `scan(checkpoint(turn))` ran all K
    again; K without `remat`."""
    assert _runs(_step_jaxpr(times).jaxpr, "tanh") == 2 * times - 1
    assert _runs(_step_jaxpr(times, remat=False).jaxpr, "tanh") == times


def test_the_turn_is_traced_linearised_and_transposed_once():
    """The forward loop, the last turn and the backward loop call ONE jitted
    turn: the step holds three programs of its name (the turn, its forward
    that keeps residuals, its transpose), the latter two called twice each
    and the same object both times, so each is lowered once."""
    called = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "jit" and eqn.params["name"] == "step":
                inner = eqn.params["jaxpr"]
                called.setdefault(id(inner), [inner, 0])[1] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(_step_jaxpr(4).jaxpr)
    assert sorted(n for _, n in called.values()) == [1, 2, 2]


def _turn_in_jax(params, labels, key, rate):
    """A turn as `repeat_kernel` makes one, closing over differentiated
    floats, an integer array and a key; carries a stream and a counter."""
    def turn(vals, it):
        h, seen = vals
        u = jnp.tanh(h @ params["w"] + params["b"])
        keep = jax.random.bernoulli(jax.random.fold_in(key, it), 1.0 - rate,
                                    u.shape)
        u = jnp.where(keep, u / (1.0 - rate), 0.0)
        u = u + 0.01 * labels.astype(jnp.float32)[:, None]
        h2 = h + u
        return (h2, seen + 1), ((h2 * h2).sum(-1), jnp.argmax(h2, -1))

    return turn


@pytest.mark.parametrize("times", [2, 4])
def test_gradients_are_the_bits_of_the_loop_that_ran_every_turn_again(times):
    """Parameter gradients, the gradient of the carry that enters the loop
    and (through a cost that weighs every turn's output) the turn outputs'
    are, in float32, the bits of `scan(checkpoint(turn))`; with dropout in the
    turn, an integer operand closed over, an integer carry and an integer
    turn output beside the float ones."""
    from paddle_tpu.ops import control_flow_ops as cf

    rs = np.random.RandomState(1)
    params = {"w": rs.randn(D, D).astype(np.float32) * 0.5,
              "b": rs.randn(D).astype(np.float32)}
    labels = np.arange(B, dtype=np.int32)
    weights = jnp.arange(1.0, times + 1.0)[:, None]

    def cost(loop):
        def of(params, h0, labels, key):
            turn = _turn_in_jax(params, labels, key, 0.25)
            (h, seen), (rows, top) = loop(turn, (h0, jnp.int32(0)))
            return h.sum() + (rows * weights).sum(), (seen, top)
        return jax.jit(jax.value_and_grad(of, argnums=(0, 1), has_aux=True))

    def oracle(turn, vals):
        return jax.lax.scan(jax.checkpoint(turn), vals,
                            jnp.arange(times, dtype=jnp.int32))

    def mine(turn, vals):
        step, ints, floats = cf._explicit(turn, vals, jnp.int32(0))
        assert len(ints) == 2 and len(floats) == 2     # labels, key | w, b
        return cf._all_turns_but_the_last_again(step, times)(
            ints, floats, vals)

    args = (params, _x(), labels, jax.random.PRNGKey(5))
    (want, (seen, top)), (d_params, d_h0) = cost(oracle)(*args)
    (got, (seen2, top2)), (d_params2, d_h02) = cost(mine)(*args)
    assert int(seen) == int(seen2) == times
    assert np.array_equal(top, top2) and np.array_equal(want, got)
    assert np.abs(d_h0).max() > 1e-3
    assert np.array_equal(d_h0, d_h02)
    for name in params:
        assert np.abs(d_params[name]).max() > 1e-3
        assert np.array_equal(d_params[name], d_params2[name]), name


def test_a_program_with_dropout_and_labels_in_the_body_matches_the_oracle(
        monkeypatch):
    """The same Program through both lowerings (the oracle put in the
    kernel's place), to float32's last digits: dropout in the body (the same
    masks: a turn's key is folded from its number either way), an int32 feed
    read inside it, and a layer in front of the loop whose gradient is the
    carry's."""
    from paddle_tpu.ops import control_flow_ops as cf

    times = 3

    def build():
        x = pt.layers.data("x", shape=[D], dtype=np.float32)
        ids = pt.layers.data("ids", shape=[1], dtype=np.int32)
        h0 = pt.layers.fc(x, size=D, param_attr=ParamAttr(name="front.w"),
                          bias_attr=False)
        loop = pt.layers.Repeat(times=times)
        with loop.block():
            h2, row = _body(h0, dropout=0.3)
            h2 = pt.layers.elementwise_add(
                h2, pt.layers.scale(pt.layers.cast(ids, np.float32), 0.01))
            loop.update(h0, h2)
            loop.turn_output(row)
        h, rows = loop()
        cost = _cost(h, _split_rows(rows, times))
        return cost, pt.append_backward(cost)

    def grads():
        prog, startup, (cost, pairs) = _programs(build)
        exe = pt.Executor()
        exe.run(startup)
        feed = {"x": _x(), "ids": np.arange(B, dtype=np.int32).reshape(B, 1)}
        got = exe.run(prog, feed=feed,
                      fetch_list=[cost] + [g for _, g in pairs])
        return got[0], {p.name: g for (p, _), g in zip(pairs, got[1:])}

    cost, mine = grads()
    monkeypatch.setattr(
        cf, "_all_turns_but_the_last_again",
        lambda step, times: lambda ints, floats, vals: jax.lax.scan(
            jax.checkpoint(lambda v, it: step(ints, floats, v, it)), vals,
            jnp.arange(times, dtype=jnp.int32)))
    want_cost, want = grads()
    # the same sums in the same order; XLA fuses a turn that stands outside
    # the loop with its neighbours, so the last bit is the compiler's
    np.testing.assert_allclose(cost, want_cost, rtol=1e-6)
    assert sorted(mine) == sorted(want) == ["b", "front.w", "n.w", "w"]
    for name in want:
        assert np.abs(want[name]).max() > 1e-3
        np.testing.assert_allclose(mine[name], want[name], rtol=2e-6,
                                   atol=1e-6, err_msg=name)


def test_the_body_is_traced_once_whatever_k_is():
    """A scan, not K copies: the step's jaxpr holds the body's matmul once
    forward and the loop as a `scan` of length K."""
    def build():
        return _looped(5)[1]

    prog, startup, h = _programs(build)
    exe = pt.Executor()
    exe.run(startup)
    state = {p.name: pt.global_scope().get(p.name) for p in prog.parameters()}
    raw = exe._raw_step(prog, [h.name])
    text = str(jax.make_jaxpr(raw)({}, state, {"x": _x()}, np.uint32(1)))
    assert text.count("dot_general") == 1 and "length=5" in text


@pytest.mark.parametrize("what", [
    "times_zero", "times_not_int", "shape", "dtype", "update_after",
    "turn_output_after", "updated_twice", "nothing_declared"])
def test_build_time_errors(what):
    x = pt.layers.data("x", shape=[D], dtype=np.float32)
    if what in ("times_zero", "times_not_int"):
        with pytest.raises(ValueError, match="at least 1"):
            pt.layers.Repeat(times=0 if what == "times_zero" else 2.0)
        return
    loop = pt.layers.Repeat(times=2)
    if what == "shape":
        with pytest.raises(ValueError, match="keeps its shape"), loop.block():
            loop.update(x, pt.layers.fc(x, size=D + 1))
    elif what == "dtype":
        with pytest.raises(ValueError, match="keeps its shape"), loop.block():
            loop.update(x, pt.layers.cast(x, np.int32))
    elif what == "updated_twice":
        with pytest.raises(ValueError, match="updated twice"), loop.block():
            loop.update(x, pt.layers.scale(x, scale=2.0))
            loop.update(x, pt.layers.scale(x, scale=3.0))
    elif what == "nothing_declared":
        with pytest.raises(ValueError, match="neither"), loop.block():
            pt.layers.scale(x, scale=2.0)
    else:
        with loop.block():
            y = pt.layers.scale(x, scale=2.0)
            loop.update(x, y)
        call = loop.update if what == "update_after" else loop.turn_output
        with pytest.raises(RuntimeError, match="after the block"):
            call(*((x, y) if what == "update_after" else (y,)))


def test_a_carry_the_trace_changes_is_named():
    """A body that hands on another dtype than the Program declared (here
    through amp) is refused when it traces, by the carry's name."""
    def build():
        x = pt.layers.data("x", shape=[D], dtype=np.float32)
        loop = pt.layers.Repeat(times=2)
        with loop.block():
            loop.update(x, pt.layers.fc(x, size=D, bias_attr=False))
        return loop()[0]

    prog, startup, out = _programs(build)
    prog.set_amp("bfloat16")
    exe = pt.Executor()
    exe.run(startup)
    with pytest.raises(RuntimeError, match="the carry x enters a turn"):
        exe.run(prog, feed={"x": _x()}, fetch_list=[out])
