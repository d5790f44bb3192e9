"""The looped decoder LM (`models.looped_lm`: one stack run K times through
`layers.Repeat`, the head and a float32 exit gate read after every turn, the
expected cost over the exits) through `Executor` against
`tests/looped_reference.py` on seeded weights, and the exit distribution's
own cases. CPU: attention takes the jnp formulation;
`tests/test_tpu_compile.py` compiles the step for a described v5e.
"""

import importlib.util
import os
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import models

sys.path.insert(0, os.path.dirname(__file__))
import looped_reference as ref  # noqa: E402

ROOT = os.path.join(os.path.dirname(__file__), "..")
SMALL = dict(vocab_size=128, hidden_size=32, num_hidden_layers=2,
             num_attention_heads=4, head_dim=8, intermediate_size=48,
             total_ut_steps=4, rope_theta=1e6, rms_norm_eps=1e-6,
             exit_beta=0.05)
B, T = 2, 24


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-12))


def _build(amp=None, cfg=SMALL, train=True, **kw):
    pt.reset()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        toks = pt.layers.data("toks", shape=[T], dtype=np.int32)
        labels = pt.layers.data("labels", shape=[T, 1], dtype=np.int32)
        cost, turn_costs, probs = models.looped_lm(
            toks, labels, vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
            num_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
            num_layers=cfg["num_hidden_layers"],
            ffn_dim=cfg["intermediate_size"], turns=cfg["total_ut_steps"],
            rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"],
            exit_beta=cfg["exit_beta"], **kw)
        if train:
            pt.optimizer.Adam(learning_rate=3e-4).minimize(cost)
    prog.random_seed = startup.random_seed = 11
    if amp:
        prog.set_amp(amp)
    return prog, startup, cost, turn_costs, probs


def _batch(seed=5):
    toks = np.random.RandomState(seed).randint(0, SMALL["vocab_size"], (B, T + 1))
    return {"toks": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:, None].astype(np.int32)}


def _first_step(amp=None, cfg=SMALL, gate=None):
    """One step through Executor on seeded weights: the system's cost, turn
    costs, exit probabilities and every parameter's gradient (read as the
    harness reads it: Adam's first moment over 1 - beta1), and the
    reference's. `gate` = (weight's standard deviation, bias) moves the
    exit gate off its start (a zero weight: every token the same exits) so
    that every token and exit has its own probability."""
    prog, startup, cost, turn_costs, probs = _build(amp, cfg)
    exe = pt.Executor()
    exe.run(startup)
    scope = pt.global_scope()
    names = [p.name for p in prog.parameters()]
    assert not np.asarray(scope.get("looped.exit.w")).any()    # starts shut
    if gate is not None:
        scope.set("looped.exit.w", (np.random.RandomState(7).randn(
            cfg["hidden_size"]) * gate[0]).astype(np.float32))
        scope.set("looped.exit.b", np.full((1,), gate[1], np.float32))
    params = [np.array(scope.get(n)) for n in names]
    feed = _batch()
    got = exe.run(prog, feed=feed, fetch_list=[cost, turn_costs, probs])
    moments = {op.inputs["Param"][0]: op.inputs["Moment1"][0]
               for op in prog.global_block().ops if op.type == "adam"}
    want_cost, want_grads = ref.loss_and_grads(cfg, params, feed)
    _, want_turns, want_probs = ref.outputs(cfg, params, feed)
    errs = {n: _rel(np.asarray(scope.get(moments[n]), np.float32) / (1 - 0.9), w)
            for n, w in zip(names, want_grads)}
    return dict(names=names, errs=errs, cost=float(got[0]),
                want_cost=float(want_cost), turns=got[1][..., 0],
                want_turns=np.asarray(want_turns), probs=got[2],
                want_probs=np.asarray(want_probs),
                grads=dict(zip(names, want_grads)))


def test_program_parameter_order_is_the_reference_order():
    prog, *_ = _build()
    names = [p.name for p in prog.parameters()]
    L = SMALL["num_hidden_layers"]
    assert len(names) == 1 + ref.PER_LAYER * L + 4
    assert names[0] == "looped.tok_emb"
    assert [n.split(".", 2)[2] for n in names[1:1 + ref.PER_LAYER]] == [
        "n1.w", "attn.wq", "attn.wk", "attn.wv", "attn.wo", "n2.w", "n3.w",
        "mlp.gate", "mlp.up", "mlp.down", "n4.w"]
    assert names[-4:] == ["looped.ln_f.w", "looped.out_w", "looped.exit.w",
                          "looped.exit.b"]
    # one repeat op; the stack, the head, its cross-entropy and the gate are
    # in its sub-block, the table and the exit cost outside
    outer = [op.type for op in prog.global_block().ops]
    assert outer[:2] == ["lookup_table", "repeat"]
    assert "exit_expected_cost" in outer and "flash_attention" not in outer
    loop = prog.global_block().ops[1]
    body = [op.type for op in prog.blocks[loop.attrs["sub_block"]].ops]
    assert body.count("flash_attention") == L
    assert body.count("softmax_with_cross_entropy") == 1
    assert loop.attrs["times"] == 4 and loop.attrs["remat"] is True


@pytest.mark.parametrize("gate", [None, (0.7, 0.3)], ids=["start", "moved"])
def test_float32_model_matches_the_reference(gate):
    r = _first_step(gate=gate)
    assert abs(r["cost"] - r["want_cost"]) < 2e-5 * max(1, abs(r["want_cost"]))
    assert _rel(r["turns"], r["want_turns"]) < 2e-5
    assert _rel(r["probs"], r["want_probs"]) < 2e-5
    assert sorted(r["errs"]) == sorted(r["names"])
    worst = max(r["errs"], key=r["errs"].get)
    assert r["errs"][worst] < 2e-4, (worst, r["errs"][worst])
    # every tensor is read in every turn and has a gradient to show for it
    assert all(np.abs(np.asarray(g)).max() > 0 for g in r["grads"].values())
    if gate:
        spread = r["probs"].std(axis=(1, 2))
        assert (spread > 1e-3).all(), spread


def test_bf16_amp_model_stays_near_the_reference():
    r = _first_step(amp="bfloat16", gate=(0.7, 0.3))
    assert abs(r["cost"] - r["want_cost"]) < 0.02
    worst = max(r["errs"], key=r["errs"].get)
    assert r["errs"][worst] < 0.2, (worst, r["errs"][worst])
    assert r["probs"].dtype == np.float32 and r["turns"].dtype == np.float32


@pytest.mark.parametrize("key,value", [("total_ut_steps", 3), ("exit_beta", 0.0)])
def test_the_reference_tells_another_model(key, value):
    """The comparison has teeth: a reference of three turns, or one without
    the entropy term, is another function."""
    r = _first_step(gate=(0.7, 0.3))
    params = [np.array(pt.global_scope().get(n)) for n in r["names"]]
    same, other = (float(ref.loss_and_grads(cfg, params, _batch())[0])
                   for cfg in (SMALL, dict(SMALL, **{key: value})))
    assert abs(other - same) > 5e-3, (other, same)


def _exit_cost(costs, gates, beta):
    """`layers.exit_expected_cost` alone, through Executor."""
    pt.reset()
    c = pt.layers.data("c", shape=list(costs.shape), dtype=np.float32,
                       append_batch_size=False)
    s = pt.layers.data("s", shape=list(gates.shape), dtype=np.float32,
                       append_batch_size=False)
    cost, probs = pt.layers.exit_expected_cost(c, s, beta=beta)
    return pt.Executor().run(feed={"c": costs, "s": gates},
                             fetch_list=[cost, probs])


def test_exit_probabilities_sum_to_one_and_the_last_takes_what_is_left():
    rng = np.random.RandomState(0)
    costs = rng.rand(4, 3, 5, 1).astype(np.float32) * 5
    gates = (rng.randn(4, 3, 5) * 3).astype(np.float32)
    cost, p = _exit_cost(costs, gates, beta=0.05)
    lam = 1 / (1 + np.exp(-gates.astype(np.float64)))
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(p[3], np.prod(1 - lam[:3], axis=0), rtol=1e-5)
    np.testing.assert_allclose(p[1], lam[1] * (1 - lam[0]), rtol=1e-5)
    want = (p * costs[..., 0]).sum(0) + 0.05 * (p * np.log(p)).sum(0)
    np.testing.assert_allclose(cost, want, rtol=1e-5, atol=1e-6)
    want_cost, want_p = ref.expected_cost(costs[..., 0], gates, 0.05)
    np.testing.assert_allclose(cost, want_cost, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(p, want_p, rtol=1e-5, atol=1e-7)
    # the last turn's gate is not read
    gates2 = gates.copy()
    gates2[3] += 7.0
    assert np.array_equal(_exit_cost(costs, gates2, 0.05)[0], cost)


@pytest.mark.parametrize("bias,turn", [(-30.0, 3), (30.0, 0)])
def test_a_shut_or_open_gate_gives_one_turns_cross_entropy(bias, turn):
    """beta 0 with the gate's bias at -30: nobody leaves early, the cost is
    exactly turn K's cross-entropy; at +30 everybody leaves at turn 1."""
    costs = np.random.RandomState(1).rand(4, 2, 6, 1).astype(np.float32) * 4
    gates = np.full((4, 2, 6), bias, np.float32)
    cost, p = _exit_cost(costs, gates, beta=0.0)
    assert np.array_equal(cost, costs[turn, ..., 0])
    assert np.array_equal(p[turn], np.ones((2, 6), np.float32))


def test_extreme_gates_stay_finite_with_the_entropy_term():
    """log p from log_sigmoid sums, never the log of a product: at |s| = 200
    a product's log is -inf and 0 x -inf is nan."""
    costs = np.ones((4, 1, 3, 1), np.float32)
    for s in (-200.0, 200.0):
        cost, p = _exit_cost(costs, np.full((4, 1, 3), s, np.float32), 0.05)
        assert np.isfinite(cost).all() and np.isfinite(p).all()
        np.testing.assert_allclose(cost, 1.0, atol=1e-6)


def test_the_gradient_reaches_the_gate_only_through_p():
    """Equal turn costs and beta 0: sum_r p_r c = c whatever the gates say,
    so the gate's weight and bias get no gradient; with the entropy term, or
    with turn costs that differ (the model's own), they do."""
    def gate_grads(beta, equal):
        pt.reset()
        h = pt.layers.data("h", shape=[4, 5, 8], dtype=np.float32,
                           append_batch_size=False)
        c = pt.layers.data("c", shape=[4, 5, 1], dtype=np.float32,
                           append_batch_size=False)
        loop = pt.layers.Repeat(times=4)
        with loop.block():
            h2 = pt.layers.scale(h, scale=1.5)
            loop.update(h, h2)
            loop.turn_output(c if equal else pt.layers.reduce_sum(
                pt.layers.elementwise_mul(h2, h2), dim=-1, keep_dim=True))
            loop.turn_output(pt.layers.exit_gate(h2, name="gate"))
        _, costs, gates = loop()
        cost = pt.layers.mean(pt.layers.exit_expected_cost(costs, gates, beta)[0])
        pairs = pt.append_backward(cost)
        # a drawn gate weight leaves a gradient under 1e-4 once in ~20 draws
        pt.default_startup_program().random_seed = 3
        exe = pt.Executor()
        exe.run(pt.default_startup_program())
        pt.global_scope().set("gate.b", np.full((1,), 0.2, np.float32))
        rng = np.random.RandomState(2)
        return exe.run(feed={"h": rng.randn(4, 5, 8).astype(np.float32),
                             "c": rng.rand(4, 5, 1).astype(np.float32)},
                       fetch_list=[g for _, g in pairs])

    for g in gate_grads(0.0, equal=True):
        assert np.abs(g).max() < 1e-7
    for beta, equal in ((0.05, True), (0.0, False)):
        assert all(np.abs(g).max() > 1e-4 for g in gate_grads(beta, equal))


def test_the_gate_is_float32_under_amp():
    prog, startup, *_ = _build(amp="bfloat16", train=False)
    loop = next(op for op in prog.global_block().ops if op.type == "repeat")
    body = prog.blocks[loop.attrs["sub_block"]].ops
    gate_out = loop.attrs["turn_outputs"][1]
    made_by = {n: op for op in body for n in op.output_names()}
    # from the closing norm's float32 output through cast, product, sum and
    # bias: no `mul` (which amp would run in bf16) on the way
    chain, name = [], gate_out
    while name in made_by and made_by[name].type != "rms_norm":
        chain.append(made_by[name].type)
        name = made_by[name].inputs["X"][0]
    assert chain == ["elementwise_add", "reduce_sum", "elementwise_mul", "cast"]
    exe = pt.Executor()
    exe.run(startup)
    probs, = exe.run(prog, feed=_batch(), fetch_list=[
        prog.global_block().ops[-2].outputs["Probs"][0]])
    assert probs.dtype == np.float32


def test_moving_a_token_changes_no_output_before_it():
    prog, startup, cost, turn_costs, probs = _build(train=False)
    exe = pt.Executor()
    exe.run(startup)
    feed = _batch()
    a = exe.run(prog, feed=feed, fetch_list=[turn_costs, probs])
    i = 13
    feed2 = {k: v.copy() for k, v in feed.items()}
    feed2["toks"][:, i] = (feed2["toks"][:, i] + 1) % SMALL["vocab_size"]
    b = exe.run(prog, feed=feed2, fetch_list=[turn_costs, probs])
    assert np.array_equal(a[0][:, :, :i], b[0][:, :, :i])
    assert np.array_equal(a[1][:, :, :i], b[1][:, :, :i])
    assert not np.array_equal(a[0][:, :, i:], b[0][:, :, i:])


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("amp", [None, "bfloat16"], ids=["float32", "amp"])
def test_configs_looped_lm_trains_at_tiny_sizes(amp):
    from paddle_tpu.obs import metrics
    from paddle_tpu.trainer import EndIteration, Trainer

    pt.reset()
    m = _load("configs/looped_lm.py", "looped_config").get_model(
        steps=30, seed=3, amp=amp)
    costs = []

    def handler(e):
        if isinstance(e, EndIteration):
            costs.append(e.cost)

    Trainer(cost=m["cost"]).train(m["reader"], num_passes=1,
                                  event_handler=handler, log_interval=10)
    first, last = float(costs[0]), float(costs[-1])
    assert np.isfinite(last) and last < first - 0.1, (first, last)
    reg = metrics.registry()
    assert reg.counter_value("pt_repeat_dispatch_total",
                             labels={"remat": "true"}) >= 1
    assert "pt_repeat_turns 4" in reg.render()
