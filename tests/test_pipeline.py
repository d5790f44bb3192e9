"""Micro-batch pipeline-parallel executor (ISSUE 14, paddle_tpu/pipeline).

The load-bearing claim is the determinism contract: for a fixed
microbatch count M, the staged GPipe schedule produces BIT-IDENTICAL
parameters to the unstaged run for every stage count K — masked bubble
cells add exact 0.0, the reverse scan drains microbatch gradients in a
K-invariant order, and the partitioner snaps automatic cuts to the
narrowest boundary so a cut never forces a cotangent across the scan
carry mid-fusion (the transformer A/B below is the regression test for
exactly that failure, observed before _narrow_cuts existed).
"""

import ast
import logging
import os

import numpy as np
import pytest

import jax

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu import parallel as pp
from paddle_tpu.obs import promparse
from paddle_tpu.obs.metrics import registry
from paddle_tpu.pipeline import (
    PipelineExecutor, split_program, stage_boundary,
)
from paddle_tpu.pipeline import partition as ppart


# ------------------------------------------------------------- builders --


def _mlp(depth=4, dim=16, markers=False, seed=7):
    pt.default_main_program().random_seed = seed
    pt.default_startup_program().random_seed = seed
    x = pt.layers.data("x", shape=[dim])
    y = pt.layers.data("y", shape=[1])
    h = x
    for i in range(depth):
        if markers and i in (depth // 2,):
            stage_boundary()
        h = pt.layers.fc(h, size=dim, act="relu")
    pred = pt.layers.fc(h, size=1)
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    return loss


def _mlp_feed(batch=8, dim=16, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(batch, dim).astype(np.float32),
            "y": rng.randn(batch, 1).astype(np.float32)}


def _tiny_transformer(seed=11, dim=32, depth=2, seqlen=8, vocab=50):
    pt.default_main_program().random_seed = seed
    pt.default_startup_program().random_seed = seed
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    logits = models.transformer_lm(toks, vocab_size=vocab, dim=dim,
                                   num_heads=1, num_layers=depth,
                                   max_len=seqlen)
    loss = pt.layers.mean(
        pt.layers.softmax_with_cross_entropy(logits, labels))
    pt.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return loss


def _tfm_feed(batch=8, seqlen=8, vocab=50, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "toks": rng.randint(0, vocab, (batch, seqlen)).astype(np.int32),
        "labels": rng.randint(0, vocab,
                              (batch, seqlen, 1)).astype(np.int32),
    }


def _params():
    return {n: np.asarray(pt.global_scope().get(n))
            for n in sorted(pt.global_scope().keys())
            if not n.startswith("@")}


def _step_params(build, feed, steps=2, **exe_kw):
    pt.reset()
    loss = build()
    exe = PipelineExecutor(**exe_kw)
    exe.run_startup(pt.default_startup_program())
    losses = []
    for s in range(steps):
        (l,) = exe.run(feed=feed(seed=s), fetch_list=[loss])
        losses.append(float(np.asarray(l)))
    return losses, _params()


# ------------------------------------------------------------ partition --


def test_split_auto_balanced_contract():
    _mlp(depth=6)
    staged = split_program(pt.default_main_program(), num_stages=3)
    assert len(staged.stages) == 3
    persist = {v.name for v in pt.default_main_program().persistables()}
    assert all(len(s.ops) >= 1 for s in staged.stages)
    for s in staged.stages:
        # persistables never cross a boundary; they enter via state
        assert not (set(s.out_names) & persist)
        assert set(s.state_names) <= persist
    # every intermediate boundary produces what the next stages consume
    for a, b in zip(staged.stages, staged.stages[1:]):
        assert a.out_names, "non-final stage must export its boundary"
        assert set(a.out_names) <= set(b.in_names) | {
            n for st in staged.stages[b.index:] for n in st.in_names}


def test_split_marker_cuts_win():
    _mlp(depth=4, markers=True)
    staged = split_program(pt.default_main_program(), num_stages=2)
    assert len(staged.stages) == 2
    # the marker sits before fc layer depth//2: stage 0 holds exactly
    # the ops of the first two fc layers (mul+add+relu each)
    first_types = [op.type for op in staged.stages[0].ops]
    assert first_types.count("mul") == 2


def test_split_unmarked_requires_num_stages():
    _mlp(depth=2)
    with pytest.raises(ValueError, match="num_stages"):
        split_program(pt.default_main_program())


def test_split_rejects_oversplit():
    _mlp(depth=2)
    with pytest.raises(ValueError, match="exceeds"):
        split_program(pt.default_main_program(), num_stages=10_000)


def test_split_rejects_sparse_embedding():
    toks = pt.layers.data("t", shape=[4], dtype=np.int32)
    y = pt.layers.data("y", shape=[1])
    emb = pt.layers.embedding(toks, size=[16, 8], is_sparse=True)
    pooled = pt.layers.reduce_mean(emb, dim=1)
    pred = pt.layers.fc(pooled, size=1)
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    with pytest.raises(NotImplementedError, match="sparse"):
        split_program(pt.default_main_program(), num_stages=2)


def test_split_rejects_trainmode_batchnorm():
    x = pt.layers.data("x", shape=[8])
    y = pt.layers.data("y", shape=[1])
    h = pt.layers.fc(x, size=8)
    h = pt.layers.batch_norm(h)  # train mode writes running stats
    pred = pt.layers.fc(h, size=1)
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    with pytest.raises(NotImplementedError, match="persistable"):
        split_program(pt.default_main_program(), num_stages=2)


def test_auto_cut_narrows_to_residual_boundary():
    """The DP balancer alone would happily cut through the middle of a
    residual block (boundary = skip tensor + mid-block tmp, width 2);
    _narrow_cuts must slide the cut to the residual stream (width 1).
    This is the partition-level guarantee behind the transformer
    bit-identity A/B below."""
    x = pt.layers.data("x", shape=[8])
    y = pt.layers.data("y", shape=[1])
    h = x
    for _ in range(4):
        b = pt.layers.fc(h, size=8, act="relu")
        b = pt.layers.fc(b, size=8)
        h = pt.layers.elementwise_add(h, b)
    pred = pt.layers.fc(h, size=1)
    loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    staged = split_program(pt.default_main_program(), num_stages=2)
    assert len(staged.stages[0].out_names) == 1, staged.stages[0].out_names


# ------------------------------------------------- fixed-seed A/B (MLP) --


@pytest.mark.parametrize("k,schedule", [(2, "gpipe"), (4, "gpipe"),
                                        (2, "1f1b")])
def test_pipeline_bitwise_vs_unstaged_mlp(k, schedule):
    """Params after 2 fixed-seed steps are BIT-identical across stage
    counts at fixed M — the core determinism contract."""
    ref_losses, ref = _step_params(_mlp, _mlp_feed, num_stages=1,
                                   num_microbatches=4)
    losses, got = _step_params(_mlp, _mlp_feed, num_stages=k,
                               num_microbatches=4, schedule=schedule)
    assert losses == ref_losses
    assert set(got) == set(ref)
    bad = [n for n in ref if not np.array_equal(ref[n], got[n])]
    assert not bad, f"K={k} {schedule}: diverged {bad[:6]}"


def test_pipeline_bitwise_vs_unstaged_transformer_autocut():
    """Regression test for the narrowed-cut fix: the auto-balancer's
    natural cut on a transformer lands mid-fc (between a mul and its
    bias add), which reassociates the upstream backward and voids
    identity wholesale; _narrow_cuts snaps it to the residual stream.

    K=2 staged and K=1 unstaged are different compiled programs. With
    jaxlib 0.9.0 XLA:CPU orders ONE reduction differently between them:
    the bias-gradient sums of the two bias adds feeding the residual
    stream at the cut (attention `wo_b` and the ffn-out bias of the
    first block), so those two bias rows and their Adam moments differ
    by 1-2 ulp. The loss, every weight matrix and every other bias stay
    bit-identical, and that is what is held: a diverging tensor must be
    one of at most two bias rows (+ moments), within 4 ulp of its
    largest element."""
    ref_losses, ref = _step_params(_tiny_transformer, _tfm_feed,
                                   steps=1, num_stages=1,
                                   num_microbatches=4)
    losses, got = _step_params(_tiny_transformer, _tfm_feed,
                               steps=1, num_stages=2, num_microbatches=4)
    assert losses == ref_losses

    def param_of(name):  # adam_N.momentM.<param> -> <param>
        return name.split(".moment1.")[-1].split(".moment2.")[-1]

    def is_bias_row(name):
        p = param_of(name)
        return ref[name].ndim == 1 and (p.endswith("_b") or ".b_" in p)

    bad = [n for n in ref if not np.array_equal(ref[n], got[n])]
    assert all(is_bias_row(n) for n in bad), \
        f"transformer K=2: non-bias tensors diverged {bad[:6]}"
    assert len({param_of(n) for n in bad}) <= 2, bad
    eps = np.finfo(np.float32).eps
    for n in bad:
        np.testing.assert_allclose(
            got[n], ref[n], rtol=0,
            atol=4 * eps * float(np.abs(ref[n]).max()), err_msg=n)


def test_pipeline_marker_cut_bitwise():
    ref_losses, ref = _step_params(lambda: _mlp(markers=True), _mlp_feed,
                                   num_stages=1, num_microbatches=2)
    losses, got = _step_params(lambda: _mlp(markers=True), _mlp_feed,
                               num_stages=2, num_microbatches=2)
    assert losses == ref_losses
    assert all(np.array_equal(ref[n], got[n]) for n in ref)


def test_pipeline_requires_divisible_batch():
    pt.reset()
    loss = _mlp()
    exe = PipelineExecutor(num_stages=2, num_microbatches=3)
    exe.run_startup(pt.default_startup_program())
    with pytest.raises(ValueError, match="divisible|microbatch"):
        exe.run(feed=_mlp_feed(batch=8), fetch_list=[loss])


# -------------------------------------------------- trainer integration --


def test_trainer_runs_on_pipeline_executor():
    loss = _mlp()

    def reader():
        for i in range(6):
            yield _mlp_feed(seed=i)

    t = pt.Trainer(loss, executor=PipelineExecutor(
        num_stages=2, num_microbatches=4))
    metrics = t.train(reader, num_passes=1, log_interval=3)
    assert np.isfinite(metrics["cost"])


def test_mesh_scan_window_fallback_names_pipeline(caplog):
    """Satellite 1: the scan-window fallback on mesh executors is LOUD
    and tells the user the pipeline executor is the alternative."""
    mesh = pp.mesh_from_spec("dp2")
    loss = _mlp()

    def reader():
        for i in range(2):
            yield _mlp_feed(seed=i)

    t = pt.Trainer(loss, executor=pp.ParallelExecutor(mesh))
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.trainer"):
        t.train(reader, num_passes=1, scan_window=2)
    assert any("PipelineExecutor" in r.message for r in caplog.records)


# ----------------------------------------------------------------- mesh --


@pytest.mark.needs_multidevice_pp
def test_pipeline_on_pp_mesh_matches_meshless():
    _, ref = _step_params(_mlp, _mlp_feed, num_stages=2,
                          num_microbatches=4)
    pt.reset()
    loss = _mlp()
    mesh = pp.mesh_from_spec("dp2,pp2")
    exe = PipelineExecutor(num_stages=2, num_microbatches=4, mesh=mesh)
    exe.run_startup(pt.default_startup_program())
    for s in range(2):
        (l,) = exe.run(feed=_mlp_feed(seed=s), fetch_list=[loss])
    assert np.isfinite(np.asarray(l))
    got = _params()
    # GSPMD changes reduction order: close, not bitwise
    for n in ref:
        np.testing.assert_allclose(ref[n], got[n], rtol=2e-4, atol=1e-5)


@pytest.mark.needs_multidevice_pp
def test_pipeline_stage_count_must_divide_pp_axis():
    _mlp()
    mesh = pp.mesh_from_spec("dp2,pp2")
    with pytest.raises(ValueError, match="pp"):
        PipelineExecutor(num_stages=3, num_microbatches=4, mesh=mesh)


# -------------------------------------------------------------- metrics --


def test_pipeline_metrics_declared_then_live():
    """Satellite 6: series exist at 0 before the first dispatch (scrape
    never sees a missing family), then report the schedule's analytic
    bubble/occupancy after it."""
    import gc

    pt.reset()
    gc.collect()  # drop earlier tests' executors: their weakref-backed
    # collectors would otherwise still answer this scrape
    loss = _mlp()
    exe = PipelineExecutor(num_stages=4, num_microbatches=4)
    fams = promparse.parse_text(registry().render())
    assert fams["pt_pipeline_bubble_fraction"].value() == 0.0
    assert fams["pt_ckpt_reshard_total"].value() == 0.0

    exe.run_startup(pt.default_startup_program())
    exe.run(feed=_mlp_feed(), fetch_list=[loss])
    fams = promparse.parse_text(registry().render())
    np.testing.assert_allclose(
        fams["pt_pipeline_bubble_fraction"].value(), 3 / 7)
    for s in range(4):
        np.testing.assert_allclose(
            fams["pt_pipeline_stage_occupancy"].value({"stage": str(s)}),
            4 / 7)


# ----------------------------------------------------- host-sync lint --


def test_stage_schedule_hot_loop_has_no_host_syncs():
    """Satellite 5: AST lint over pipeline/schedule.py — the staged-step
    trace functions must never call a host-sync primitive (device_get /
    block_until_ready / np.asarray / .item / .tolist); one sync inside
    the tick body would serialize the whole grid per step."""
    import paddle_tpu.pipeline.schedule as sched

    src = open(sched.__file__.rstrip("c")).read()
    tree = ast.parse(src)
    hot = {"raw", "tick", "run_stage", "probe", "_staged_step"}
    banned = {"device_get", "block_until_ready", "asarray", "item",
              "tolist", "copy_to_host_async"}
    offenders = []

    class V(ast.NodeVisitor):
        def __init__(self):
            self.stack = []

        def visit_FunctionDef(self, node):
            self.stack.append(node.name)
            self.generic_visit(node)
            self.stack.pop()

        def visit_Attribute(self, node):
            if node.attr in banned and set(self.stack) & hot:
                offenders.append((self.stack[-1], node.attr, node.lineno))
            self.generic_visit(node)

    V().visit(tree)
    assert not offenders, (
        f"host syncs in the stage-schedule hot loop: {offenders}")
