"""The Executor's record of its compiled step programs (ISSUE 40): which
program op each instruction of the optimized HLO belongs to
(`core/provenance.py`), read once at a cache miss while
`FLAGS.enable_timers` is on and published as
`pt_executor_instruction_scope{program,instruction,scope,via}`.

(a) a small AMP transformer block under Adam, compiled by XLA:CPU: every
    instruction the device would run gets a rule, a copy of a donated
    argument is `argument.<variable>`, a copy with one named user goes to
    that user, an update fused into its gradient's op is listed as a member;
(b) with timers off nothing is lowered beyond `jax.jit`'s own and the family
    is absent;
(c) the parser and the three rules on hand-written HLO.
The readers of the family are in `tests/test_provenance_readers.py`.
"""

import gc
import importlib.util
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.core import executor as ex
from paddle_tpu.core import provenance
from paddle_tpu.flags import FLAGS
from paddle_tpu.obs import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = "pt_executor_instruction_scope"
T, B, V = 16, 2, 64


@pytest.fixture
def timers_on():
    saved = FLAGS.enable_timers
    FLAGS.enable_timers = True
    try:
        yield
    finally:
        FLAGS.enable_timers = saved


def _block():
    """One transformer block under bf16 AMP and Adam: (cost, feed)."""
    pt.reset()
    toks = pt.layers.data("toks", shape=[T], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[T, 1], dtype=np.int32)
    logits = models.transformer_lm(toks, vocab_size=V, dim=32, num_heads=2,
                                   num_layers=1, max_len=T)
    cost = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    pt.optimizer.Adam(learning_rate=3e-4).minimize(cost)
    pt.default_main_program().set_amp("bfloat16")
    rng = np.random.RandomState(0)
    return cost, {"toks": rng.randint(0, V, (B, T)).astype(np.int32),
                  "labels": rng.randint(0, V, (B, T, 1)).astype(np.int32)}


@pytest.fixture
def step(timers_on, monkeypatch):
    """The block's step program compiled with timers on: (its compiled text,
    its table, the executor)."""
    texts = []
    table = provenance.table
    monkeypatch.setattr(provenance, "table",
                        lambda text: texts.append(text) or table(text))
    cost, feed = _block()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    exe.run(feed=feed, fetch_list=[cost])
    whole = table(texts[-1])
    # the executor keeps what a trace cannot name itself
    assert exe._provenance[-1] == dict(
        whole, rows=provenance.published(whole["rows"]))
    return texts[-1], whole, exe


def _device_instructions(text):
    """Every instruction of the entry computation and of the bodies it calls
    that is no parameter, constant, tuple plumbing or container."""
    _, entry, computations = provenance.parse(text)
    pending, seen, out = [entry], set(), []
    while pending:
        comp = pending.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for inst in computations[comp]:
            if inst.opcode in provenance.CONTAINERS:
                pending += inst.called
            elif inst.opcode not in ("parameter", "constant") \
                    + provenance.PLUMBING:
                out.append(inst)
    return out


def test_every_device_instruction_of_a_compiled_block_gets_a_rule(step):
    text, table, _ = step
    rows = {r["instruction"]: r for r in table["rows"]}
    ran = _device_instructions(text)
    assert len(ran) > 200
    assert [i.name for i in ran if i.name not in rows] == []
    assert {r["rule"] for r in rows.values()} == {"root", "fused", "neighbour"}
    for row in rows.values():
        assert row["scopes"], row
        # the listing weights of an instruction's scopes sum to 1
        assert sum(w for _, _, w in row["scopes"]) == pytest.approx(1.0), row


def test_fusion_without_an_op_name_is_named_by_its_members(step):
    text, table, _ = step
    _, _, computations = provenance.parse(text)
    by_name = {i.name: i for insts in computations.values() for i in insts}
    fused = [r for r in table["rows"] if r["rule"] == "fused"]
    assert fused
    for row in fused:
        assert by_name[row["instruction"]].op_name == ""
        assert all(via == "fused" for _, via, _ in row["scopes"])
        weights = [w for _, _, w in row["scopes"]]
        assert weights == sorted(weights, reverse=True)   # heaviest first


def test_copy_of_a_donated_argument_is_named_by_its_variable(step):
    _, table, _ = step
    donated = ex.rebound_persistables(pt.default_main_program())
    arguments = {r["scopes"][0][0] for r in table["rows"]
                 if r["scopes"][0][1] == "argument"}
    assert arguments
    assert arguments <= {"argument." + n for n in donated}
    assert "argument.tfm.out_w" in arguments
    assert all(r["opcode"] == "copy" for r in table["rows"]
               if r["scopes"][0][1] == "argument")


def test_copy_with_one_named_user_goes_to_the_consumer(step):
    text, table, _ = step
    _, entry, computations = provenance.parse(text)
    named = {r["instruction"]: r["scopes"][0][0] for r in table["rows"]
             if r["rule"] != "neighbour"}
    consumers = [r for r in table["rows"] if r["scopes"][0][1] == "consumer"]
    assert consumers
    for row in consumers:
        users = [i.name for i in computations[entry]
                 if row["instruction"] in i.operands]
        scopes = {provenance.scope_of(named[u])[0] for u in users
                  if u in named}
        if len(users) == len([u for u in users if u in named]):
            assert scopes == {provenance.scope_of(row["scopes"][0][0])[0]}


def test_root_fusion_lists_the_other_ops_it_carries_as_members(step):
    """What `opt.carried_device_ms` reads on the chip, where XLA fuses an
    update into its gradient's GEMM (XLA:CPU fuses nothing into a `dot`: the
    hand-written module below has that case): a fusion named by its root
    lists its members' other scopes."""
    _, table, _ = step
    carriers = [r for r in provenance.published(table["rows"])
                if r["rule"] == "root"]
    assert carriers
    for row in carriers:
        assert row["opcode"] == "fusion"
        assert row["scopes"][0][1] == "root"
        assert {via for _, via, _ in row["scopes"][1:]} == {"member"}
        assert row["scopes"][0][0] not in [s for s, _, _ in row["scopes"][1:]]


def test_family_is_rendered_for_what_the_trace_cannot_name(step):
    _, table, _ = step
    lines = [ln for ln in metrics.registry().render().splitlines()
             if ln.startswith(FAMILY + "{")]
    published = provenance.published(table["rows"])
    mine = [ln for ln in lines if f'program="{table["program"]}"' in ln]
    assert len(mine) == sum(len(r["scopes"]) for r in published)
    plain = [r for r in table["rows"]
             if r["rule"] == "root" and len(r["scopes"]) == 1]
    assert plain and not any(
        f'instruction="{r["instruction"]}"' in ln for r in plain[:20]
        for ln in mine)
    assert any('via="argument"' in ln and 'scope="argument.tfm.out_w"' in ln
               for ln in mine)


def test_stats_command_shows_the_family(step, tmp_path, capsys):
    from paddle_tpu import cli

    path = tmp_path / "exposition.txt"
    path.write_text(metrics.registry().render())
    assert cli.main(["stats", "--file", str(path)]) == 0
    shown = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith(FAMILY)]
    assert len(shown) == 1 and "gauge" in shown[0] and "series" in shown[0]


class _CountsLowerings:
    """A jitted step that counts the calls of its `lower`."""

    def __init__(self, fn, lowered):
        self.fn, self.lowered = fn, lowered

    def __call__(self, *args):
        return self.fn(*args)

    def lower(self, *args):
        self.lowered.append(len(args))
        return self.fn.lower(*args)


@pytest.mark.parametrize("timers", [False, True], ids=["off", "on"])
def test_only_a_traced_run_lowers_a_step_program_again(monkeypatch, timers):
    gc.collect()    # an earlier test's executor, and its tables with it
    lowered, compile_ = [], ex.Executor._compile
    monkeypatch.setattr(
        ex.Executor, "_compile", lambda self, *args: _CountsLowerings(
            compile_(self, *args), lowered))
    monkeypatch.setattr(FLAGS, "enable_timers", timers)
    cost, feed = _block()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    first = exe.run(feed=feed, fetch_list=[cost])
    again = exe.run(feed=feed, fetch_list=[cost])   # a cache hit
    assert np.isfinite(first[0]) and again[0] < first[0]
    assert exe.cache_stats == {"hits": 1, "misses": 2,
                               "plans_built": 2, "plans_reused": 1}
    # once a compiled program (startup, step), with the call's own arguments
    assert lowered == ([4, 4] if timers else [])
    assert len(exe._provenance) == (2 if timers else 0)
    assert (FAMILY in metrics.registry().render()) == timers


def test_window_program_is_recorded_too(timers_on):
    """`run_window`'s cache-miss branch reads its program's text as `run`'s
    does: the scan's body is a `while` whose instructions get rows."""
    import jax.numpy as jnp

    pt.reset()
    x = pt.layers.data("x", shape=[8])
    y = pt.layers.data("y", shape=[1])
    pred = pt.layers.fc(pt.layers.fc(x, size=16, act="tanh"), size=1)
    cost = pt.layers.mean(pt.layers.square_error_cost(pred, y))
    pt.optimizer.Adam(learning_rate=0.01).minimize(cost)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    before = len(exe._provenance)
    rng = np.random.RandomState(0)
    feed = {"x": jnp.asarray(rng.randn(3, 4, 8).astype(np.float32)),
            "y": jnp.asarray(rng.randn(3, 4, 1).astype(np.float32))}
    ys, _ = exe.run_window(feed=feed, fetch_list=[cost])
    assert np.all(np.isfinite(np.asarray(ys[0])))
    assert len(exe._provenance) == before + 1
    rows = exe._provenance[-1]["rows"]
    assert exe._provenance[-1]["program"].startswith("jit_win.")
    # inside the scan every op_name starts `jit(win)/while/body/...`: by the
    # trace readers' rule the scope of the body's rows is the loop
    assert "while" in {provenance.scope_of(s)[0] for r in rows
                       for s, _, _ in r["scopes"]}


# -- (c) hand-written HLO -----------------------------------------------------
PLAIN = """HloModule jit_raw, is_scheduled=true

%fused_computation (param_0: f32[8,8], param_1: f32[8,8]) -> f32[8,8] {
  %param_0 = f32[8,8]{1,0} parameter(0)
  %param_1 = f32[8,8]{1,0} parameter(1)
  %dot.1 = f32[8,8]{1,0} dot(%param_0, %param_1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(raw)/transpose(jvp(mul.fc_0.tmp_1))/dot_general"}
  %multiply.1 = f32[8,8]{1,0} multiply(%dot.1, %dot.1), metadata={op_name="jit(raw)/adam.fc_0.w/mul"}
  ROOT %subtract.1 = f32[8,8]{1,0} subtract(%param_1, %multiply.1), metadata={op_name="jit(raw)/adam.fc_0.w/sub"}
}

%fused_computation.1 (param_0.1: f32[8,8]) -> (f32[8,8], f32[8]) {
  %param_0.1 = f32[8,8]{1,0} parameter(0)
  %exp.1 = f32[8,8]{1,0} exponential(%param_0.1), metadata={op_name="jit(raw)/jvp(gelu.fc_0.tmp_2)/exp"}
  %constant.1 = f32[] constant(0)
  %reduce.1 = f32[8]{0} reduce(%exp.1, %constant.1), dimensions={1}, to_apply=%region_0, metadata={op_name="jit(raw)/jvp(mean.mean_0.tmp_3)/reduce_sum"}
  ROOT %tuple.1 = (f32[8,8]{1,0}, f32[8]{0}) tuple(%exp.1, %reduce.1)
}

ENTRY %main.9 (donated__fc_0_w__.1: f32[8,8], feed__x__.1: f32[8,8]) -> (f32[8,8], f32[8]) {
  %donated__fc_0_w__.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="donated[\\'fc_0.w\\']"}
  %feed__x__.1 = f32[8,8]{1,0:T(8,128)} parameter(1), metadata={op_name="feed[\\'x\\']"}
  %copy.1 = f32[8,8]{0,1:T(8,128)(2,1)} copy(%donated__fc_0_w__.1)
  %copy.2 = f32[8,8]{0,1} copy(%feed__x__.1)
  %fusion.1 = f32[8,8]{1,0} fusion(%copy.2, %copy.1), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(raw)/transpose(jvp(mul.fc_0.tmp_1))/dot_general"}
  %fusion.2 = (f32[8,8]{1,0}, f32[8]{0}) fusion(%copy.1), kind=kLoop, calls=%fused_computation.1
  %get-tuple-element.1 = f32[8,8]{1,0} get-tuple-element(%fusion.2), index=0
  %get-tuple-element.2 = f32[8]{0} get-tuple-element(%fusion.2), index=1
  %copy.3 = f32[8]{0:S(1)} copy(%get-tuple-element.2)
  ROOT %tuple.2 = (f32[8,8]{1,0}, f32[8]{0}) tuple(%fusion.1, %copy.3)
}
"""

LOOP = """HloModule jit_win

body (arg: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  arg = (s32[], f32[4,8]{1,0}) parameter(0)
  one = s32[] constant(1)
  count = s32[] get-tuple-element(arg), index=0
  carried = f32[4,8]{1,0} get-tuple-element(arg), index=1
  staged = f32[4,8]{0,1} copy(carried)
  doubled = f32[4,8]{1,0} add(staged, staged), metadata={op_name="jit(win)/while/body/closed_call/scale.scale_0.tmp_1/add"}
  next = s32[] add(count, one)
  prefetched = f32[4,8]{1,0} copy(doubled)
  ROOT out = (s32[], f32[4,8]{1,0}) tuple(next, prefetched)
}

cond (arg.1: (s32[], f32[4,8])) -> pred[] {
  arg.1 = (s32[], f32[4,8]{1,0}) parameter(0)
  three = s32[] constant(3)
  count.1 = s32[] get-tuple-element(arg.1), index=0
  ROOT less = pred[] compare(count.1, three), direction=LT
}

ENTRY main (x: f32[4,8]) -> f32[4,8] {
  x = f32[4,8]{1,0} parameter(0), metadata={op_name="feeds['x']"}
  zero = s32[] constant(0)
  init = (s32[], f32[4,8]{1,0}) tuple(zero, x)
  loop = (s32[], f32[4,8]{1,0}) while(init), condition=cond, body=body, metadata={op_name="jit(win)/jvp(recurrent.rnn_0.tmp_2)/while"}
  ROOT last = f32[4,8]{1,0} get-tuple-element(loop), index=1
}
"""

ASYNC = """HloModule jit_raw

%async_computation (p: bf16[2048,64]) -> bf16[2048,64] {
  %p = bf16[2048,64]{1,0} parameter(0)
  ROOT %gathered = bf16[2048,64]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(raw)/jvp(moe_ffn.moe_0.tmp_1)/experts/gmm"}
}

ENTRY %main (donated__moe_0_w_up__.1: bf16[8192,64]) -> bf16[2048,64] {
  %donated__moe_0_w_up__.1 = bf16[8192,64]{1,0} parameter(0), metadata={op_name="donated[\\'moe_0.w_up\\']"}
  %slice-start.1 = ((bf16[8192,64]{1,0}), bf16[2048,64]{1,0:S(1)}, s32[]{:S(2)}) slice-start(%donated__moe_0_w_up__.1), slice={[0:2048], [0:64]}
  %slice-done.1 = bf16[2048,64]{1,0:S(1)} slice-done(%slice-start.1)
  %async-start.1 = ((bf16[2048,64]{1,0}), bf16[2048,64]{1,0}, u32[]) async-start(%slice-done.1), calls=%async_computation
  %async-done.1 = bf16[2048,64]{1,0} async-done(%async-start.1), calls=%async_computation
  %copy-start.1 = (bf16[2048,64]{1,0}, bf16[2048,64]{1,0}, u32[]{:S(2)}) copy-start(%async-done.1)
  ROOT %copy-done.1 = bf16[2048,64]{1,0} copy-done(%copy-start.1)
}
"""


def _rows(text):
    return {r["instruction"]: r for r in provenance.table(text)["rows"]}


def test_parser_reads_names_with_a_percent_sign():
    module, entry, computations = provenance.parse(PLAIN)
    assert (module, entry) == ("jit_raw", "main.9")
    assert sorted(computations) == ["fused_computation",
                                    "fused_computation.1", "main.9"]
    fusion = next(i for i in computations["main.9"] if i.name == "fusion.1")
    assert (fusion.opcode, fusion.operands, fusion.called, fusion.elements) \
        == ("fusion", ["copy.2", "copy.1"], ["fused_computation"], 64)
    assert provenance.scope_of(fusion.op_name) \
        == ("mul.fc_0.tmp_1", "transpose(jvp")
    multi = next(i for i in computations["main.9"] if i.name == "fusion.2")
    assert (multi.op_name, multi.elements) == ("", 72)   # a tuple's, summed
    layout = next(i for i in computations["main.9"] if i.name == "copy.1")
    assert (layout.opcode, layout.elements) == ("copy", 64)


def test_rules_on_a_root_a_tuple_rooted_fusion_and_their_copies():
    rows = _rows(PLAIN)
    # root: the GEMM's fusion carries the update of the weight it differentiates
    assert rows["fusion.1"]["rule"] == "root"
    assert rows["fusion.1"]["scopes"] == [
        ["transpose(jvp(mul.fc_0.tmp_1))", "root", pytest.approx(1 / 3)],
        ["adam.fc_0.w", "member", pytest.approx(2 / 3)]]
    # fused: rooted in a tuple, named by its members, weights summing to 1
    assert rows["fusion.2"]["rule"] == "fused"
    assert rows["fusion.2"]["scopes"] == [
        ["jvp(gelu.fc_0.tmp_2)", "fused", pytest.approx(64 / 72)],
        ["jvp(mean.mean_0.tmp_3)", "fused", pytest.approx(8 / 72)]]
    assert sum(w for _, _, w in rows["fusion.2"]["scopes"]) \
        == pytest.approx(1.0)
    # neighbour: one named user; two users of different ops and a donated
    # argument behind them; a named producer through a get-tuple-element
    assert rows["copy.2"]["scopes"] == [
        ["transpose(jvp(mul.fc_0.tmp_1))", "consumer", 1.0]]
    assert rows["copy.1"]["scopes"] == [["argument.fc_0.w", "argument", 1.0]]
    assert rows["copy.3"]["scopes"] == [
        ["jvp(gelu.fc_0.tmp_2)", "producer", 1.0]]
    assert "tuple.2" not in rows and "get-tuple-element.1" not in rows
    assert [r["instruction"] for r in provenance.published(rows.values())] \
        == ["copy.1", "copy.2", "fusion.1", "fusion.2", "copy.3"]


def test_rules_inside_a_while_body_with_names_that_carry_no_percent_sign():
    module, entry, computations = provenance.parse(LOOP)
    assert (module, entry) == ("jit_win", "main")
    assert [i.name for i in computations["body"]] == [
        "arg", "one", "count", "carried", "staged", "doubled", "next",
        "prefetched", "out"]
    rows = _rows(LOOP)
    # the body's and the condition's instructions have rows, the loop has none
    assert "loop" not in rows and "init" not in rows
    assert rows["doubled"]["rule"] == "root"
    assert rows["staged"]["scopes"] == [
        ["while", "consumer", 1.0]]     # `scope_of`'s rule on a bare loop
    # what feeds the next iteration through the body's root has no user
    # here: the nearest named producer
    assert rows["prefetched"]["scopes"] == [["while", "producer", 1.0]]
    # no named neighbour at all: the scope of the loop that runs the body
    assert rows["next"]["scopes"] == [
        ["jvp(recurrent.rnn_0.tmp_2)", "caller", 1.0]]
    assert rows["less"]["scopes"] == [
        ["jvp(recurrent.rnn_0.tmp_2)", "caller", 1.0]]


def test_rules_on_an_async_pair_and_a_slice_of_a_donated_argument():
    rows = _rows(ASYNC)
    kernel = ["jvp(moe_ffn.moe_0.tmp_1)", "fused", 1.0]
    # the pair is named by what its wrapped computation runs
    assert rows["async-start.1"]["scopes"] == [kernel]
    assert rows["async-done.1"]["scopes"] == [kernel]
    # the slice that feeds it goes to its consumer, the copy out of it to its
    # producer, both halves of each
    for name in ("slice-start.1", "slice-done.1"):
        assert rows[name]["scopes"] == [[kernel[0], "consumer", 1.0]]
    for name in ("copy-start.1", "copy-done.1"):
        assert rows[name]["scopes"] == [[kernel[0], "producer", 1.0]]


LONG = """HloModule jit_raw

ENTRY %main (p0: f32[8], p1: f32[8], p2: f32[8], p3: f32[8], p4: f32[8], p5: f32[8]) -> f32[48] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  %p2 = f32[8]{0} parameter(2)
  %p3 = f32[8]{0} parameter(3)
  %p4 = f32[8]{0} parameter(4)
  %p5 = f32[8]{0} parameter(5)
  %copy.6 = f32[8]{0:S(1)} copy(%p5)
  ROOT %concatenate.7 = f32[48]{0} concatenate(%p0, %p1, %p2, %p3, %p4, /*index=5*/%copy.6), dimensions={0}, metadata={op_name="jit(raw)/jvp(concat.concat_0.tmp_1)/concatenate"}
}
"""


def test_parser_drops_the_index_comment_before_every_fifth_operand():
    """The printer writes `/*index=5*/%copy.6` with no space: read as part of
    the name it cut the edge to every fifth operand of a long list, and the
    broadcasts four layers' loops read as their sixth stayed unnamed on the
    chip (glm, 0.41 ms a step)."""
    _, entry, computations = provenance.parse(LONG)
    assert computations[entry][-1].operands == [
        "p0", "p1", "p2", "p3", "p4", "copy.6"]
    assert _rows(LONG)["copy.6"]["scopes"] == [
        ["jvp(concat.concat_0.tmp_1)", "consumer", 1.0]]


def _xplane():
    spec = importlib.util.spec_from_file_location(
        "xplane_for_provenance", os.path.join(ROOT, "chipbench", "xplane.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("op_name", [
    "jit(raw)/transpose(jvp(mul.fc_394.tmp_395))/dot_general:",
    "jit(raw)/jvp(moe_ffn.nemotron_h.h3.moe.tmp_25)/dispatch/scatter-add",
    "jit(raw)/adam.tfm.tok_emb/sub",
    "jit(raw)/jit(_where)/jvp(rms_norm.rms_norm_0.tmp_1)/select_n",
    "jit(raw)/while/body/add",
    "jit(raw)/while",
    "transpose(jvp(mul.fc_394.tmp_395))",
    "argument.tfm.tok_emb",
    "",
])
def test_scope_of_is_the_trace_readers_rule(op_name):
    assert provenance.scope_of(op_name) == _xplane().scope_of(op_name)
