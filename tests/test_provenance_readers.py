"""The three readers of `pt_executor_instruction_scope` (ISSUE 40:
`step.xla_inserted_ms`, `step.unnamed_ms`, `opt.carried_device_ms`) on run
records made by hand, as `chipbench/tests/test_bounded_step_share.py` does for
its reader: an op table as `xplane.reduce` gives it and a registry as a
window records it (series named as the program's registry renders them)."""

import importlib.util
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
FAMILY = "pt_executor_instruction_scope"
STEP, STARTUP = "jit_raw.0a1b2c3d", "jit_raw.99999999"
GEMM = "jit(raw)/transpose(jvp(mul.fc_0.tmp_1))/dot_general"
STEPS = 10


def _reader(name):
    path = os.path.join(ROOT, "chipbench", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "t_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _row(name, opcode, ns, op_name="", shape="f32[8,8]", container=False):
    scope, transform = "", ""
    m = re.match(r"jit\(raw\)/((?:\w+\()*)([^()/]+)\)*/", op_name)
    if m:
        scope, transform = m.group(2), m.group(1).rstrip("(")
    return {"name": "%" + name, "opcode": opcode, "shape": shape,
            "target": None, "container": container, "count": STEPS, "ns": ns,
            "op_name": op_name, "scope": scope, "transform": transform}


def _series(program, instruction, scope, via, weight=1.0):
    return {f'{FAMILY}{{instruction="{instruction}",program="{program}",'
            f'scope="{scope}",via="{via}"}}': weight}


OPS = [
    # the weight-gradient GEMM with two updates fused in: named by the trace
    _row("fusion.1", "fusion", 40_000_000, GEMM),
    _row("fusion.2", "fusion", 20_000_000,
         "jit(raw)/jvp(mul.fc_0.tmp_1)/dot_general"),
    # Adam standing alone
    _row("fusion.3", "fusion", 5_000_000, "jit(raw)/adam.tfm.tok_emb/sub"),
    # what XLA put between them: no op_name
    _row("copy.1", "copy", 3_000_000),
    _row("copy-done.2", "copy-done", 2_000_000, shape="bf16[2048,64]"),
    _row("fusion.4", "fusion", 1_500_000, shape="(f32[8,8]"),
    _row("custom-call.5", "custom-call", 600_000),
    # another program's row in the window, and a constant several ops share
    _row("convert.6", "convert", 400_000),
    _row("copy.7", "copy", 30_000),
    # a loop covers its body's rows: never summed
    _row("while.8", "while", 9_000_000, container=True),
]
REGISTRY = {
    "pt_executor_donated_bytes": 8.0e9,
    **_series(STEP, "fusion.1", "transpose(jvp(mul.fc_0.tmp_1))", "root", 0.2),
    **_series(STEP, "fusion.1", "adam.fc_0.w", "member", 0.5),
    **_series(STEP, "fusion.1", "adam.fc_0.b", "member", 0.3),
    **_series(STEP, "copy.1", "argument.moe_3.w_up", "argument"),
    **_series(STEP, "copy-done.2", "jvp(moe_ffn.moe_3.tmp_4)", "consumer"),
    **_series(STEP, "fusion.4", "jvp(gelu.fc_0.tmp_2)", "fused", 0.25),
    **_series(STEP, "fusion.4", "adam.fc_0.w", "fused", 0.75),
    **_series(STEP, "custom-call.5", "transpose(jvp(mul.fc_0.tmp_1))",
              "producer"),
    # the startup program names a `convert.6` too, and covers far less time
    **_series(STARTUP, "convert.6", "uniform_random.fc_0.w", "consumer"),
}
PROGRAM_OPS = [
    {"type": "mul", "scope": "mul.fc_0.tmp_1", "inputs": {"X": ["x"]},
     "outputs": {"Out": ["fc_0.tmp_1"]}},
    {"type": "autodiff", "scope": "autodiff", "inputs": {}, "outputs": {}},
    {"type": "adam", "scope": "adam.fc_0.w",
     "inputs": {"Param": ["fc_0.w"]}, "outputs": {"ParamOut": ["fc_0.w"]}},
    {"type": "adam", "scope": "adam.fc_0.b",
     "inputs": {"Param": ["fc_0.b"]}, "outputs": {"ParamOut": ["fc_0.b"]}},
    {"type": "adam", "scope": "adam.tfm.tok_emb",
     "inputs": {"Param": ["tfm.tok_emb"]},
     "outputs": {"ParamOut": ["tfm.tok_emb"]}},
]


def _run(**changed):
    run = {"trace": {"ops": OPS}, "registry": REGISTRY, "steps": STEPS,
           "program_ops": PROGRAM_OPS}
    run.update(changed)
    return run


def test_named_and_unnamed_sum_to_what_the_table_has_without_a_scope():
    named, unnamed = _reader("step.xla_inserted_ms"), _reader("step.unnamed_ms")
    run = _run()
    no_scope = sum(r["ns"] for r in OPS
                   if not r["container"] and not r["scope"]) / 1e6 / STEPS
    assert named.compute(run) == pytest.approx(0.71)
    assert unnamed.compute(run) == pytest.approx(0.043)
    assert named.compute(run) + unnamed.compute(run) == pytest.approx(no_scope)


def test_inserted_rows_are_put_down_to_the_op_they_belong_to():
    info = _reader("step.xla_inserted_ms").info(_run())
    assert info["by_op_and_pass_ms"] == pytest.approx({
        "argument plain": 0.3, "moe_ffn jvp": 0.2,
        "adam plain": 0.15,     # the unrooted fusion goes to its heaviest scope
        "mul transpose": 0.06})
    assert list(info["by_op_and_pass_ms"]) == [
        "argument plain", "moe_ffn jvp", "adam plain", "mul transpose"]
    assert info["by_opcode_ms"] == pytest.approx({
        "copy": 0.3, "copy-done": 0.2, "fusion": 0.15, "custom-call": 0.06})
    assert info["by_via_ms"] == pytest.approx({
        "argument": 0.3, "consumer": 0.2, "fused": 0.15, "producer": 0.06})
    assert info["longest"][0] == [
        "%copy.1", "copy", "f32[8,8]", "argument.moe_3.w_up", "argument",
        pytest.approx(0.3)]
    assert [row[0] for row in info["longest"]] == [
        "%copy.1", "%copy-done.2", "%fusion.4", "%custom-call.5"]


def test_rows_left_without_a_name_are_listed_from_a_twentieth_of_a_ms():
    reader = _reader("step.unnamed_ms")
    assert reader.info(_run()) == {"rows": []}     # 0.04 and 0.003 ms a step
    # `convert.6` is named by the STARTUP program's table only: the step
    # program is the one whose instructions cover the most device time
    assert reader.info(_run(steps=5)) == {"rows": [
        ["%convert.6", "convert", "f32[8,8]", pytest.approx(0.08)]]}


def test_carrier_counts_once_though_two_updates_ride_in_it():
    reader = _reader("opt.carried_device_ms")
    run = _run()
    # fusion.1 (two Adam members, once) and the unrooted fusion.4; not
    # fusion.3, which IS an optimizer op and is `opt.device_ms`'s
    assert reader.compute(run) == pytest.approx(4.0 + 0.15)
    info = reader.info(run)
    assert info["by_carrier_op_and_pass_ms"] == pytest.approx(
        {"mul transpose": 4.0, "adam plain": 0.15})
    assert info["optimizer_members_weight_mean"] == pytest.approx(
        {"mul transpose": 0.8, "adam plain": 0.75})
    assert info["carriers"] == 2
    assert info["opt.device_ms"] == pytest.approx(0.5)


@pytest.mark.parametrize("changed", [
    {"trace": None},                                   # an untraced run
    {"trace": {"ops": []}},                            # a rehearsal on the CPU
    {"registry": {"pt_executor_donated_bytes": 8.0e9}},   # a parent of PR 40
    {"registry": None},
], ids=["no_trace", "empty_trace", "no_family", "no_registry"])
@pytest.mark.parametrize("name", ["step.xla_inserted_ms", "step.unnamed_ms",
                                  "opt.carried_device_ms"])
def test_nothing_to_read_gives_nothing_and_does_not_raise(name, changed):
    assert _reader(name).compute(_run(**changed)) is None


def test_carried_time_needs_an_optimizer_op():
    reader = _reader("opt.carried_device_ms")
    assert reader.compute(_run(program_ops=PROGRAM_OPS[:2])) is None
    assert reader.compute(_run(program_ops=None)) is None
    # optimizer ops, and none rides in another op's fusion: 0, not nothing
    alone = {k: v for k, v in REGISTRY.items() if "adam." not in k}
    assert reader.compute(_run(registry=alone)) == 0.0
