"""The benchmark's own checks, in the tier-1 run: `chipbench/selftest.py`
(no backend), the cases of `chipbench/tests/` (JAX on the CPU), and what
PR 27 added for the olmoe-1b-7b configuration: its FLOPs arithmetic, its
kernel's operations and bytes, its readers on hand-made run records, its
copy of the plain reference against the tree's, and one CPU rehearsal of its
cell through `chipbench/run.py`.
"""

import importlib.util
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    path = os.path.join(BENCH, *parts)
    spec = importlib.util.spec_from_file_location(
        "cb_" + re.sub(r"\W", "_", "_".join(parts)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the harness's own pytest cases, collected here under their own names
for _cases in ("test_harness.py", "test_bounded_step_share.py"):
    globals().update({name: fn for name, fn in
                      vars(_load("tests", _cases)).items()
                      if name.startswith("test_")})


def test_selftest_passes():
    out = subprocess.run([sys.executable, os.path.join(BENCH, "selftest.py")],
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-1000:]
    assert re.search(r"(\d+) of \1 checks passed", out.stdout), out.stdout[-300:]


def _config():
    with open(os.path.join(BENCH, "configs", "olmoe-1b-7b", "config.json")) as f:
        return json.load(f)


CELL = {"batch": 1, "seqlen": 4096}


def test_olmoe_flops_per_token():
    flops = _load("flops.py")
    cfg = _config()
    config_dir = os.path.join(BENCH, "configs", "olmoe-1b-7b")
    got = flops.train_flops_per_item(cfg, CELL, config_dir)
    assert got == 1071906816.0            # ISSUE 27: 1.072e9 at T 4096
    # the published 16 layers: the layer's part 16 times, the head once
    full = flops.train_flops_per_item(
        dict(cfg, num_hidden_layers=16), CELL, config_dir)
    head = 3 * 2 * 2048 * 50304
    assert full - head == 16 * (got - head)


def test_olmoe_config_keeps_the_published_sizes():
    cfg = _config()
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    differs = [k for k, v in published.items() if cfg.get(k, "absent") != v]
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 16}
    assert cfg["num_hidden_layers"] == 1


def test_moe_grouped_matmul_counts_on_a_hand_made_cell():
    kernel = _load("kernels", "moe_grouped_matmul.py")
    cfg = {"num_hidden_layers": 2, "hidden_size": 8, "intermediate_size": 4,
           "num_experts": 3, "num_experts_per_tok": 2}
    flops, bytes_ = kernel.flops_and_bytes(cfg, {"batch": 5, "seqlen": 10})
    rows = 5 * 10 * 2
    assert flops == 2 * 18 * rows * 8 * 4
    assert bytes_ == 2 * 2 * (9 * 3 * 8 * 4 + rows * (5 * 8 + 7 * 4))
    # the cell's own: 1.24 TFLOP against 3.7 GB, compute-bound by a hair
    flops, bytes_ = kernel.flops_and_bytes(_config(), CELL)
    assert flops == 18 * 32768 * 2048 * 1024
    assert 3.5e9 < bytes_ < 3.9e9
    assert flops / 197e12 > bytes_ / 819e9


def _row(scope, ns, target=None, transform="", inner="", container=False):
    op_name = f"jit(raw)/{transform}{scope}{')' * transform.count('(')}"
    return {"name": "%x", "opcode": "custom-call" if target else "fusion",
            "shape": "", "target": target, "container": container, "count": 2,
            "ns": ns, "op_name": op_name + (f"/{inner}/dot" if inner else "/dot"),
            "scope": scope, "transform": transform.rstrip("(")}


MOE = "moe_ffn.olmoe.h0.moe.tmp_7"


def _run_record():
    ops = [
        _row(MOE, 4_000_000, "tpu_custom_call", "", "experts"),
        _row(MOE, 6_000_000, "tpu_custom_call", "transpose(jvp(", "experts"),
        _row(MOE, 1_000_000, None, "jvp(", "route"),
        _row(MOE, 3_000_000, None, "", "dispatch"),
        _row(MOE, 9_000_000, None, "", "", container=True),   # a loop: left out
        _row("flash_attention.attn.tmp_3", 8_000_000, "tpu_custom_call"),
        _row("mul.fc_9.tmp_9", 5_000_000),
    ]
    return {
        "steps": 2, "trace": {"ops": ops}, "device": {"kind": "TPU v5 lite"},
        "config": {"num_hidden_layers": 1, "hidden_size": 2048,
                   "intermediate_size": 1024, "num_experts": 64,
                   "num_experts_per_tok": 8, "num_attention_heads": 16},
        "cell": {"batch": 1, "seqlen": 4096},
        "program_ops": [
            {"type": "moe_ffn", "scope": MOE, "inputs": {"X": ["h"]},
             "outputs": {"Out": ["olmoe.h0.moe.tmp_7"]}},
            {"type": "mul", "scope": "mul.fc_9.tmp_9",
             "inputs": {"X": ["h"], "Y": ["w"]}, "outputs": {"Out": ["l"]}}],
    }


def test_moe_readers_on_a_hand_made_run_record():
    run = _run_record()
    device = _load("layer_metrics", "moe.device_ms.py")
    assert device.compute(run) == pytest.approx((4 + 6 + 1 + 3) / 2)
    info = device.info(run)
    assert info["by_inner_scope_ms"] == pytest.approx(
        {"experts": 5.0, "route": 0.5, "dispatch": 1.5})
    assert info["by_pass_ms"] == pytest.approx(
        {"plain": 3.5, "jvp": 0.5, "transpose": 3.0})
    dispatch = _load("layer_metrics", "moe.dispatch_ms.py")
    assert dispatch.compute(run) == pytest.approx((1 + 3) / 2)
    gmm = _load("layer_metrics", "kernel.gmm_roofline.py")
    # 1.2369 TFLOP at 197 TFLOP/s = 6.279 ms over the kernels' 5 ms a step
    assert gmm.compute(run) == pytest.approx(100 * 6.2787 / 5.0, rel=1e-3)
    assert gmm.info(run)["bound"] == "compute"
    assert gmm.info(run)["kernels_per_step"] == 2.0
    # nothing to read: no routed op in the Program, or no trace
    for reader in (device, dispatch, gmm):
        assert reader.compute(dict(run, program_ops=run["program_ops"][1:])) is None
        assert reader.compute(dict(run, trace=None)) is None


ATTN = "flash_attention.attn.tmp_3"


def test_attn_device_ms_on_a_hand_made_run_record():
    """PR 28: the attention op's whole scope, kernels and the ops around
    them, leaves only."""
    attn = _load("layer_metrics", "attn.device_ms.py")
    ops = [
        _row(ATTN, 4_000_000, "tpu_custom_call"),
        _row(ATTN, 2_000_000, "tpu_custom_call", "jvp("),
        _row(ATTN, 10_000_000, "tpu_custom_call", "transpose(jvp("),
        _row(ATTN, 3_000_000, None, "transpose(jvp("),        # a layout copy
        _row(ATTN, 1_000_000, None, "jvp("),
        _row(ATTN, 50_000_000, None, "", container=True),      # a loop: left out
        _row("flash_attention_2.tmp_0", 7_000_000),            # another op type
        _row(MOE, 6_000_000, "tpu_custom_call"),
    ]
    run = {"steps": 2, "trace": {"ops": ops}}
    assert attn.compute(run) == pytest.approx(10.0)
    info = attn.info(run)
    assert (info["kernels_ms"], info["not_kernels_ms"]) == (8.0, 2.0)
    assert info["by_pass_ms"] == pytest.approx(
        {"plain": 2.0, "jvp": 1.5, "transpose": 6.5})
    # nothing to read: no attention op in the trace, or no trace (a program
    # without the scopes, an untraced run)
    assert attn.compute({"steps": 2, "trace": {"ops": ops[-2:]}}) is None
    assert attn.compute({"steps": 2, "trace": None}) is None
    assert attn.compute({"steps": 2}) is None


def test_plain_forward_ms_on_a_hand_made_run_record():
    """PR 33: the forward ops' device time outside differentiation. A step
    that holds its forward once leaves only what no parameter reaches."""
    reader = _load("layer_metrics", "step.plain_forward_ms.py")
    adam = {"type": "adam", "scope": "adam.w", "inputs": {"Param": ["w"]},
            "outputs": {"ParamOut": ["w"]}}
    autodiff = {"type": "autodiff", "scope": "autodiff.w@GRAD",
                "inputs": {"Loss": ["l"]}, "outputs": {"Grads": ["w@GRAD"]}}
    run = _run_record()
    run["program_ops"] += [autodiff, adam]
    run["trace"]["ops"] += [
        _row("adam.w", 7_000_000),                       # behind the autodiff
        _row("mul.fc_9.tmp_9", 2_000_000, None, "jvp("),
        _row("mul.fc_9.tmp_9", 2_500_000, None, "transpose(jvp("),
        _row("", 1_500_000),                             # no scope
    ]
    # the routed op's two plain rows (its container left out), the attention
    # op's is of no listed op, the mul's plain row
    assert reader.compute(run) == pytest.approx((4 + 3 + 5) / 2)
    assert reader.info(run) == {"by_op_type_ms": pytest.approx(
        {"moe_ffn": 3.5, "mul": 2.5})}
    assert list(reader.info(run)["by_op_type_ms"]) == ["moe_ffn", "mul"]
    # one trace of the forward: every forward row is `jvp`
    once = dict(run, trace={"ops": [r for r in run["trace"]["ops"]
                                    if r["transform"] or r["scope"] == "adam.w"]})
    assert reader.compute(once) == 0.0
    assert reader.info(once) == {"by_op_type_ms": {}}
    # nothing to read: an inference Program, no Program, no trace
    assert reader.compute(dict(run, program_ops=run["program_ops"][:2])) is None
    assert reader.compute(dict(run, program_ops=None)) is None
    assert reader.compute(dict(run, trace=None)) is None


def test_attn_device_ms_on_the_recorded_scoped_trace():
    """The parent's gpt2-small step as recorded by PR 26 (TPU v5 lite, seed
    2147486001, two steps): PERF.md's 68.58 ms, 28.34 of it not kernels;
    the kernels' part is what kernel.flash_roofline divides by."""
    import gzip

    xplane = _load("xplane.py")
    with gzip.open(os.path.join(
            BENCH, "testdata", "gpt2_two_steps_scoped.trace.json.gz")) as f:
        trace = json.load(f)
    with open(os.path.join(BENCH, "configs", "gpt2-small", "config.json")) as f:
        config = json.load(f)
    run = {"steps": 2, "trace": xplane.reduce(trace, chips=1),
           "config": config, "cell": {"batch": 12, "seqlen": 1024},
           "device": {"kind": "TPU v5 lite"}}
    attn = _load("layer_metrics", "attn.device_ms.py")
    assert abs(attn.compute(run) - 68.582494) < 1e-9
    info = attn.info(run)
    assert abs(info["kernels_ms"] - 40.242065) < 1e-9
    assert abs(info["not_kernels_ms"] - 28.340429) < 1e-9
    assert sum(info["by_pass_ms"].values()) == pytest.approx(68.582494)
    flash = _load("layer_metrics", "kernel.flash_roofline.py")
    assert flash.info(run)["kernel_ms_per_step"] == pytest.approx(
        info["kernels_ms"])


def test_attn_device_ms_is_in_the_manifest_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"]
                 if m["name"] == "attn.device_ms")
    assert entry["layer"] == "Kernels"
    # every cell of the configurations PR 28 found; a configuration added
    # since reads the same reader through a wrapper of its own name
    assert entry["workloads"] == [
        w["name"] for w in manifest["workloads"]
        if w["config"] in ("gpt2-small", "olmoe-1b-7b")]
    wrapped = {m["name"] for m in manifest["per_layer"]
               if m["name"].endswith(".attn_device_ms")}
    assert wrapped == {"nemotron.attn_device_ms", "glm.attn_device_ms",
                       "trinity.attn_device_ms", "lfm2.attn_device_ms",
                       # PR 44: a reader of its own (the rows are inside a
                       # loop's body, where the scope is the loop's)
                       "ouro.attn_device_ms"}


def test_load_max_over_mean_reads_the_counters_and_checks_the_sum():
    reader = _load("layer_metrics", "moe.load_max_over_mean.py")
    cfg = {"num_experts": 4, "num_experts_per_tok": 2}
    cell = {"batch": 1, "seqlen": 8}
    series = 'pt_moe_expert_tokens_total{expert="%d",layer="olmoe.h0.moe"}'
    reg = {series % 0: 20.0, series % 1: 4.0, series % 2: 8.0,
           "pt_executor_donated_bytes": 7.0}         # expert 3 never chosen
    run = {"steps": 2, "config": cfg, "cell": cell, "registry": reg}
    assert reader.compute(run) == pytest.approx(20 / (32 / 4))
    assert reader.compute(dict(run, registry={"x": 1.0})) is None
    with pytest.raises(ValueError, match="dropped or counted twice"):
        reader.compute(dict(run, registry=dict(reg, **{series % 2: 7.0})))


WRAPPERS = {"olmoe.head_device_ms": "head.device_ms",
            "olmoe.flash_roofline": "kernel.flash_roofline",
            "olmoe.opt_device_ms": "opt.device_ms",
            "olmoe.donated_gib": "step.donated_gib",
            "olmoe.feed_produce_ms_per_step": "feed.produce_ms_per_step"}


@pytest.mark.parametrize("name", sorted(WRAPPERS))
def test_a_wrapper_returns_what_the_reader_it_wraps_returns(name):
    run = _run_record()
    run["program_ops"].append(
        {"type": "adam", "scope": "adam.w", "inputs": {"Param": ["w"]},
         "outputs": {"ParamOut": ["w"]}})
    run["trace"]["ops"].append(_row("adam.w", 2_000_000))
    run["program_ops"].append(
        {"type": "softmax_with_cross_entropy",
         "scope": "softmax_with_cross_entropy.s",
         "inputs": {"Logits": ["l"], "Label": ["y"]},
         "outputs": {"Softmax": ["s"], "Loss": ["c"]}})
    run["registry"] = {"pt_executor_donated_bytes": 7.5e9}
    run["timers_s"] = {"prefetch.read": 0.5, "prefetch.batch": 0.25}
    wrapper = _load("layer_metrics", name + ".py")
    wrapped = _load("layer_metrics", WRAPPERS[name] + ".py")
    assert wrapper.WRAPS == WRAPPERS[name]
    got, want = wrapper.compute(run), wrapped.compute(run)
    assert got is not None and got == want
    if hasattr(wrapped, "info"):
        assert wrapper.info(run) == wrapped.info(run)
    # and nothing where the wrapped reader finds nothing
    empty = dict(run, trace=None, registry={}, timers_s={})
    assert wrapper.compute(empty) is None and wrapped.compute(empty) is None


def test_the_benchmarks_reference_is_the_trees_reference_bit_for_bit():
    """`chipbench/configs/olmoe-1b-7b/reference.py` is a copy of
    `tests/olmoe_reference.py`: the same cost, gradients and router logits
    to the bit on the CPU, so the two cannot drift apart unseen."""
    import olmoe_reference as tree

    copy = _load("configs", "olmoe-1b-7b", "reference.py")
    cfg = dict(_config(), hidden_size=32, num_attention_heads=2,
               intermediate_size=16, num_experts=8, num_experts_per_tok=2,
               vocab_size=64, num_hidden_layers=2)
    d, f, E, V = 32, 16, 8, 64
    r = np.random.RandomState(0)
    shapes = [(V, d)]
    for _ in range(2):
        shapes += [(d,), (d, d), (d, d), (d, d), (d,), (d,), (d, d), (d,),
                   (d, E), (E, d, f), (E, d, f), (E, f, d)]
    shapes += [(d,), (d, V)]
    params = [(r.randn(*s) * 0.2 + (len(s) == 1)).astype(np.float32)
              for s in shapes]
    toks = r.randint(0, V, (2, 17))
    feed = {"toks": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:, None].astype(np.int32)}
    assert copy.prepare(feed) is feed
    (c1, g1), (c2, g2) = (m.loss_and_grads(cfg, params, feed)
                          for m in (tree, copy))
    assert float(c1) == float(c2) and np.isfinite(float(c1))
    assert len(g1) == len(g2) == len(params)
    for a, b in zip(g1, g2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(tree.router_logits(cfg, params, feed),
                    copy.router_logits(cfg, params, feed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the text: everything from the imports down is the tree's
    text = lambda m: open(m.__file__).read().split("import math\n", 1)[1]  # noqa: E731
    assert text(copy).startswith(text(tree))


def test_the_olmoe_cell_rehearses_on_the_cpu(tmp_path):
    """`run.py --rehearse-cpu` of the new cell: the harness finds the
    configuration's files by name, the first step agrees with the plain
    reference at the rehearsal's tolerances, the counters reach the run
    record, and every metric's name carries the rehearsal's prefix."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "olmoe-1b-7b.train-log10", "--rehearse-cpu", "--trace", "1",
         "--seed", "2147486099"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["rehearsal"] and not result["failed"]
    names = set(result["metrics"])
    assert all(n.startswith("REHEARSAL_ON_CPU.") for n in names)
    assert {"REHEARSAL_ON_CPU.moe.load_max_over_mean",
            "REHEARSAL_ON_CPU.olmoe.donated_gib",
            "REHEARSAL_ON_CPU.loop.dispatch_per_step"} <= names
    assert result["metrics"]["REHEARSAL_ON_CPU.loop.dispatch_per_step"][
        "value"] == 2.0                      # sync_every 2 in the rehearsal


# ------------------------------------------- nemotron-3-nano-30b-a3b (PR 32) ---
NEMO = "nemotron-3-nano-30b-a3b"
NEMO_CELL = {"batch": 1, "seqlen": 8192}


def _nemo_config():
    with open(os.path.join(BENCH, "configs", NEMO, "config.json")) as f:
        return json.load(f)


def test_nemotron_flops_per_token():
    flops = _load("flops.py")
    cfg = _nemo_config()
    config_dir = os.path.join(BENCH, "configs", NEMO)
    got = flops.train_flops_per_item(cfg, NEMO_CELL, config_dir)
    assert got == 3 * 715001856.0            # ISSUE 32: "near 0.72 GFLOP"
    own = _load("configs", NEMO, "flops.py")
    assert own.mamba_scan_flops_per_token(cfg) == 2757632.0
    # one more M block adds the mixer's part, one more held expert a pair's
    more = own.forward_flops_per_token(
        dict(cfg, hybrid_override_pattern="MEMEM*EMEM"), 8192)
    assert more - 715001856.0 == 55394304 + 22020096 + 2757632
    wider = own.forward_flops_per_token(dict(cfg, held_experts=[0, 16]), 8192)
    assert wider - 715001856.0 == pytest.approx(4 * 7483392.0)


def test_nemotron_config_keeps_the_published_sizes():
    cfg = _nemo_config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f) if e["name"] ==
                         "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert cfg["source"] == published["source_url"]
    differs = [k for k, v in published["config"].items()
               if cfg.get(k, "absent") != v]
    assert sorted(differs) == sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
         "vocab_size"])
    assert cfg["published"] == {k: published["config"][k] for k in differs}
    # the cut: the published pattern's first nine, 8 of 128 experts behind a
    # router that stays 128 wide, an eighth of the vocabulary
    assert cfg["hybrid_override_pattern"] == \
        cfg["published"]["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert cfg["num_hidden_layers"] == 9 and cfg["vocab_size"] * 8 == 131072
    assert cfg["router_experts"] == 128 and cfg["held_experts"] == [0, 8]
    for key in ("assumed", "departures", "deployment", "distortion"):
        assert cfg[key], key
    assert "routed_parameters" not in cfg or cfg["routed_parameters"]["reason"]


def test_nemotron_kernels_count_on_hand_made_cells():
    scan = _load("kernels", "mamba2_scan.py")
    cfg = {"hybrid_override_pattern": "MEM*", "mamba_num_heads": 4,
           "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
           "chunk_size": 3}
    flops, bytes_ = scan.flops_and_bytes(cfg, {"batch": 2, "seqlen": 5})
    per_token = (2 * 16 * 2 + 2 * 8 * 4) * 2 + 4 * 4 * 8 * 16
    assert flops == 2 * 10 * 3 * per_token
    x, bc, dt = 32, 64, 4
    assert bytes_ == 2 * 10 * ((2 * (x + bc) + 4 * dt + 4 * x)
                               + (2 * (x + bc) + 4 * dt + 4 * x)
                               + (2 * (x + bc) + 4 * dt))
    flops, bytes_ = scan.flops_and_bytes(_nemo_config(), NEMO_CELL)
    assert flops == 4 * 8192 * 3 * 2757632.0     # 0.27 TFLOP: 1.4 ms at peak
    assert bytes_ == 4 * 8192 * 70400.0          # 2.31 GB: 2.8 ms at peak
    assert flops / 197e12 < bytes_ / 819e9       # memory-bound, by 2 to 1
    flash = _load("kernels", "nemotron_flash_attention.py")
    plain = _load("kernels", "flash_attention.py")
    cfg = dict(_nemo_config())
    got = flash.flops_and_bytes(cfg, NEMO_CELL)
    # one attention block of the nine, and K/V at 2 heads: the plain count
    # at one layer has the same FLOPs and the same bytes
    assert got == plain.flops_and_bytes(dict(cfg, num_hidden_layers=1),
                                        NEMO_CELL)
    assert got[0] == 32 * 12 * (8192 * 8193 // 2) * 128
    assert plain.flops_and_bytes(cfg, NEMO_CELL)[0] == 9 * got[0]


MIXER = "mamba2_mixer.nemotron_h.h0.mamba.tmp_3"
NEMO_MOE = "moe_ffn.nemotron_h.h1.moe.tmp_7"


def _nemo_run_record():
    ops = [
        _row(MIXER, 6_000_000, None, "", "in_proj"),
        _row(MIXER, 8_000_000, None, "jvp(", "scan"),
        _row(MIXER, 20_000_000, None, "transpose(jvp(", "scan"),
        _row(MIXER, 2_000_000, None, "", "gate_norm"),
        _row(MIXER, 90_000_000, None, "", "scan", container=True),  # a loop
        _row(NEMO_MOE, 4_000_000, "tpu_custom_call", "", "experts"),
        _row(NEMO_MOE, 2_000_000, None, "", "shared"),
        _row("flash_attention.nemotron_h.h5.attn.tmp_30", 10_000_000,
             "tpu_custom_call"),
        _row("flash_attention.nemotron_h.h5.attn.tmp_30", 2_000_000, None,
             "transpose(jvp("),
        _row("mul.fc_9.tmp_9", 5_000_000),
    ]
    held = 'pt_moe_held_pairs_total{expert="%d",layer="nemotron_h.h1.moe"}'
    every = 'pt_moe_expert_tokens_total{expert="%d",layer="nemotron_h.h1.moe"}'
    registry = {held % 0: 3000.0, held % 1: 3144.0,
                every % 0: 3000.0, every % 1: 3144.0, every % 100: 92160.0,
                "pt_executor_donated_bytes": 8.0e9}
    return {
        "steps": 2, "trace": {"ops": ops}, "device": {"kind": "TPU v5 lite"},
        "config": _nemo_config(), "cell": dict(NEMO_CELL), "registry": registry,
        "program_ops": [
            {"type": "mamba2_mixer", "scope": MIXER, "inputs": {"X": ["h"]},
             "outputs": {"Out": ["nemotron_h.h0.mamba.tmp_3"]}},
            {"type": "moe_ffn", "scope": NEMO_MOE, "inputs": {"X": ["h"]},
             "outputs": {"Out": ["nemotron_h.h1.moe.tmp_7"]}},
            {"type": "mul", "scope": "mul.fc_9.tmp_9",
             "inputs": {"X": ["h"], "Y": ["w"]}, "outputs": {"Out": ["l"]}}],
    }


def test_ssm_readers_on_a_hand_made_run_record():
    run = _nemo_run_record()
    device = _load("layer_metrics", "ssm.device_ms.py")
    assert device.compute(run) == pytest.approx((6 + 8 + 20 + 2) / 2)
    info = device.info(run)
    assert info["by_inner_scope_ms"] == pytest.approx(
        {"in_proj": 3.0, "scan": 14.0, "gate_norm": 1.0})
    assert info["by_pass_ms"] == pytest.approx(
        {"plain": 4.0, "jvp": 4.0, "transpose": 10.0})
    scan = _load("layer_metrics", "ssm.scan_ms.py")
    assert scan.compute(run) == pytest.approx(14.0)
    assert scan.info(run)["run_by"] == "xla"
    roofline = _load("layer_metrics", "kernel.ssm_scan_roofline.py")
    # 2.307 GB at 819 GB/s = 2.817 ms (0.2711 TFLOP would take 1.376) over
    # the scans' 14 ms a step
    assert roofline.compute(run) == pytest.approx(100 * 2.8167 / 14.0, rel=1e-3)
    assert roofline.info(run)["bound"] == "memory"
    assert roofline.info(run)["run_by"] == "xla"
    # nothing to read: a Program without the op (the parent of the PR that
    # added it), or no trace
    for reader in (device, scan, roofline):
        assert reader.compute(dict(run, program_ops=run["program_ops"][1:])) is None
        assert reader.compute(dict(run, trace=None)) is None


@pytest.mark.parametrize("kernels", [True, False], ids=["kernels", "xla"])
def test_ssm_conv_ms_reads_the_mixers_conv_scope(kernels):
    """`ssm.conv_ms`: `ssm.device_ms`'s rows under the mixer's inner `conv`
    scope, by pass, with who ran them and the path the program counted; on a
    program without that counter (the parent of PR 50) the time all the
    same, and nothing where there is no mixer or no trace."""
    run = _nemo_run_record()
    target = "tpu_custom_call" if kernels else None
    run["trace"]["ops"] += [
        _row(MIXER, 1_000_000, target, "jvp(", "conv"),
        _row(MIXER, 1_200_000, target, "transpose(jvp(", "conv"),
        _row(MIXER, 2_000_000, target, "transpose(jvp(", "conv"),
        _row(MIXER, 200_000, None, "transpose(jvp(", "conv"),    # dw's sums
        _row(MIXER, 50_000_000, None, "", "conv", container=True),
        _row(NEMO_MOE, 7_000_000, None, "", "conv")]     # another op's scope
    counted = 'pt_ssm_conv_dispatch_total{path="pallas"}'
    if kernels:
        run["registry"][counted] = 4.0
    conv = _load("layer_metrics", "ssm.conv_ms.py")
    assert conv.compute(run) == pytest.approx(4.4 / 2)
    info = conv.info(run)
    assert info["by_pass_ms"] == pytest.approx({"jvp": 0.5, "transpose": 1.7})
    assert info["kernels_ms"] == pytest.approx(2.1 if kernels else 0.0)
    assert info["run_by"] == ("kernels" if kernels else "xla")
    assert info["dispatch"] == ({counted: 4.0} if kernels else {})
    # the other readers of the layer see the rows as the layer's, not the scan's
    assert _load("layer_metrics", "ssm.device_ms.py").info(run)[
        "by_inner_scope_ms"]["conv"] == pytest.approx(2.2)
    assert _load("layer_metrics", "ssm.scan_ms.py").compute(run) == \
        pytest.approx(14.0)
    assert conv.compute(dict(run, program_ops=run["program_ops"][1:])) is None
    assert conv.compute(dict(run, trace=None)) is None
    assert conv.compute(dict(run, registry=None)) == pytest.approx(2.2)


def test_nemotron_flash_roofline_and_moe_wrapper_on_a_hand_made_run_record():
    run = _nemo_run_record()
    flash = _load("layer_metrics", "nemotron.flash_roofline.py")
    # 1.649 TFLOP at 197 TFLOP/s = 8.372 ms over the kernels' 5 ms a step:
    # over 100 and shown as it is (a hand-made time)
    assert flash.compute(run) == pytest.approx(100 * 8.3724 / 5.0, rel=1e-3)
    assert flash.info(run)["kernels_per_step"] == 1.0
    assert flash.compute(dict(run, trace=None)) is None
    moe = _load("layer_metrics", "nemotron.moe_device_ms.py")
    assert moe.compute(run) == pytest.approx(3.0)
    assert moe.info(run)["by_inner_scope_ms"] == pytest.approx(
        {"experts": 2.0, "shared": 1.0})
    assert moe.info(run)["kernels_ms"] == pytest.approx(2.0)
    assert moe.compute(dict(run, trace=None)) is None


def test_held_pair_share_reads_the_counters_and_checks_the_sum():
    reader = _load("layer_metrics", "moe.held_pair_share.py")
    run = _nemo_run_record()             # 2 steps x 8 192 tokens x 6 pairs
    assert reader.compute(run) == pytest.approx(6144 / 98304)   # 0.0625
    assert reader.info(run)["held_pairs_per_step"] == 3072.0
    # every expert held (no such counter), or no registry: nothing to read
    only_all = {k: v for k, v in run["registry"].items()
                if "held_pairs" not in k}
    assert reader.compute(dict(run, registry=only_all)) is None
    assert reader.compute(dict(run, registry=None)) is None
    with pytest.raises(ValueError, match="dropped or counted twice"):
        reader.compute(dict(run, steps=3))


def test_nemotron_routed_readers_count_a_share_of_the_experts():
    """The readers REVIEW (PR 32) asked for, each with the count a chip's
    share needs: the kernels' roofline over the HELD pairs and two stacks at
    the published width, routing's time without the shared expert, the load
    over the 128 experts the router scores."""
    gmm = _load("kernels", "nemotron_grouped_matmul.py")
    cfg = _nemo_config()
    d, f = 2688, 1856                       # the published width, not 1920
    flops, bytes_ = gmm.flops_and_bytes(cfg, NEMO_CELL)
    rows = 4 * 8192 * 6 * 8 / 128           # even routing: 3 072 a block
    assert flops == 12 * rows * d * f
    assert bytes_ == 2 * (6 * 4 * 8 * d * f + rows * 5 * (d + f))
    assert gmm.flops_and_bytes(cfg, NEMO_CELL, rows=100.0) == (
        12 * 100.0 * d * f, 2 * (6 * 4 * 8 * d * f + 100.0 * 5 * (d + f)))
    run = _nemo_run_record()                # 2 steps, 6 144 held pairs
    roofline = _load("layer_metrics", "nemotron.gmm_roofline.py")
    # 0.184 TFLOP (0.93 ms at peak) and 2.055 GB, the 32 held experts' two
    # stacks three times over most of it (2.51 ms at 819 GB/s): memory-bound
    want_bytes = 2 * (6 * 4 * 8 * d * f + 3072.0 * 5 * (d + f))
    assert roofline.info(run)["flops_per_step"] == 12 * 3072.0 * d * f
    assert roofline.info(run)["bytes_per_step"] == want_bytes
    assert roofline.info(run)["held_pairs_per_step"] == 3072.0
    assert roofline.compute(run) == pytest.approx(
        100 * want_bytes / 819e9 / 2e-3, rel=1e-3)    # the kernels' 2 ms
    assert roofline.info(run)["bound"] == "memory"
    no_held = {k: v for k, v in run["registry"].items()
               if "held_pairs" not in k}
    assert roofline.compute(dict(run, registry=no_held)) is None
    assert roofline.compute(dict(run, trace=None)) is None
    dispatch = _load("layer_metrics", "nemotron.moe_dispatch_ms.py")
    run["trace"]["ops"].append(_row(NEMO_MOE, 3_000_000, None, "", "combine"))
    # the kernel's 4 ms and the shared expert's 2 ms are not routing's
    assert dispatch.compute(run) == pytest.approx(1.5)
    assert dispatch.compute(dict(run, trace=None)) is None
    load = _load("layer_metrics", "nemotron.load_max_over_mean.py")
    # 98 304 pairs over 128 scored experts: mean 768, the busiest 92 160
    assert load.compute(run) == pytest.approx(92160 / 768)
    assert load.compute(dict(run, registry={})) is None
    with pytest.raises(ValueError, match="dropped or counted twice"):
        load.compute(dict(run, steps=3))
    with pytest.raises(KeyError):           # why it is no plain wrapper
        _load("layer_metrics", "moe.load_max_over_mean.py").compute(run)


NEMO_WRAPPERS = {"nemotron.head_device_ms": "head.device_ms",
                 "nemotron.feed_produce_ms_per_step":
                 "feed.produce_ms_per_step",
                 "nemotron.opt_device_ms": "opt.device_ms",
                 "nemotron.donated_gib": "step.donated_gib",
                 "nemotron.attn_device_ms": "attn.device_ms",
                 "nemotron.moe_device_ms": "moe.device_ms"}


@pytest.mark.parametrize("name", sorted(NEMO_WRAPPERS))
def test_a_nemotron_wrapper_returns_what_the_reader_it_wraps_returns(name):
    run = _nemo_run_record()
    run["program_ops"] += [
        {"type": "adam", "scope": "adam.w", "inputs": {"Param": ["w"]},
         "outputs": {"ParamOut": ["w"]}},
        {"type": "softmax_with_cross_entropy",
         "scope": "softmax_with_cross_entropy.s",
         "inputs": {"Logits": ["l"], "Label": ["y"]},
         "outputs": {"Softmax": ["s"], "Loss": ["c"]}}]
    run["trace"]["ops"].append(_row("adam.w", 2_000_000))
    run["timers_s"] = {"prefetch.read": 0.004, "prefetch.batch": 0.002}
    wrapper = _load("layer_metrics", name + ".py")
    wrapped = _load("layer_metrics", NEMO_WRAPPERS[name] + ".py")
    assert wrapper.WRAPS == NEMO_WRAPPERS[name]
    got, want = wrapper.compute(run), wrapped.compute(run)
    assert got is not None and got == want
    empty = dict(run, trace=None, registry={}, timers_s={})
    assert wrapper.compute(empty) is None and wrapped.compute(empty) is None


def test_the_manifest_lists_the_nemotron_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = NEMO + ".train-log10"
    entry = next(w for w in manifest["workloads"] if w["name"] == cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        NEMO, "train-log10", 1)
    config = next(c for c in manifest["configs"] if c["name"] == NEMO)
    assert config["reduced"] == _nemo_config()["reduced"]
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [cell]]
    assert mine == ["ssm.device_ms", "ssm.scan_ms", "kernel.ssm_scan_roofline",
                    "nemotron.flash_roofline", "nemotron.attn_device_ms",
                    "nemotron.moe_device_ms", "moe.held_pair_share",
                    "nemotron.head_device_ms", "nemotron.opt_device_ms",
                    "nemotron.donated_gib", "nemotron.moe_dispatch_ms",
                    "nemotron.gmm_roofline", "nemotron.load_max_over_mean",
                    "nemotron.feed_produce_ms_per_step",
                    "ssm.conv_ms"]                       # PR 50, the list's last
    assert manifest["per_layer"][97] == {      # the last until PR 57's eleven
        "name": "ssm.conv_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "State-space mixers",
        "moves": "items_s", "workloads": [cell]}
    for name in mine:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seqlen"], traffic["sync_every"],
            traffic["warmup_steps"], traffic["trace_seconds"]) == (
        1, 8192, 10, 20, 4)


def test_the_benchmarks_nemotron_reference_is_the_trees_bit_for_bit():
    """`chipbench/configs/nemotron-3-nano-30b-a3b/reference.py` is a copy of
    `tests/nemotron_h_reference.py`: the same cost, gradients and router
    logits to the bit on the CPU, so the two cannot drift apart unseen."""
    import nemotron_h_reference as tree

    copy = _load("configs", NEMO, "reference.py")
    cfg = dict(_nemo_config(), **_nemo_config()["rehearsal"])
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    conv = H * P + 2 * G * N
    E, held, f = cfg["router_experts"], 4, cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    kinds = {
        "M": [(d,), (d, 2 * H * P + 2 * G * N + H), (4, conv), (conv,), (H,),
              (H,), (H,), (H * P,), (H * P, d)],
        "*": [(d,), (d, 64), (d, 32), (d, 32), (64, d)],
        "E": [(d,), (d, E), (held, d, f), (held, f, d), (E,), (d, fs),
              (fs, d)]}
    r = np.random.RandomState(0)
    shapes = [(V, d)] + [s for kind in cfg["hybrid_override_pattern"]
                         for s in kinds[kind]] + [(d,), (d, V)]
    params = [(r.randn(*s) * 0.2 + (len(s) == 1)).astype(np.float32)
              for s in shapes]
    toks = r.randint(0, V, (2, 41))
    feed = {"toks": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:, None].astype(np.int32)}
    assert copy.prepare(feed) is feed
    (c1, g1), (c2, g2) = (m.loss_and_grads(cfg, params, feed)
                          for m in (tree, copy))
    assert float(c1) == float(c2) and np.isfinite(float(c1))
    assert len(g1) == len(g2) == len(params)
    for a, b in zip(g1, g2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(tree.router_logits(cfg, params, feed),
                    copy.router_logits(cfg, params, feed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    text = lambda m: open(m.__file__).read().split("import math\n", 1)[1]  # noqa: E731
    assert text(copy).startswith(text(tree))


def test_the_nemotron_cell_rehearses_on_the_cpu(tmp_path):
    """`run.py --rehearse-cpu` of the new cell: the harness finds the
    configuration's files by name, the first step agrees with the plain
    reference at the rehearsal's tolerances, both routed counters reach the
    run record, and every metric's name carries the rehearsal's prefix."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         NEMO + ".train-log10", "--rehearse-cpu", "--trace", "1",
         "--seed", "2147486099"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["rehearsal"] and not result["failed"]
    names = set(result["metrics"])
    assert all(n.startswith("REHEARSAL_ON_CPU.") for n in names)
    assert {"REHEARSAL_ON_CPU.moe.held_pair_share",
            "REHEARSAL_ON_CPU.nemotron.donated_gib",
            "REHEARSAL_ON_CPU.loop.dispatch_per_step"} <= names
    share = result["metrics"]["REHEARSAL_ON_CPU.moe.held_pair_share"]["value"]
    assert 0.1 < share < 0.45                # 4 of 16 experts held: 0.25


# ------------------------------------------------------ glm-4.7-flash (PR 37) ---
GLM = "glm-4.7-flash"
GLM_CELL = {"batch": 1, "seqlen": 8192}


def _glm_config():
    with open(os.path.join(BENCH, "configs", GLM, "config.json")) as f:
        return json.load(f)


def test_glm_flops_per_token():
    flops = _load("flops.py")
    cfg = _glm_config()
    config_dir = os.path.join(BENCH, "configs", GLM)
    got = flops.train_flops_per_item(cfg, GLM_CELL, config_dir)
    assert got == 3 * 956432384.0 == 2869297152.0       # ISSUE 37's arithmetic
    own = _load("configs", GLM, "flops.py")
    # latent attention: five projections and the causal kernels, a layer
    assert own.attention_flops_per_token(cfg, 8192) == 43515904 + 83886080
    # one more routed layer adds its attention, router, shared expert and
    # half a pair; one more held expert an eighth of a pair a routed layer
    more = own.forward_flops_per_token(dict(cfg, num_hidden_layers=6), 8192)
    assert more - 956432384.0 == 127401984 + 262144 + 18874368 + 9437184
    wider = own.forward_flops_per_token(dict(cfg, held_experts=[0, 9]), 8192)
    assert wider - 956432384.0 == pytest.approx(4 * 9437184.0 / 8)
    dense = own.forward_flops_per_token(dict(cfg, first_k_dense_replace=2), 8192)
    assert dense - 956432384.0 == 125829120 - (262144 + 18874368 + 9437184)


def test_glm_config_keeps_the_published_sizes():
    cfg = _glm_config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f)
                         if e["name"] == "GLM-4.7-Flash")
    assert cfg["source"] == published["source_url"]
    differs = [k for k, v in published["config"].items()
               if cfg.get(k, "absent") != v]
    assert sorted(differs) == sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size"])
    assert cfg["published"] == {k: published["config"][k] for k in differs} \
        == {"num_hidden_layers": 47, "n_routed_experts": 64,
            "vocab_size": 154880}
    # the cut: the dense layer and four routed ones, 8 of 64 experts behind a
    # router that stays 64 wide, an eighth of the vocabulary; no width cut
    assert cfg["num_hidden_layers"] == 5 and cfg["first_k_dense_replace"] == 1
    assert cfg["vocab_size"] * 8 == 154880
    assert cfg["router_experts"] == 64 and cfg["held_experts"] == [0, 8]
    assert (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"]) == (768, 512, 192, 64,
                                                            256)
    assert "8-chip" in cfg["deployment"] and "47" in cfg["distortion"]
    for key in ("assumed", "departures", "deployment", "distortion",
                "gradient_limits"):
        assert cfg[key], key
    assert "routed_parameters" not in cfg
    # the parameters this chip holds: ISSUE 37's count
    d, f, H = 2048, 1536, 20
    mla = d * 768 + 768 * H * 256 + d * 576 + 512 * H * 448 + H * 256 * d + 1280
    dense = mla + 3 * d * 10240 + 2 * d
    routed = mla + d * 64 + 9 * 3 * d * f + 2 * d
    assert dense + 4 * routed + 2 * 19360 * d + d == 591294720


def test_glm_kernels_count_on_hand_made_cells():
    flash = _load("kernels", "glm_flash_attention.py")
    plain = _load("kernels", "flash_attention.py")
    cfg = {"num_hidden_layers": 3, "num_attention_heads": 2,
           "qk_nope_head_dim": 6, "qk_rope_head_dim": 2, "v_head_dim": 8}
    flops, bytes_ = flash.flops_and_bytes(cfg, {"batch": 2, "seqlen": 5})
    assert flops == 3 * 2 * 2 * 6 * 2 * 15 * 8     # six matmuls over 15 pairs
    assert bytes_ == 3 * 12 * (2 * 5 * 2 * 8) * 2  # twelve tensors, 2 bytes
    # at one head size it is the plain count with that head size spelled out
    assert (flops, bytes_) == plain.flops_and_bytes(
        dict(cfg, head_dim=8), {"batch": 2, "seqlen": 5})
    got = flash.flops_and_bytes(_glm_config(), GLM_CELL)
    assert got[0] == 5 * 20 * 12 * (8192 * 8193 // 2) * 256   # 10.3 TFLOP
    assert got[1] == 5 * 12 * 8192 * 5120 * 2
    assert got[0] / 197e12 > got[1] / 819e9                   # compute-bound
    # what `kernels/flash_attention.py` would read: a head of 2048 / 20
    assert plain.flops_and_bytes(_glm_config(), GLM_CELL)[0] < 0.5 * got[0]
    gmm = _load("kernels", "glm_grouped_matmul.py")
    cfg = _glm_config()
    d, f = 2048, 1536
    flops, bytes_ = gmm.flops_and_bytes(cfg, GLM_CELL)
    rows = 4 * 8192 * 4 * 8 / 64            # even routing: 4 096 a layer
    assert gmm.routed_layers(cfg) == 4 and rows == 16384
    assert flops == 18 * rows * d * f
    assert bytes_ == 2 * (9 * 4 * 8 * d * f + rows * (5 * d + 7 * f))
    assert gmm.flops_and_bytes(cfg, GLM_CELL, rows=100.0) == (
        18 * 100.0 * d * f, 2 * (9 * 4 * 8 * d * f + 100.0 * (5 * d + 7 * f)))


GLM_MOE = "moe_ffn.glm_moe.h1.moe.tmp_40"
GLM_FLASH = "flash_attention.latent_attention_0.tmp_12"
GLM_PARTS = {"q_down": "mul.fc_0.tmp_1", "q_norm": "rms_norm.q.tmp_2",
             "q_up": "mul.fc_1.tmp_3", "rot_q": "rotary_embedding.r.tmp_4",
             "kv_down": "mul.fc_2.tmp_5", "split": "split.s.tmp_6",
             "kv_norm": "rms_norm.kv.tmp_8", "kv_up": "mul.fc_3.tmp_9",
             "rot_k": "rotary_embedding.r.tmp_10",
             "assemble": "latent_kv_expand.latent_attention_0.tmp_11",
             "out": "mul.fc_4.tmp_13"}


def _glm_run_record():
    P = GLM_PARTS
    ops = [
        _row(P["q_down"], 2_000_000), _row(P["q_up"], 4_000_000, None, "jvp("),
        _row(P["kv_down"], 2_000_000), _row(P["kv_up"], 6_000_000),
        _row(P["out"], 8_000_000, None, "transpose(jvp("),
        _row(P["q_norm"], 600_000), _row(P["kv_norm"], 400_000),
        _row(P["rot_q"], 1_000_000), _row(P["rot_k"], 200_000),
        _row(P["split"], 300_000), _row(P["assemble"], 1_500_000),
        _row(GLM_FLASH, 20_000_000, "tpu_custom_call", "jvp("),
        _row(GLM_FLASH, 40_000_000, "tpu_custom_call", "transpose(jvp("),
        _row(GLM_FLASH, 1_000_000, None, "transpose(jvp("),
        _row(GLM_FLASH, 90_000_000, None, "", container=True),    # a loop
        _row(GLM_MOE, 4_000_000, "tpu_custom_call", "", "experts"),
        _row(GLM_MOE, 2_000_000, None, "", "shared"),
        _row(GLM_MOE, 3_000_000, None, "", "combine"),
        _row("mul.fc_9.tmp_9", 5_000_000),        # the head: no part of MLA
        _row("rms_norm.ln_in.tmp_0", 700_000),    # the layer's own norm: none
    ]
    held = 'pt_moe_held_pairs_total{expert="%d",layer="glm_moe.h1.moe"}'
    every = 'pt_moe_expert_tokens_total{expert="%d",layer="glm_moe.h1.moe"}'
    registry = {held % 0: 4000.0, held % 1: 4192.0,
                every % 0: 4000.0, every % 1: 4192.0, every % 50: 57344.0,
                "pt_executor_donated_bytes": 7.0e9}

    def op(kind, scope, inputs, outputs):
        return {"type": kind, "scope": scope, "inputs": inputs,
                "outputs": outputs}

    program_ops = [
        op("rms_norm", "rms_norm.ln_in.tmp_0", {"X": ["x"]}, {"Y": ["h"]}),
        op("mul", P["q_down"], {"X": ["h"], "Y": ["wq_a"]}, {"Out": ["t1"]}),
        op("rms_norm", P["q_norm"], {"X": ["t1"]}, {"Y": ["t2"]}),
        op("mul", P["q_up"], {"X": ["t2"], "Y": ["wq_b"]}, {"Out": ["t3"]}),
        op("rotary_embedding", P["rot_q"], {"X": ["t3"]}, {"Out": ["t4"]}),
        op("mul", P["kv_down"], {"X": ["h"], "Y": ["wkv_a"]}, {"Out": ["t5"]}),
        op("split", P["split"], {"X": ["t5"]}, {"Out": ["t6", "t7"]}),
        op("rms_norm", P["kv_norm"], {"X": ["t6"]}, {"Y": ["t8"]}),
        op("mul", P["kv_up"], {"X": ["t8"], "Y": ["wkv_b"]}, {"Out": ["t9"]}),
        op("rotary_embedding", P["rot_k"], {"X": ["t7"]}, {"Out": ["t10"]}),
        op("latent_kv_expand", P["assemble"], {"KV": ["t9"], "KRope": ["t10"]},
           {"K": ["t11"], "V": ["t11v"]}),
        op("flash_attention", GLM_FLASH,
           {"Q": ["t4"], "K": ["t11"], "V": ["t11v"]}, {"Out": ["t12"]}),
        op("mul", P["out"], {"X": ["t12"], "Y": ["wo"]}, {"Out": ["t13"]}),
        op("moe_ffn", GLM_MOE, {"X": ["h2"]},
           {"Out": ["glm_moe.h1.moe.tmp_40"]}),
        op("mul", "mul.fc_9.tmp_9", {"X": ["hf"], "Y": ["w"]},
           {"Out": ["l"]})]
    return {"steps": 2, "trace": {"ops": ops}, "registry": registry,
            "device": {"kind": "TPU v5 lite"}, "config": _glm_config(),
            "cell": GLM_CELL, "program_ops": program_ops}


def test_mla_readers_find_the_layers_parts_from_the_programs_structure():
    run = _glm_run_record()
    reader = _load("layer_metrics", "mla.device_ms.py")
    parts = reader.parts(run["program_ops"])
    P = GLM_PARTS
    assert parts == {
        P["q_down"]: "q_down", P["q_norm"]: "q_norm", P["q_up"]: "q_up",
        P["rot_q"]: "rotary", P["rot_k"]: "rotary", P["kv_down"]: "kv_down",
        P["split"]: "split", P["kv_norm"]: "kv_norm", P["kv_up"]: "kv_up",
        P["assemble"]: "assemble", GLM_FLASH: "kernels", P["out"]: "out"}
    # every leaf row of the layer, forward and backward; not the loop, not
    # the head's GEMM, not the layer's own input norm, not the routed op
    assert reader.compute(run) == pytest.approx(87.0 / 2)
    info = reader.info(run)
    assert info["by_part_ms"] == pytest.approx(
        {"q_down": 1.0, "q_up": 2.0, "kv_down": 1.0, "kv_up": 3.0, "out": 4.0,
         "q_norm": 0.3, "kv_norm": 0.2, "rotary": 0.6, "split": 0.15,
         "assemble": 0.75, "kernels": 30.5})
    assert info["by_pass_ms"] == pytest.approx(
        {"plain": 7.0, "jvp": 12.0, "transpose": 24.5})
    assemble = _load("layer_metrics", "mla.assemble_ms.py")
    # neither a GEMM nor a kernel: norms, rotary, split, assemble, and the
    # 1 ms XLA runs under the kernels' scope
    assert assemble.compute(run) == pytest.approx(
        (0.6 + 0.4 + 1.0 + 0.2 + 0.3 + 1.5 + 1.0) / 2)
    assert assemble.info(run)["by_part_ms"]["kernels"] == pytest.approx(0.5)
    # a Program without the op (every other configuration, the parent of the
    # PR that added it), or no trace: nothing to read, and nothing raised
    for empty in (dict(run, trace=None), dict(run, program_ops=None),
                  dict(run, program_ops=_nemo_run_record()["program_ops"])):
        assert reader.compute(empty) is None
        assert assemble.compute(empty) is None


def test_glm_rooflines_on_a_hand_made_run_record():
    run = _glm_run_record()
    flash = _load("layer_metrics", "glm.flash_roofline.py")
    # 10.31 TFLOP at 197 TFLOP/s = 52.3 ms over the kernels' 30 ms a step:
    # over 100 and shown as it is (a hand-made time)
    need = 5 * 20 * 12 * (8192 * 8193 // 2) * 256 / 197e12
    assert flash.compute(run) == pytest.approx(100 * need / 30e-3, rel=1e-3)
    assert flash.info(run)["kernels_per_step"] == 2.0
    assert flash.info(run)["bound"] == "compute"
    assert flash.compute(dict(run, trace=None)) is None
    gmm = _load("layer_metrics", "glm.gmm_roofline.py")
    d, f = 2048, 1536                       # 2 steps, 8 192 held pairs
    want_bytes = 2 * (9 * 4 * 8 * d * f + 4096.0 * (5 * d + 7 * f))
    assert gmm.info(run)["flops_per_step"] == 18 * 4096.0 * d * f
    assert gmm.info(run)["bytes_per_step"] == want_bytes
    assert gmm.info(run)["held_pairs_per_step"] == 4096.0
    assert gmm.compute(run) == pytest.approx(
        100 * want_bytes / 819e9 / 2e-3, rel=1e-3)    # the kernels' 2 ms
    assert gmm.info(run)["bound"] == "memory"
    no_held = {k: v for k, v in run["registry"].items()
               if "held_pairs" not in k}
    assert gmm.compute(dict(run, registry=no_held)) is None
    assert gmm.compute(dict(run, trace=None)) is None


def test_glm_routed_readers_count_a_share_of_the_experts():
    run = _glm_run_record()                 # 2 steps x 8 192 tokens x 4 pairs
    share = _load("layer_metrics", "glm.held_pair_share.py")
    assert share.compute(run) == pytest.approx(8192 / 65536)    # 0.125
    assert share.info(run)["held_pairs_per_step"] == 4096.0
    with pytest.raises(ValueError, match="dropped or counted twice"):
        share.compute(dict(run, steps=3))
    moe = _load("layer_metrics", "glm.moe_device_ms.py")
    assert moe.compute(run) == pytest.approx(4.5)
    assert moe.info(run)["by_inner_scope_ms"] == pytest.approx(
        {"experts": 2.0, "shared": 1.0, "combine": 1.5})
    assert moe.info(run)["kernels_ms"] == pytest.approx(2.0)
    dispatch = _load("layer_metrics", "glm.moe_dispatch_ms.py")
    # the kernel's 4 ms and the shared expert's 2 ms are not routing's
    assert dispatch.compute(run) == pytest.approx(1.5)
    load = _load("layer_metrics", "glm.load_max_over_mean.py")
    # 65 536 pairs over 64 scored experts: mean 1 024, the busiest 57 344
    assert load.compute(run) == pytest.approx(57344 / 1024)
    for reader in (share, moe, dispatch, load):
        assert reader.compute(dict(run, trace=None, registry={})) is None


GLM_WRAPPERS = {"glm.head_device_ms": "head.device_ms",
                "glm.feed_produce_ms_per_step": "feed.produce_ms_per_step",
                "glm.opt_device_ms": "opt.device_ms",
                "glm.donated_gib": "step.donated_gib",
                "glm.attn_device_ms": "attn.device_ms",
                "glm.held_pair_share": "moe.held_pair_share",
                "glm.moe_device_ms": "nemotron.moe_device_ms",
                "glm.moe_dispatch_ms": "nemotron.moe_dispatch_ms",
                "glm.load_max_over_mean": "nemotron.load_max_over_mean"}


@pytest.mark.parametrize("name", sorted(GLM_WRAPPERS))
def test_a_glm_wrapper_returns_what_the_reader_it_wraps_returns(name):
    run = _glm_run_record()
    run["program_ops"] += [
        {"type": "adam", "scope": "adam.w", "inputs": {"Param": ["w"]},
         "outputs": {"ParamOut": ["w"]}},
        {"type": "softmax_with_cross_entropy",
         "scope": "softmax_with_cross_entropy.s",
         "inputs": {"Logits": ["l"], "Label": ["y"]},
         "outputs": {"Softmax": ["s"], "Loss": ["c"]}}]
    run["trace"]["ops"].append(_row("adam.w", 2_000_000))
    run["timers_s"] = {"prefetch.read": 0.004, "prefetch.batch": 0.002}
    wrapper = _load("layer_metrics", name + ".py")
    wrapped = _load("layer_metrics", GLM_WRAPPERS[name] + ".py")
    assert wrapper.WRAPS == GLM_WRAPPERS[name]
    got, want = wrapper.compute(run), wrapped.compute(run)
    assert got is not None and got == want
    if hasattr(wrapper, "info"):
        assert wrapper.info(run) == wrapped.info(run)
    empty = dict(run, trace=None, registry={}, timers_s={})
    assert wrapper.compute(empty) is None and wrapped.compute(empty) is None


def test_the_manifest_lists_the_glm_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = GLM + ".train-log10"
    assert manifest["workloads"][4]["name"] == cell      # appended in PR 37
    entry = manifest["workloads"][4]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        GLM, "train-log10", 1)
    config = manifest["configs"][3]
    assert config["name"] == GLM and config["source"] == _glm_config()["source"]
    assert config["reduced"] == _glm_config()["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [cell]]
    assert mine == ["mla.device_ms", "mla.assemble_ms", "glm.flash_roofline",
                    "glm.gmm_roofline", "glm.moe_device_ms",
                    "glm.moe_dispatch_ms", "glm.held_pair_share",
                    "glm.load_max_over_mean", "glm.attn_device_ms",
                    "glm.head_device_ms", "glm.opt_device_ms",
                    "glm.donated_gib", "glm.feed_produce_ms_per_step"]
    # appended in PR 37; PR 38 appended one metric of both shares' cells,
    # PR 40 three of every cell (no `workloads` list), PR 42 its own cell's
    names = [m["name"] for m in manifest["per_layer"]]
    every_cell = ["step.xla_inserted_ms", "step.unnamed_ms",
                  "opt.carried_device_ms"]
    end = names.index("opt.carried_device_ms") + 1
    assert names[end - len(mine) - 4:end] == mine \
        + ["moe.bounded_step_share"] + every_cell
    assert manifest["per_layer"][end - 4]["workloads"] == [
        NEMO + ".train-log10", cell]
    assert not any("workloads" in m
                   for m in manifest["per_layer"][end - 3:end])
    layers = {m["name"]: m["layer"] for m in manifest["per_layer"]}
    assert layers["mla.device_ms"] == layers["mla.assemble_ms"] \
        == "Latent attention"
    for name in mine:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
    # no reader that was there lists the new cell: their entries are untouched
    assert not [m["name"] for m in manifest["per_layer"][:end - 4]
                if cell in m.get("workloads", ()) and m["name"] not in mine]
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seqlen"], traffic["sync_every"],
            traffic["warmup_steps"], traffic["trace_seconds"]) == (
        1, 8192, 10, 20, 4)


def test_the_benchmarks_glm_reference_is_the_trees_bit_for_bit():
    """`chipbench/configs/glm-4.7-flash/reference.py` is a copy of
    `tests/glm_moe_reference.py`, text for text, and gives the same cost,
    gradients and routers to the bit on the CPU, its own choice or a handed
    one: the two cannot drift apart unseen."""
    import glm_moe_reference as tree

    copy = _load("configs", GLM, "reference.py")
    assert open(copy.__file__).read() == open(tree.__file__).read()
    cfg = dict(_glm_config(), **_glm_config()["rehearsal"])
    d, V, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n, R, Dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"])
    E, held, f = cfg["router_experts"], 4, cfg["moe_intermediate_size"]
    attn = [(d,), (d, rq), (rq,), (rq, H * (n + R)), (d, r + R), (r,),
            (r, H * (n + Dv)), (H * Dv, d), (d,)]
    kinds = {"dense": [(d, cfg["intermediate_size"])] * 2
             + [(cfg["intermediate_size"], d)],
             "routed": [(d, E), (held, d, f), (held, d, f), (held, f, d),
                        (E,), (d, f), (d, f), (f, d)]}
    rng = np.random.RandomState(0)
    shapes = [(V, d)] + [s for kind in tree._kinds(cfg)
                         for s in attn + kinds[kind]] + [(d,), (d, V)]
    params = [(rng.randn(*s) * 0.2 + (len(s) == 1)).astype(np.float32)
              for s in shapes]
    toks = rng.randint(0, V, (2, 41))
    feed = {"toks": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:, None].astype(np.int32)}
    assert copy.prepare(feed) is feed
    own = [m.loss_grads_and_routers(cfg, params, feed) for m in (tree, copy)]
    choice = copy.chosen(cfg, params, [z for _, _, z in own[1][2]])
    handed = [m.loss_grads_and_routers(cfg, params, feed, choice)
              for m in (tree, copy)]
    for (c1, g1, r1), (c2, g2, r2) in (own, handed):
        assert float(c1) == float(c2) and np.isfinite(float(c1))
        assert len(g1) == len(g2) == len(params) and len(r1) == len(r2) == 2
        for a, b in zip(g1, g2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(r1, r2):
            np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b[2]))
    # the reference's own choice, handed back to it, is its own result
    assert float(own[0][0]) == float(handed[0][0])
    assert [int(m.sum()) for m in choice] == [2 * 40 * 3] * 2


def test_the_glm_cell_rehearses_on_the_cpu(tmp_path):
    """`run.py --rehearse-cpu` of the new cell: the harness finds the
    configuration's files by name, the first step agrees with the plain
    reference (handed the program's choice) at the rehearsal's tolerances,
    both routed counters reach the run record, and every metric's name
    carries the rehearsal's prefix."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         GLM + ".train-log10", "--rehearse-cpu", "--trace", "1",
         "--seed", "2147486099"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["rehearsal"] and not result["failed"]
    names = set(result["metrics"])
    assert all(n.startswith("REHEARSAL_ON_CPU.") for n in names)
    assert {"REHEARSAL_ON_CPU.glm.held_pair_share",
            "REHEARSAL_ON_CPU.glm.load_max_over_mean",
            "REHEARSAL_ON_CPU.glm.donated_gib",
            "REHEARSAL_ON_CPU.loop.dispatch_per_step"} <= names
    share = result["metrics"]["REHEARSAL_ON_CPU.glm.held_pair_share"]["value"]
    assert 0.1 < share < 0.45                # 4 of 16 experts held: 0.25
    assert "choice_counts_off_program" in result["compared"]


# ---------------------------------------------------------------------------
# PR 42: trinity-mini (window and global attention layers in one model)
TRI = "trinity-mini"
TRI_CELL = {"batch": 1, "seqlen": 8192}
W_, G_ = "sliding_attention", "full_attention"


def _tri_config():
    with open(os.path.join(BENCH, "configs", TRI, "config.json")) as f:
        return json.load(f)


def test_trinity_flops_per_token():
    flops = _load("flops.py")
    cfg = _tri_config()
    config_dir = os.path.join(BENCH, "configs", TRI)
    got = flops.train_flops_per_item(cfg, TRI_CELL, config_dir)
    # ISSUE 42's arithmetic with the global layer's keys counted as the
    # window layers' are ((T + 1) / 2, where the issue wrote T / 2: 8 192 more)
    assert got == 3 * 737951744.0 == 2213855232.0
    assert 737951744 - 737943552 == 4 * 32 * 128 // 2
    own = _load("configs", TRI, "flops.py")
    assert own.keys_seen(8192) == 4096.5
    assert own.keys_seen(8192, 2048) == 1792.125
    assert own.keys_seen(1024, 2048) == own.keys_seen(1024) == 512.5
    # five projections and the kernels over the keys a query sees, by kind
    assert own.attention_flops_per_token(cfg, 8192, G_) == 54525952 + 67117056
    assert own.attention_flops_per_token(cfg, 8192, W_) == 54525952 + 29362176
    # one more routed window layer adds its attention, router, shared expert
    # and a pair; one more held expert a sixteenth of a pair a routed layer
    more = own.forward_flops_per_token(
        dict(cfg, layer_types=cfg["layer_types"] + [W_]), 8192)
    assert more - 737951744.0 == 83888128 + 524288 + 12582912 + 12582912
    wider = own.forward_flops_per_token(dict(cfg, held_experts=[0, 17]), 8192)
    assert wider - 737951744.0 == pytest.approx(4 * 12582912.0 / 16)
    dense = own.forward_flops_per_token(dict(cfg, num_dense_layers=2), 8192)
    assert dense - 737951744.0 == 75497472 - (524288 + 2 * 12582912)
    # every layer global (no window in the model): 4 x 37.75 M more a token
    flat = own.forward_flops_per_token(dict(cfg, layer_types=[G_] * 5), 8192)
    assert flat - 737951744.0 == 4 * (67117056 - 29362176)


def test_trinity_config_keeps_the_published_sizes():
    cfg = _tri_config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f)
                         if e["name"] == "Trinity-Mini")
    assert cfg["source"] == published["source_url"]
    differs = [k for k, v in published["config"].items()
               if cfg.get(k, "absent") != v]
    assert sorted(differs) == sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "layer_types", "num_dense_layers",
         "num_experts", "vocab_size"])
    assert cfg["published"] == {k: published["config"][k] for k in differs}
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["num_experts"],
            cfg["published"]["vocab_size"],
            cfg["published"]["num_dense_layers"]) == (32, 128, 200192, 2)
    # the cut: published layers 1-5 (one dense layer, four routed ones, one
    # whole period 3 window : 1 global among the routed), 16 of 128 experts
    # behind a router that stays 128 wide, an eighth of the vocabulary
    assert cfg["layer_types"] == published["config"]["layer_types"][1:6] \
        == [W_, W_, G_, W_, W_]
    assert cfg["num_hidden_layers"] == 5 and cfg["num_dense_layers"] == 1
    assert cfg["layer_types"][1:].count(W_) == 3 * cfg["layer_types"][1:].count(G_)
    assert cfg["vocab_size"] * 8 == 200192
    assert cfg["router_experts"] == 128 and cfg["held_experts"] == [0, 16]
    # no width is cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
            cfg["num_key_value_heads"], cfg["sliding_window"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["route_scale"]) == (
        2048, 32, 128, 4, 2048, 6144, 1024, 8, 2.826)
    assert "8-chip" in cfg["deployment"] and "32" in cfg["distortion"]
    for key in ("assumed", "departures", "deployment", "distortion",
                "gradient_limits"):
        assert cfg[key], key
    for key in ("qk_norm", "output_gate", "positions", "window_edge", "norms",
                "mup", "router", "aux_cost", "optimizer", "compute_dtype"):
        assert cfg["assumed"][key], key
    # the parameters this chip holds: ISSUE 42's count
    d, f = 2048, 1024
    attn = 2 * d * 4096 + 2 * d * 512 + 4096 * d + 256
    dense = attn + 3 * d * 6144 + 4 * d
    routed = attn + d * 128 + 17 * 3 * d * f + 4 * d
    assert (attn, dense, routed) == (27263232, 65020160, 134488320)
    assert dense + 4 * routed + 2 * 25024 * d + d == 705473792


def test_trinity_kernels_count_on_hand_made_cells():
    flash = _load("kernels", "trinity_flash_attention.py")
    plain = _load("kernels", "flash_attention.py")
    cfg = {"layer_types": [W_, G_, W_], "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 8, "sliding_window": 3,
           "num_hidden_layers": 3}
    cell = {"batch": 2, "seqlen": 5}
    # a window of 3 over 5 positions: 1 + 2 + 3 + 3 + 3 = 12 pairs of the
    # causal 15
    assert flash.pairs(5, 3) == 12 and flash.pairs(5) == 15
    assert flash.pairs(5, 5) == flash.pairs(5, 9) == 15
    flops, bytes_ = flash.flops_and_bytes(cfg, cell)
    assert flops == 2 * 4 * 6 * 2 * 8 * (12 + 15 + 12)
    # Q, O, dO, dQ at 4 heads, K, V, dK, dV at 2: six of each, three layers
    assert bytes_ == 3 * 6 * (2 * 5 * 4 * 8 + 2 * 5 * 2 * 8) * 2
    # without a window in the model it is the plain causal count, K/V heads
    # and all
    wide = dict(cfg, layer_types=[G_] * 3)
    assert flash.flops_and_bytes(wide, cell) == plain.flops_and_bytes(
        wide, cell)
    assert flash.flops_and_bytes(dict(cfg, sliding_window=5), cell) \
        == plain.flops_and_bytes(cfg, cell)
    one = flash.flops_and_bytes(cfg, cell, [W_])
    assert one[0] == 2 * 4 * 12 * 8 * 12 and one[1] == bytes_ / 3
    got = flash.flops_and_bytes(_tri_config(), TRI_CELL)
    window, whole = 14681088, 33558528           # ISSUE 42's pairs: 43.75 %
    assert flash.pairs(8192, 2048) == window and flash.pairs(8192) == whole
    assert got[0] == 32 * 12 * 128 * (4 * window + whole)      # 4.54 TFLOP
    assert got[1] == 5 * 6 * 8192 * (4096 + 512) * 2
    assert got[0] / 197e12 > got[1] / 819e9                    # compute-bound
    # every layer counted causal: 1.8 times the work the step needs
    assert plain.flops_and_bytes(_tri_config(), TRI_CELL)[0] \
        == 32 * 12 * 128 * 5 * whole
    gmm = _load("kernels", "trinity_grouped_matmul.py")
    cfg = _tri_config()
    d, f = 2048, 1024
    flops, bytes_ = gmm.flops_and_bytes(cfg, TRI_CELL)
    rows = 4 * 8192 * 8 * 16 / 128          # even routing: 8 192 a layer
    assert gmm.routed_layers(cfg) == 4 and rows == 32768
    assert flops == 18 * rows * d * f
    assert bytes_ == 2 * (9 * 4 * 16 * d * f + rows * (5 * d + 7 * f))
    assert gmm.flops_and_bytes(cfg, TRI_CELL, rows=100.0) == (
        18 * 100.0 * d * f, 2 * (9 * 4 * 16 * d * f + 100.0 * (5 * d + 7 * f)))


TRI_MOE = "moe_ffn.afmoe.h1.moe.tmp_66"


def _tri_layer(i, window):
    """(program ops, {part: scope}) of one gated attention layer, as
    `models.afmoe_lm` appends them."""
    def op(kind, name, inputs, outputs):
        return {"type": kind, "scope": f"{kind}.{name}", "inputs": inputs,
                "outputs": outputs}

    n = f"l{i}"
    ops = [op("rms_norm", n + "n1", {"X": [n + "x"]}, {"Y": [n + "h"]}),
           op("mul", n + "q", {"X": [n + "h"], "Y": ["wq"]}, {"Out": [n + "q"]}),
           op("mul", n + "k", {"X": [n + "h"], "Y": ["wk"]}, {"Out": [n + "k"]}),
           op("mul", n + "v", {"X": [n + "h"], "Y": ["wv"]}, {"Out": [n + "v"]}),
           op("rms_norm", n + "qn", {"X": [n + "q"]}, {"Y": [n + "qn"]}),
           op("rms_norm", n + "kn", {"X": [n + "k"]}, {"Y": [n + "kn"]})]
    parts = {"q_proj": ops[1], "k_proj": ops[2], "v_proj": ops[3],
             "q_norm": ops[4], "k_norm": ops[5]}
    q, k = n + "qn", n + "kn"
    if window:
        ops += [op("rotary_embedding", n + "qr", {"X": [q]}, {"Out": [n + "qr"]}),
                op("rotary_embedding", n + "kr", {"X": [k]}, {"Out": [n + "kr"]})]
        parts.update(q_rotary=ops[-2], k_rotary=ops[-1])
        q, k = n + "qr", n + "kr"
    ops += [op("flash_attention", n + "o", {"Q": [q], "K": [k], "V": [n + "v"]},
               {"Out": [n + "o"]}),
            op("mul", n + "g", {"X": [n + "h"], "Y": ["wg"]}, {"Out": [n + "g"]}),
            op("sigmoid", n + "s", {"X": [n + "g"]}, {"Out": [n + "s"]}),
            op("elementwise_mul", n + "m", {"X": [n + "o"], "Y": [n + "s"]},
               {"Out": [n + "m"]}),
            op("mul", n + "out", {"X": [n + "m"], "Y": ["wo"]},
               {"Out": [n + "out"]})]
    parts.update(kernels=ops[-5], gate_proj=ops[-4], gate_sigmoid=ops[-3],
                 gate_mul=ops[-2], out_proj=ops[-1])
    return ops, {part: o["scope"] for part, o in parts.items()}


def _tri_run_record():
    """Two steps of a window layer, a global layer and one routed layer."""
    win_ops, win = _tri_layer(0, True)
    glo_ops, glo = _tri_layer(1, False)
    ops = [
        _row(win["kernels"], 6_000_000, "tpu_custom_call", "jvp("),
        _row(win["kernels"], 12_000_000, "tpu_custom_call", "transpose(jvp("),
        _row(win["kernels"], 1_000_000, None, "transpose(jvp("),  # dK, dV sums
        _row(win["kernels"], 50_000_000, None, "", container=True),   # a loop
        _row(glo["kernels"], 9_000_000, "tpu_custom_call", "jvp("),
        _row(glo["kernels"], 18_000_000, "tpu_custom_call", "transpose(jvp("),
        _row(win["q_proj"], 4_000_000), _row(win["gate_proj"], 4_000_000),
        _row(win["out_proj"], 4_000_000, None, "transpose(jvp("),
        _row(glo["k_proj"], 1_000_000), _row(glo["v_proj"], 1_000_000),
        _row(win["q_norm"], 600_000), _row(win["k_norm"], 200_000),
        _row(glo["q_norm"], 600_000), _row(win["q_rotary"], 800_000),
        _row(win["k_rotary"], 200_000), _row(win["gate_sigmoid"], 400_000),
        _row(glo["gate_mul"], 1_200_000),
        _row(TRI_MOE, 4_000_000, "tpu_custom_call", "", "experts"),
        _row(TRI_MOE, 2_000_000, None, "", "shared"),
        _row(TRI_MOE, 3_000_000, None, "", "combine"),
        _row("mul.fc_9.tmp_9", 5_000_000),        # the head: no part of it
        _row("rms_norm.l0n1", 700_000),           # the layer's own n1: none
    ]
    held = 'pt_moe_held_pairs_total{expert="%d",layer="afmoe.h1.moe"}'
    every = 'pt_moe_expert_tokens_total{expert="%d",layer="afmoe.h1.moe"}'
    path = 'pt_moe_row_path_total{layer="afmoe.h1.moe",path="%d"}'
    registry = {held % 0: 8000.0, held % 1: 8384.0,
                every % 0: 8000.0, every % 1: 8384.0, every % 100: 114688.0,
                path % 0: 2.0, "pt_executor_donated_bytes": 8.4e9}
    program_ops = win_ops + glo_ops + [
        {"type": "moe_ffn", "scope": TRI_MOE, "inputs": {"X": ["h2"]},
         "outputs": {"Out": ["afmoe.h1.moe.tmp_66"]}},
        {"type": "mul", "scope": "mul.fc_9.tmp_9",
         "inputs": {"X": ["hf"], "Y": ["w"]}, "outputs": {"Out": ["l"]}}]
    return {"steps": 2, "trace": {"ops": ops}, "registry": registry,
            "device": {"kind": "TPU v5 lite"}, "config": _tri_config(),
            "cell": TRI_CELL, "program_ops": program_ops}, win, glo


def test_window_readers_tell_the_layers_apart_by_structure():
    run, win, glo = _tri_run_record()
    reader = _load("layer_metrics", "attn.window_ms.py")
    layers = reader.layers(run["program_ops"])
    assert [layer["kind"] for layer in layers] == ["window", "global"]
    assert layers[0]["parts"] == {scope: part for part, scope in win.items()}
    assert layers[1]["parts"] == {scope: part for part, scope in glo.items()}
    # the window layer's kernels, forward and backward; not the rows XLA
    # runs around them, not the loop, not the global layer's
    assert reader.compute(run) == pytest.approx(18.0 / 2)
    info = reader.info(run)
    assert (info["window_layers"], info["global_layers"]) == (1, 1)
    assert info["global_kernels_ms"] == pytest.approx(27.0 / 2)
    assert info["one_window_layer_over_one_global"] == pytest.approx(18 / 27)
    assert info["by_pass_ms"] == pytest.approx(
        {"window_forward_ms": 3.0, "window_backward_ms": 6.0,
         "global_forward_ms": 4.5, "global_backward_ms": 9.0})
    assemble = _load("layer_metrics", "attn.assemble_ms.py")
    # neither a GEMM nor a kernel, both layers: the norms, the rotary passes,
    # the gate's two passes and the 1 ms under the kernels' scope
    assert assemble.compute(run) == pytest.approx(
        (0.6 + 0.2 + 0.6 + 0.8 + 0.2 + 0.4 + 1.2 + 1.0) / 2)
    assert assemble.info(run)["by_part_ms"] == pytest.approx(
        {"q_norm": 0.6, "k_norm": 0.1, "q_rotary": 0.4, "k_rotary": 0.1,
         "gate_sigmoid": 0.2, "gate_mul": 0.6, "around_kernels": 0.5})
    # a Program without a gated attention layer (every other configuration,
    # the parent of the PR that added it), or no trace: nothing, not raised
    for empty in (dict(run, trace=None), dict(run, program_ops=None),
                  dict(run, program_ops=_glm_run_record()["program_ops"]),
                  dict(run, program_ops=_nemo_run_record()["program_ops"])):
        assert reader.compute(empty) is None
        assert assemble.compute(empty) is None


def test_trinity_rooflines_on_a_hand_made_run_record():
    run, _, _ = _tri_run_record()
    flash = _load("layer_metrics", "trinity.flash_roofline.py")
    # the five layers' 4.54 TFLOP at 197 TFLOP/s over the kernels' 22.5 ms a
    # step (hand-made times): shown as it is
    window, whole = 14681088, 33558528
    need = 32 * 12 * 128 * (4 * window + whole) / 197e12
    assert flash.compute(run) == pytest.approx(100 * need / 22.5e-3, rel=1e-3)
    info = flash.info(run)
    assert info["kernels_per_step"] == 4.0 and info["bound"] == "compute"
    # each kind on its own kernels: four window layers' need over 9 ms, the
    # global layer's over 13.5 ms
    assert info["window_layers_pct"] == pytest.approx(
        100 * 32 * 12 * 128 * 4 * window / 197e12 / 9e-3, rel=1e-3)
    assert info["global_layers_pct"] == pytest.approx(
        100 * 32 * 12 * 128 * whole / 197e12 / 13.5e-3, rel=1e-3)
    assert flash.compute(dict(run, trace=None)) is None
    gmm = _load("layer_metrics", "trinity.gmm_roofline.py")
    d, f = 2048, 1024                       # 2 steps, 16 384 held pairs
    want_bytes = 2 * (9 * 4 * 16 * d * f + 8192.0 * (5 * d + 7 * f))
    assert gmm.info(run)["flops_per_step"] == 18 * 8192.0 * d * f
    assert gmm.info(run)["bytes_per_step"] == want_bytes
    assert gmm.info(run)["held_pairs_per_step"] == 8192.0
    assert gmm.compute(run) == pytest.approx(
        100 * want_bytes / 819e9 / 2e-3, rel=1e-3)    # the kernels' 2 ms
    assert gmm.info(run)["bound"] == "memory"
    no_held = {k: v for k, v in run["registry"].items()
               if "held_pairs" not in k}
    assert gmm.compute(dict(run, registry=no_held)) is None
    assert gmm.compute(dict(run, trace=None)) is None


TRI_WRAPPERS = {"trinity.head_device_ms": "head.device_ms",
                "trinity.feed_produce_ms_per_step": "feed.produce_ms_per_step",
                "trinity.opt_device_ms": "opt.device_ms",
                "trinity.donated_gib": "step.donated_gib",
                "trinity.attn_device_ms": "attn.device_ms",
                "trinity.held_pair_share": "moe.held_pair_share",
                "trinity.bounded_step_share": "moe.bounded_step_share",
                "trinity.moe_device_ms": "nemotron.moe_device_ms",
                "trinity.moe_dispatch_ms": "nemotron.moe_dispatch_ms",
                "trinity.load_max_over_mean": "nemotron.load_max_over_mean"}


@pytest.mark.parametrize("name", sorted(TRI_WRAPPERS))
def test_a_trinity_wrapper_returns_what_the_reader_it_wraps_returns(name):
    run, _, _ = _tri_run_record()           # 2 steps x 8 192 tokens x 8 pairs
    run["program_ops"] += [
        {"type": "adam", "scope": "adam.w", "inputs": {"Param": ["w"]},
         "outputs": {"ParamOut": ["w"]}},
        {"type": "softmax_with_cross_entropy",
         "scope": "softmax_with_cross_entropy.s",
         "inputs": {"Logits": ["l"], "Label": ["y"]},
         "outputs": {"Softmax": ["s"], "Loss": ["c"]}}]
    run["trace"]["ops"].append(_row("adam.w", 2_000_000))
    run["timers_s"] = {"prefetch.read": 0.004, "prefetch.batch": 0.002}
    wrapper = _load("layer_metrics", name + ".py")
    wrapped = _load("layer_metrics", TRI_WRAPPERS[name] + ".py")
    assert wrapper.WRAPS == TRI_WRAPPERS[name]
    got, want = wrapper.compute(run), wrapped.compute(run)
    assert got is not None and got == want
    if hasattr(wrapper, "info"):
        assert wrapper.info(run) == wrapped.info(run)
    empty = dict(run, trace=None, registry={}, timers_s={})
    assert wrapper.compute(empty) is None and wrapped.compute(empty) is None
    expected = {"trinity.held_pair_share": 16384 / 131072,       # 0.125
                "trinity.bounded_step_share": 1.0,
                "trinity.load_max_over_mean": 114688 / 1024,
                "trinity.moe_device_ms": 4.5,
                "trinity.moe_dispatch_ms": 1.5}
    if name in expected:
        assert got == pytest.approx(expected[name])


def test_the_manifest_lists_the_trinity_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = TRI + ".train-log10"
    assert manifest["workloads"][5]["name"] == cell      # appended in PR 42
    entry = manifest["workloads"][5]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        TRI, "train-log10", 1)
    assert sum(w["chips"] for w in manifest["workloads"][:6]) == 6   # no 4-chip
    config = manifest["configs"][4]
    assert config["name"] == TRI and config["source"] == _tri_config()["source"]
    assert config["reduced"] == _tri_config()["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers", "num_experts",
        "vocab_size"]
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [cell]]
    assert mine == ["attn.window_ms", "attn.assemble_ms",
                    "trinity.flash_roofline", "trinity.gmm_roofline",
                    "trinity.moe_device_ms", "trinity.moe_dispatch_ms",
                    "trinity.held_pair_share", "trinity.load_max_over_mean",
                    "trinity.bounded_step_share", "trinity.attn_device_ms",
                    "trinity.head_device_ms", "trinity.opt_device_ms",
                    "trinity.donated_gib", "trinity.feed_produce_ms_per_step"]
    # appended in PR 42; PR 44 appended its own cell's behind them
    names = [m["name"] for m in manifest["per_layer"]]
    end = names.index(mine[-1]) + 1
    assert names[end - len(mine):end] == mine
    layers = {m["name"]: m["layer"] for m in manifest["per_layer"]}
    assert layers["attn.window_ms"] == layers["attn.assemble_ms"] \
        == "Window attention"
    for name in mine:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
    # no reader that was there lists the new cell: their entries are untouched
    assert not [m["name"] for m in manifest["per_layer"][:end - len(mine)]
                if cell in m.get("workloads", ())]
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seqlen"], traffic["sync_every"],
            traffic["warmup_steps"], traffic["trace_seconds"]) == (
        1, 8192, 10, 20, 4)


def test_the_benchmarks_trinity_reference_is_the_trees_bit_for_bit():
    """`chipbench/configs/trinity-mini/reference.py` is a copy of
    `tests/afmoe_reference.py`, text for text, and gives the same cost,
    gradients and routers to the bit on the CPU, its own choice or a handed
    one: the two cannot drift apart unseen."""
    import afmoe_reference as tree

    copy = _load("configs", TRI, "reference.py")
    assert open(copy.__file__).read() == open(tree.__file__).read()
    cfg = dict(_tri_config(), **_tri_config()["rehearsal"])
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    E, held, f = cfg["router_experts"], 4, cfg["moe_intermediate_size"]
    attn = [(d,), (d, H * D), (d, KV * D), (d, KV * D), (D,), (D,),
            (d, H * D), (H * D, d), (d,), (d,)]
    kinds = {"dense": [(d, cfg["intermediate_size"])] * 2
             + [(cfg["intermediate_size"], d)],
             "routed": [(d, E), (held, d, f), (held, d, f), (held, f, d),
                        (E,), (d, f), (d, f), (f, d)]}
    rng = np.random.RandomState(0)
    shapes = [(V, d)] + [s for kind in tree._kinds(cfg)
                         for s in attn + kinds[kind] + [(d,)]] + [(d,), (d, V)]
    params = [(rng.randn(*s) * 0.2 + (len(s) == 1)).astype(np.float32)
              for s in shapes]
    toks = rng.randint(0, V, (2, 41))
    feed = {"toks": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:, None].astype(np.int32)}
    assert copy.prepare(feed) is feed
    own = [m.loss_grads_and_routers(cfg, params, feed) for m in (tree, copy)]
    choice = copy.chosen(cfg, params, [z for _, _, z in own[1][2]])
    handed = [m.loss_grads_and_routers(cfg, params, feed, choice)
              for m in (tree, copy)]
    for (c1, g1, r1), (c2, g2, r2) in (own, handed):
        assert float(c1) == float(c2) and np.isfinite(float(c1))
        assert len(g1) == len(g2) == len(params) and len(r1) == len(r2) == 2
        for a, b in zip(g1, g2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(r1, r2):
            np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b[2]))
    # the reference's own choice, handed back to it, is its own result
    assert float(own[0][0]) == float(handed[0][0])
    assert [int(m.sum()) for m in choice] == [2 * 40 * 3] * 2


def test_the_trinity_cell_rehearses_on_the_cpu(tmp_path):
    """`run.py --rehearse-cpu` of the new cell: the harness finds the
    configuration's files by name, the first step agrees with the plain
    reference (handed the program's choice) at the rehearsal's tolerances,
    the routed counters reach the run record, and every metric's name
    carries the rehearsal's prefix."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         TRI + ".train-log10", "--rehearse-cpu", "--trace", "1",
         "--seed", "2147486142"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["rehearsal"] and not result["failed"]
    names = set(result["metrics"])
    assert all(n.startswith("REHEARSAL_ON_CPU.") for n in names)
    assert {"REHEARSAL_ON_CPU.trinity.held_pair_share",
            "REHEARSAL_ON_CPU.trinity.load_max_over_mean",
            "REHEARSAL_ON_CPU.trinity.bounded_step_share",
            "REHEARSAL_ON_CPU.trinity.donated_gib",
            "REHEARSAL_ON_CPU.loop.dispatch_per_step"} <= names
    share = result["metrics"][
        "REHEARSAL_ON_CPU.trinity.held_pair_share"]["value"]
    assert 0.1 < share < 0.45                # 4 of 16 experts held: 0.25
    assert "choice_counts_off_program" in result["compared"]


# PR 44: ouro-2.6b (one stack of layers run four times through `layers.Repeat`)
OURO = "ouro-2.6b"
OURO_CELL = {"batch": 1, "seqlen": 4096}
LOOP_SCOPE = "repeat.looped.turns.out_256"
_FWD = f"jit(raw)/jvp({LOOP_SCOPE})/while/body/closed_call/repeat.turn/"
_BWD = f"jit(raw)/transpose(jvp({LOOP_SCOPE}))/while/body/closed_call/checkpoint/"


def _ouro_config():
    with open(os.path.join(BENCH, "configs", OURO, "config.json")) as f:
        return json.load(f)


def test_ouro_flops_per_token():
    flops = _load("flops.py")
    cfg = _ouro_config()
    got = flops.train_flops_per_item(cfg, OURO_CELL,
                                     os.path.join(BENCH, "configs", OURO))
    # ISSUE 44's arithmetic: 3 x [K x (L x (2 x 51 380 224 + 2 T d) + 2 d V
    # + 2 d)], a causal query's keys counted as the other families count them
    # ((T + 1) / 2: 2 d more a layer application than the issue's 2 T d)
    assert got == 3 * 4630659072.0 == 13891977216.0
    assert round(got * 4096 / 1e12, 1) == 56.9
    own = _load("configs", OURO, "flops.py")
    layer = 2 * 51380224 + 2 * 2048 * 4097
    assert own.forward_flops_per_token(cfg, 4096) == 4 * (
        8 * layer + 2 * 2048 * 49152 + 2 * 2048)
    # a turn more adds a whole stack, a head and a gate; a layer more adds K
    # applications; recomputed work counts nothing (no term for it)
    assert own.forward_flops_per_token(dict(cfg, total_ut_steps=5), 4096) \
        == 5 * (8 * layer + 2 * 2048 * 49152 + 2 * 2048)
    assert own.forward_flops_per_token(dict(cfg, num_hidden_layers=9), 4096) \
        - own.forward_flops_per_token(cfg, 4096) == 4 * layer
    # the shares ISSUE 44 and the cell's `why` state
    turn = 8 * layer + 2 * 2048 * 49152 + 2 * 2048
    assert round(100 * 2 * 2048 * 49152 / turn, 1) == 17.4
    assert round(100 * 8 * layer / turn, 1) == 82.6


def test_ouro_config_keeps_the_published_sizes():
    cfg = _ouro_config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f)
                         if e["name"] == "Ouro-2.6B")
    assert cfg["source"] == published["source_url"]
    differs = [k for k, v in published["config"].items()
               if cfg.get(k, "absent") != v]
    assert sorted(differs) == sorted(cfg["reduced"]) == [
        "layer_types", "num_hidden_layers"]
    assert cfg["published"] == {k: published["config"][k] for k in differs}
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["layer_types"] == published["config"]["layer_types"][:8]
    # no width, no head, no row of the vocabulary and no turn is cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["vocab_size"], cfg["total_ut_steps"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["early_exit_threshold"]) == (
        2048, 16, 128, 16, 5632, 49152, 4, 1000000, 1e-6, 1)
    assert cfg["exit_beta"] == 0.05 and "exit_beta" in cfg["assumed"]
    for key in ("assumed", "departures", "deployment", "distortion"):
        assert cfg[key], key
    for key in ("basis", "layer", "attention", "ffn", "turns", "embedding",
                "exits", "cost", "early_exit_threshold", "optimizer",
                "compute_dtype", "initialisers", "remat_policy"):
        assert cfg["assumed"][key], key
    assert "six pipeline stages" in cfg["deployment"] \
        and "17.4 %" in cfg["distortion"]
    # the parameters this chip holds: ISSUE 44's count
    d, f, V = 2048, 5632, 49152
    layer = 4 * d * d + 3 * d * f + 4 * d
    assert layer == 51388416
    assert 8 * layer + 2 * V * d + d + d + 1 == 612438017
    assert "612 438 017" in cfg["deployment"]


def test_ouro_flash_kernel_counts_k_times_l_layer_applications():
    ours = _load("kernels", "ouro_flash_attention.py")
    plain = _load("kernels", "flash_attention.py")
    cfg = _ouro_config()
    flops, bytes_ = ours.flops_and_bytes(cfg, OURO_CELL)
    once = plain.flops_and_bytes(dict(cfg, num_hidden_layers=1), OURO_CELL)
    assert (flops, bytes_) == (32 * once[0], 32 * once[1])
    pairs = 4096 * 4097 // 2
    assert once[0] == 16 * 6 * 2 * pairs * 128      # nothing for a recomputed
    assert once[1] == 12 * 4096 * 2048 * 2          # forward, flops or bytes
    # K is part of the count: three turns need three quarters
    assert ours.flops_and_bytes(dict(cfg, total_ut_steps=3), OURO_CELL)[0] \
        == 24 * once[0]


def _ouro_row(op_name, ns, target=None, container=False, opcode="fusion",
              count=2):
    xplane = _load("xplane.py")
    scope, transform = xplane.scope_of(op_name)
    return {"name": "%f", "opcode": opcode, "shape": "", "target": target,
            "container": container, "count": count, "ns": ns,
            "op_name": op_name, "scope": scope, "transform": transform}


def _ouro_run_record():
    """Two steps of a looped model's trace, by hand: the body's rows carry
    the `repeat` op's scope first and their own op's further down the path
    (the three forms read on the chip, PR 44)."""
    def op(kind, out, inputs, slot="Out"):
        return {"type": kind, "scope": f"{kind}.{out}", "inputs": inputs,
                "outputs": {slot: [out]}}

    program_ops = [
        op("lookup_table", "emb", {"Ids": ["toks"], "W": ["tok_emb"]}),
        {"type": "repeat", "scope": LOOP_SCOPE, "inputs": {"Carried": ["emb"]},
         "outputs": {"Out": ["looped.turns.out_256"], "Turns": ["ce_t", "s_t"]}},
        {"type": "exit_expected_cost", "scope": "exit_expected_cost.cost",
         "inputs": {"TurnCosts": ["ce_t"], "GateLogits": ["s_t"]},
         "outputs": {"Cost": ["cost"], "Probs": ["p"]}},
        op("mean", "loss", {"X": ["cost"]}),
        {"type": "adam", "scope": "adam.wq", "inputs": {"Param": ["wq"]},
         "outputs": {"ParamOut": ["wq"]}},
        # the sub-block
        op("rms_norm", "n1", {"X": ["emb"], "Scale": ["g1"]}, "Y"),
        op("mul", "q0", {"X": ["n1"], "Y": ["wq"]}),
        op("mul", "k0", {"X": ["n1"], "Y": ["wk"]}),
        op("mul", "v", {"X": ["n1"], "Y": ["wv"]}),
        op("rotary_embedding", "q", {"X": ["q0"]}),
        op("rotary_embedding", "k", {"X": ["k0"]}),
        op("flash_attention", "att", {"Q": ["q"], "K": ["k"], "V": ["v"]}),
        op("mul", "o", {"X": ["att"], "Y": ["wo"]}),
        op("rms_norm", "hf", {"X": ["o"], "Scale": ["gf"]}, "Y"),
        op("mul", "logits", {"X": ["hf"], "Y": ["out_w"]}),
        {"type": "softmax_with_cross_entropy",
         "scope": "softmax_with_cross_entropy.sm",
         "inputs": {"Logits": ["logits"], "Label": ["labels"]},
         "outputs": {"Softmax": ["sm"], "Loss": ["ce"]}},
    ]
    K = "tpu_custom_call"
    rows = [
        _ouro_row(_FWD + "flash_attention.att/pallas_call", 8_000_000, K),
        _ouro_row(_BWD + "rematted_computation/repeat.turn/flash_attention.att/pallas_call", 8_000_000, K),
        _ouro_row(_BWD + "repeat.turn/flash_attention.att/pallas_call", 16_000_000, K),
        _ouro_row(_BWD + "repeat.turn/flash_attention.att/reduce_sum", 2_000_000),
        _ouro_row(_FWD + "mul.q0/dot_general", 4_000_000),
        _ouro_row(_BWD + "rematted_computation/repeat.turn/mul.o/dot_general", 2_000_000),
        _ouro_row(_BWD + "repeat.turn/rotary_embedding.k/mul", 1_000_000),
        _ouro_row(_FWD + "mul.logits/dot_general", 6_000_000),
        _ouro_row(_BWD + "repeat.turn/mul.logits/dot_general", 12_000_000),
        _ouro_row(_BWD + "rematted_computation/repeat.turn/softmax_with_cross_entropy.sm/reduce_sum", 1_000_000),
        _ouro_row(_FWD + "rms_norm.n1/mul", 500_000),
        # the loop's own: no inner op's scope on the path
        _ouro_row(f"jit(raw)/transpose(jvp({LOOP_SCOPE}))/while/body/closed_call/add_any", 1_500_000),
        # the two loops themselves: containers, in no sum
        # (they reach the trace without an `op_name`, as read on the chip)
        _ouro_row("", 19_000_000, container=True, opcode="while"),
        _ouro_row("", 44_000_000, container=True, opcode="while"),
        # outside the loop
        _ouro_row("jit(raw)/jvp(exit_expected_cost.cost)/repeat.exit/exp", 300_000),
        _ouro_row("jit(raw)/transpose(jvp(mean.loss))/div", 100_000),
        _ouro_row("jit(raw)/adam.wq/mul", 3_000_000),
        _ouro_row("jit(raw)/jvp(lookup_table.emb)/gather", 700_000),
    ]
    return {"steps": 2, "program_ops": program_ops, "trace": {"ops": rows},
            "config": _ouro_config(), "cell": dict(OURO_CELL),
            "device": {"kind": "TPU v5 lite"},
            "registry": {"pt_repeat_saved_bytes": 4 * 2**25 + 2 * 4 * 4096 * 4.0}}


def test_loop_readers_find_a_bodys_rows_by_the_inner_scope():
    run = _ouro_run_record()
    body = _load("layer_metrics", "repeat.body_device_ms.py")
    scopes, loops = body._program(run)
    assert loops == {LOOP_SCOPE} and LOOP_SCOPE not in scopes
    assert body.inner_scope(_BWD + "repeat.turn/mul.q0/dot_general", scopes) == "mul.q0"
    assert body.inner_scope(f"jit(raw)/jvp({LOOP_SCOPE})/while", scopes) == ""
    found = {(r["op_name"].rsplit("/", 2)[-2], which)
             for r, inner, kind, which in body.body_rows(run)}
    assert ("flash_attention.att", "forward") in found
    assert ("flash_attention.att", "recomputed") in found
    assert ("flash_attention.att", "backward") in found
    # every leaf row under the loop, containers and the rows outside left out
    assert body.compute(run) == pytest.approx((8 + 8 + 16 + 2 + 4 + 2 + 1 + 6
                                               + 12 + 1 + .5 + 1.5) / 2)
    info = body.info(run)
    assert info["by_pass_ms"] == pytest.approx(
        {"forward": 9.25, "recomputed": 5.5, "backward": 16.25})
    assert info["no_inner_op_ms"] == pytest.approx(0.75)
    assert info["by_inner_op_type_ms"]["flash_attention"] == pytest.approx(
        {"forward": 4.0, "recomputed": 4.0, "backward": 9.0})
    assert info["while_containers_ms"] == pytest.approx(31.5)
    assert info["containers_over_their_bodies_ms"] == pytest.approx(0.5)
    recompute = _load("layer_metrics", "repeat.recompute_ms.py")
    assert recompute.compute(run) == pytest.approx(5.5)
    assert recompute.info(run)["over_forward"] == pytest.approx(5.5 / 9.25)
    saved = _load("layer_metrics", "repeat.saved_gib.py")
    assert saved.compute(run) == pytest.approx(0.125 + 2**17 / 2**30)
    attn = _load("layer_metrics", "ouro.attn_device_ms.py")
    assert attn.compute(run) == pytest.approx(17.0)
    info = attn.info(run)
    assert info.pop("by_pass_ms") == pytest.approx(
        {"forward": 4.0, "recomputed": 4.0, "backward": 9.0})
    assert info == pytest.approx({"kernels_ms": 16.0, "not_kernels_ms": 1.0,
                                  "rotary_ms": 0.5, "projections_ms": 3.0})
    assert attn.around(run["program_ops"]) == {
        "rotary": {"rotary_embedding.q", "rotary_embedding.k"},
        "projections": {"mul.q0", "mul.k0", "mul.v", "mul.o"}}
    head = _load("layer_metrics", "ouro.head_device_ms.py")
    assert head.head_scopes(run["program_ops"]) == (
        {"softmax_with_cross_entropy.sm": "cross_entropy", "mul.logits": "gemm"},
        {"exit_expected_cost.cost", "mean.loss"})
    assert head.compute(run) == pytest.approx((6 + 12 + 1 + .3 + .1) / 2)
    assert head.info(run)["gemm"] == pytest.approx(
        {"forward": 3.0, "recomputed": 0.0, "backward": 6.0})
    assert head.info(run)["exit_cost_ms"] == pytest.approx(0.2)
    roof = _load("layer_metrics", "ouro.flash_roofline.py")
    need = _load("kernels", "ouro_flash_attention.py").flops_and_bytes(
        run["config"], run["cell"])
    want = _load("roofline.py").share(*need, 0.016, "TPU v5 lite")
    assert roof.compute(run) == pytest.approx(want[0])
    assert roof.info(run)["kernel_ms_by_pass"] == pytest.approx(
        {"forward": 4.0, "recomputed": 4.0, "backward": 8.0})
    assert roof.info(run)["bound"] == want[1]
    # the readers that go by the OUTER scope see nothing inside the loop
    assert _load("layer_metrics", "attn.device_ms.py").compute(run) is None
    # and what the loop's readers, the rows outside it and nothing else hold
    # is the whole of the leaf rows
    leaf = sum(r["ns"] for r in run["trace"]["ops"] if not r["container"])
    outside = 300_000 + 100_000 + 3_000_000 + 700_000
    assert leaf / 1e6 / 2 == pytest.approx(body.compute(run) + outside / 2e6)
    # nothing to read without a loop, a trace or the gauge: None, no raise
    for empty in (dict(run, trace=None), dict(run, program_ops=[]),
                  dict(run, program_ops=[o for o in run["program_ops"]
                                         if o["type"] != "repeat"])):
        for reader in (body, recompute, attn, head, roof):
            assert reader.compute(empty) is None
    assert saved.compute(dict(run, registry={})) is None


OURO_WRAPPERS = {"ouro.opt_device_ms": "opt.device_ms",
                 "ouro.donated_gib": "step.donated_gib",
                 "ouro.feed_produce_ms_per_step": "feed.produce_ms_per_step"}


@pytest.mark.parametrize("name", sorted(OURO_WRAPPERS))
def test_an_ouro_wrapper_returns_what_the_reader_it_wraps_returns(name):
    run = _ouro_run_record()
    run["registry"]["pt_executor_donated_bytes"] = 7.35e9
    run["timers_s"] = {"prefetch.read": 0.004, "prefetch.batch": 0.002}
    wrapper = _load("layer_metrics", name + ".py")
    wrapped = _load("layer_metrics", OURO_WRAPPERS[name] + ".py")
    assert wrapper.WRAPS == OURO_WRAPPERS[name]
    got, want = wrapper.compute(run), wrapped.compute(run)
    assert got is not None and got == want
    empty = dict(run, trace=None, registry={}, timers_s={})
    assert wrapper.compute(empty) is None and wrapped.compute(empty) is None
    if name == "ouro.opt_device_ms":
        assert got == pytest.approx(1.5)     # Adam stands alone behind the loop


def test_the_manifest_lists_the_ouro_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = OURO + ".train-log10"
    assert manifest["workloads"][6]["name"] == cell      # appended in PR 44
    entry = manifest["workloads"][6]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        OURO, "train-log10", 1)
    assert sum(w["chips"] for w in manifest["workloads"][:7]) == 7   # no 4-chip
    config = manifest["configs"][5]
    assert config["name"] == OURO and config["source"] == _ouro_config()["source"]
    assert config["reduced"] == _ouro_config()["reduced"] == [
        "num_hidden_layers", "layer_types"]
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [cell]]
    assert mine == ["repeat.body_device_ms", "repeat.recompute_ms",
                    "repeat.saved_gib", "ouro.attn_device_ms",
                    "ouro.head_device_ms", "ouro.flash_roofline",
                    "ouro.opt_device_ms", "ouro.donated_gib",
                    "ouro.feed_produce_ms_per_step"]
    names = [m["name"] for m in manifest["per_layer"]]
    end = names.index(mine[-1]) + 1                      # appended in PR 44
    assert names[end - len(mine):end] == mine
    # PR 46's, every cell; PR 49 appended its own cell's behind it
    assert names[end] == "attn.masked_pair_share"
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert {by_name[n]["layer"] for n in mine[:3]} == {"Looped stack"}
    assert by_name["repeat.saved_gib"]["moves"] == "peak_hbm_gib" \
        == by_name["ouro.donated_gib"]["moves"]
    assert by_name["ouro.flash_roofline"]["unit"] == "%"
    for name in mine:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
    # no reader that was there lists the new cell: their entries are untouched
    assert not [m["name"] for m in manifest["per_layer"][:end - len(mine)]
                if cell in m.get("workloads", ())]
    # no step tail: the cell reports items_s, peak_hbm_gib and setup_s
    tail = next(m for m in manifest["end_to_end"] if m["name"] == "step_ms_p90")
    assert cell not in tail["workloads"]
    assert manifest["run_seconds"] == 36
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seqlen"], traffic["sync_every"],
            traffic["warmup_steps"], traffic["trace_seconds"]) == (
        1, 4096, 10, 20, 6)
    for why in (entry["why"], config["why"]):
        assert len(why) <= 200 and "\n" not in why


def test_the_benchmarks_ouro_reference_is_the_trees_bit_for_bit():
    """`chipbench/configs/ouro-2.6b/reference.py` is a copy of
    `tests/looped_reference.py`, text for text, and gives the same cost and
    gradients to the bit on the CPU: the two cannot drift apart unseen."""
    import looped_reference as tree

    copy = _load("configs", OURO, "reference.py")
    assert open(copy.__file__).read() == open(tree.__file__).read()
    cfg = dict(_ouro_config(), **_ouro_config()["rehearsal"])
    d, V, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    layer = [(d,), (d, width), (d, width), (d, width), (width, d), (d,), (d,),
             (d, f), (d, f), (f, d), (d,)]
    shapes = [(V, d)] + layer * cfg["num_hidden_layers"] \
        + [(d,), (d, V), (d,), (1,)]
    rng = np.random.RandomState(0)
    params = [(rng.randn(*s) * 0.2 + (len(s) == 1)).astype(np.float32)
              for s in shapes]
    params[-2] -= 1.0                  # the gate's weight: about zero, not one
    toks = rng.randint(0, V, (2, 41))
    feed = {"toks": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:, None].astype(np.int32)}
    assert copy.prepare(feed) is feed
    (c1, g1), (c2, g2) = (m.loss_and_grads(cfg, params, feed)
                          for m in (tree, copy))
    assert float(c1) == float(c2) and np.isfinite(float(c1))
    assert len(g1) == len(g2) == len(params)
    for a, b in zip(g1, g2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.abs(np.asarray(a)).max() > 0


def test_the_ouro_cell_rehearses_on_the_cpu(tmp_path):
    """`run.py --rehearse-cpu` of the new cell: the harness finds the
    configuration's files by name, the first step agrees with the plain
    reference at the rehearsal's tolerances, the loop's gauge reaches the run
    record, and every metric's name carries the rehearsal's prefix."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         OURO + ".train-log10", "--rehearse-cpu", "--trace", "1",
         "--seed", "2147486144"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["rehearsal"] and not result["failed"]
    names = set(result["metrics"])
    assert all(n.startswith("REHEARSAL_ON_CPU.") for n in names)
    assert {"REHEARSAL_ON_CPU.repeat.saved_gib",
            "REHEARSAL_ON_CPU.ouro.donated_gib",
            "REHEARSAL_ON_CPU.ouro.feed_produce_ms_per_step",
            "REHEARSAL_ON_CPU.loop.dispatch_per_step"} <= names
    # the carries [2, 64, 64] of the three turns the loop runs again (of four:
    # PR 56) and the stacked costs and gate logits
    assert result["metrics"]["REHEARSAL_ON_CPU.repeat.saved_gib"]["value"] \
        == (3 * 2 * 64 * 64 * 4 + 2 * 4 * 2 * 64 * 4) / 2**30
    assert "choice_counts_off_program" not in result["compared"]


def test_the_parents_tree_ends_at_once_on_a_cell_it_lacks(tmp_path):
    """A manifest without the cell (the parent's own) ends `run.py` with exit
    code 2 before any backend starts."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "no-such-config.train-log10", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert out.returncode == 2 and "no cell" in out.stderr


# ---------------------------------------------------------------------------
# PR 46: attn.masked_pair_share, the share of the computed score pairs that the
# mask throws away, from the gauge the attention op sets when it is traced.

PAIRS = 'pt_flash_attention_pairs{pairs="%s",path="%s"}'
MASKED_SHARES = [
    # id, the window's registry, the share
    # gpt2-small on the parent's arithmetic: 3 blocks of 512 x 512 computed a
    # layer for T (T + 1) / 2 pairs: 1 - 524 800 / 786 432 = 0.3327
    ("whole_crossed_blocks", {PAIRS % ("computed", "packed"): 12 * 144 * 786432.0,
                              PAIRS % ("kept", "packed"): 12 * 144 * 524800.0},
     1 - 524800 / 786432),
    # a crossed block as two strips, three quarters of it: 2.5 blocks computed
    ("strips", {PAIRS % ("computed", "packed"): 655360.0,
                PAIRS % ("kept", "packed"): 524800.0},
     1 - 524800 / 655360),
    # trinity's two paths summed: four window layers at 17.5 blocks of 1024
    # (7 whole, 14 crossed) and the global one at 34 (28 and 8), for 14 and 32
    # blocks' worth kept: 16 / 104
    ("two_paths", {PAIRS % ("computed", "packed_window"): 4 * 17.5,
                   PAIRS % ("kept", "packed_window"): 4 * 14.0,
                   PAIRS % ("computed", "packed"): 34.0,
                   PAIRS % ("kept", "packed"): 32.0,
                   "pt_flash_attention_dispatch_total{path=\"packed\"}": 0.0},
     16 / 104),
    ("no_series_the_parent", {"pt_executor_donated_bytes": 8.4e9}, None),
    ("no_registry", None, None),
]


@pytest.mark.parametrize("registry,share", [c[1:] for c in MASKED_SHARES],
                         ids=[c[0] for c in MASKED_SHARES])
def test_masked_pair_share_reads_the_ops_pairs_gauge(registry, share):
    reader = _load("layer_metrics", "attn.masked_pair_share.py")
    got = reader.compute({"registry": registry})
    assert got == (share if share is None else pytest.approx(share))
    if share is not None:
        assert set(reader.info({"registry": registry})["pairs_by_path"]) \
            <= {"packed", "packed_window", "xla"}


def test_the_manifest_lists_the_masked_pair_share_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"]
                 if m["name"] == "attn.masked_pair_share")
    assert entry == {"name": "attn.masked_pair_share", "unit": "ratio",
                     "better": "lower", "source": "program_counter",
                     "layer": "Kernels", "moves": "items_s"}
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                       entry["name"] + ".py"))


def test_the_traced_op_publishes_the_pairs_the_reader_reads():
    """The program's side: an attention op traced on each path sets
    `pt_flash_attention_pairs`, which `registry_snapshot` reads as a gauge (so
    `registry_delta` keeps its value at the close, though the op was traced
    before the window opened), and the reader gives the share."""
    import jax.numpy as jnp

    from paddle_tpu.ops import flash_ops

    train = _load("drivers", "train.py")
    x = jnp.zeros((1, 256, 2, 64), jnp.float32)
    before = train.registry_snapshot()
    flash_ops.flash_attention(x, x, x, causal=True)               # XLA here
    flash_ops.flash_attention(x, x, x, causal=True, window=128)
    after = train.registry_snapshot()
    series = PAIRS % ("computed", "xla")
    assert after[series][0] == "gauge"
    grew = {s: after[s][1] - before.get(s, ("gauge", 0.0))[1]
            for s in (PAIRS % ("computed", "xla"), PAIRS % ("kept", "xla"))}
    band = 256 * 257 // 2
    narrow = 128 * 129 // 2 + 128 * 128
    assert grew == {PAIRS % ("computed", "xla"): 2 * 2 * 256 * 256,
                    PAIRS % ("kept", "xla"): 2 * (band + narrow)}
    reader = _load("layer_metrics", "attn.masked_pair_share.py")
    share = reader.compute({"registry": train.registry_delta(after, after)})
    kept, computed = (after[PAIRS % (k, "xla")][1] for k in ("kept", "computed"))
    assert share == pytest.approx(1 - kept / computed)


# PR 49: lfm2-24b-a2b (gated short-convolution operators and one attention
# layer in five, a sigmoid top-4-of-64 router with a choice bias)
LFM2 = "lfm2-24b-a2b"
LFM2_CELL = {"batch": 1, "seqlen": 16384}
C_, A_ = "conv", "full_attention"


def _lfm2_config():
    with open(os.path.join(BENCH, "configs", LFM2, "config.json")) as f:
        return json.load(f)


def test_lfm2_flops_per_token():
    flops = _load("flops.py")
    cfg = _lfm2_config()
    config_dir = os.path.join(BENCH, "configs", LFM2)
    got = flops.train_flops_per_item(cfg, LFM2_CELL, config_dir)
    assert got == 3 * 439357440.0 == 1318072320.0
    own = _load("configs", LFM2, "flops.py")
    # an operator is its two projections; the attention layer its four and
    # the kernels over the (T + 1) / 2 keys a query sees
    assert own.operator_flops_per_token(cfg, 16384, C_) == 33554432
    assert own.operator_flops_per_token(cfg, 16384, A_) == 20971520 + 67112960
    with pytest.raises(ValueError, match="unknown kind"):
        own.operator_flops_per_token(cfg, 16384, "sliding_attention")
    # the shares ISSUE 49 states: conv 30 %, dense MLP 33 %, attention 20 %,
    # head 8 %, held experts 9 %
    whole = 439357440.0
    assert round(100 * 4 * 33554432 / whole) == 31
    assert round(100 * 6 * 2048 * 11776 / whole) == 33
    assert round(100 * (20971520 + 67112960) / whole) == 20
    assert round(100 * 2 * 2048 * 8192 / whole) == 8
    assert round(100 * 4 * (262144 + 9437184) / whole) == 9
    # one more routed conv layer adds an operator, a router and half a pair
    more = own.forward_flops_per_token(
        dict(cfg, layer_types=cfg["layer_types"] + [C_]), 16384)
    assert more - whole == 33554432 + 262144 + 9437184
    wider = own.forward_flops_per_token(dict(cfg, held_experts=[0, 16]), 16384)
    assert wider - whole == 4 * 9437184
    # at T 8192 (the cell's fallback) only the kernels' part moves
    short = own.forward_flops_per_token(cfg, 8192)
    assert whole - short == 4 * 32 * 64 * 8192 / 2


def test_lfm2_config_keeps_the_published_sizes():
    cfg = _lfm2_config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f)
                         if e["name"] == "LFM2-24B-A2B")
    assert cfg["source"] == published["source_url"]
    differs = [k for k, v in published["config"].items()
               if cfg.get(k, "absent") != v]
    assert sorted(differs) == sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "layer_types", "num_dense_layers",
         "num_experts", "vocab_size"])
    assert cfg["published"] == {k: published["config"][k] for k in differs}
    assert cfg["rope_parameters"] == published["config"]["rope_parameters"]
    # the cut: published layers 1-5 (one dense layer, four routed ones, one
    # whole period 3 conv : 1 attention among the routed), 8 of 64 experts
    # behind a router that stays 64 wide, an eighth of the vocabulary
    assert cfg["layer_types"] == published["config"]["layer_types"][1:6] \
        == [C_, A_, C_, C_, C_]
    assert cfg["num_hidden_layers"] == 5 and cfg["num_dense_layers"] == 1
    assert cfg["layer_types"][1:].count(C_) == 3 * cfg["layer_types"][1:].count(A_)
    assert cfg["vocab_size"] * 8 == 65536
    assert cfg["router_experts"] == 64 and cfg["held_experts"] == [0, 8]
    # no width is cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["conv_L_cache"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == (
        2048, 32, 8, 3, 11776, 1536, 4, 1)
    assert "8-chip" in cfg["deployment"] and "40" in cfg["distortion"]
    for key in ("assumed", "departures", "deployment", "distortion",
                "gradient_limits"):
        assert cfg[key], key
    for key in ("basis", "head_size", "stream", "conv_operator",
                "attention_operator", "dense_ffn", "router", "aux_cost",
                "optimizer", "compute_dtype", "initialisers"):
        assert cfg["assumed"][key], key
    assert any("UNTIED" in d for d in cfg["departures"])
    # the parameters this chip holds: ISSUE 49's count
    d = 2048
    conv = d * 3 * d + 3 * d + d * d
    attn = 2 * d * d + 2 * d * 512 + 2 * 64
    experts = 8 * 3 * d * 1536
    dense = conv + 3 * d * 11776 + 2 * d
    attn_routed = attn + d * 64 + experts + 2 * d
    conv_routed = conv + d * 64 + experts + 2 * d
    assert (conv, attn, dense, attn_routed, conv_routed) == (
        16783360, 10485888, 89139200, 86118528, 92416000)
    assert dense + attn_routed + 3 * conv_routed + 2 * 8192 * d + d == 486062208


def test_lfm2_kernels_count_on_hand_made_cells():
    flash = _load("kernels", "lfm2_flash_attention.py")
    plain = _load("kernels", "flash_attention.py")
    cfg = {"layer_types": [C_, A_, C_, A_], "num_attention_heads": 4,
           "num_key_value_heads": 2, "hidden_size": 32,
           "num_hidden_layers": 4}
    cell = {"batch": 2, "seqlen": 5}
    assert flash.attention_layers(cfg) == 2
    flops, bytes_ = flash.flops_and_bytes(cfg, cell)
    # two attention layers of four; heads of 32 / 4 = 8; 15 causal pairs
    assert flops == 2 * 2 * 4 * 6 * 2 * 15 * 8
    assert bytes_ == 2 * 6 * (2 * 5 * 4 * 8 + 2 * 5 * 2 * 8) * 2
    assert plain.flops_and_bytes(cfg, cell)[0] == 2 * flops   # every layer
    got = flash.flops_and_bytes(_lfm2_config(), LFM2_CELL)
    pairs = 16384 * 16385 // 2
    assert got[0] == 32 * 12 * 64 * pairs                      # 3.30 TFLOP
    assert got[1] == 6 * 16384 * (2048 + 512) * 2
    assert got[0] / 197e12 > got[1] / 819e9                    # compute-bound
    gmm = _load("kernels", "lfm2_grouped_matmul.py")
    cfg = _lfm2_config()
    d, f = 2048, 1536
    flops, bytes_ = gmm.flops_and_bytes(cfg, LFM2_CELL)
    rows = 4 * 16384 * 4 * 8 / 64           # even routing: 8 192 a layer
    assert gmm.routed_layers(cfg) == 4 and rows == 32768
    assert flops == 18 * rows * d * f
    assert bytes_ == 2 * (9 * 4 * 8 * d * f + rows * (5 * d + 7 * f))
    assert gmm.flops_and_bytes(cfg, LFM2_CELL, rows=100.0) == (
        18 * 100.0 * d * f, 2 * (9 * 4 * 8 * d * f + 100.0 * (5 * d + 7 * f)))


LFM2_MOE = "moe_ffn.lfm2.h1.moe.tmp_40"
LFM2_CONV = ("short_conv_operator.lfm2.h0.conv.tmp_3",
             "short_conv_operator.lfm2.h2.conv.tmp_50")
LFM2_ATTN = "flash_attention.lfm2.h1.attn.tmp_30"


def _lfm2_run_record():
    """Two steps of two operators, the attention layer and one routed
    layer."""
    first, second = LFM2_CONV
    ops = [
        _row(first, 6_000_000, None, "jvp(", "in_proj"),
        _row(first, 1_000_000, "tpu_custom_call", "jvp(", "mix"),
        _row(first, 2_000_000, None, "jvp(", "out_proj"),
        _row(first, 12_000_000, None, "transpose(jvp(", "in_proj"),
        _row(first, 2_000_000, "tpu_custom_call", "transpose(jvp(", "mix"),
        _row(first, 400_000, None, "transpose(jvp(", "mix"),   # dw's sum
        _row(first, 4_000_000, None, "transpose(jvp(", "out_proj"),
        _row(second, 1_000_000, "tpu_custom_call", "jvp(", "mix"),
        _row(second, 2_000_000, "tpu_custom_call", "transpose(jvp(", "mix"),
        _row(second, 30_000_000, None, "", container=True),    # a loop: out
        _row(LFM2_ATTN, 10_000_000, "tpu_custom_call", "jvp("),
        _row(LFM2_ATTN, 30_000_000, "tpu_custom_call", "transpose(jvp("),
        _row(LFM2_ATTN, 1_000_000, None, "transpose(jvp("),    # dK, dV sums
        _row(LFM2_MOE, 4_000_000, "tpu_custom_call", "", "experts"),
        _row(LFM2_MOE, 2_000_000, None, "", "dispatch"),
        _row(LFM2_MOE, 3_000_000, None, "", "combine"),
        _row("mul.fc_9.tmp_9", 5_000_000),        # the head: no part of it
        _row("rms_norm.l0n1", 700_000),           # a stream norm: none
    ]
    held = 'pt_moe_held_pairs_total{expert="%d",layer="lfm2.h1.moe"}'
    every = 'pt_moe_expert_tokens_total{expert="%d",layer="lfm2.h1.moe"}'
    path = 'pt_moe_row_path_total{layer="lfm2.h1.moe",path="%d"}'
    registry = {held % 0: 8000.0, held % 1: 8384.0,
                every % 0: 8000.0, every % 1: 8384.0, every % 60: 114688.0,
                path % 0: 2.0, "pt_executor_donated_bytes": 5.83e9,
                'pt_short_conv_dispatch_total{path="pallas"}': 0.0,
                "pt_short_conv_bytes": 2 * 738226176.0}
    program_ops = [
        {"type": "short_conv_operator", "scope": first,
         "inputs": {"X": ["h0"]}, "outputs": {"Out": ["lfm2.h0.conv.tmp_3"]}},
        {"type": "flash_attention", "scope": LFM2_ATTN,
         "inputs": {"Q": ["q"], "K": ["k"], "V": ["v"]},
         "outputs": {"Out": ["lfm2.h1.attn.tmp_30"]}},
        {"type": "moe_ffn", "scope": LFM2_MOE, "inputs": {"X": ["h2"]},
         "outputs": {"Out": ["lfm2.h1.moe.tmp_40"]}},
        {"type": "short_conv_operator", "scope": second,
         "inputs": {"X": ["h3"]}, "outputs": {"Out": ["lfm2.h2.conv.tmp_50"]}},
        {"type": "mul", "scope": "mul.fc_9.tmp_9",
         "inputs": {"X": ["hf"], "Y": ["w"]}, "outputs": {"Out": ["l"]}}]
    return {"steps": 2, "trace": {"ops": ops}, "registry": registry,
            "device": {"kind": "TPU v5 lite"}, "config": _lfm2_config(),
            "cell": dict(LFM2_CELL), "program_ops": program_ops}


def test_conv_readers_find_the_operators_rows_by_op_type_and_inner_scope():
    run = _lfm2_run_record()
    device = _load("layer_metrics", "conv.device_ms.py")
    # every leaf row under an operator's scope, forward and backward; not
    # the loop, not the attention layer's, the routed layer's or the head's
    assert device.compute(run) == pytest.approx(30.4 / 2)
    info = device.info(run)
    assert info["operators"] == 2
    assert info["by_inner_scope_ms"] == pytest.approx(
        {"in_proj": 9.0, "mix": 3.2, "out_proj": 3.0})
    assert info["by_pass_ms"] == pytest.approx(
        {"jvp": 5.0, "transpose": 10.2})
    mix = _load("layer_metrics", "conv.mix_ms.py")
    assert mix.compute(run) == pytest.approx(6.4 / 2)
    info = mix.info(run)
    assert info["run_by"] == "kernels" and info["kernels_ms"] == pytest.approx(3.0)
    assert info["by_pass_ms"] == pytest.approx({"jvp": 1.0, "transpose": 2.2})
    assert info["bytes_per_step"] == 2 * 738226176.0
    assert list(info["dispatch"]) == [
        'pt_short_conv_dispatch_total{path="pallas"}']
    # the kernels' share of their roofline: two operators' operands and
    # results (11 T d bf16 elements each and the float32 taps) at 819 GB/s
    # over the 3.2 ms a step under `mix`; memory-bound
    roof = _load("layer_metrics", "kernel.short_conv_roofline.py")
    need = _load("kernels", "gated_short_conv.py")
    flops, bytes_ = need.flops_and_bytes(run["config"], run["cell"])
    assert need.operators(run["config"]) == 4
    assert bytes_ == 4 * (11 * 16384 * 2048 * 2 + 12 * 3 * 2048) == 2953084928
    assert flops == 4 * 16384 * 2048 * 24
    assert roof.compute(run) == pytest.approx(
        100 * bytes_ / 819e9 / 3.2e-3, rel=1e-3)
    assert roof.info(run)["bound"] == "memory"
    assert roof.info(run)["program_counted_bytes"] == 2 * 738226176.0
    # XLA's plain form: the same rows with no kernel among them, and no
    # boundary to count bytes at: the share is left out
    for r in run["trace"]["ops"]:
        if r["scope"] in LFM2_CONV:
            r["target"] = None
    assert mix.info(run)["run_by"] == "xla"
    assert roof.compute(run) is None
    # a Program without the op (every other configuration, the parent of the
    # PR that added it), or no trace: nothing, not raised
    for empty in (dict(run, trace=None), dict(run, program_ops=None),
                  dict(run, program_ops=_glm_run_record()["program_ops"]),
                  dict(run, program_ops=_nemo_run_record()["program_ops"])):
        assert device.compute(empty) is None and mix.compute(empty) is None
        assert roof.compute(empty) is None


def test_lfm2_rooflines_on_a_hand_made_run_record():
    run = _lfm2_run_record()
    flash = _load("layer_metrics", "lfm2.flash_roofline.py")
    # one layer's 3.30 TFLOP at 197 TFLOP/s over the kernels' 20 ms a step
    need = 32 * 12 * 64 * (16384 * 16385 // 2) / 197e12
    assert flash.compute(run) == pytest.approx(100 * need / 20e-3, rel=1e-3)
    info = flash.info(run)
    assert info["kernels_per_step"] == 2.0 and info["bound"] == "compute"
    assert flash.compute(dict(run, trace=None)) is None
    gmm = _load("layer_metrics", "lfm2.gmm_roofline.py")
    d, f = 2048, 1536                       # 2 steps, 16 384 held pairs
    want_bytes = 2 * (9 * 4 * 8 * d * f + 8192.0 * (5 * d + 7 * f))
    assert gmm.info(run)["flops_per_step"] == 18 * 8192.0 * d * f
    assert gmm.info(run)["bytes_per_step"] == want_bytes
    assert gmm.info(run)["held_pairs_per_step"] == 8192.0
    assert gmm.compute(run) == pytest.approx(
        100 * want_bytes / 819e9 / 2e-3, rel=1e-3)    # the kernels' 2 ms
    assert gmm.info(run)["bound"] == "memory"
    no_held = {k: v for k, v in run["registry"].items()
               if "held_pairs" not in k}
    assert gmm.compute(dict(run, registry=no_held)) is None
    assert gmm.compute(dict(run, trace=None)) is None


LFM2_WRAPPERS = {"lfm2.head_device_ms": "head.device_ms",
                 "lfm2.feed_produce_ms_per_step": "feed.produce_ms_per_step",
                 "lfm2.opt_device_ms": "opt.device_ms",
                 "lfm2.donated_gib": "step.donated_gib",
                 "lfm2.attn_device_ms": "attn.device_ms",
                 "lfm2.held_pair_share": "moe.held_pair_share",
                 "lfm2.bounded_step_share": "moe.bounded_step_share",
                 "lfm2.moe_device_ms": "nemotron.moe_device_ms",
                 "lfm2.moe_dispatch_ms": "nemotron.moe_dispatch_ms",
                 "lfm2.load_max_over_mean": "nemotron.load_max_over_mean"}


@pytest.mark.parametrize("name", sorted(LFM2_WRAPPERS))
def test_an_lfm2_wrapper_returns_what_the_reader_it_wraps_returns(name):
    run = _lfm2_run_record()                # 2 steps x 16 384 tokens x 4 pairs
    run["program_ops"] += [
        {"type": "adam", "scope": "adam.w", "inputs": {"Param": ["w"]},
         "outputs": {"ParamOut": ["w"]}},
        {"type": "softmax_with_cross_entropy",
         "scope": "softmax_with_cross_entropy.s",
         "inputs": {"Logits": ["l"], "Label": ["y"]},
         "outputs": {"Softmax": ["s"], "Loss": ["c"]}}]
    run["trace"]["ops"].append(_row("adam.w", 2_000_000))
    run["timers_s"] = {"prefetch.read": 0.004, "prefetch.batch": 0.002}
    wrapper = _load("layer_metrics", name + ".py")
    wrapped = _load("layer_metrics", LFM2_WRAPPERS[name] + ".py")
    assert wrapper.WRAPS == LFM2_WRAPPERS[name]
    got, want = wrapper.compute(run), wrapped.compute(run)
    assert got is not None and got == want
    if hasattr(wrapper, "info"):
        assert wrapper.info(run) == wrapped.info(run)
    empty = dict(run, trace=None, registry={}, timers_s={})
    assert wrapper.compute(empty) is None and wrapped.compute(empty) is None
    expected = {"lfm2.held_pair_share": 16384 / 131072,          # 0.125
                "lfm2.bounded_step_share": 1.0,
                "lfm2.load_max_over_mean": 114688 / 2048,
                "lfm2.moe_device_ms": 4.5,
                "lfm2.moe_dispatch_ms": 2.5,
                "lfm2.attn_device_ms": 20.5}
    if name in expected:
        assert got == pytest.approx(expected[name])


def test_the_manifest_lists_the_lfm2_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = LFM2 + ".train-log10"
    assert manifest["workloads"][7]["name"] == cell      # appended in PR 49
    entry = manifest["workloads"][7]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        LFM2, "train-log10", 1)
    assert sum(w["chips"] for w in manifest["workloads"][:8]) == 8   # no 4-chip
    config = manifest["configs"][6]
    assert config["name"] == LFM2 and config["source"] == _lfm2_config()["source"]
    assert config["reduced"] == _lfm2_config()["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers", "num_experts",
        "vocab_size"]
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [cell]]
    assert mine[:3] == ["conv.device_ms", "conv.mix_ms",
                        "kernel.short_conv_roofline"]
    assert sorted(mine[3:]) == sorted(
        list(LFM2_WRAPPERS) + ["lfm2.flash_roofline", "lfm2.gmm_roofline"])
    names = [m["name"] for m in manifest["per_layer"]]
    end = names.index(mine[-1]) + 1                      # appended in PR 49
    assert names[end - len(mine):end] == mine            # contiguous, in order
    # PR 50 appended one reader of the hybrid's cell behind them
    assert names[end:end + 1] == ["ssm.conv_ms"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert {by_name[n]["layer"] for n in mine
            if n.startswith(("conv.",))} == {"Short-conv operators"}
    assert by_name["kernel.short_conv_roofline"]["layer"] == "Kernels"
    assert by_name["lfm2.donated_gib"]["moves"] == "peak_hbm_gib"
    assert {by_name[n]["moves"] for n in mine} == {"items_s", "peak_hbm_gib"}
    assert {by_name[n]["unit"] for n in mine if "roofline" in n} == {"%"}
    for name in mine:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
    # no reader that was there lists the new cell: their entries are untouched
    assert not [m["name"] for m in manifest["per_layer"][:end - len(mine)]
                + manifest["per_layer"][end:]
                if cell in m.get("workloads", ())]
    # no step tail: the cell reports items_s, peak_hbm_gib and setup_s
    tail = next(m for m in manifest["end_to_end"] if m["name"] == "step_ms_p90")
    assert cell not in tail["workloads"]
    assert manifest["run_seconds"] == 36
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seqlen"], traffic["sync_every"],
            traffic["warmup_steps"], traffic["trace_seconds"]) == (
        1, 16384, 10, 20, 4)
    for why in (entry["why"], config["why"]):
        assert len(why) <= 200 and "\n" not in why


def test_the_benchmarks_lfm2_reference_is_the_trees_bit_for_bit():
    """`chipbench/configs/lfm2-24b-a2b/reference.py` is a copy of
    `tests/lfm2_moe_reference.py`, text for text, and gives the same cost,
    gradients and routers to the bit on the CPU, its own choice or a handed
    one: the two cannot drift apart unseen."""
    import lfm2_moe_reference as tree

    copy = _load("configs", LFM2, "reference.py")
    assert open(copy.__file__).read() == open(tree.__file__).read()
    cfg = dict(_lfm2_config(), **_lfm2_config()["rehearsal"])
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D, K = d // H, cfg["conv_L_cache"]
    E, held, f = cfg["router_experts"], 4, cfg["moe_intermediate_size"]
    operator = {C_: [(d, 3 * d), (K, d), (d, d)],
                A_: [(d, H * D), (d, KV * D), (d, KV * D), (D,), (D,),
                     (H * D, d)]}
    ffn = {"dense": [(d, cfg["intermediate_size"])] * 2
           + [(cfg["intermediate_size"], d)],
           "routed": [(d, E), (held, d, f), (held, d, f), (held, f, d), (E,)]}
    rng = np.random.RandomState(0)
    shapes = [(V, d)] + [s for op, kind in zip(cfg["layer_types"],
                                               tree._kinds(cfg))
                         for s in [(d,)] + operator[op] + [(d,)] + ffn[kind]] \
        + [(d,), (d, V)]
    params = [(rng.randn(*s) * 0.2 + (len(s) == 1 and s != (E,))).astype(
        np.float32) for s in shapes]
    params = [np.zeros_like(p) if p.shape == (E,) else p for p in params]
    toks = rng.randint(0, V, (2, 41))
    feed = {"toks": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:, None].astype(np.int32)}
    assert copy.prepare(feed) is feed
    own = [m.loss_grads_and_routers(cfg, params, feed) for m in (tree, copy)]
    choice = copy.chosen(cfg, params, [z for _, _, z in own[1][2]])
    handed = [m.loss_grads_and_routers(cfg, params, feed, choice)
              for m in (tree, copy)]
    for (c1, g1, r1), (c2, g2, r2) in (own, handed):
        assert float(c1) == float(c2) and np.isfinite(float(c1))
        assert len(g1) == len(g2) == len(params) and len(r1) == len(r2) == 2
        for a, b in zip(g1, g2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(r1, r2):
            np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b[2]))
    # the reference's own choice, handed back to it, is its own result
    assert float(own[0][0]) == float(handed[0][0])
    assert [int(m.sum()) for m in choice] == [2 * 40 * 2] * 2


def test_the_lfm2_cell_rehearses_on_the_cpu(tmp_path):
    """`run.py --rehearse-cpu` of the new cell: the harness finds the
    configuration's files by name, the first step agrees with the plain
    reference (handed the program's choice) at the rehearsal's tolerances,
    the routed counters and the operators' byte gauge reach the run record,
    every new metric file returns a number or nothing on the rehearsal's
    run, and every metric's name carries the rehearsal's prefix."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         LFM2 + ".train-log10", "--rehearse-cpu", "--trace", "1",
         "--seed", "2147486149"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["rehearsal"] and not result["failed"]
    names = set(result["metrics"])
    assert all(n.startswith("REHEARSAL_ON_CPU.") for n in names)
    assert {"REHEARSAL_ON_CPU.lfm2.held_pair_share",
            "REHEARSAL_ON_CPU.lfm2.load_max_over_mean",
            "REHEARSAL_ON_CPU.lfm2.bounded_step_share",
            "REHEARSAL_ON_CPU.lfm2.donated_gib",
            "REHEARSAL_ON_CPU.lfm2.feed_produce_ms_per_step",
            "REHEARSAL_ON_CPU.attn.masked_pair_share",
            "REHEARSAL_ON_CPU.loop.dispatch_per_step"} <= names
    # XLA:CPU has no device plane: the trace's readers return nothing
    assert not {"REHEARSAL_ON_CPU.conv.device_ms",
                "REHEARSAL_ON_CPU.conv.mix_ms"} & names
    share = result["metrics"][
        "REHEARSAL_ON_CPU.lfm2.held_pair_share"]["value"]
    assert 0.1 < share < 0.5                 # 4 of 16 experts held: 0.25
    assert "choice_counts_off_program" in result["compared"]
    info = next(json.loads(line.split("info ", 1)[1])
                for line in out.stdout.splitlines() if "] info {" in line)
    assert info["cell"] == LFM2 + ".train-log10" and info["rehearsal"]


# PR 57: phi-4-mini-flash-reasoning (Mamba-1 mixers, differential attention
# under a window, whole and on another layer's keys and values, a gated memory
# unit, a tied head)
PHI4 = "phi-4-mini-flash-reasoning"
PHI4_CELL = {"batch": 1, "seqlen": 8192}


def _phi4_config():
    with open(os.path.join(BENCH, "configs", PHI4, "config.json")) as f:
        return json.load(f)


def test_phi4_flops_per_token():
    flops = _load("flops.py")
    cfg = _phi4_config()
    config_dir = os.path.join(BENCH, "configs", PHI4)
    got = flops.train_flops_per_item(cfg, PHI4_CELL, config_dir)
    assert got == 3 * 1527004640.0 == 4581013920.0
    own = _load("configs", PHI4, "flops.py")
    mixing = {k: own.mixing_flops_per_token(cfg, 8192, k)
              for k in ("mamba", "gmu", "window", "full", "cross")}
    assert mixing == {"mamba": 82247680, "gmu": 52428800,
                      "window": 39321600 + 7619040,
                      "full": 39321600 + 62922240,
                      "cross": 26214400 + 62922240}
    with pytest.raises(ValueError, match="unknown layer kind"):
        own.mixing_flops_per_token(cfg, 8192, "conv")
    # the shares ISSUE 57 states: the MLPs 62 %, the head 8 %; whole-length
    # attention's kernels 62.9 MFLOP a token, the windowed layer's 7.6
    whole = 1527004640.0
    assert round(100 * 6 * 6 * 2560 * 10240 / whole) == 62
    assert round(100 * 2 * 2560 * 25008 / whole) == 8
    assert own.keys_seen(8192) == 4096.5
    assert own.keys_seen(8192, 512) == (512 * 513 / 2 + 7680 * 512) / 8192
    # at T 4096 (the cell's fallback) only the two whole-length layers move
    short = own.forward_flops_per_token(cfg, 4096)
    assert whole - short == pytest.approx(
        2 * 6 * 40 * 64 * 2048 + 6 * 40 * 64 * (
            own.keys_seen(8192, 512) - own.keys_seen(4096, 512)))
    assert round(3 * short / 1e9, 2) == 4.39
    # the whole model: 9 mixers, 8 windowed, 1 whole, 7 units, 7 cross
    full = dict(cfg, num_hidden_layers=32, layer_ids=None, vocab_size=200064)
    assert own.forward_flops_per_token(full, 8192) == (
        9 * 82247680 + 8 * 46940640 + 102243840 + 7 * 52428800
        + 7 * 89136640 + 32 * 157286400 + 2 * 2560 * 200064)


def test_phi4_config_keeps_the_published_sizes():
    cfg = _phi4_config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(e for e in map(json.loads, f)
                         if e["name"] == "Phi-4-mini-flash-reasoning")
    assert cfg["source"] == published["source_url"]
    differs = [k for k, v in published["config"].items()
               if cfg.get(k, "absent") != v]
    assert sorted(differs) == sorted(cfg["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {k: published["config"][k] for k in differs}
    # the cut: one period of the self-decoder, the boundary pair, one period
    # of the cross-decoder; an eighth of the vocabulary
    assert cfg["layer_ids"] == [0, 1, 16, 17, 18, 19]
    assert cfg["num_hidden_layers"] == len(cfg["layer_ids"]) == 6
    assert cfg["vocab_size"] * 8 == 200064
    ref = _load("configs", PHI4, "reference.py")
    assert [k for _, k in ref.held_layers(cfg)] == [
        "mamba", "window", "mamba", "full", "gmu", "cross"]
    # no width is cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["sliding_window"], cfg["mb_per_layer"], cfg["mamba_d_state"],
            cfg["mamba_d_conv"], cfg["mamba_expand"], cfg["mamba_dt_rank"]) \
        == (2560, 40, 20, 10240, 512, 2, 16, 4, 2, 160)
    assert cfg["tie_word_embeddings"] and not cfg["mlp_bias"]
    assert "8-chip" in cfg["deployment"] and "32" in cfg["distortion"]
    for key in ("assumed", "departures", "deployment", "distortion",
                "gradient_limits", "held_layers"):
        assert cfg[key], key
    for key in ("basis", "mixer", "layer_map", "gated_memory_unit",
                "attention", "stream", "optimizer", "compute_dtype",
                "initialisers"):
        assert cfg["assumed"][key], key
    # the parameters this chip holds: ISSUE 57's count
    d, f, c = 2560, 10240, 5120
    mlp = d * 2 * f + f * d
    mixer = d * 2 * c + 4 * c + c + c * 192 + 160 * c + c + c * 16 + c + c * d
    attn = d * 5120 + 5120 + 4 * 64 + 128 + d * d + d
    cross = d * d + d + 4 * 64 + 128 + d * d + d
    gmu = 2 * d * c
    assert (mixer, mlp, attn, gmu, cross) == (
        41241600, 78643200, 19668864, 26214400, 13112704)
    layer = lambda mixing: mixing + mlp + 4 * d  # noqa: E731
    assert (layer(mixer), layer(attn), layer(gmu), layer(cross)) == (
        119895040, 98322304, 104867840, 91766144)
    assert (2 * layer(mixer) + 2 * layer(attn) + layer(gmu) + layer(cross)
            + 2 * d + 25008 * d) == 697094272


def test_phi4_kernels_count_on_hand_made_cells():
    cfg = _phi4_config()
    scan = _load("kernels", "selective_scan.py")
    assert scan.held_kinds(cfg).count("mamba") == 2
    ops, bytes_ = scan.flops_and_bytes(cfg, PHI4_CELL)
    C, N, T = 5120, 16, 8192
    assert ops == 2 * T * 27 * C * N
    # forward: x, B, C, y bf16 and dt float32; backward: those, dy and the
    # five gradients; A and D twice and their gradients
    assert bytes_ == 2 * (T * ((2 * C + 2 * N) * 2 + 4 * C)
                          + T * ((4 * C + 4 * N) * 2 + 8 * C)
                          + 12 * (C * N + C))
    # what the program's own gauge counts for the same two ops
    import sys as _sys
    _sys.path.insert(0, ROOT)
    from paddle_tpu.ops import ssm_ops
    assert 2 * ssm_ops.selective_scan_bytes(1, T, C, N, 2)[0] == bytes_
    assert ssm_ops.selective_scan_bytes(1, T, C, N, 2)[1] == 64 * C * N * 4
    flash = _load("kernels", "phi4_flash_attention.py")
    plain = _load("kernels", "flash_attention.py")
    assert flash.kept_pairs(8192) == 8192 * 8193 // 2
    assert flash.kept_pairs(8192, 512) == 512 * 513 // 2 + 7680 * 512
    assert flash.kept_pairs(256, 512) == 256 * 257 // 2
    got = flash.flops_and_bytes(cfg, PHI4_CELL)
    # a whole-length layer: four launches of what the plain count gives one
    # layer of 20 heads over 10 of 64
    one = plain.flops_and_bytes(
        {"num_attention_heads": 20, "num_key_value_heads": 10,
         "num_hidden_layers": 1, "hidden_size": 1280}, PHI4_CELL)
    windowed = 4 * 20 * 12 * flash.kept_pairs(8192, 512) * 64
    assert got == (2 * 4 * one[0] + windowed, 3 * 4 * one[1])


PHI4_MIXER = ("mamba1_mixer.phi4.h0.mamba.tmp_3",
              "mamba1_mixer.phi4.h16.mamba.tmp_40")


def _phi4_run_record():
    """Two steps of two mixers, one attention layer, one memory unit."""
    first, second = PHI4_MIXER
    flash = "flash_attention.phi4.h1.attn.kernels.tmp_%d"
    ops = [
        _row(first, 6_000_000, None, "jvp(", "in_proj"),
        _row(first, 1_000_000, "tpu_custom_call", "jvp(", "conv"),
        _row(first, 500_000, None, "jvp(", "dt_bc"),
        _row(first, 3_000_000, "tpu_custom_call", "jvp(", "scan"),
        _row(first, 200_000, None, "jvp(", "gate"),
        _row(first, 2_000_000, None, "jvp(", "out_proj"),
        _row(first, 3_000_000, "tpu_custom_call", "transpose(jvp(", "scan"),
        _row(first, 7_000_000, "tpu_custom_call", "transpose(jvp(", "scan"),
        _row(first, 300_000, None, "transpose(jvp(", "scan"),   # dA's sum
        _row(second, 3_000_000, "tpu_custom_call", "jvp(", "scan"),
        _row(second, 10_000_000, "tpu_custom_call", "transpose(jvp(", "scan"),
        _row(second, 30_000_000, None, "", container=True),     # a loop: out
        _row("mul.phi4.h1.attn.qkv.tmp_10", 2_000_000, None, "jvp("),
        _row("split_head_pairs.phi4.h1.attn.qkv.tmp_13", 100_000, None, "jvp("),
        _row(flash % 20, 5_000_000, "tpu_custom_call", "jvp("),
        _row(flash % 21, 5_000_000, "tpu_custom_call", "jvp("),
        _row(flash % 21, 15_000_000, "tpu_custom_call", "transpose(jvp("),
        _row(flash % 21, 400_000, None, "transpose(jvp("),      # dK, dV sums
        _row("diff_combine.phi4.h1.attn.combine.tmp_24", 600_000, None, "jvp("),
        _row("diff_combine.phi4.h1.attn.combine.tmp_24", 1_400_000, None,
             "transpose(jvp("),
        _row("mul.phi4.h1.attn.out_proj.tmp_25", 1_000_000, None, "jvp("),
        _row("mul.phi4.h18.gmu.gate_proj.tmp_60", 1_000_000, None, "jvp("),
        _row("silu_gate.phi4.h18.gmu.gate.tmp_61", 200_000, None, "jvp("),
        _row("mul.phi4.h18.gmu.out_proj.tmp_62", 1_000_000, None,
             "transpose(jvp("),
        _row("silu_gate.phi4.h1.mlp.gate.tmp_30", 900_000, None, "jvp("),
        _row("matmul.matmul_0.tmp_0", 5_000_000),     # the head: no part of it
    ]

    def op(kind, out, **inputs):
        return {"type": kind, "scope": f"{kind}.{out}",
                "inputs": {k: [v] for k, v in inputs.items()},
                "outputs": {"Out": [out]}}

    attn = "phi4.h1.attn."
    program_ops = [
        {"type": "mamba1_mixer", "scope": first, "inputs": {"X": ["h0"]},
         "outputs": {"Out": ["phi4.h0.mamba.tmp_3"],
                     "Memory": ["phi4.h0.mamba.tmp_4"]}},
        op("mul", attn + "qkv.tmp_10", X="u", Y="w"),
        {"type": "split_head_pairs",
         "scope": "split_head_pairs." + attn + "qkv.tmp_13",
         "inputs": {"X": ["q"]},
         "outputs": {"First": [attn + "qkv.tmp_13"],
                     "Second": [attn + "qkv.tmp_14"]}},
        op("flash_attention", attn + "kernels.tmp_20", Q="q1", K="k1", V="v1"),
        op("flash_attention", attn + "kernels.tmp_21", Q="q1", K="k1", V="v2"),
        op("diff_combine", attn + "combine.tmp_24", A11="a", A12="b"),
        op("mul", attn + "out_proj.tmp_25", X="o", Y="wo"),
        op("silu_gate", "phi4.h1.mlp.gate.tmp_30", X="up"),
        {"type": "mamba1_mixer", "scope": second, "inputs": {"X": ["h2"]},
         "outputs": {"Out": ["phi4.h16.mamba.tmp_40"],
                     "Memory": ["phi4.h16.mamba.tmp_41"]}},
        op("mul", "phi4.h18.gmu.gate_proj.tmp_60", X="u", Y="wg"),
        op("silu_gate", "phi4.h18.gmu.gate.tmp_61",
           X="phi4.h16.mamba.tmp_41", Gate="phi4.h18.gmu.gate_proj.tmp_60"),
        op("mul", "phi4.h18.gmu.out_proj.tmp_62", X="g", Y="wo"),
        op("matmul", "matmul_0.tmp_0", X="hf", Y="phi4.tok_emb")]
    registry = {'pt_selective_scan_dispatch_total{path="pallas"}': 0.0,
                "pt_selective_scan_bytes": 2 * 737e6,
                "pt_selective_scan_saved_state_bytes": 2 * 20971520.0,
                "pt_diff_attention_launches_total": 12.0,
                "pt_executor_donated_bytes": 8.37e9}
    return {"steps": 2, "trace": {"ops": ops}, "registry": registry,
            "device": {"kind": "TPU v5 lite"}, "config": _phi4_config(),
            "cell": dict(PHI4_CELL), "program_ops": program_ops}


def test_phi4_readers_find_their_rows_by_op_type_name_and_inner_scope():
    run = _phi4_run_record()
    mixers = _load("layer_metrics", "ssm1.device_ms.py")
    # every leaf row under a mixer's scope, forward and backward; not the
    # loop, not the attention layer's, the unit's or the head's
    assert mixers.compute(run) == pytest.approx(36.0 / 2)
    info = mixers.info(run)
    assert info["mixers"] == 2
    assert info["by_inner_scope_ms"] == pytest.approx(
        {"in_proj": 3.0, "conv": 0.5, "dt_bc": 0.25, "scan": 13.15,
         "gate": 0.1, "out_proj": 1.0})
    assert info["by_pass_ms"] == pytest.approx(
        {"jvp": 7.85, "transpose": 10.15})
    scan = _load("layer_metrics", "ssm1.scan_ms.py")
    assert scan.compute(run) == pytest.approx(26.3 / 2)
    info = scan.info(run)
    assert info["run_by"] == "kernels"
    assert info["kernels_ms"] == pytest.approx(13.0)
    assert info["bytes_per_step"] == 2 * 737e6
    assert info["saved_state_bytes"] == 2 * 20971520.0
    assert list(info["dispatch"]) == [
        'pt_selective_scan_dispatch_total{path="pallas"}']
    # the scans' share of their roofline: the operands and results of two
    # ops at 819 GB/s over the 13.15 ms a step under `scan`; memory's bound
    roof = _load("layer_metrics", "kernel.selective_scan_roofline.py")
    need = _load("kernels", "selective_scan.py")
    ops, bytes_ = need.flops_and_bytes(run["config"], run["cell"])
    assert roof.compute(run) == pytest.approx(
        100 * bytes_ / 819e9 / 13.15e-3, rel=1e-3)
    assert roof.compute(run) < 100
    assert roof.info(run)["bound"] == "memory"
    assert roof.info(run)["run_by"] == "kernels"
    # XLA's plain form: the same rows by their scope, whoever runs them
    for r in run["trace"]["ops"]:
        if r["scope"] in PHI4_MIXER:
            r["target"] = None
    assert scan.info(run)["run_by"] == "xla"
    assert roof.compute(run) is not None
    run = _phi4_run_record()
    unit = _load("layer_metrics", "gmu.device_ms.py")
    assert unit.units(run["program_ops"]) == ["phi4.h18.gmu."]
    assert unit.compute(run) == pytest.approx(2.2 / 2)     # not the MLP's gate
    assert unit.info(run)["by_scope_ms"] == pytest.approx(
        {"gate_proj": 0.5, "gate": 0.1, "out_proj": 0.5})
    attn = _load("layer_metrics", "diffattn.device_ms.py")
    assert attn.layers(run["program_ops"]) == ["phi4.h1.attn."]
    assert attn.compute(run) == pytest.approx(30.5 / 2)
    info = attn.info(run)
    assert info["by_scope_ms"] == pytest.approx(
        {"qkv": 1.05, "kernels": 12.7, "combine": 1.0, "out_proj": 0.5})
    assert info["kernels_ms"] == pytest.approx(12.5)
    assert info["launches_per_step"] == 12.0
    assert info["by_layer_ms"] == pytest.approx({"phi4.h1.attn.": 15.25})
    combine = _load("layer_metrics", "diffattn.combine_ms.py")
    assert combine.compute(run) == pytest.approx(1.0)
    assert combine.info(run)["by_pass_ms"] == pytest.approx(
        {"jvp": 0.3, "transpose": 0.7})
    flash = _load("layer_metrics", "phi4.flash_roofline.py")
    need = _load("kernels", "phi4_flash_attention.py")
    flops, bytes_ = need.flops_and_bytes(run["config"], run["cell"])
    assert flash.compute(run) == pytest.approx(
        100 * flops / 197e12 / 12.5e-3, rel=1e-3)
    assert flash.info(run)["bound"] == "compute"
    # a Program without the ops (every other configuration, the parent of the
    # PR that added them), or no trace: nothing, not raised
    for empty in (dict(run, trace=None), dict(run, program_ops=None),
                  dict(run, program_ops=_glm_run_record()["program_ops"]),
                  dict(run, program_ops=_lfm2_run_record()["program_ops"])):
        for reader in (mixers, scan, roof, unit, attn, combine):
            assert reader.compute(empty) is None
    assert flash.compute(dict(run, trace=None)) is None


PHI4_WRAPPERS = {"phi4.head_device_ms": "head.device_ms",
                 "phi4.feed_produce_ms_per_step": "feed.produce_ms_per_step",
                 "phi4.opt_device_ms": "opt.device_ms",
                 "phi4.donated_gib": "step.donated_gib"}


@pytest.mark.parametrize("name", sorted(PHI4_WRAPPERS))
def test_a_phi4_wrapper_returns_what_the_reader_it_wraps_returns(name):
    run = _phi4_run_record()
    run["program_ops"] += [
        {"type": "adam", "scope": "adam.phi4.tok_emb",
         "inputs": {"Param": ["phi4.tok_emb"]},
         "outputs": {"ParamOut": ["phi4.tok_emb"]}},
        {"type": "softmax_with_cross_entropy",
         "scope": "softmax_with_cross_entropy.s",
         "inputs": {"Logits": ["matmul_0.tmp_0"], "Label": ["y"]},
         "outputs": {"Softmax": ["s"], "Loss": ["c"]}}]
    run["trace"]["ops"].append(_row("adam.phi4.tok_emb", 2_000_000))
    run["timers_s"] = {"prefetch.read": 0.004, "prefetch.batch": 0.002}
    wrapper = _load("layer_metrics", name + ".py")
    wrapped = _load("layer_metrics", PHI4_WRAPPERS[name] + ".py")
    assert wrapper.WRAPS == PHI4_WRAPPERS[name]
    got, want = wrapper.compute(run), wrapped.compute(run)
    assert got is not None and got == want
    empty = dict(run, trace=None, registry={}, timers_s={})
    assert wrapper.compute(empty) is None and wrapped.compute(empty) is None
    # the tied head: the walk back from the cost ends in the `matmul` and
    # takes the table's Adam update with it
    expected = {"phi4.head_device_ms": 3.5, "phi4.opt_device_ms": 1.0}
    if name in expected:
        assert got == pytest.approx(expected[name])


def test_the_manifest_lists_the_phi4_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = PHI4 + ".train-log10"
    assert manifest["workloads"][8]["name"] == cell      # appended in PR 57
    entry = manifest["workloads"][8]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        PHI4, "train-log10", 1)
    assert sum(w["chips"] for w in manifest["workloads"][:9]) == 9   # no 4-chip
    config = manifest["configs"][7]
    assert config["name"] == PHI4 and config["source"] == _phi4_config()["source"]
    assert config["reduced"] == _phi4_config()["reduced"] == [
        "num_hidden_layers", "vocab_size"]
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [cell]]
    assert mine == ["ssm1.device_ms", "ssm1.scan_ms",
                    "kernel.selective_scan_roofline", "gmu.device_ms",
                    "diffattn.device_ms", "diffattn.combine_ms",
                    "phi4.flash_roofline", "phi4.head_device_ms",
                    "phi4.opt_device_ms", "phi4.donated_gib",
                    "phi4.feed_produce_ms_per_step"]
    names = [m["name"] for m in manifest["per_layer"]]
    end = names.index(mine[-1]) + 1
    assert names[end - len(mine):end] == mine and names.index(mine[0]) == 98
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert {by_name[n]["layer"] for n in mine[:2] + mine[3:4]} == {
        "Mamba-1 mixers"}
    assert {by_name[n]["layer"] for n in mine[4:6]} == {
        "Differential attention"}
    assert by_name["phi4.donated_gib"]["moves"] == "peak_hbm_gib"
    assert {by_name[n]["moves"] for n in mine} == {"items_s", "peak_hbm_gib"}
    assert {by_name[n]["unit"] for n in mine if "roofline" in n} == {"%"}
    for name in mine:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
    # no reader that was there lists the new cell: their entries are untouched
    assert not [m["name"] for m in manifest["per_layer"][:98]
                if cell in m.get("workloads", ())]
    tail = next(m for m in manifest["end_to_end"] if m["name"] == "step_ms_p90")
    assert cell not in tail["workloads"]
    assert manifest["run_seconds"] == 36
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seqlen"], traffic["sync_every"],
            traffic["warmup_steps"], traffic["trace_seconds"],
            traffic["mesh"]) == (1, 8192, 10, 20, 4, None)
    for why in (entry["why"], config["why"]):
        assert len(why) <= 200 and "\n" not in why


def test_the_benchmarks_phi4_reference_is_the_trees_bit_for_bit():
    """`chipbench/configs/phi-4-mini-flash-reasoning/reference.py` is a copy
    of `tests/phi4flash_reference.py`, text for text, and gives the same cost
    and gradients to the bit on the CPU: the two cannot drift apart unseen."""
    import phi4flash_reference as tree

    copy = _load("configs", PHI4, "reference.py")
    assert open(copy.__file__).read() == open(tree.__file__).read()
    cfg = dict(_phi4_config(), **_phi4_config()["rehearsal"])
    d, V, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    c, N = cfg["mamba_expand"] * d, cfg["mamba_d_state"]
    R, K = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    D = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * D
    tail = [(D,)] * 4 + [(2 * D,), (d, d), (d,)]
    mixing = {"mamba": [(d, 2 * c), (K, c), (c,), (c, R + 2 * N), (R, c),
                        (c,), (c, N), (c,), (c, d)],
              "gmu": [(d, c), (c, d)],
              "window": [(d, d + 2 * kv), (d + 2 * kv,)] + tail,
              "cross": [(d, d), (d,)] + tail}
    mixing["full"] = mixing["window"]
    shapes = [(V, d)] + [s for _, kind in tree.held_layers(cfg)
                         for s in [(d,), (d,)] + mixing[kind]
                         + [(d,), (d,), (d, 2 * f), (f, d)]] + [(d,), (d,)]
    rng = np.random.RandomState(0)
    params = [(rng.randn(*s) * 0.2).astype(np.float32) for s in shapes]
    toks = rng.randint(0, V, (2, 41))
    feed = {"toks": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:, None].astype(np.int32)}
    assert copy.prepare(feed) is feed
    (c1, g1), (c2, g2) = (m.loss_and_grads(cfg, params, feed)
                          for m in (tree, copy))
    assert float(c1) == float(c2) and np.isfinite(float(c1))
    assert len(g1) == len(g2) == len(params) == 86
    for a, b in zip(g1, g2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(np.abs(np.asarray(a)).max()) > 0


def test_the_phi4_cell_rehearses_on_the_cpu(tmp_path):
    """`run.py --rehearse-cpu` of the new cell: the harness finds the
    configuration's files by name, the first step agrees with the plain
    reference at the rehearsal's tolerances (the tied table's gradient among
    them), the scans' and the pair arithmetic's gauges reach the run record,
    every new metric file returns a number or nothing on the rehearsal's run,
    and every metric's name carries the rehearsal's prefix."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         PHI4 + ".train-log10", "--rehearse-cpu", "--trace", "1",
         "--seed", "2147486157"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["rehearsal"] and not result["failed"]
    names = set(result["metrics"])
    assert all(n.startswith("REHEARSAL_ON_CPU.") for n in names)
    assert {"REHEARSAL_ON_CPU.phi4.donated_gib",
            "REHEARSAL_ON_CPU.phi4.feed_produce_ms_per_step",
            "REHEARSAL_ON_CPU.attn.masked_pair_share",
            "REHEARSAL_ON_CPU.loop.dispatch_per_step"} <= names
    # XLA:CPU has no device plane: the trace's readers return nothing
    assert not {"REHEARSAL_ON_CPU.ssm1.device_ms",
                "REHEARSAL_ON_CPU.diffattn.device_ms",
                "REHEARSAL_ON_CPU.kernel.selective_scan_roofline"} & names
    assert "choice_counts_off_program" not in result["compared"]   # dense
    info = next(json.loads(line.split("info ", 1)[1])
                for line in out.stdout.splitlines() if "] info {" in line)
    assert info["cell"] == PHI4 + ".train-log10" and info["rehearsal"]
    assert info["gradient_error_worst"][1] < 0.2
