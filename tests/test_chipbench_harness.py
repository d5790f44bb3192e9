"""The benchmark's own checks, in the tier-1 run: `chipbench/selftest.py`
(no backend), the cases of `chipbench/tests/test_harness.py` (JAX on the
CPU), and what PR 27 added for the olmoe-1b-7b configuration: its FLOPs
arithmetic, its kernel's operations and bytes, its readers on hand-made run
records, its copy of the plain reference against the tree's, and one CPU
rehearsal of its cell through `chipbench/run.py`.
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
globals().update({name: fn for name, fn in
                  vars(_load("tests", "test_harness.py")).items()
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
    assert wrapped == {"nemotron.attn_device_ms"}


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
                    "nemotron.feed_produce_ms_per_step"]
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
