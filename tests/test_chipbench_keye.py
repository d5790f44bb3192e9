"""The benchmark's side of the Keye-VL configuration (PR 60), in a file of its
own (`tests/test_chipbench_harness.py` is the tier-1 run's longest): the
manifest's entries, `config.json` against the published sizes, the twin
reference, `flops.py` and the kernel counts on hand-made cells, the new readers
on a hand-made run record, and the cell's rehearsal on the CPU, traced and
untraced.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
sys.path.insert(0, ROOT)
NAME = "keye-vl-2.0-30b-a3b"
CELL = NAME + ".train-log10"
FOLDER = os.path.join(BENCH, "configs", NAME)

# the catalog row's `config` (guide `model-configs`, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
NEW_READERS = ("dsa.device_ms", "dsa.indexer_ms", "dsa.select_ms",
               "dsa.attend_ms", "rope.tables_ms", "dsa.kept_pair_share",
               "kernel.sparse_attn_roofline")
WRAPPERS = {
    "keye.moe_device_ms": "nemotron.moe_device_ms",
    "keye.moe_dispatch_ms": "nemotron.moe_dispatch_ms",
    "keye.held_pair_share": "moe.held_pair_share",
    "keye.load_max_over_mean": "nemotron.load_max_over_mean",
    "keye.bounded_step_share": "moe.bounded_step_share",
    "keye.head_device_ms": "head.device_ms",
    "keye.opt_device_ms": "opt.device_ms",
    "keye.donated_gib": "step.donated_gib",
    "keye.feed_produce_ms_per_step": "feed.produce_ms_per_step"}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _reader(name):
    from chipbench.readers import load_reader

    return load_reader(name)


CONFIG = _json(FOLDER, "config.json")
CELL_FILE = _json(BENCH, "workloads", CELL + ".json")


def test_the_manifest_lists_the_cell_and_its_metrics():
    manifest = _json(ROOT, "BENCHMARK.json")
    assert manifest["configs"][-1]["name"] == NAME
    assert manifest["configs"][-1]["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"]
    assert manifest["configs"][-1]["source"] == CONFIG["source"]
    entry = manifest["workloads"][-1]
    assert (entry["name"], entry["config"], entry["traffic"],
            entry["chips"]) == (CELL, NAME, "train-log10", 1)
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == list(NEW_READERS) + [
        "keye.moe_device_ms", "keye.moe_dispatch_ms", "keye.gmm_roofline",
        "keye.held_pair_share", "keye.load_max_over_mean",
        "keye.bounded_step_share", "keye.head_device_ms",
        "keye.opt_device_ms", "keye.donated_gib",
        "keye.feed_produce_ms_per_step"]
    assert manifest["per_layer"][-len(mine):] == mine      # appended, last
    assert len(mine) == 17 and len(manifest["per_layer"]) == 126 <= 128
    assert {m["layer"] for m in mine[:6]} == {"Sparse attention"}
    # nothing that was there names the new cell, and every cell-less metric
    # is one the cell reports
    assert not [m["name"] for m in manifest["per_layer"]
                if CELL in m.get("workloads", ()) and m not in mine]
    for m in mine:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
    assert (CELL_FILE["batch"], CELL_FILE["seqlen"], CELL_FILE["sync_every"],
            CELL_FILE["warmup_steps"], CELL_FILE["image_spans"],
            CELL_FILE["image_grid"]) == (1, 16384, 10, 20, 4, 32)
    assert (CELL_FILE["rehearsal"]["seqlen"],
            CONFIG["rehearsal"]["sa_config"]["topk"],
            CONFIG["rehearsal"]["num_hidden_layers"]) == (96, 16, 2)


def test_config_keeps_the_published_sizes():
    """Every key of the catalog row under its own name; the four that differ
    are `reduced` and stated under `published`; no width differs."""
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["num_local_experts"], CONFIG["vocab_size"]) == (
        4, 16, 16, 151936 // 8)
    assert CONFIG["router_experts"] == 128 and CONFIG["held_experts"] == [0, 16]
    assert CONFIG["layer_ids"] == [0, 1, 2, 3]
    assert "one of the 8" in CONFIG["deployment"]
    assert {"qk_norm", "rotary", "positions", "indexer", "selection",
            "experts", "optimizer", "compute_dtype", "initialisers"} <= set(
        CONFIG["assumed"])
    assert any("tower" in d for d in CONFIG["departures"])
    assert any("KL" in d for d in CONFIG["departures"])


def test_the_benchmarks_reference_is_the_trees_bit_for_bit():
    with open(os.path.join(FOLDER, "reference.py"), "rb") as a, \
            open(os.path.join(ROOT, "tests", "keye_vl_reference.py"),
                 "rb") as b:
        assert a.read() == b.read()
    ref = _load(os.path.join(FOLDER, "reference.py"), "keye_bench_reference")
    for entry in ("prepare", "loss_and_grads", "chosen", "kept", "scores",
                  "loss_grads_routers_and_keepers"):
        assert callable(getattr(ref, entry))


def test_flops_per_token_counts_the_kept_pairs_and_the_indexer_once():
    flops = _load(os.path.join(FOLDER, "flops.py"), "keye_bench_flops")
    assert flops.kept_per_row(16384, 2048) * 16384 == 31_458_304
    assert flops.kept_per_row(2048, 2048) == 2049 / 2      # every causal key
    layer = (37_748_736 + 4 * 32 * 128 * 31_458_304 / 16384 + 524_288
             + 9_437_184)
    assert flops.trained_forward_flops_per_token(CONFIG, 16384) == (
        4 * layer + 77_791_232) == 394_465_280
    indexer = 4 * (2 * 2_260_992 + 2048 * 16385 / 2)
    assert flops.indexer_forward_flops_per_token(CONFIG, 16384) == indexer
    assert flops.train_flops_per_item(CONFIG, {"seqlen": 16384}) == (
        3 * 394_465_280 + indexer) == 1_268_596_736


def test_sparse_attention_kernel_counts_on_a_hand_made_cell():
    from chipbench.kernels import flash_attention, sparse_attention

    assert sparse_attention.kept_pairs(16384, 2048) == 31_458_304
    cfg = dict(num_hidden_layers=3, num_attention_heads=4,
               num_key_value_heads=2, head_dim=8, sa_config={"topk": 4})
    cell = {"batch": 2, "seqlen": 10}
    pairs = 4 * 5 // 2 + 6 * 4                    # 10 + 24 kept of 55 causal
    flops, bytes_ = sparse_attention.flops_and_bytes(cfg, cell)
    assert flops == 3 * 2 * 4 * 6 * 2 * pairs * 8
    q_like, kv_like = 2 * 10 * 4 * 8, 2 * 10 * 2 * 8
    assert bytes_ == 3 * ((6 * q_like + 6 * kv_like) * 2
                          + 2 * 2 * 10 * 128 * 4)
    # every key kept: the dense causal count, the bits aside
    dense = dict(cfg, sa_config={"topk": 10})
    want = flash_attention.flops_and_bytes(dense, cell)
    got = sparse_attention.flops_and_bytes(dense, cell)
    assert got[0] == want[0] and got[1] == want[1] + 3 * 2 * 2 * 10 * 128 * 4
    real = sparse_attention.flops_and_bytes(CONFIG, CELL_FILE)
    assert real[0] == 4 * 32 * 12 * 31_458_304 * 128


def _row(scope, ns, op_name="", target="", transform="", count=1):
    return {"name": "%f", "opcode": "fusion", "shape": "", "target": target,
            "container": False, "count": count, "ns": ns, "op_name": op_name,
            "scope": scope, "transform": transform}


def _program_op(kind, first, slot="Out"):
    return {"type": kind, "scope": f"{kind}.{first}", "inputs": {},
            "outputs": {slot: [first]}}


def _run_record():
    layer = "keye.h0.attn."
    ops = [_program_op("mul", layer + "qkv.tmp_0"),
           _program_op("rms_norm", layer + "q_norm.tmp_0", "Y"),
           _program_op("rotary_embedding", layer + "q_rope.tmp_0"),
           _program_op("mul", layer + "indexer.tmp_0"),
           {"type": "sparse_keep", "scope": "sparse_keep." + layer
            + "select.tmp_0", "inputs": {},
            "outputs": {"Keep": [layer + "select.tmp_0"],
                        "Chosen": [layer + "select.tmp_1"]}},
           _program_op("sparse_attention", layer + "kernels.tmp_0"),
           _program_op("mul", layer + "out_proj.tmp_0"),
           _program_op("mul", "keye.h0.moe.tmp_0")]
    s = {op["outputs"][next(iter(op["outputs"]))][0]: op["scope"]
         for op in ops}
    keep = s[layer + "select.tmp_0"]
    rows = [
        _row(s[layer + "qkv.tmp_0"], 3e6),
        _row(s[layer + "qkv.tmp_0"], 5e6, transform="transpose(jvp"),
        _row(s[layer + "q_rope.tmp_0"], 1e6, target="tpu_custom_call"),
        _row(s[layer + "q_rope.tmp_0"], 4e5,
             op_name="jit(raw)/rotary_embedding.x/tables/cos"),
        _row(s[layer + "indexer.tmp_0"], 2e6),
        _row(keep, 7e6, op_name=f"jit(raw)/{keep}/while/body/indexer/dot"),
        _row(keep, 9e6, op_name=f"jit(raw)/{keep}/while/body/select/while"),
        _row(s[layer + "kernels.tmp_0"], 40e6, target="tpu_custom_call"),
        _row(s[layer + "kernels.tmp_0"], 80e6, target="tpu_custom_call",
             transform="transpose(jvp"),
        _row(s[layer + "kernels.tmp_0"], 1e6),
        _row(s[layer + "out_proj.tmp_0"], 2e6),
        _row(s["keye.h0.moe.tmp_0"], 50e6)]
    return {"steps": 2, "program_ops": ops, "trace": {"ops": rows},
            "config": CONFIG, "cell": CELL_FILE,
            "device": {"kind": "TPU v5 lite"},
            "registry": {"pt_sparse_attention_kept_pairs": 4 * 31_458_304.0,
                         "pt_sparse_attention_causal_pairs":
                         4 * 16384 * 16385 / 2,
                         "pt_sparse_attention_saved_choice_bytes":
                         4 * 2.0 ** 25}}


def test_the_new_readers_on_a_hand_made_run_record():
    run = _run_record()
    got = {name: _reader(name).compute(run) for name in NEW_READERS}
    assert got["dsa.device_ms"] == pytest.approx(150.4e6 / 1e6 / 2)
    assert got["dsa.indexer_ms"] == pytest.approx((2e6 + 7e6) / 1e6 / 2)
    assert got["dsa.select_ms"] == pytest.approx(9e6 / 1e6 / 2)
    assert got["dsa.attend_ms"] == pytest.approx(121e6 / 1e6 / 2)
    assert got["rope.tables_ms"] == pytest.approx(4e5 / 1e6 / 2)
    assert got["dsa.kept_pair_share"] == pytest.approx(
        31_458_304 / (16384 * 16385 / 2))
    assert 0.23 < got["dsa.kept_pair_share"] < 0.24
    info = _reader("dsa.device_ms").info(run)
    assert info["kernels_ms"] == pytest.approx(121e6 / 1e6 / 2)
    assert set(info["by_scope_ms"]) == {"qkv", "q_rope", "indexer", "select",
                                        "kernels", "out_proj"}
    # the roofline: the kept pairs' FLOPs over the kernels' 60 ms a step
    from chipbench import roofline
    from chipbench.kernels import sparse_attention

    flops, bytes_ = sparse_attention.flops_and_bytes(CONFIG, CELL_FILE)
    want, bound = roofline.share(flops, bytes_, 0.060, "TPU v5 lite")
    assert got["kernel.sparse_attn_roofline"] == pytest.approx(want)
    assert 0 < want < 100 and bound == "compute"
    assert _reader("kernel.sparse_attn_roofline").info(run)[
        "kernels_per_step"] == 1.0


def test_the_new_readers_find_nothing_on_a_parent():
    """A program without the op (the parent of this PR, which the driver runs
    with these files laid over it): every new reader returns None and does
    not raise."""
    run = _run_record()
    run["program_ops"] = [op for op in run["program_ops"]
                          if not op["type"].startswith("sparse_")]
    run["registry"] = {}
    for name in NEW_READERS:
        assert _reader(name).compute(run) is None, name
    bare = {"steps": 1, "trace": None, "registry": None, "config": CONFIG,
            "cell": CELL_FILE, "device": {"kind": "TPU v5 lite"}}
    for name in NEW_READERS + ("keye.gmm_roofline",):
        assert _reader(name).compute(bare) is None, name


@pytest.mark.parametrize("name", sorted(WRAPPERS))
def test_a_wrapper_returns_what_the_reader_it_wraps_returns(name):
    wrapper, wrapped = _reader(name), _reader(WRAPPERS[name])
    assert wrapper.WRAPS == WRAPPERS[name]
    run = dict(_run_record(), counters={}, spans={})
    try:
        want = wrapped.compute(run)
    except Exception as e:  # noqa: BLE001 - whatever it raises, both raise
        with pytest.raises(type(e)):
            wrapper.compute(run)
    else:
        assert wrapper.compute(run) == want


def test_gmm_roofline_hands_the_shared_count_its_missing_key():
    from chipbench.kernels import glm_grouped_matmul

    flops, bytes_ = glm_grouped_matmul.flops_and_bytes(
        dict(CONFIG, first_k_dense_replace=0), CELL_FILE)
    rows = 4 * 16384 * 8 * 16 / 128               # even routing, four layers
    assert flops == 18 * rows * 2048 * 768
    assert bytes_ == 2 * (9 * 4 * 16 * 2048 * 768
                          + rows * (5 * 2048 + 7 * 768))
    assert "first_k_dense_replace=0" in open(os.path.join(
        BENCH, "layer_metrics", "keye.gmm_roofline.py")).read()


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_rehearses_on_the_cpu(trace, tmp_path):
    """`run.py --rehearse-cpu` of the new cell at T 96, topk 16, two layers:
    `correct`, the kept sets on the rule and none turned beyond a near-tie,
    the registry's metrics in a traced run's line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse-cpu", "--trace", trace], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["compared"]["kept_sets_off_rule"] == [0.0, 0]
    assert line["compared"]["kept_turned_not_near_tie"] == [0.0, 0]
    names = set(line["metrics"])
    assert all(n.startswith("REHEARSAL_ON_CPU.") for n in names)
    if trace == "1":
        assert {"REHEARSAL_ON_CPU.dsa.kept_pair_share",
                "REHEARSAL_ON_CPU.attn.masked_pair_share",
                "REHEARSAL_ON_CPU.keye.held_pair_share"} <= names
    else:
        assert {"REHEARSAL_ON_CPU.items_s",
                "REHEARSAL_ON_CPU.setup_s"} <= names
