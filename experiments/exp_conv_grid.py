"""Reproduce the reference's flagship conv-net benchmark tables cell by
cell on the TPU (the image-side counterpart of benchmarks/lstm_grid.json).

Reference cells: K40m ms/batch for AlexNet bs64-512, GoogleNet bs64-256,
SmallNet bs64-512 (benchmark/README.md:33-59, PaddlePaddle rows) and the
CPU MKL-DNN VGG-19 train img/s (IntelOptimizedPaddle.md:30-36) + the
VGG-19 bs16 inference row (IntelOptimizedPaddle.md:66-73, 96.75 img/s).

Each cell runs in its own subprocess (fresh HBM) through bench.py's own
timing loop; records land in benchmarks/conv_grid.json with the
calibration probes. Run on TPU: python experiments/exp_conv_grid.py
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CELLS = [
    ("alexnet", 64, {}), ("alexnet", 128, {}), ("alexnet", 256, {}),
    ("alexnet", 512, {}),
    ("googlenet", 64, {}), ("googlenet", 128, {}), ("googlenet", 256, {}),
    ("smallnet", 64, {"BENCH_STEPS": "200"}),
    ("smallnet", 128, {"BENCH_STEPS": "200"}),
    ("smallnet", 256, {"BENCH_STEPS": "200"}),
    ("smallnet", 512, {"BENCH_STEPS": "100"}),
    ("vgg", 64, {}), ("vgg", 128, {}),
    ("vgg", 256, {"BENCH_REMAT": "dots"}),
    ("vgg_infer", 16, {"BENCH_MODEL": "vgg", "BENCH_INFER": "1",
                       "BENCH_STEPS": "60"}),
]


def run_cell(model, batch, extra):
    env = dict(os.environ)
    env.update({"BENCH_MODEL": model, "BENCH_BATCH": str(batch),
                "BENCH_STEPS": "40"})
    env.update(extra)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=2400)
    if r.returncode != 0:
        return {"error": (r.stderr or r.stdout)[-400:]}
    return json.loads(r.stdout.strip().splitlines()[-1])


def main():
    results = []
    for model, batch, extra in CELLS:
        rec = run_cell(model, batch, extra)
        rec.update({"cell_model": model, "cell_batch": batch})
        if "value" in rec and rec.get("unit") == "images/sec":
            rec["ms_per_batch"] = round(batch / rec["value"] * 1000.0, 3)
        results.append(rec)
        print(json.dumps(rec), flush=True)
    out = {
        "note": ("reference cells: K40m ms/batch benchmark/README.md:33-59"
                 " (PaddlePaddle rows); VGG-19 train img/s + bs16 infer "
                 "IntelOptimizedPaddle.md:30-36,66-73. vs_baseline = our "
                 "img/s over the reference's."),
        "device": "TPU v5e (1 chip), bf16 AMP",
        "cells": results,
    }
    with open(os.path.join(REPO, "benchmarks", "conv_grid.json"), "w") as f:
        json.dump(out, f, indent=1)
    print("written benchmarks/conv_grid.json")


if __name__ == "__main__":
    main()
