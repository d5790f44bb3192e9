#!/usr/bin/env python3
"""The toy of this directory through `drivers/train.py:run` on the machine it
is started on, at the sizes of `config.json` and `cell.json` (rows 8192, k
2048, two choosing layers): what `run.py` does for a cell of BENCHMARK.json,
for a Program that is none. Not part of the benchmark's command.

    chiprun -- python3 chipbench/tests/kept_toy/on_chip.py --seeds 1,2,3 \
        [--routed] [--fault k_minus_1|future_key|negated|chosen_not_used|other_input] \
        [--seconds 8]
    python3 chipbench/tests/kept_toy/on_chip.py --rehearse-cpu

One line a seed: `correct`, every number compared beside its limit, each
layer's numbers of the kept sets, `after_window_s`, and the fullest chip's
books at the window's close (the program's) and once the yardstick has run
(a process's peak never falls, so with several seeds the first line's is the
one to read). Without a TPU and without `--rehearse-cpu` it exits non-zero.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="7")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--routed", action="store_true")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    import run as run_py

    cell = run_py.load_json(os.path.join(HERE, "cell.json"))
    config = run_py.load_json(os.path.join(HERE, "config.json"))
    config["routed"] = args.routed
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        cell.update(cell["rehearsal"])
        config.update(config["rehearsal"])
    import jax

    import paddle_tpu as pt
    from paddle_tpu import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    if devs[0].platform != ("cpu" if args.rehearse_cpu else "tpu"):
        print(f"kept_toy: needs a TPU (or --rehearse-cpu); JAX reports "
              f"{devs[0].platform}", file=sys.stderr)
        return 3
    train = run_py.load_module(os.path.join(BENCH, "drivers", "train.py"))
    model = run_py.load_module(os.path.join(HERE, "model.py"))
    sound = dict(config)
    config = dict(config, fault=args.fault)

    def memory_stats():
        return [d.memory_stats() or {} for d in devs[:1]]

    class Model:       # the program is built with the fault, the reference
        __file__ = model.__file__      # is handed the configuration without

        @staticmethod
        def get_model(_, cell, seed):
            return model.get_model(config, cell, seed)

    for seed in (int(s) for s in args.seeds.split(",")):
        pt.reset()
        t_start = time.time()
        ctx = run_py.Ctx(
            name="kept_toy", cell=cell, config=sound, seed=seed,
            seconds=min(args.seconds, 3.0) if args.rehearse_cpu
            else args.seconds, trace=False, rehearsal=args.rehearse_cpu,
            t_start=t_start, t_chip=t_start, clock=run_py.CompileClock(),
            memory_stats=memory_stats,
            memory_peaks=lambda: run_py.memory_peaks(memory_stats()),
            yardstick_cache_dir=os.path.join(
                os.path.dirname(BENCH), ".chipbench_cache"),
            load_module=run_py.load_module, model=Model)
        run = train.run(ctx)
        run_py.book_memory(run)
        print(json.dumps({
            "seed": seed, "routed": args.routed, "fault": args.fault,
            "correct": train.correct(run) == [],
            "not_correct": train.correct(run), "compared": train.compared(run),
            "kept_by_layer": run["kept"], "choice_by_layer": run["choice"],
            "gradient_errors_largest": train.info(run)[
                "gradient_errors_largest"],
            "steps": run["steps"], "items_s": run["items"] / run["window_s"],
            "after_window_s": run["after_window_s"],
            "books_at_close": run["memory_peaks"],
            "books_after_the_yardstick": run_py.memory_peaks(memory_stats()),
            "device": devs[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
