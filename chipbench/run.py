#!/usr/bin/env python3
"""chipbench: one cell of BENCHMARK.json, once, in one process that owns
the cell's chips.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 chipbench/run.py --workload <cell> --rehearse-cpu     (no chip; tiny sizes)

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` `breakdown`,
and last `compared`: every number `correct` held to a limit, beside it (the
last lines of standard error say the same). `--trace 0` prints the cell's end-to-end metrics, `--trace 1`
its per-layer metrics (taken over a short traced window of its own).
Without a TPU holding the cell's chips the script exits non-zero and
prints no result; `--rehearse-cpu` runs the same control flow on XLA:CPU
and prefixes every metric's name, so no device metric can come from it.

Driven by data (see README.md): the cell is `workloads/<cell>.json`, its
configuration `configs/<config>/`, its driver `drivers/<driver>.py`, and
every metric a reader of its own under `end_to_end/` or `layer_metrics/`,
all found by the names in BENCHMARK.json.
"""

from __future__ import annotations

import time

_T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REHEARSAL_PREFIX = "REHEARSAL_ON_CPU."


def log(msg: str) -> None:
    print(f"[chipbench +{time.time() - _T_START:6.1f}s] {msg}", flush=True)


def load_module(path: str):
    name = "chipbench_" + os.path.relpath(path, HERE).replace(os.sep, "_")
    name = "".join(c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class CompileClock:
    """Programs JAX built (compiled, or read back from the persistent
    cache) and the seconds that took, plus cache hits and misses: copied
    from chip_smoke.py's `_CompileClock`. `built` inside the measured
    window must stay 0."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.built = self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.built += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Ctx:
    """What a driver gets."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.trace_dir = None

    def start_trace(self):
        import jax

        self.trace_dir = os.path.join(ROOT, ".chipbench_trace", self.name)
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # no per-call Python events
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def stop_trace(self):
        import jax

        jax.profiler.stop_trace()


def memory_peaks(stats) -> dict:
    """The fullest chip's peaks so far, in bytes, by the runtime's two books
    and as `peak_hbm_gib` counts them (`bytes`). The v5e runtime keeps
    `bytes_in_use` (live arrays: parameters, optimizer state, feeds, a
    step's outputs from its dispatch on) apart from `bytes_reserved` (the
    running program's scratch), and reports the peak of each, not of their
    sum. The sum of the two peaks is therefore at or above the true peak; in
    a training loop it is reached, because every step holds its outputs and
    its scratch together (gpt2-small: 4.15 + 6.86 = 11.01 GB against 10.85
    GB by the compiler's own count, PR 23). `in_use` alone (4.15 GB) would
    leave out what decides whether a batch fits. `stats` is each chip's
    `memory_stats()`; empty where the backend reports nothing (XLA:CPU)."""
    mem = [m for m in stats if m.get("peak_bytes_in_use")]
    if not mem:
        return {}
    return {"in_use": max(m["peak_bytes_in_use"] for m in mem),
            "reserved": max(m.get("peak_bytes_reserved", 0) for m in mem),
            "bytes": max(m["peak_bytes_in_use"] + m.get("peak_bytes_reserved", 0)
                         for m in mem)}


def book_memory(run) -> None:
    """`memory_peaks` and `memory_peak_bytes` of a run record, from the
    `memory_stats` its driver read at the window's close: before the plain
    reference ran, so the peaks are the program's. No later reading of the
    process reaches them (a peak never falls again)."""
    run["memory_peaks"] = memory_peaks(run["memory_stats"])
    run["memory_peak_bytes"] = run["memory_peaks"].get("bytes")


def _metric_entries(manifest, section, cell_name):
    return [m for m in manifest[section]
            if cell_name in m.get("workloads", [cell_name])]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()

    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        print(f"chipbench: no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    cell = load_json(os.path.join(HERE, "workloads", entry["name"] + ".json"))
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == entry["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            print(f"chipbench: workloads/{entry['name']}.json and "
                  f"BENCHMARK.json disagree on {key}", file=sys.stderr)
            return 2
    # who counts this configuration's FLOPs is settled here, in the first
    # second, and not after the window: a family that neither flops.py nor
    # the configuration's own flops.py counts ends the run by name
    config_dir = os.path.dirname(os.path.join(ROOT, cfg_entry["file"]))
    flops = load_module(os.path.join(HERE, "flops.py"))
    flops_per_item = flops.family_arithmetic(config, config_dir)
    seconds = args.seconds if args.seconds is not None \
        else float(manifest["run_seconds"])
    if args.rehearse_cpu:
        config.update(config.get("rehearsal", {}))
        cell.update(cell.get("rehearsal", {}))
        seconds = min(seconds, 3.0)
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell["chips"] > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={cell['chips']}")
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print("chipbench: the system under test (paddle_tpu/) is not in "
              "this checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)

    import jax

    from paddle_tpu import compile_cache

    # the program's own rule: JAX_COMPILATION_CACHE_DIR if set, else the
    # fixed <checkout>/.jax_cache. Every program is kept, however fast it
    # compiled, so that a warm run builds nothing
    cache_dir = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    t_chip = time.time()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if args.rehearse_cpu:
        if device["platform"] != "cpu":
            print(f"chipbench: a rehearsal stays on the CPU: {device}",
                  file=sys.stderr)
            return 3
        log(f"REHEARSAL ON THE CPU at tiny sizes: {device}")
    elif device["platform"] != "tpu" or len(devs) < cell["chips"]:
        print(f"chipbench: cell {entry['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX reports {device}", file=sys.stderr)
        return 3
    # the yardstick's own programs (a driver builds them once its window has
    # closed) are kept apart, at a fixed path in the checkout, so that they
    # take no room from the program's
    yardstick_cache_dir = os.path.join(ROOT, ".chipbench_cache")
    log(f"cell {entry['name']} seed {args.seed} seconds {seconds} trace "
        f"{args.trace} on {device}; compile cache {cache_dir}, the "
        f"yardstick's {yardstick_cache_dir}")

    def memory_stats():
        return [d.memory_stats() or {} for d in devs[:cell["chips"]]]

    driver = load_module(os.path.join(HERE, "drivers", config["driver"] + ".py"))
    ctx = Ctx(name=entry["name"], cell=cell, config=config, seed=args.seed,
              seconds=seconds, trace=bool(args.trace),
              rehearsal=args.rehearse_cpu, t_start=_T_START, t_chip=t_chip,
              clock=CompileClock(), memory_stats=memory_stats,
              yardstick_cache_dir=yardstick_cache_dir,
              memory_peaks=lambda: memory_peaks(memory_stats()),
              load_module=load_module,
              model=load_module(os.path.join(config_dir, "model.py")))
    run = driver.run(ctx)
    if args.rehearse_cpu:
        run["min_intervals"] = 5   # three seconds at tiny sizes: control flow only
    run.update(cell=cell, config=config, device=device,
               setup_s=run["t0_wall"] - _T_START,
               compile_s=ctx.clock.seconds, cache_hits=ctx.clock.hits,
               cache_misses=ctx.clock.misses)
    book_memory(run)

    if ctx.trace_dir:
        xplane = load_module(os.path.join(HERE, "xplane.py"))
        try:
            run["trace"] = xplane.reduce_dir(ctx.trace_dir, chips=cell["chips"])
        except ValueError as e:
            if not args.rehearse_cpu:   # XLA:CPU has no device plane
                raise
            log(f"rehearsal: no device trace to reduce ({e})")
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)

    section = "per_layer" if args.trace else "end_to_end"
    folder = "layer_metrics" if args.trace else "end_to_end"
    metrics, problems, notes = {}, driver.correct(run), {}
    for m in _metric_entries(manifest, section, entry["name"]):
        reader = load_module(os.path.join(HERE, folder, m["name"] + ".py"))
        try:
            value = reader.compute(run)
        except Exception as e:  # noqa: BLE001 — a reader that cannot answer fails the run
            print(f"chipbench: metric {m['name']}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return 4
        if value is None:
            continue   # nothing to read in this cell: left out of the line
        if hasattr(reader, "info"):   # what the reader adds to the info line
            notes[m["name"]] = reader.info(run)
        key = (REHEARSAL_PREFIX if args.rehearse_cpu else "") + m["name"]
        metrics[key] = {"value": float(value), "unit": m["unit"]}

    # an earlier line, for PERF.md; the driver reads only the last
    info = {"cell": entry["name"], "setup_s": run["setup_s"],
            "compile_or_cache_read_s": run["compile_s"],   # the whole process
            "cache_hits": run["cache_hits"], "cache_misses": run["cache_misses"],
            **driver.info(run), **notes,
            "memory_stats_fullest_at_close": max(
                run["memory_stats"],
                key=lambda m: m.get("peak_bytes_in_use", 0)
                + m.get("peak_bytes_reserved", 0))}
    if not args.rehearse_cpu:
        per_item = float(flops_per_item(config, cell))
        rate = run["items"] / run["window_s"]
        peak = flops.peak_flops(device["kind"])
        info["model_flops_per_item"] = per_item
        info["mfu_pct" + ("_traced_window" if args.trace else "")] = (
            100.0 * rate * per_item / (peak * cell["chips"]))
    else:
        info["rehearsal"] = True
    info["wall_s"] = time.time() - _T_START
    log("info " + json.dumps(info))
    for p in problems:
        log("NOT CORRECT: " + p)

    dev = dict(device, memory_peak_bytes=run["memory_peak_bytes"])
    out = {"correct": not problems, "attempted": run["attempted"],
           "failed": run["failed"], "metrics": metrics, "device": dev}
    if args.trace and run.get("trace"):
        dev["busy_s"] = run["trace"]["busy_s_mean"]
        dev["window_s"] = run["trace"]["window_s"]
        out["breakdown"] = run["trace"]["breakdown"]
    if args.rehearse_cpu:
        out["rehearsal"] = True
    # every number compared, beside its limit: the run's last lines on
    # standard error, and the last key of the result
    out["compared"] = driver.compared(run)
    for name, (value, limit) in out["compared"].items():
        print(f"chipbench: compared {name} {value} limit {limit}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
