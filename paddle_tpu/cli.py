"""Command-line driver: `python -m paddle_tpu <command> ...`.

Reference: the `paddle` shell wrapper (paddle/scripts/submit_local.sh.in:6-7,
177-180 — `paddle train / merge_model / pserver2 ...`) and the trainer
binary's flag-driven main (paddle/trainer/TrainerMain.cpp:32). The
"config is a program" philosophy carries over: the --config argument is a
Python file that builds the model on the default programs and exposes

    def get_model() -> dict:
        return {
            "cost": <loss Variable>,
            "reader": <callable yielding batches>,
            "feed_order": [<data Variables>],        # optional if reader
                                                     # yields feed dicts
            "metrics": {"name": Variable, ...},      # optional
            "num_passes": int,                        # optional default 1
        }

Commands:
  train       --config M.py [--num_passes N] [--save_dir D]
              [--mesh dp2,pp2] [--microbatches M] [--pipeline_stages K]
              [flags...]
              --mesh trains over a device mesh (axes dp/mp/sp/pp). A
              pp axis — or --pipeline_stages K — selects the
              micro-batch pipeline executor (paddle_tpu/pipeline):
              the program is cut into K stages (stage_boundary()
              markers or auto-balanced), each step drives
              --microbatches M slices through the GPipe tick grid
              (default M = 2K; bubble fraction (K-1)/(M+K-1)). A
              dp/mp-only mesh selects the ParallelExecutor.
              notable flags for the pipelined loop (README "Training"):
              --prefetch_to_device N  DevicePrefetcher queue depth
                                      (default 2; 0 disables)
              --sync_every N          host-sync cadence of the async step
                                      loop (default: follow --log_period;
                                      1 = fully synchronous legacy loop;
                                      env PT_FLAGS_SYNC_EVERY)
              --scan_window K         fuse K steps into ONE compiled
                                      lax.scan window: 1 host dispatch
                                      per K steps, syncs at window edges
                                      only (default 0 = per-step loop;
                                      env PT_FLAGS_SCAN_WINDOW; single-
                                      device executors only)
              --log_period N          print cost every N batches (reading
                                      the lazy cost is itself a sync)
              observability (README "Observability"):
              --trace_out PATH        arm span tracing; export a Chrome
                                      trace-event JSON (Perfetto) at exit
                                      (env PT_FLAGS_TRACE)
              --stats_period N        log a runtime-stats line every N
                                      steps (paddle_tpu.stats logger)
              --dump_stats            print the unified metrics registry
                                      + timer table at exit
  merge_model --model_dir D --out O   (MergeModel.cpp parity: checkpoint
                                       params -> single deployable dir)
  serve       --model_dir D [--model name=dir ...] [--host H] [--port P]
              [--max_batch_size N] [--max_wait_ms M] [--max_queue Q]
              [--timeout_ms T] [--seq_len_buckets 64,128,...] [--warmup 0|1]
              [--max_slots S] [--gen_queue Q] [--gen_timeout_ms T]
              [--prefix_cache_mb MB [--prefix_quant int8]]
              [--draft_model D [--draft_k K]]
              [--mesh dp1,mp2] [--drain_s S] [--quant int8]
              [--slo model=interactive|batch ...]
              [--replicas N [--standby K] [--probe_interval_ms P]
               [--autoscale --min_replicas A --max_replicas B
                --cooldown_s C]]
              [--disaggregate --prefill_replicas N --decode_replicas M
               [--handoff_quant int8]]
              batching HTTP inference server over saved inference
              models (paddle_tpu.serving): /predict, /healthz, /metrics
              — generation models additionally serve /generate
              (continuous batching over S decode slots, NDJSON
              streaming with "stream": true).
              --mesh runs the replica sharded over a device mesh (the
              artifact's sharding sidecar places params; README
              "Scale-out serving"); SIGTERM drains in-flight work for
              up to --drain_s seconds before exit.
              --replicas N turns this process into a ROUTER that
              pre-forks N replica serve processes (plus --standby
              warmed spares), join-shortest-queue balances /predict
              and /generate over them (streaming passes through),
              retries shed/503s on another replica, circuit-breaks and
              replaces dead replicas (paddle_tpu.serving.router).
              --slo model=batch marks a model's traffic as the
              sheddable tier: at queue pressure batch requests shed
              strictly before interactive ones ever queue behind them,
              and the router JSQ-scores picks per class
              (paddle_tpu.fleetctl.tenancy; a request may self-demote
              via X-PT-SLO-Class or "slo" in the body).
              --autoscale arms the control loop: warm standbys are
              promoted under sustained queue/occupancy pressure and
              idle replicas drained + retired, between --min_replicas
              and --max_replicas, with --cooldown_s between actions
              (paddle_tpu.fleetctl.autoscaler; watch /admin/fleet)
              --disaggregate splits the fleet into N PREFILL replicas
              (prefix program only) and M DECODE replicas (slot pool):
              /generate runs the prefix on a prefill replica, ships
              the decode boot state as a handoff payload (bit-
              identical admission; --handoff_quant int8 halves the
              bytes) and streams tokens from a decode replica; with
              --autoscale each class scales on its own signal
              (paddle_tpu.serving.disagg)
  fleetctl    rollout --router URL --model_dir D [--model NAME]
              | status --router URL
              control-plane client for a serve --replicas router:
              rollout = zero-downtime version flip (warm new artifact
              in fresh replicas, verify the program fingerprint from
              meta.json on /healthz, atomically flip the router, drain
              the old version); status = router + fleet + autoscaler
              state in one JSON doc (GET /admin/fleet)
              --quant int8 asserts the artifact is a quantized one
              (see `quant` below) and serves its low-precision fast
              path; an fp artifact fails loudly instead of silently
              serving at fp cost
  quant       --model_dir D --out O [--samples N] [--mode int8]
              [--no-check]
              post-training int8 quantization of a saved inference
              artifact (paddle_tpu.quant): calibrates activation
              ranges on N deterministic synthetic samples drawn from
              the artifact's feed specs (default 8), rewrites matmul
              sites to int8 kernels with per-channel weight scales,
              prints the loud mixed-precision report, and saves the
              converted artifact to O (meta.json carries the quant
              block: mode, scales digest, calibration sample count —
              stale-scale artifacts fail at load). --no-check skips
              the fp-vs-quant output-delta check run
  route--replica http://host:port [--replica ...] [--host H]
              [--port P] [--probe_interval_ms P] [--request_timeout_ms T]
              stand-alone router over ALREADY-RUNNING replica servers
              (the cross-host deployment: one route process in front
              of serve processes on other machines)
  tune        --kernel K --shape k=v,k=v [--shape ...]
              [--dtype bf16|f32|int8]
              [--dry-run] [--iters N] [--warmup N] [--mesh dp4]
              | --config M.py [--dry-run ...]
              kernel sweep (paddle_tpu.tune): time every legal config of
              a named kernel family at each shape (or at every tunable
              site of a model config) against the rule's own choice and
              print the ranking. Writes nothing: a kernel's tiles are
              decided by its family's rule in tune/space.py, and what a
              sweep finds is written there. --mesh dp4 takes the
              --config sweep's shapes PER SHARD (what the kernels
              dispatch under a mesh). --dry-run lists candidates without
              timing (works on any backend; timing requires a TPU).
              Kernels: bahdanau (B,S,A,C), flash (Tq,Tk), lstm/gru
              (B,H), quant (M,K,N — int8).
  stats       --url http://host:port | --file exposition.txt [--raw 1]
              scrape (or read) a Prometheus /metrics exposition, parse
              it with the paddle_tpu.obs.promparse grammar, and print a
              per-family summary — the CLI view of the unified metrics
              registry a serving process exposes and a training run
              dumps at exit (--dump_stats)
  flags       print the flag registry
  version     print the version
"""

from __future__ import annotations

import os
import runpy
import sys

from .flags import FLAGS, flags_help, parse_flags


def _load_config(path: str) -> dict:
    ns = runpy.run_path(path)
    if "get_model" not in ns:
        raise SystemExit(f"config {path!r} must define get_model()")
    model = ns["get_model"]()
    if "cost" not in model or "reader" not in model:
        raise SystemExit("get_model() must return at least cost and reader")
    return model


def _cmd_train(argv) -> int:
    import numpy as np

    from .trainer import CheckpointConfig, Trainer

    train_opts = ("config", "num_passes", "save_dir", "trace_out", "mesh")
    cfg = {}
    rest = []
    i = 0
    while i < len(argv):
        a = argv[i]
        name, eq, val = a.partition("=") if a.startswith("--") else ("", "", "")
        name = name[2:].replace("-", "_")  # same normalization as parse_flags
        if name in train_opts:
            # both '--config x' and '--config=x' forms
            if eq:
                cfg[name] = val
                i += 1
            elif i + 1 < len(argv) and not argv[i + 1].startswith("--"):
                cfg[name] = argv[i + 1]
                i += 2
            else:
                raise SystemExit(f"flag --{name} requires a value")
        else:
            rest.append(a)
            i += 1
    try:
        leftover = parse_flags(rest)
    except ValueError as e:
        raise SystemExit(str(e)) from None
    bad = [a for a in leftover if a.startswith("--")]
    if bad:
        # gflags parity: the reference errors on unknown flags rather than
        # silently training with defaults (a typo'd --log_perod=10 must
        # not be ignored). A known flag lands here too when its value is
        # missing — tell those two cases apart.
        from .flags import _REGISTRY

        msgs = []
        for a in bad:
            fname = a[2:].split("=", 1)[0].replace("-", "_")
            if fname in _REGISTRY:
                msgs.append(f"flag --{fname} requires a value")
            else:
                msgs.append(f"unknown flag: {a}")
        raise SystemExit("\n".join(msgs) + f"\n{flags_help()}")
    if "config" not in cfg:
        raise SystemExit("train requires --config <model.py>")
    from .obs import trace as obs_trace

    if cfg.get("trace_out"):
        # arm before the model builds so that the capture holds the
        # builds too: each `executor.call` that compiles has an
        # `executor.build` span in it, with its phases (`build.trace`,
        # `.lower`, `.compile`) and why it was built (core/build.py);
        # exported in finish() below (and idempotently by the atexit
        # hook if the env flag armed it first)
        obs_trace.arm(out=cfg["trace_out"])
    model = _load_config(cfg["config"])
    if FLAGS.stats_period:
        # the trainer emits the periodic runtime-stats line through the
        # paddle_tpu.stats logger; a CLI run that asked for it must see
        # it without configuring logging first
        import logging

        slog = logging.getLogger("paddle_tpu.stats")
        if not slog.handlers:
            h = logging.StreamHandler()
            h.setFormatter(logging.Formatter("%(name)s: %(message)s"))
            slog.addHandler(h)
            slog.setLevel(logging.INFO)
    num_passes = int(cfg.get("num_passes", model.get("num_passes", 1)))
    # checkpointing (and its auto-resume) only when the user asks for it:
    # a default dir would make a rerun of a finished job silently resume
    # past the last pass and train nothing
    save_dir = cfg.get("save_dir", "")
    ckpt = CheckpointConfig(checkpoint_dir=save_dir) if save_dir else None
    executor = None
    mesh_spec = cfg.get("mesh", "")
    if mesh_spec or FLAGS.pipeline_stages or FLAGS.microbatches:
        # --mesh dp2,pp2 trains over a device mesh; a pp axis (or
        # --pipeline_stages) selects the micro-batch pipeline executor,
        # a dp/mp-only mesh the ParallelExecutor
        mesh = None
        pp_size = 1
        if mesh_spec:
            from .parallel.mesh import mesh_from_spec, parse_mesh_spec

            try:
                pp_size = dict(parse_mesh_spec(mesh_spec)).get("pp", 1)
                mesh = mesh_from_spec(mesh_spec)
            except ValueError as e:
                raise SystemExit(f"--mesh {mesh_spec}: {e}") from None
        stages = int(FLAGS.pipeline_stages) or pp_size
        if stages > 1 or FLAGS.microbatches:
            from .pipeline import PipelineExecutor

            stages = max(stages, 1)
            executor = PipelineExecutor(
                num_stages=stages,
                num_microbatches=int(FLAGS.microbatches) or 2 * stages,
                mesh=mesh,
            )
        elif mesh is not None:
            from .parallel import ParallelExecutor

            executor = ParallelExecutor(mesh)
    trainer = Trainer(cost=model["cost"], checkpoint_config=ckpt,
                      executor=executor)

    def log_handler(event):
        from .trainer import EndIteration, EndPass

        if isinstance(event, EndIteration):
            if event.batch_id % FLAGS.log_period == 0:
                ms = ", ".join(f"{k}={v:.5g}" for k, v in event.metrics.items())
                print(f"pass {event.pass_id} batch {event.batch_id} "
                      f"cost={event.cost:.6g}" + (f" {ms}" if ms else ""))
        elif isinstance(event, EndPass):
            ms = ", ".join(f"{k}={v:.5g}" for k, v in event.metrics.items())
            print(f"Pass {event.pass_id} done: {ms}")

    from .resilience import PREEMPT_EXIT_CODE, PreemptedError

    def finish():
        # dump-at-exit observability: export the trace capture (if any)
        # and print the same unified metrics surface a serving process
        # exposes on /metrics
        if obs_trace.armed():
            tr = obs_trace.disarm(export=True)
            out = getattr(tr, "out", None) if tr is not None else None
            if out:
                print(f"trace written to {out} ({tr.event_count()} "
                      f"events, {tr.dropped_total()} dropped)", flush=True)
        if FLAGS.dump_stats:
            from . import profiler
            from .obs import metrics as obs_metrics

            profiler.global_stat_set().print_all_status()
            print(obs_metrics.registry().render(), end="")

    try:
        metrics = trainer.train(
            model["reader"],
            num_passes=num_passes,
            feed_order=model.get("feed_order"),
            fetch_metrics=model.get("metrics"),
            event_handler=log_handler,
        )
    except PreemptedError as e:
        # EX_TEMPFAIL: the scheduler should reschedule this job; a rerun
        # with the same --save_dir resumes from the emergency checkpoint
        print(f"preempted: {e}", flush=True)
        finish()
        return PREEMPT_EXIT_CODE
    print("final:", {k: round(float(v), 6) for k, v in metrics.items()})
    finish()
    return 0


def _cmd_merge_model(argv) -> int:
    """Checkpoint/params dir → single deployable inference dir."""
    args = dict(zip(argv[::2], argv[1::2]))
    model_dir = args.get("--model_dir")
    out = args.get("--out")
    config = args.get("--config")
    if not (model_dir and out and config):
        raise SystemExit(
            "merge_model requires --config <infer_model.py> --model_dir "
            "<params> --out <dir>; the config must define get_inference() "
            "returning (feed_names, fetch_vars)")
    import paddle_tpu as pt

    ns = runpy.run_path(config)
    if "get_inference" not in ns:
        raise SystemExit("config must define get_inference()")
    feed_names, fetch_vars = ns["get_inference"]()
    # accept either a plain params dir (save_params) or a trainer
    # checkpoint dir (pick the latest serial)
    if pt.io.get_latest_checkpoint_serial(model_dir) >= 0:
        pt.io.load_checkpoint(model_dir)
    else:
        pt.io.load_params(model_dir)
    pt.io.save_inference_model(out, feed_names, fetch_vars)
    print(f"merged model written to {out}")
    return 0


def _parse_kv(argv, known):
    """--k v / --k=v option parsing (list-valued keys may repeat)."""
    opts: dict = {}
    i = 0
    while i < len(argv):
        a = argv[i]
        if not a.startswith("--"):
            raise SystemExit(f"unexpected argument {a!r}")
        name, eq, val = a.partition("=")
        name = name[2:].replace("-", "_")
        if name not in known:
            raise SystemExit(f"unknown option --{name}")
        if known[name] is bool:
            # bare flag: --autoscale (or explicit --autoscale=0)
            opts[name] = val if eq else "1"
            i += 1
            continue
        if not eq:
            if i + 1 >= len(argv):
                raise SystemExit(f"option --{name} requires a value")
            val = argv[i + 1]
            i += 1
        if known[name] is list:
            opts.setdefault(name, []).append(val)
        else:
            opts[name] = val
        i += 1
    return opts


def _model_is_generative(model_dir: str) -> bool:
    """Cheap pre-load check: does the artifact's meta.json carry the
    generation sidecar (io.save_inference_model on a beam-search
    model)? Decides whether serve passes continuous-batching knobs."""
    import json as _json
    import os as _os

    try:
        with open(_os.path.join(model_dir, "meta.json")) as f:
            return bool(_json.load(f).get("generation"))
    except (OSError, ValueError):
        return False


_SERVE_KNOWN = {
    "model_dir": str, "model": list, "host": str, "port": str,
    "max_batch_size": str, "max_wait_ms": str, "max_queue": str,
    "timeout_ms": str, "seq_len_buckets": str, "warmup": str,
    "max_slots": str, "gen_queue": str, "gen_timeout_ms": str,
    # generation serving v3: device-resident prefix cache +
    # speculative decoding (forwarded to replica children so a fleet
    # caches/drafts identically on every replica)
    "prefix_cache_mb": str, "prefix_quant": str,
    "draft_model": str, "draft_k": str,
    "trace_out": str, "mesh": str, "drain_s": str, "quant": str,
    # multi-tenancy: per-model SLO class specs (model=interactive|batch);
    # forwarded to replica children so admission tiers match the
    # router's per-class picks
    "slo": list,
    # fleet mode (router + replica processes); NOT forwarded to the
    # replica children
    "replicas": str, "standby": str, "probe_interval_ms": str,
    # fleet control plane (fleetctl.autoscaler): warm-standby
    # promotion under pressure, drain-and-retire when idle
    "autoscale": bool, "min_replicas": str, "max_replicas": str,
    "cooldown_s": str,
    # disaggregated serving (serving/disagg): phase-specialized
    # replica classes with device-state handoff
    "disaggregate": bool, "prefill_replicas": str,
    "decode_replicas": str, "handoff_quant": str,
}
_FLEET_ONLY = ("replicas", "standby", "probe_interval_ms", "host",
               "port", "trace_out", "autoscale", "min_replicas",
               "max_replicas", "cooldown_s", "disaggregate",
               "prefill_replicas", "decode_replicas", "handoff_quant")


def _cmd_serve(argv) -> int:
    """Batching inference server over saved inference models. With
    --replicas N this process becomes a ROUTER: it pre-forks N replica
    serve processes (plus --standby warm spares), load-balances
    /predict and /generate across them join-shortest-queue, and
    fails over on replica death (serving/router.py)."""
    from .serving import BucketPolicy, ModelRegistry, make_server

    opts = _parse_kv(argv, _SERVE_KNOWN)
    if (int(opts.get("replicas", 0) or 0) > 0
            or opts.get("disaggregate", "0")
            not in ("0", "false", "no", "")):
        return _serve_fleet(opts)
    if opts.get("trace_out"):
        from .obs import trace as obs_trace

        obs_trace.arm(out=opts["trace_out"])
        print(f"span tracing armed; Chrome trace JSON will be written "
              f"to {opts['trace_out']} at shutdown", flush=True)
    models = {}
    if "model_dir" in opts:
        models["default"] = opts["model_dir"]
    for spec in opts.get("model", []):
        name, eq, d = spec.partition("=")
        if not eq:
            raise SystemExit(
                f"--model needs name=dir, got {spec!r}")
        models[name] = d
    if not models:
        raise SystemExit("serve requires --model_dir <dir> or at least "
                         "one --model name=dir")
    mesh = None
    if opts.get("mesh"):
        # mesh-sharded replica: ONE model served across chips — params
        # carrying the artifact's sharding sidecar land sharded, the
        # HTTP surface is unchanged (README "Scale-out serving")
        from .parallel.mesh import mesh_from_spec

        mesh = mesh_from_spec(opts["mesh"])
    policy = BucketPolicy(
        max_batch_size=int(opts.get("max_batch_size", 64)),
        seq_len_buckets=tuple(
            int(t) for t in opts.get("seq_len_buckets", "").split(",")
            if t.strip()),
    )
    # continuous-batching knobs for generation models (ignored — and
    # rejected by the registry — for feed-forward ones)
    scheduler_kw = {
        "max_slots": int(opts.get("max_slots", 8)),
        "max_queue": int(opts.get("gen_queue", 64)),
        "timeout_ms": float(opts.get("gen_timeout_ms", 30000.0)),
    }
    # serving v3 knobs stay absent unless asked for, so the scheduler's
    # defaults (cache off, no draft) govern and old artifacts' sidecar
    # draft models still auto-apply
    if opts.get("prefix_cache_mb"):
        scheduler_kw["prefix_cache_mb"] = float(opts["prefix_cache_mb"])
    if opts.get("prefix_quant"):
        scheduler_kw["prefix_cache_quant"] = opts["prefix_quant"]
    if opts.get("draft_model"):
        scheduler_kw["draft_model"] = opts["draft_model"]
    if opts.get("draft_k"):
        scheduler_kw["draft_k"] = int(opts["draft_k"])
    from .fleetctl.tenancy import SLOPolicy

    registry = ModelRegistry(
        slo_policy=SLOPolicy.from_specs(opts.get("slo", [])))
    for name, d in models.items():
        engine, _ = registry.add(
            name, model_dir=d, policy=policy, mesh=mesh,
            quantize=opts.get("quant") or None,
            max_wait_ms=float(opts.get("max_wait_ms", 5.0)),
            max_queue=int(opts.get("max_queue", 256)),
            timeout_ms=float(opts.get("timeout_ms", 2000.0)),
            scheduler_kw=(scheduler_kw
                          if _model_is_generative(d) else None),
        )
        if opts.get("warmup", "1") not in ("0", "false", "no"):
            n = engine.warmup()
            print(f"model {name!r}: warmed {n} bucket programs",
                  flush=True)
        if engine.generation_spec() is not None:
            spec = engine.generation_spec()
            print(f"model {name!r}: generation serving on /generate/"
                  f"{name} (beam_size={spec.beam_size} "
                  f"max_len={spec.max_len} "
                  f"slots={scheduler_kw['max_slots']})", flush=True)
    server = make_server(registry, host=opts.get("host", "127.0.0.1"),
                         port=int(opts.get("port", 8866)))
    registry.start()
    # SIGTERM = graceful shutdown (the replica half of the router's
    # failover contract, mirroring the trainer's preemption drain):
    # stop accepting, then DRAIN in-flight work — queued predicts and
    # running generation streams finish (bounded by --drain_s) before
    # the process exits, so a router-managed replica being descheduled
    # never tears a client's stream mid-token.
    import signal
    import threading

    term = {"signaled": False}

    def _on_term(signum, frame):
        term["signaled"] = True
        threading.Thread(target=server.shutdown, daemon=True).start()

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:
        pass  # not the main thread (embedded use): caller owns signals
    print(f"serving {registry.names()} on "
          f"http://{server.server_address[0]}:{server.port}", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        drain_s = (float(opts.get("drain_s", 30.0))
                   if term["signaled"] else 0.0)
        if drain_s:
            print(f"SIGTERM: draining in-flight work "
                  f"(up to {drain_s:g}s)", flush=True)
        registry.stop(drain_s=drain_s)
        if drain_s:
            # the scheduler/batcher have delivered every result; give
            # in-flight (daemon) handler threads a beat to flush their
            # final chunks down the socket before the interpreter exits
            import time as _time

            _time.sleep(0.5)
            print("drained; exiting", flush=True)
        server.server_close()
        from .obs import trace as obs_trace

        if obs_trace.armed():
            tr = obs_trace.disarm(export=True)
            out = getattr(tr, "out", None) if tr is not None else None
            if out:
                print(f"trace written to {out}", flush=True)
    return 0


def _serve_fleet(opts) -> int:
    """serve --replicas N: router + pre-forked replica fleet."""
    from .fleetctl.tenancy import SLOPolicy
    from .serving.router import Fleet, Router, make_router_server, \
        replica_spawner

    # child argv = every serving option EXCEPT the fleet-only ones;
    # children bind port 0 on loopback and print their URL
    if not opts.get("model_dir") and not opts.get("model"):
        raise SystemExit("serve requires --model_dir <dir> or at "
                         "least one --model name=dir")
    child_args = []
    for k, v in opts.items():
        if k in _FLEET_ONLY:
            continue
        if isinstance(v, list):
            child_args.extend(f"--{k}={x}" for x in v)
        else:
            child_args.append(f"--{k}={v}")
    disagg_on = (opts.get("disaggregate", "0")
                 not in ("0", "false", "no", ""))
    standby = int(opts.get("standby", 0))
    router = Router(
        probe_interval_s=float(opts.get("probe_interval_ms", 500)) / 1e3,
        slo_policy=SLOPolicy.from_specs(opts.get("slo", [])))
    if disagg_on:
        # disaggregated topology: two replica classes behind one
        # router, /generate phase-split through a DisaggDispatcher
        from .serving.disagg import DisaggFleet

        npf = int(opts.get("prefill_replicas", 1))
        ndec = int(opts.get("decode_replicas", 1))
        n = npf + ndec
        fleet = DisaggFleet(replica_spawner(child_args),
                            prefill_replicas=npf,
                            decode_replicas=ndec,
                            standby=standby, router=router)
    else:
        n = int(opts["replicas"])
        fleet = Fleet(replica_spawner(child_args), replicas=n,
                      standby=standby, router=router)

    # rollout hook: model_dir -> spawn_fn serving THAT artifact with
    # this fleet's serve flags (fleetctl rollout warms the new version
    # through it, then repoints standby respawns)
    def _spawn_template(model_dir):
        args = [a for a in child_args
                if not a.startswith(("--model_dir=", "--model="))]
        args.append(f"--model_dir={model_dir}")
        return replica_spawner(args)

    fleet.spawn_template = _spawn_template
    print(f"spawning {n} replica(s)"
          + (f" + {standby} warm standby" if standby else "")
          + " ...", flush=True)
    fleet.start()
    for r in router.replicas():
        print(f"  replica {r.name}: {r.url}"
              + (f" [{r.phase}]" if r.phase else ""), flush=True)
    scaler = None
    if opts.get("autoscale", "0") not in ("0", "false", "no", ""):
        if disagg_on:
            from .serving.disagg import make_phase_autoscalers

            scaler = make_phase_autoscalers(fleet).start()
            print("phase autoscalers armed: prefill scales on queue "
                  "age/depth, decode on slot occupancy", flush=True)
        else:
            from .fleetctl import Autoscaler, AutoscalerConfig

            cfg = AutoscalerConfig(
                min_replicas=int(opts.get("min_replicas", 1)),
                max_replicas=int(opts.get("max_replicas",
                                          max(n, 1) + max(standby, 1))),
                cooldown_s=float(opts.get("cooldown_s", 3.0)))
            scaler = Autoscaler(fleet, cfg).start()
            print(f"autoscaler armed: {cfg.min_replicas}.."
                  f"{cfg.max_replicas} replicas, "
                  f"cooldown {cfg.cooldown_s:g}s", flush=True)
    dispatcher = None
    if disagg_on:
        from .serving.disagg import DisaggDispatcher

        dispatcher = DisaggDispatcher(
            router, quant=opts.get("handoff_quant") or None)
        print("disaggregated dispatch armed: /generate phase-splits "
              "prefill -> handoff -> decode"
              + (f" (handoff quant {opts['handoff_quant']})"
                 if opts.get("handoff_quant") else ""), flush=True)
    server = make_router_server(
        router, host=opts.get("host", "127.0.0.1"),
        port=int(opts.get("port", 8866)),
        fleet=fleet, autoscaler=scaler, disagg=dispatcher)
    server.serve_background()

    import signal
    import threading

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, lambda s, f: stop.set())
        except ValueError:
            pass
    print(f"routing /predict and /generate for {n} replica(s) on "
          f"http://{server.server_address[0]}:{server.port}", flush=True)
    try:
        while not stop.wait(0.5):
            pass
    except KeyboardInterrupt:
        pass
    print("stopping fleet (graceful: replicas drain in-flight work)",
          flush=True)
    if scaler is not None:
        scaler.stop()
    server.shutdown()
    fleet.stop(graceful=True)
    server.server_close()
    return 0


def _cmd_fleetctl(argv) -> int:
    """Control-plane client for a running fleet router: `rollout`
    POSTs /admin/rollout (zero-downtime version flip), `status` GETs
    /admin/fleet (router health + fleet + autoscaler in one doc)."""
    import json as _json
    import urllib.error
    import urllib.request

    if not argv or argv[0] in ("-h", "--help"):
        raise SystemExit(
            "usage: fleetctl rollout --router URL --model_dir D "
            "[--model NAME]\n       fleetctl status --router URL")
    verb, rest = argv[0], argv[1:]
    known = {"router": str, "model_dir": str, "model": str,
             "drain_timeout_s": str}
    opts = _parse_kv(rest, known)
    url = (opts.get("router") or "http://127.0.0.1:8866").rstrip("/")
    try:
        if verb == "status":
            with urllib.request.urlopen(url + "/admin/fleet",
                                        timeout=10.0) as f:
                payload = _json.load(f)
        elif verb == "rollout":
            if not opts.get("model_dir"):
                raise SystemExit("fleetctl rollout requires "
                                 "--model_dir <new artifact dir>")
            body = {"model_dir": opts["model_dir"],
                    "model": opts.get("model", "default")}
            if opts.get("drain_timeout_s"):
                body["drain_timeout_s"] = float(opts["drain_timeout_s"])
            req = urllib.request.Request(
                url + "/admin/rollout",
                data=_json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            # rollout blocks through warm+verify+flip+drain; size the
            # client timeout for a model load, not a ping
            with urllib.request.urlopen(req, timeout=600.0) as f:
                payload = _json.load(f)
        else:
            raise SystemExit(
                f"unknown fleetctl verb {verb!r}; try: rollout, status")
    except urllib.error.HTTPError as e:
        try:
            detail = _json.load(e).get("error", "")
        except Exception:
            detail = ""
        print(f"fleetctl {verb} failed: HTTP {e.code} {detail}",
              file=sys.stderr)
        return 1
    except urllib.error.URLError as e:
        print(f"cannot reach router at {url}: {e.reason}",
              file=sys.stderr)
        return 1
    print(_json.dumps(payload, indent=2, sort_keys=True))
    return 0


def _cmd_route(argv) -> int:
    """Stand-alone router over ALREADY-RUNNING replicas (spawned by
    `serve` on other hosts/ports, or by an external scheduler)."""
    from .serving.router import Router, make_router_server

    known = {"replica": list, "host": str, "port": str,
             "probe_interval_ms": str, "request_timeout_ms": str}
    opts = _parse_kv(argv, known)
    urls = opts.get("replica", [])
    if not urls:
        raise SystemExit("route requires at least one "
                         "--replica http://host:port")
    router = Router(
        replicas=urls,
        probe_interval_s=float(opts.get("probe_interval_ms", 500)) / 1e3,
        request_timeout_s=float(
            opts.get("request_timeout_ms", 120000)) / 1e3)
    server = make_router_server(
        router, host=opts.get("host", "127.0.0.1"),
        port=int(opts.get("port", 8866)))
    router.start()
    print(f"routing {len(urls)} replica(s) on "
          f"http://{server.server_address[0]}:{server.port}", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        router.close()
        server.server_close()
    return 0


_DTYPE_ALIASES = {"bf16": "bfloat16", "bfloat16": "bfloat16",
                  "f32": "float32", "fp32": "float32",
                  "float32": "float32", "int8": "int8", "i8": "int8"}


def _fmt_cfg(cfg) -> str:
    if cfg is None:
        return "<none>"
    return ",".join(f"{k}={v}" for k, v in sorted(cfg.items()))


def _fmt_shape(params) -> str:
    return _fmt_cfg({k: v for k, v in params.items() if k != "dtype"})


def _cmd_tune(argv) -> int:
    """Kernel sweep front-end (paddle_tpu.tune): prints, writes nothing."""
    from .tune import harness, space

    if argv and not argv[0].startswith("--"):
        raise SystemExit(
            f"unknown tune verb {argv[0]!r}: there is no tuned table to "
            "export, import or merge. `tune --kernel <family> --shape "
            "k=v,... [--dry-run]` or `tune --config <model.py>` sweeps "
            "and prints a ranking")

    dry = False
    rest = []
    for a in argv:
        if a in ("--dry-run", "--dry_run"):
            dry = True
        else:
            rest.append(a)
    known = {"kernel": str, "shape": list, "dtype": str, "iters": str,
             "warmup": str, "config": str, "mesh": str}
    opts = _parse_kv(rest, known)
    dp = 1
    if "mesh" in opts:
        from .parallel.mesh import parse_mesh_spec

        dp = dict(parse_mesh_spec(opts["mesh"])).get("dp", 1)
    dtype = _DTYPE_ALIASES.get(opts.get("dtype", "bf16"))
    if dtype is None:
        raise SystemExit(f"--dtype must be bf16, f32 or int8, got "
                         f"{opts['dtype']!r}")

    cases = []
    if "config" in opts:
        # model sweep: build the model's program, scan it for tunable
        # kernel sites with concrete shapes — at the PER-SHARD batch
        # when --mesh declares the dp degree the model will run under
        _load_config(opts["config"])
        sites = space.cases_from_program(dp=dp)
        if not sites:
            print("no tunable kernel sites with concrete shapes found "
                  "in the model program")
        cases.extend(
            {"family": s["family"], "params": s["params"],
             "dtype": s["dtype"]} for s in sites)
    if "kernel" in opts:
        shapes = opts.get("shape", [])
        if not shapes:
            raise SystemExit("tune --kernel requires at least one "
                             "--shape k=v,k=v (e.g. --shape "
                             "B=256,S=60,A=512,C=512)")
        try:
            fam = space.get_family(opts["kernel"])
        except KeyError as e:
            raise SystemExit(str(e)) from None
        for spec in shapes:
            try:
                params = {k: int(v) for k, _, v in
                          (kv.partition("=") for kv in spec.split(","))}
            except ValueError:
                raise SystemExit(
                    f"bad --shape {spec!r}: expected k=v,k=v with "
                    "integer values") from None
            # user-facing bahdanau shapes take the raw source length S;
            # the kernels run over S padded (the signature's Sp)
            if fam.name == "bahdanau_attention" and "S" in params \
                    and "Sp" not in params:
                params["Sp"] = space.pad_s(params.pop("S"))
            cases.append({"family": fam.name, "params": params,
                          "dtype": dtype})
    if not cases:
        raise SystemExit("tune requires --kernel <family> --shape ... "
                         "and/or --config <model.py>")

    if dry:
        for c in cases:
            try:
                info = harness.list_candidates(c["family"], c["params"],
                                               c["dtype"])
            except (ValueError, KeyError) as e:
                print(f"{c['family']}: {e}")
                continue
            print(f"kernel {info['kernel']}  {_fmt_shape(info['params'])}"
                  f"  dtype={c['dtype']}")
            print(f"  analytic default: {_fmt_cfg(info['default'])}")
            print(f"  {len(info['candidates'])} legal candidates:")
            for cfg in info["candidates"]:
                mark = "   (analytic default)" \
                    if cfg == info["default"] else ""
                print(f"    {_fmt_cfg(cfg)}{mark}")
        return 0

    try:
        harness.ensure_timeable()
    except harness.TuningUnavailable as e:
        raise SystemExit(str(e)) from None
    iters = int(opts.get("iters", 7))
    warmup = int(opts.get("warmup", 2))
    for c in cases:
        try:
            rep = harness.tune_case(c["family"], c["params"], c["dtype"],
                                    iters=iters, warmup=warmup)
        except (NotImplementedError, ValueError) as e:
            print(f"{c['family']}: skipped — {e}")
            continue
        print(f"kernel {rep['kernel']}  {_fmt_shape(rep['params'])}  "
              f"dtype={c['dtype']}  device={rep['device_kind']}")
        for r in rep["rows"]:
            if not r["numerics_ok"]:
                t = "   FAILED numerics"
            else:
                t = f"{r['median_s'] * 1e3:10.3f} ms"
            marks = ("   (default)" if r["is_default"] else "") + \
                    ("   <- best" if r["config"] == rep["best"] else "")
            print(f"    {_fmt_cfg(r['config']):<28}{t}{marks}")
        if "speedup_vs_default" in rep:
            print(f"  best {_fmt_cfg(rep['best'])}: "
                  f"{rep['speedup_vs_default']:.3f}x vs analytic default")
    return 0


def _synthetic_samples(feed_specs, feed_names, n, batch=4):
    """Deterministic calibration feeds from an artifact's feed specs:
    seed-0 standard-normal floats / small-range ints, -1 dims pinned to
    the calibration batch (dim 0) or 8 (inner dims). Synthetic ranges
    are a stand-in for real traffic — good enough for the smoke path;
    production calibration should feed recorded samples through
    quant.calibrate directly."""
    import numpy as np

    rng = np.random.RandomState(0)
    samples = []
    for _ in range(n):
        feed = {}
        for name in feed_names:
            spec = (feed_specs or {}).get(name)
            if spec is None:
                raise SystemExit(
                    f"feed {name!r} has no shape/dtype spec in meta.json "
                    "(pre-serving artifact?); re-export the model or "
                    "calibrate programmatically via paddle_tpu.quant")
            shape = [batch if i == 0 and d == -1 else (8 if d == -1 else d)
                     for i, d in enumerate(spec["shape"])]
            dtype = np.dtype(spec["dtype"])
            if dtype.kind in "iu":
                feed[name] = rng.randint(0, 8, size=shape).astype(dtype)
            else:
                feed[name] = rng.standard_normal(shape).astype(dtype)
        samples.append(feed)
    return samples


def _cmd_quant(argv) -> int:
    """Post-training int8 quantization of a saved inference artifact:
    load → calibrate activation ranges on deterministic synthetic
    samples → rewrite matmul sites to quantized kernels → save the
    converted artifact (with the quant sidecar io.py validates at
    load). The loud mixed-precision report goes to stdout."""
    from . import io as pt_io
    from . import quant
    from .core.executor import Executor, Scope

    no_check = False
    argv = list(argv)
    while "--no-check" in argv or "--no_check" in argv:
        argv.remove("--no-check" if "--no-check" in argv
                    else "--no_check")
        no_check = True
    known = {"model_dir": str, "out": str, "samples": str, "mode": str}
    opts = _parse_kv(argv, known)
    model_dir, out = opts.get("model_dir"), opts.get("out")
    if not (model_dir and out):
        raise SystemExit("quant requires --model_dir <dir> --out <dir>")
    mode = opts.get("mode", "int8")
    n_samples = int(opts.get("samples", 8))
    scope = Scope()
    exe = Executor()
    program, feed_names, fetch_names = pt_io.load_inference_model(
        model_dir, scope=scope)
    if getattr(program, "_quant_meta", None):
        raise SystemExit(f"{model_dir} is already quantized "
                         f"({program._quant_meta.get('mode')})")
    samples = _synthetic_samples(getattr(program, "_serving_meta", None),
                                 feed_names, n_samples)
    calib = quant.calibrate(program, samples, scope=scope, exe=exe)
    check = None if no_check else samples[0]
    try:
        report = quant.convert(
            program, scope=scope, calib=calib, mode=mode,
            check_feed=check, fetch_list=fetch_names if check else None,
            exe=exe)
    except ValueError as e:
        raise SystemExit(str(e))
    print(report.summary())
    pt_io.save_inference_model(out, feed_names, fetch_names,
                               main_program=program, scope=scope)
    print(f"quantized model written to {out}")
    return 0


def _cmd_stats(argv) -> int:
    """Scrape/parse a Prometheus exposition and print a summary: the
    consumer side of the unified metrics registry (obs.promparse is the
    same parser the tier-1 smoke test validates the renderer with)."""
    from .obs import promparse

    known = {"url": str, "file": str, "raw": str}
    opts = _parse_kv(argv, known)
    if "url" in opts:
        import urllib.request

        url = opts["url"]
        if not url.rstrip("/").endswith("/metrics"):
            url = url.rstrip("/") + "/metrics"
        with urllib.request.urlopen(url, timeout=10) as r:
            text = r.read().decode()
    elif "file" in opts:
        with open(opts["file"]) as f:
            text = f.read()
    else:
        raise SystemExit(
            "stats requires --url http://host:port (a serving process's "
            "/metrics) or --file <exposition.txt>")
    try:
        families = promparse.parse_text(text)
    except promparse.ParseError as e:
        raise SystemExit(f"exposition did not parse: {e}") from None
    if opts.get("raw") in ("1", "true", "yes"):
        print(text, end="")
        return 0
    print(f"{'family':<48}{'type':>10}{'series':>8}{'value':>14}")
    for name in sorted(families):
        f = families[name]
        if f.type == "histogram":
            count = sum(v for n, _, v in f.samples
                        if n == f"{name}_count")
            total = sum(v for n, _, v in f.samples if n == f"{name}_sum")
            val = f"n={int(count)} sum={total:.4g}"
        elif len(f.samples) == 1:
            val = f"{f.samples[0][2]:.6g}"
        else:
            val = f"{len(f.samples)} series"
        print(f"{name:<48}{f.type:>10}{len(f.samples):>8}{val:>14}")
        if f.type not in ("histogram",) and 1 < len(f.samples) <= 8:
            for sname, labels, v in f.samples:
                lb = ",".join(f"{k}={x}" for k, x in sorted(labels.items()))
                print(f"    {sname}{{{lb}}} {v:.6g}")
    print(f"{len(families)} families parsed OK")
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help", "help"):
        print(__doc__)
        return 0
    cmd, rest = argv[0], argv[1:]
    from . import compile_cache

    compile_cache.enable()
    if cmd == "train":
        return _cmd_train(rest)
    if cmd == "merge_model":
        return _cmd_merge_model(rest)
    if cmd == "serve":
        return _cmd_serve(rest)
    if cmd == "route":
        return _cmd_route(rest)
    if cmd == "fleetctl":
        return _cmd_fleetctl(rest)
    if cmd == "tune":
        return _cmd_tune(rest)
    if cmd == "quant":
        return _cmd_quant(rest)
    if cmd == "stats":
        return _cmd_stats(rest)
    if cmd == "flags":
        print(flags_help())
        return 0
    if cmd == "version":
        from .version import full_version

        print(full_version)
        return 0
    raise SystemExit(f"unknown command {cmd!r}; try: train, merge_model, "
                     "serve, route, fleetctl, tune, quant, stats, flags, "
                     "version")


if __name__ == "__main__":
    sys.exit(main())
