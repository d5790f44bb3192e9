"""Driver `train`: `Trainer.train` over the configuration's endless seeded
reader for the cell's window, the way `paddle_tpu train` runs it
(`cli._cmd_train`: default programs, `Trainer(cost, executor=...)`,
`train(reader, feed_order=..., event_handler=..., log_interval=...)`).

Everything the harness learns comes from outside the program: the
trainer's events (`BeginIteration` / `EndIteration`), its exact counters
(`host_dispatch_count`, `host_sync_count`), its `profiler.StatSet` timers
and, in a traced run, the profiler's trace. The window:

    warm-up steps (compile or cache read, first syncs)   -> set-up
    fenced cost read; [start_trace]; t0                   -> window opens
    every `sync_every` steps a fenced cost read: one interval
    first read at or past the deadline: t1; trainer.stop() -> window closes

so the window starts after a fence and ends in a fence on the last
step's cost, and `items_s` is all its steps over all its time.

The plain reference is the yardstick and not the program, so it runs last
(PR 30): until the window has closed and the memory books have been read,
only the program under test has run in this process, and `peak_hbm_gib`
and `setup_s` are its own. A run goes:

    startup from --seed; a fingerprint of every parameter     -> kept
    first step: its cost, a sample of every Adam first moment  -> kept (host)
    warm-up, the window; at its close memory_stats() is read   -> the record's
    the trainer's state dropped; startup again from --seed;
    fingerprints compared; reference.py on those weights and
    the first batch; costs and gradients compared              -> `correct`
"""

from __future__ import annotations

import math
import time


def _executor(cell):
    if not cell.get("mesh"):
        return None
    from paddle_tpu.parallel import ParallelExecutor
    from paddle_tpu.parallel.mesh import mesh_from_spec

    return ParallelExecutor(mesh_from_spec(cell["mesh"]))


class _Window:
    """The event handler. One instance, one run."""

    def __init__(self, ctx, trainer, sync_every, warmup, seconds):
        self.ctx, self.trainer = ctx, trainer
        self.moments = None       # the first step's gradients, sampled
        self.memory_stats = None  # each chip's books at the window's close
        self.sync_every, self.warmup, self.seconds = sync_every, warmup, seconds
        self.steps = 0            # EndIterations seen since the start
        self.first_cost = None    # the first read after initialisation
        self.t0 = self.t1 = None
        self.t0_wall = None
        self.last_read = None
        self.intervals = []       # seconds per step, one per fenced read
        self.costs = []           # the fenced reads inside the window
        self.bad_intervals = 0
        self._gap_ann = self._step_ann = None
        self.at_open = self.at_close = None

    # -- profiler annotations (host spans on the trace's own clock) -------
    def _ann(self, name):
        if not self.ctx.trace:
            return None
        import jax

        a = jax.profiler.TraceAnnotation(name)
        a.__enter__()
        return a

    @staticmethod
    def _close(a):
        if a is not None:
            a.__exit__(None, None, None)

    def _snapshot(self):
        from paddle_tpu import profiler

        t = self.trainer
        stats = profiler.global_stat_set().as_dict()
        return {"dispatches": t.host_dispatch_count, "syncs": t.host_sync_count,
                "programs_built": self.ctx.clock.built,
                "compile_s": self.ctx.clock.seconds,
                "cache_misses": self.ctx.clock.misses,
                "timers": {k: v["total"] for k, v in stats.items()},
                "registry": registry_snapshot()}

    def _read(self, event, name="chipbench.cost_read"):
        a = self._ann(name)
        c = float(event.cost)     # the fence: blocks until this step is done
        now = time.perf_counter()
        self._close(a)
        return c, now

    def __call__(self, event):
        from paddle_tpu.trainer import BeginIteration, EndIteration

        if isinstance(event, BeginIteration):
            self._close(self._gap_ann)
            self._gap_ann = None
            self._step_ann = self._ann("chipbench.prepare_and_dispatch")
            return
        if not isinstance(event, EndIteration):
            return
        self._close(self._step_ann)
        self._step_ann = None
        self.steps += 1
        if self.t0 is None:
            self._warmup_step(event)
        else:
            self._window_step(event)
        if self.t1 is None and self.t0 is not None:
            self._gap_ann = self._ann("chipbench.wait_for_batch")

    def _warmup_step(self, event):
        if self.steps == 1:
            self.first_cost, _ = self._read(event, "chipbench.warmup_read")
            self.moments = _first_moments(self.trainer)
        if self.steps < self.warmup:
            return
        self._read(event, "chipbench.warmup_read")   # fence before the window
        if self.ctx.trace:
            self.ctx.start_trace()
        self.at_open = self._snapshot()
        self.t0_wall = time.time()
        self.t0 = self.last_read = time.perf_counter()

    def _window_step(self, event):
        n = self.steps - self.warmup
        if n % self.sync_every:
            return
        cost, now = self._read(event)
        self.intervals.append((now - self.last_read) / self.sync_every)
        self.last_read = now
        self.costs.append(cost)
        if not math.isfinite(cost):
            self.bad_intervals += 1
        if now - self.t0 >= self.seconds:
            self.t1 = now
            # the books, before anything but the program has run here
            self.memory_stats = self.ctx.memory_stats()
            self.at_close = self._snapshot()
            if self.ctx.trace:
                self.ctx.stop_trace()
            self.trainer.stop()


def registry_snapshot(text=None):
    """Every series of the program's one metrics registry
    (`obs.metrics.registry()`), flat: `{series: [kind, value]}` with the
    series as Prometheus writes it (`name` or `name{label="v",...}`) and kind
    `counter` or `gauge`. Read from the registry's own rendering, its public
    face and the only one that holds the render-time collectors
    (`pt_executor_*`, the faults, the timers). A histogram gives its `_count`
    and `_sum` (as counters) and its quantile gauges; its buckets are left
    out."""
    if text is None:
        from paddle_tpu.obs import metrics

        text = metrics.registry().render()
    kinds, out = {}, {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, family, kind = line.split(" ", 3)
            kinds[family] = kind.strip()
            continue
        if not line or line.startswith("#"):
            continue
        series, _, value = line.rpartition(" ")
        family = series.partition("{")[0]
        kind = kinds.get(family)
        if kind is None:    # a histogram's own samples
            base, _, part = family.rpartition("_")
            if kinds.get(base) != "histogram" or part == "bucket":
                continue
            kind = "counter"
        try:
            out[series] = [kind, float(value)]
        except ValueError:
            continue
    return out


def registry_delta(at_open, at_close):
    """What the window did to the registry: a counter's close minus open (a
    series born inside the window opened at 0), a gauge's value at the
    close."""
    return {series: value - at_open.get(series, (kind, 0.0))[1]
            if kind == "counter" else value
            for series, (kind, value) in at_close.items()}


# The plain reference (`configs/<config>/reference.py`: float32
# `jax.numpy`, matmuls at the highest precision) is held to the system's
# first step on the same weights and the same batch. What the first step
# left is kept; the reference runs after the window, on weights made again
# from the seed (`fingerprints` holds them to the first startup's):
#  - the first cost: |difference| over max(1, |reference|). On the chip
#    (PR 23, 29 runs, 6 seeds) at most 2.9e-6 for gpt2-small; the bound is
#    seven times that. Weak alone: with fresh weights the cost sits within
#    2e-3 of ln(vocabulary).
#  - every parameter's gradient. Adam's first moment after the first step
#    is (1 - beta1) x the gradient the step computed, so the gradients are
#    read from the optimizer's state with no second program: per tensor,
#    over a strided sample of at most GRAD_SAMPLE elements, rms(system -
#    reference) over rms(reference), where a tensor whose reference
#    gradient is (near) zero, such as a key bias, is held to a tenth of
#    the median tensor's rms instead. A wrong backward pass or a wrong
#    gradient hand-over to the optimizer reads near 1; bf16 AMP against
#    float32 read at most 0.013, the median tensor 0.006 (gpt2-small on
#    the chip, PR 23, 29 runs, 6 seeds); the bound is four times that.
# The tiny models of the CPU rehearsal are looser in both: their cell's
# `rehearsal` block carries its own tolerances, read only in a rehearsal.
#
# Gradients behind a discrete choice. A routed layer sends each token to
# the k experts its router scores highest, and rounding (bf16 AMP against
# the float32 reference) turns a near tie between the k-th and the (k+1)-th
# the other way. Such a token's share of the gradient then lands in another
# expert: not a wrong backward pass, and not within 0.05. The experiment
# (`tests/test_harness.py`, one OLMoE-shaped block in plain `jax.numpy`, 64
# experts, top-8, bf16 matmul inputs against float32 at `highest`; CPU, PR
# 26, 3 seeds x 2 routers) reads, for a share s of tokens whose expert set
# differs (0.008-0.031), 0.035-0.077 on the expert stacks, the router and
# the norm weight before them, where the dense tensors read 0.003-0.006, and
# on the worst expert stack err^2 / s = 0.169-0.193 every time. That is the arithmetic: a flipped token takes one
# of its k pair-contributions out and puts one in, 2 s N of the N k that
# make up the gradient, each at about 0.84 of the mean gate weight (the
# tie sits at the low end of the top k): err^2 = 0.84^2 x (2 / k) x s =
# 0.176 s at k = 8. Which tokens flip cannot be read from outside the
# program, but how many can is in the reference's own float32 router: bf16
# rounding of both matmul inputs moves a logit by about 0.0023 of the rms
# of a token's logits and a gap by 0.0032 of it, so s = the share of tokens
# whose k-th gap is under 1.3 x 2^-9 of that rms (0.025, 0.039, 0.041 so
# counted where 0.025, 0.031, 0.022 flipped with the router in bf16 too). The rule: a configuration names in
# `config.json` (`routed_parameters`: `names`, fnmatch patterns on the
# program's parameter names; `top_k`; `reason`) the parameters whose
# gradient flows only through a top-k choice, and its `reference.py` gives
# `router_logits(config, params, feed)` (a list of float32 [tokens,
# experts], one per routed layer). For those parameters only,
#     tolerance = min(ROUTED_CAP, sqrt(GRAD_TOL^2 + (2 / top_k) x share))
# where `share` is the share of tokens whose gap between the k-th and the
# (k+1)-th logit is under TIE_UNITS x 2^-9 x the rms of the token's centred
# logits: three times the gap that flips, so about twice the error a sound
# run reads (0.155-0.176 allowed where 0.038-0.077 was read). No near
# ties, no allowance; no `router_logits`, share 0. The cap is this file's,
# not the configuration's: a gradient that is missing or doubled reads 1,
# one halved 0.5, one handed to the wrong parameter 1.4 (the test shows
# each failing), so 0.2 leaves a factor 2.5 under the mildest fault. Every
# parameter that is not named stays at GRAD_TOL, so lower precision in
# attention, head or embedding still fails. The first cost's 2e-5 stays: a
# flipped token moves the mean cost by 4e-6 to 5e-5 over 512 tokens in the
# experiment and 6e-6 at T 256 and half widths (ISSUE 26); a cell's own
# tokens a step are tens of thousands.
REFERENCE_TOL = 2e-5
GRAD_TOL = 0.05
GRAD_SAMPLE = 65536
ROUTED_CAP = 0.2
TIE_UNITS = 4.0


def near_tie_share(logits, top_k):
    """Share of the rows of `logits` ([tokens, experts], float32) whose gap
    between the `top_k`-th and the next largest is under TIE_UNITS x 2^-9
    of the rms of the row's centred logits (see above)."""
    import jax.numpy as jnp

    z = -jnp.sort(-jnp.asarray(logits, jnp.float32), axis=-1)
    gap = z[:, top_k - 1] - z[:, top_k]
    scale = jnp.sqrt(jnp.mean(
        jnp.square(z - z.mean(-1, keepdims=True)), axis=-1))
    return jnp.mean(gap < TIE_UNITS * 2.0**-9 * scale)


def gradient_tolerances(names, config, grad_tol, share):
    """{parameter: tolerance}: `grad_tol` for every parameter, and the
    routed rule's for those `config["routed_parameters"]` names."""
    import fnmatch

    routed = config.get("routed_parameters") or {}
    allowed = min(max(ROUTED_CAP, grad_tol),
                  math.sqrt(grad_tol**2 + 2.0 / routed["top_k"] * share)
                  ) if routed else grad_tol
    return {n: allowed if any(fnmatch.fnmatchcase(n, pat)
                              for pat in routed.get("names", ()))
            else grad_tol for n in names}


def _sample(x):
    flat = x.reshape(-1)
    return flat[::-(-flat.size // GRAD_SAMPLE)]


def _parameters(trainer, scope):
    return [scope.get(p.name) for p in trainer.main_program.parameters()]


def fingerprints(params):
    """[[bits, sum], ...]: per parameter its bits summed as uint32 (wrapping)
    and its float32 sum, on the host. Two reductions a tensor in one program:
    what a second startup from the same seed has to give again, exactly."""
    import jax
    import jax.numpy as jnp

    def one(x):
        bits = jax.lax.bitcast_convert_type(
            x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))
        return jnp.sum(bits.astype(jnp.uint32)), jnp.sum(x.astype(jnp.float32))

    return [[int(b), float(t)] for b, t in jax.device_get(
        jax.jit(lambda ps: [one(x) for x in ps])(params))]


def _first_moments(trainer):
    """Right after the first step: a sample of every Adam first moment,
    (1 - beta1) x the gradient that step computed, taken to the host (a few
    MB), so that nothing of the check lies on the chip through the window.
    {parameter: (sample, 1 - beta1)}; parameters that no `adam` op updates
    are not compared."""
    import jax

    moment = {}
    for block in trainer.main_program.blocks:
        for op in block.ops:
            if op.type == "adam":
                moment[op.inputs["Param"][0]] = (
                    op.inputs["Moment1"][0], 1.0 - op.attrs.get("beta1", 0.9))
    names = [p.name for p in trainer.main_program.parameters()
             if p.name in moment]
    samples = jax.device_get(jax.jit(lambda ms: [_sample(m) for m in ms])(
        [trainer.scope.get(moment[n][0]) for n in names]))
    return {n: (m, moment[n][1]) for n, m in zip(names, samples)}


def _after_the_window(ctx, trainer, model, at_startup, moments):
    """The yardstick's turn, once the books are read and the profiler has
    stopped: the trained state is dropped, startup runs again from the seed
    and is held to the first startup's fingerprints, and the plain reference
    gives its cost and a sample of each parameter's gradient on those weights
    and the first batch (the reader is a function of the seed). Returns what
    `correct` compares."""
    import os

    import jax
    import jax.numpy as jnp

    from paddle_tpu import Scope

    t_begin = time.time()
    trainer.scope.vars.clear()    # parameters and optimizer state, trained
    scope = Scope()
    trainer.exe.run_startup(trainer.startup_program, scope=scope)
    params = _parameters(trainer, scope)
    del scope                     # Adam's fresh moments go, the weights stay
    names = [p.name for p in trainer.main_program.parameters()]
    differ = [n for n, a, b in zip(names, at_startup, fingerprints(params))
              if a != b]
    t_startup = time.time()

    ref = ctx.load_module(
        os.path.join(os.path.dirname(ctx.model.__file__), "reference.py"))
    first = ref.prepare(next(iter(model["reader"]())))

    def cost_and_sampled_grads(params, first):
        cost, grads = ref.loss_and_grads(ctx.config, params, first)
        return cost, [_sample(g) for g in grads]

    cost, grads = jax.jit(cost_and_sampled_grads)(params, first)
    out = {"reference_first_cost": float(cost), "near_tie_share": 0.0,
           "startup_differs": differ, "gradient_errors": None}
    routed = ctx.config.get("routed_parameters")
    if routed and hasattr(ref, "router_logits"):
        def share(params, first):
            layers = ref.router_logits(ctx.config, params, first)
            return sum(near_tie_share(z, routed["top_k"])
                       for z in layers) / len(layers)

        out["near_tie_share"] = float(jax.jit(share)(params, first))
    if moments:
        refs = [g for n, g in zip(names, grads) if n in moments]
        scales = [s for _, s in moments.values()]

        def errors(samples, refs):
            return relative_errors([m.astype(jnp.float32) / s
                                    for m, s in zip(samples, scales)], refs)

        errs = jax.jit(errors)([m for m, _ in moments.values()], refs)
        out["gradient_errors"] = dict(zip(moments, (float(e) for e in errs)))
    out["after_window_s"] = {"second_startup": t_startup - t_begin,
                             "reference": time.time() - t_startup}
    return out


def relative_errors(grads, refs):
    """Per tensor, rms(gradient - reference) over the reference's rms, the
    latter no smaller than a tenth of the median tensor's."""
    import jax.numpy as jnp

    def rms(x):
        return jnp.sqrt(jnp.mean(jnp.square(x)))

    ref_rms = jnp.stack([rms(g) for g in refs])
    diff = jnp.stack([rms(g - r) for g, r in zip(grads, refs)])
    return diff / jnp.maximum(ref_rms, 0.1 * jnp.median(ref_rms))


def program_ops(program):
    """The Program's ops as the readers of the device trace need them:
    type, inputs and outputs by slot, and `scope`, the `jax.named_scope`
    `Executor` traces the op under (`core/executor.py:_op_scope`: `<op
    type>.<first output variable>`, the rule copied here), which is what
    `xplane.reduce`'s `ops` rows carry as their `scope`."""
    out = []
    for block in program.blocks:
        for op in block.ops:
            first = next((n for names in op.outputs.values() for n in names), "")
            out.append({"type": op.type,
                        "scope": f"{op.type}.{first}" if first else op.type,
                        "inputs": {k: list(v) for k, v in op.inputs.items()},
                        "outputs": {k: list(v) for k, v in op.outputs.items()}})
    return out


def run(ctx):
    """Returns the run record the metric readers take their numbers from."""
    from paddle_tpu import profiler
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.trainer import Trainer

    cell = ctx.cell
    sync_every, warmup = int(cell["sync_every"]), int(cell["warmup_steps"])
    if warmup < 2 * sync_every or warmup % sync_every:
        raise SystemExit(
            f"chipbench: warmup_steps {warmup} must be a multiple of "
            f"sync_every {sync_every} and at least twice it (the first "
            f"sync compiles)")
    if ctx.trace:
        # host timers on: prepareBatchData is the in-loop feed time
        FLAGS.enable_timers = True
    model = ctx.model.get_model(ctx.config, cell, ctx.seed)
    trainer = Trainer(cost=model["cost"], executor=_executor(cell))
    trainer.init()    # startup: the weights, on the device, from the seed
    at_startup = fingerprints(_parameters(trainer, trainer.scope))  # a fence
    startup_done, startup_compile_s = time.time(), ctx.clock.seconds
    peak_after_startup = ctx.memory_peaks()
    profiler.global_stat_set().reset()
    seconds = min(ctx.seconds, float(cell["trace_seconds"])) if ctx.trace \
        else ctx.seconds
    win = _Window(ctx, trainer, sync_every, warmup, seconds)
    trainer.train(model["reader"], num_passes=1,
                  feed_order=model["feed_order"], event_handler=win,
                  log_interval=sync_every)
    if win.t1 is None:
        raise SystemExit("chipbench: the trainer returned before the window "
                         "closed")
    steps = win.steps - warmup
    delta = {k: win.at_close[k] - win.at_open[k]
             for k in ("dispatches", "syncs", "programs_built", "cache_misses")}
    timers = {k: v - win.at_open["timers"].get(k, 0.0)
              for k, v in win.at_close["timers"].items()}
    # where `setup_s` went, on clocks the run has anyway: reaching the chip
    # (imports, the runtime's start), startup (the Program built, its weights
    # made, their fingerprints read back), the step programs' compile or cache
    # read (the compile clock between startup and the window), and the rest of
    # the warm-up (tracing the Program, the steps themselves)
    step_programs = win.at_open["compile_s"] - startup_compile_s
    setup_split = {"reach_chip": ctx.t_chip - ctx.t_start,
                   "startup": startup_done - ctx.t_chip,
                   "step_program_compile_or_cache_read": step_programs,
                   "warmup": win.t0_wall - startup_done - step_programs}
    check = _after_the_window(ctx, trainer, model, at_startup, win.moments)
    return {
        **check,
        "memory_stats": win.memory_stats, "setup_split_s": setup_split,
        "steps": steps, "items": steps * model["items_per_step"],
        "window_s": win.t1 - win.t0, "t0_wall": win.t0_wall,
        "intervals_s": win.intervals, "costs": win.costs,
        "first_cost": win.first_cost, "bad_intervals": win.bad_intervals,
        "tolerances": dict(
            {"reference_tol": REFERENCE_TOL, "grad_tol": GRAD_TOL},
            **(cell["rehearsal"].get("tolerances", {})
               if ctx.rehearsal else {})),
        "peak_after_startup": peak_after_startup,
        "counters": delta, "timers_s": timers,
        "registry": registry_delta(win.at_open["registry"],
                                   win.at_close["registry"]),
        "program_ops": program_ops(trainer.main_program) if ctx.trace else None,
        "attempted": steps, "failed": win.bad_intervals * sync_every,
    }


def info(run):
    """What the run's `info` line says of the window."""
    errs = run["gradient_errors"]
    worst = errs and max(errs, key=errs.get)
    return {"steps": run["steps"], "window_s": run["window_s"],
            "intervals": len(run["intervals_s"]),
            "counters_in_window": run["counters"],
            "first_cost": run["first_cost"], "last_cost": run["costs"][-1],
            "reference_first_cost": run["reference_first_cost"],
            "gradient_error_worst": worst and [worst, errs[worst]],
            "gradient_error_median": errs and sorted(errs.values())[len(errs) // 2],
            "near_tie_share": run["near_tie_share"],
            "gradient_tolerance_max": max(_tolerances(run).values(),
                                          default=None),
            "startup_differs": run["startup_differs"],
            "setup_split_s": run["setup_split_s"],
            "after_window_s": run["after_window_s"],
            "peak_after_startup": run["peak_after_startup"],
            "peak_final": run.get("memory_peaks")}


def _tolerances(run):
    return gradient_tolerances(run["gradient_errors"] or (), run["config"],
                               run["tolerances"]["grad_tol"],
                               run["near_tie_share"])


def compared(run):
    """{name: [number, limit]}: every number `correct` holds to a limit, for
    the run's last lines. The gradient is the tensor nearest its own limit."""
    want, tol = run["reference_first_cost"], run["tolerances"]
    allowed = _tolerances(run)
    errs = run["gradient_errors"] or {}
    worst = max(errs, key=lambda n: errs[n] / allowed[n], default=None)
    out = {"startup_tensors_differing": [len(run["startup_differs"]), 0],
           "cost_reads_not_finite": [run["bad_intervals"], 0],
           "last_cost_over_first": [run["costs"][-1] / run["first_cost"], 1.0],
           "first_cost_off_reference": [
               abs(run["first_cost"] - want) / max(1.0, abs(want)),
               tol["reference_tol"]],
           "programs_built_in_window": [run["counters"]["programs_built"], 0],
           "cache_misses_in_window": [run["counters"]["cache_misses"], 0]}
    if worst is not None:
        out["gradient_error_nearest_limit"] = [errs[worst], allowed[worst]]
    return out


def correct(run):
    """What a train cell owes: finite costs, a loss that fell, a first step
    that agrees with the plain reference on weights the seed gives again,
    and nothing built inside the window. Returns a list of what failed
    (empty = ok)."""
    bad = []
    if run["startup_differs"]:
        bad.append(f"startup did not reproduce the weights: run again from "
                   f"the same seed after the window, {run['startup_differs']} "
                   f"differ, so the plain reference saw other weights than "
                   f"the first step")
    costs = run["costs"]
    if not costs or run["bad_intervals"] or not math.isfinite(run["first_cost"]):
        bad.append("a cost read was not finite")
    elif not costs[-1] < run["first_cost"]:
        bad.append(f"the loss did not fall: first {run['first_cost']}, "
                   f"last {costs[-1]}")
    off, limit = compared(run)["first_cost_off_reference"]
    if not off <= limit:
        bad.append(f"the first cost {run['first_cost']} is off the plain "
                   f"reference's {run['reference_first_cost']} by {off} "
                   f"(> {limit})")
    allowed = _tolerances(run)
    for name, err in (run["gradient_errors"] or {}).items():
        if not err <= allowed[name]:
            bad.append(f"the first step's gradient of {name} is off the plain "
                       f"reference's by {err} of its rms (> {allowed[name]})")
    if run["counters"]["programs_built"] or run["counters"]["cache_misses"]:
        bad.append(f"programs were built inside the window: {run['counters']}")
    return bad
