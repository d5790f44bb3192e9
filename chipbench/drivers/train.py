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
    fingerprints compared; the first step of a program that
    gives out a discrete choice (a router's experts, the
    candidates a row keeps) read once more for its routers'
    logits and its kept sets (and startup a third time);
    reference.py on those weights and the first batch, under
    the choice of experts those logits give and the kept sets
    as handed; costs, gradients and each choice compared      -> `correct`
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
# from the seed (`fingerprints` holds them to the first startup's). Every
# limit below has its two readings in PERF.md section 2.
#  - The first cost: |difference| over max(1, |reference|), REFERENCE_TOL.
#    Sound runs read at most 1.01e-5 (three configurations, 370 runs); the
#    hybrid's published precision (a bf16 residual stream) reads 8.4e-5.
#  - Every parameter's gradient, GRAD_TOL whatever the tensor. Adam's first
#    moment after the first step is (1 - beta1) x the gradient the step
#    computed, so the gradients are read from the optimizer's state: per
#    tensor, over `_sample`'s elements, rms(system - reference) over
#    rms(reference), the latter no smaller than a tenth of the median
#    tensor's (a key bias's gradient is all but zero). bf16 AMP against
#    float32 reads at most 0.026; a gradient missing or doubled reads 1, one
#    halved 0.5, one handed to the wrong parameter 1.4.
# The CPU rehearsal's tiny models are looser: a cell's `rehearsal` block
# carries its own tolerances, read only in a rehearsal.
#
# Tensors behind a discrete choice (PR 36). A routed layer sends each token
# to the k experts its router scores highest, and bf16 rounding turns a near
# tie between the k-th and the (k+1)-th the other way. A reference that
# chose for itself then computed another function: a turned token's share
# of the gradient lands in another expert, and where hidden states repeat
# (the hybrid's traffic: each 16 times) the turned copies add with ONE
# error vector. An allowance in quadrature over turned tokens (PRs 26-35)
# failed one sound run in six and no control. So the reference is TOLD the
# choice, and the choice is held on its own:
#  1. After the window the first step is run once more (`_first_step_again`)
#     on a second startup's weights and the reader's first batch, through
#     the trainer's executor and Program, with every routed op's
#     `RouterLogits` added to the trainer's own fetch list (a routed op is
#     one that writes that output). Its forward is the timed step's (the two
#     compiled programs differ in each router's own fusions only); its
#     backward rounds differently, so cost and first moments are held to the
#     timed step's at SECOND_COST_TOL and SECOND_GRAD_TOL (read: 3.4e-6 and
#     0.025 at most; another batch reads 1.4), not to the bit. Fetching the
#     routed ops' input too moved XLA's rounding of the whole forward
#     (routed tensors then read 0.05-0.07): only the logits are asked for.
#  2. `reference.py:chosen` applies the published top-k rule to those
#     logits, and `loss_grads_and_routers(..., choice)` scores its own gates
#     for the experts so chosen.
#  3. `choice_numbers` holds the choice; each number is in `compared`:
#     (a) `choice_counts_off_program`, limit 0: the pair counts an expert
#         under the derived choice against the program's `TokensPerExpert`.
#         The program gives out logits and counts, not indices: one that
#         keeps k-1 experts, or chooses by a bias its logits do not report,
#         differs here.
#     (b) `router_weight_rounding_share`, ROUTER_TOL: the projection of
#         (program's logits - reference's) on what rounding the router's
#         WEIGHT to bf16 does to the logits: 0 for a float32 router (read:
#         0.008 at most), 1 for one whose matmul takes bf16 inputs (read:
#         0.995-1.007). The rms of the difference cannot tell the two: a
#         sound deep layer is 0.19 % off by what its mixers rounded, with a
#         bf16 router 0.30 %; the first routed block 0.078 % and 0.25 %.
#     (c) `turned_rows_not_near_tie`, limit 0: rows whose derived set is not
#         the reference's own top k, where the reference's gap between the
#         best expert that left and the worst that came is TIE_UNITS x 2^-9
#         x the rms of the row's centred logits or more. The handed choice
#         may differ from the reference's own only where the reference all
#         but ties (read: 4.9 units at most; eight rows of wrong router
#         input 2 000), so a wrong router cannot lead the reference astray.
#         It implies `turned_row_share` <= `near_tie_share`, printed too.
#
# The second kind of discrete choice (PR 59): a row keeps k of its candidates
# (the keys a learned sparse attention attends: the top 2 048 of up to 16 384
# by an indexer's score). Thousands of candidates a row all but tie, so a
# float32 reference that chose for itself attends other keys than a sound
# bf16 program in every row (arithmetic, PR 59, numpy, one layer at T 8192,
# k 2048: a bf16 rounding of the layer's input turns 3.0 keys a row and
# moves the attention output 4.3 % of its rms), and no allowance holds. The
# same protocol, on other data:
#  1. An op that keeps k of each row's candidates writes `Chosen`: int32
#     [rows, k], a row's kept candidates by index, in any order, -1 where the
#     row has fewer than k valid candidates. No scores are asked for: [rows,
#     candidates] float32 is 1 GiB a layer at 16 384 x 16 384.
#     `_first_step_again` fetches each beside the routers' logits.
#  2. `reference.py:loss_grads_routers_and_keepers(..., choice, kept)` attends
#     the handed keys (its own attention over them, a later layer's hidden
#     state its own) and gives back, a choosing layer, what `scores(config,
#     keeper, row0, rows)` reads its OWN float32 scores of a block of rows
#     from; `kept(config, scores, valid)` is the published rule on them.
#  3. `kept_numbers` holds the sets, a block of at most KEPT_BLOCK rows at a
#     time (no [rows, candidates] array is ever whole), the worst layer's in
#     `compared`:
#     (a) `kept_sets_off_rule`, limit 0: rows whose handed indices are not
#         exactly min(k, valid candidates) distinct valid candidates. A
#         program that keeps k - 1, a key twice or a key from the future
#         differs here.
#     (b) `kept_turned_not_near_tie`, limit 0: rows whose handed set is not
#         the reference's own top k where the reference's gap between the
#         best candidate that left and the worst that came is KEPT_TIE_UNITS
#         x 2^-9 x the rms of the row's centred VALID scores or more
#         (`turned_rows`, as for a router, at a width of its own: the rms
#         itself). An indexer that scores the wrong input, or keeps by
#         another rule, differs here. The width is not the router's 8 units:
#         an indexer's score is a sum of products of three projections, not
#         one matmul, and the widest of a row's thousands of near ties is an
#         extreme (read, sound, every matmul in bf16: 12-81 units over 12
#         seeds x 2 layers at T 1024, k 256 on the CPU, 20-101 on the chip at
#         rows 8192, k 2048; the indexer fed another layer's input 1 800-
#         2 074 and 2 286-3 668, the top k of -I 4 235-4 975 and 4 429-
#         4 786: PERF.md section 2). What it cannot show: in what precision a
#         sound indexer scores; the published one runs in fp8, and under the
#         handed sets no gradient depends on it.
#     Printed and not held (every row all but ties, so a share of rows holds
#     nothing): the share of (row, slot) entries turned, the share of valid
#     candidates inside the tie band, the largest turned gap in units.
REFERENCE_TOL = 2e-5
GRAD_TOL = 0.05
GRAD_SAMPLE = 65536
SECOND_COST_TOL = 2e-5
SECOND_GRAD_TOL = 0.1
ROUTER_TOL = 0.2
TIE_UNITS = 8.0
KEPT_TIE_UNITS = 512.0
KEPT_BLOCK = 512


def _rms(x):
    import jax.numpy as jnp

    return jnp.sqrt(jnp.mean(jnp.square(x)))


def _row_scale(z, valid=None):
    """[N, E] -> [N]: the rms of each row's centred logits; of those of its
    `valid` candidates (bool [N, E]) where that is given."""
    import jax.numpy as jnp

    if valid is None:
        return jnp.sqrt(jnp.mean(jnp.square(z - z.mean(-1, keepdims=True)), -1))
    n = jnp.maximum(valid.sum(-1), 1)
    mean = jnp.sum(jnp.where(valid, z, 0.0), -1) / n
    return jnp.sqrt(jnp.sum(jnp.where(
        valid, jnp.square(z - mean[:, None]), 0.0), -1) / n)


def near_tie_share(logits, own):
    """Share of the rows of `logits` ([tokens, experts], float32) whose gap
    between the least of the chosen (`own`, 0/1: the row's top k) and the
    largest of the others is under TIE_UNITS x 2^-9 of the rms of the row's
    centred logits (see above)."""
    import jax.numpy as jnp

    z = jnp.asarray(logits, jnp.float32)
    gap = (jnp.min(jnp.where(own > 0, z, jnp.inf), -1)
           - jnp.max(jnp.where(own > 0, -jnp.inf, z), -1))
    return jnp.mean(gap < TIE_UNITS * 2.0**-9 * _row_scale(z))


def turned_rows(z_ref, handed, own, valid=None):
    """(turned, gap): the rows in which the 0/1 masks `handed` and `own`
    ([N, E]; `own` the top k of `z_ref`, the reference's float32 logits)
    differ, and in those rows the gap in `z_ref` between the best expert that
    left and the worst that came, in units of 2^-9 x the row's scale (0.0
    where nothing turned). With `valid` (bool [N, E]: the candidates a row
    may keep) the scale is that of the valid scores, and a handed candidate
    that is not valid is no turn: a row that only lost one reads -inf, under
    any width (the rule on the sets is held apart)."""
    import jax.numpy as jnp

    left, came = (own > 0) & (handed == 0), (handed > 0) & (own == 0)
    if valid is not None:
        came = came & valid
    turned = jnp.any(left | came, axis=-1)
    gap = (jnp.max(jnp.where(left, z_ref, -jnp.inf), -1)
           - jnp.min(jnp.where(came, z_ref, jnp.inf), -1))
    units = jnp.where(turned, gap / (2.0**-9 * _row_scale(z_ref, valid)), 0.0)
    return turned, units


def choice_numbers(router, own, handed, z_prog, counts_prog):
    """One routed layer's numbers of point 3 above, as a dict of scalars.
    `router` is the reference's (input [N, d], weight [d, E], logits
    [N, E]); `handed` and `own` are 0/1 [N, E]; `z_prog` [N, E] and
    `counts_prog` [E] are the program's fetched logits and pair counts.
    Beside those that are held: the share of rows turned, the largest turned
    gap, the reference's near-tie share and the program's logits against
    the reference's."""
    import jax
    import jax.numpy as jnp

    h, w, z_ref = router
    # what rounding the router's weight to bf16 does to the logits, and how
    # much of that is in the program's: 0 for a float32 router, 1 for one
    # whose matmul takes bf16 inputs
    # (`reduce_precision`: a cast to bf16 and back is a round trip that XLA
    # is free to drop, and on the TPU does)
    rounding = jnp.dot(h, jax.lax.reduce_precision(w, 8, 7) - w,
                       precision=jax.lax.Precision.HIGHEST)
    turned, units = turned_rows(z_ref, handed, own)
    return {
        "counts_off_program": jnp.sum(jnp.abs(
            handed.sum(0).astype(jnp.int32) - counts_prog.astype(jnp.int32))),
        "weight_rounding_share": jnp.sum((z_prog - z_ref) * rounding)
        / jnp.sum(jnp.square(rounding)),
        "turned_not_near_tie": jnp.sum(units >= TIE_UNITS),
        "turned_share": jnp.mean(turned),
        "turned_gap_units_max": jnp.max(units),
        "near_tie_share": near_tie_share(z_ref, own),
        "logits_off_reference": _rms(z_prog - z_ref) / _rms(z_ref)}


def kept_numbers(scores, kept, handed):
    """One choosing layer's numbers of the second kind's point 3, as a dict
    of scalars. `handed` is the program's `Chosen`, int32 [N, k];
    `scores(row0, rows)` gives the reference's own float32 scores [rows,
    candidates] of the rows from `row0` on and which candidates each may keep
    (bool); `kept(z, valid)` is the published rule on them (0/1). Reduced
    over blocks of the largest divisor of N that is at most KEPT_BLOCK rows,
    one block alive at a time. Beside the two that are held: the share of
    (row, slot) entries turned, the share of valid candidates a tie band's
    width from changing sides, the largest turned gap."""
    import jax
    import jax.numpy as jnp

    rows, k = handed.shape
    block = max(b for b in range(1, min(rows, KEPT_BLOCK) + 1) if rows % b == 0)

    def one(row0):
        z, valid = scores(row0, block)
        z, valid = z.astype(jnp.float32), valid > 0
        own = kept(z, valid) > 0
        index = jax.lax.dynamic_slice_in_dim(handed, row0, block)
        # the handed set as a mask: an index outside the candidates is
        # dropped (and -1 is no index), one given twice is set once
        width = z.shape[-1]
        inside = (index >= 0) & (index < width)
        theirs = jnp.zeros(z.shape, bool).at[
            jnp.arange(block)[:, None], jnp.where(inside, index, width)
        ].set(True, mode="drop")
        due = jnp.minimum(k, valid.sum(-1))
        off_rule = ((index != -1).sum(-1) != due) \
            | ((theirs & valid).sum(-1) != due)
        _, units = turned_rows(z, theirs, own, valid)
        # a tie band's width from changing sides: a kept candidate over the
        # best that is out, one that is out under the least that is kept
        band = KEPT_TIE_UNITS * 2.0**-9 * _row_scale(z, valid)[:, None]
        best_out = jnp.max(jnp.where(valid & ~own, z, -jnp.inf), -1)[:, None]
        least_in = jnp.min(jnp.where(own, z, jnp.inf), -1)[:, None]
        near = valid & jnp.where(own, z - best_out < band, least_in - z < band)
        return {"off_rule": off_rule.sum(),
                "not_near_tie": jnp.sum(units >= KEPT_TIE_UNITS),
                "came": jnp.sum(theirs & valid & ~own), "due": due.sum(),
                "near": near.sum(), "valid": valid.sum(),
                "units": jnp.max(units)}

    parts = jax.lax.map(one, jnp.arange(0, rows, block))
    total = {name: part.sum() for name, part in parts.items()}
    return {"sets_off_rule": total["off_rule"],
            "turned_not_near_tie": total["not_near_tie"],
            "turned_entry_share": total["came"] / total["due"],
            "near_tie_candidate_share": total["near"] / total["valid"],
            "turned_gap_units_max": jnp.max(parts["units"])}


def kept_numbers_by_layer(ref, config, keepers, sets):
    """`kept_numbers` of each choosing layer, through the reference's own
    `scores` and `kept`: `keepers` as `loss_grads_routers_and_keepers` gave
    them, `sets` the program's `Chosen` a layer."""
    return [kept_numbers(
        lambda row0, rows, keeper=keeper: ref.scores(config, keeper, row0, rows),
        lambda z, valid: ref.kept(config, z, valid), theirs)
        for keeper, theirs in zip(keepers, sets)]


def _sample(x):
    """At most GRAD_SAMPLE elements of `x`, flattened, at one stride: the
    smallest that keeps the count and shares no factor with the last
    dimension, so that every column is read (ceil(size / 65 536) alone is 6
    on a [2688, 128] router: its even columns only, 4 of 8 held experts)."""
    flat = x.reshape(-1)
    stride = -(-flat.size // GRAD_SAMPLE)
    while math.gcd(stride, x.shape[-1] if x.ndim else 1) != 1:
        stride += 1
    return flat[::stride]


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


def _first_moments(trainer, scope=None):
    """Right after the first step: a sample of every Adam first moment,
    (1 - beta1) x the gradient that step computed, taken to the host (a few
    MB), so that nothing of the check lies on the chip through the window.
    {parameter: (sample, 1 - beta1)}; parameters that no `adam` op updates
    are not compared."""
    import jax

    scope = trainer.scope if scope is None else scope
    moment = {}
    for block in trainer.main_program.blocks:
        for op in block.ops:
            if op.type == "adam":
                moment[op.inputs["Param"][0]] = (
                    op.inputs["Moment1"][0], 1.0 - op.attrs.get("beta1", 0.9))
    names = [p.name for p in trainer.main_program.parameters()
             if p.name in moment]
    samples = jax.device_get(jax.jit(lambda ms: [_sample(m) for m in ms])(
        [scope.get(moment[n][0]) for n in names]))
    return {n: (m, moment[n][1]) for n, m in zip(names, samples)}


def routed_layers(program):
    """The Program's routed ops, in order: every op that writes a
    `RouterLogits` output, by the names of its logits and its per-expert
    pair counts."""
    return [{"logits": op.outputs["RouterLogits"][0],
             "counts": op.outputs["TokensPerExpert"][0]}
            for block in program.blocks for op in block.ops
            if "RouterLogits" in op.outputs]


def choosing_layers(program):
    """The Program's ops that keep k of each row's candidates, in order:
    every op that writes a `Chosen` output (int32 [rows, k]), by its name."""
    return [op.outputs["Chosen"][0]
            for block in program.blocks for op in block.ops
            if "Chosen" in op.outputs]


def _first_step_again(trainer, model, scope, layers, choosers=()):
    """The training step, once more, as the trainer ran the timed first one:
    the same program through the same executor on a fresh startup's `scope`
    and the reader's first batch, with each routed layer's logits and each
    choosing layer's kept sets fetched beside what the trainer fetches.
    Returns (cost, sampled first moments, [(logits, counts) per routed
    layer], [`Chosen` per choosing layer], still on the device). The step
    donates and rebinds the scope's parameters: the caller drops the
    scope."""
    batch = next(iter(model["reader"]()))
    if model["feed_order"] is not None:
        from paddle_tpu.data.feeder import DataFeeder

        batch = DataFeeder(model["feed_order"]).feed(batch)
    program = trainer.main_program
    var = program.global_block().var
    # the trainer's own fetch list (`Trainer._train`: the cost, then what the
    # layers registered to be counted every step), and behind it only what
    # the window's program does not give out already
    names = [s["var"] for s in getattr(program, "step_statistics", ())]
    names += [layer[k] for layer in layers for k in ("logits", "counts")
              if layer[k] not in names]
    names += [c for c in choosers if c not in names]
    outs = trainer.exe.run(
        program, feed=batch, scope=scope, as_numpy=False,
        fetch_list=[trainer.cost] + [var(n) for n in names])
    got = dict(zip(names, outs[1:]))
    fetched = [(got[layer["logits"]], got[layer["counts"]])
               for layer in layers]
    return (float(outs[0]), _first_moments(trainer, scope), fetched,
            [got[c] for c in choosers])


def _startup_again(trainer, at_startup):
    """(scope, names of the parameters whose fingerprint is not the first
    startup's): the startup program run from the seed into a fresh scope."""
    from paddle_tpu import Scope

    scope = Scope()
    trainer.exe.run_startup(trainer.startup_program, scope=scope)
    names = [p.name for p in trainer.main_program.parameters()]
    return scope, [n for n, a, b in zip(
        names, at_startup, fingerprints(_parameters(trainer, scope))) if a != b]


def _own_compile_cache(path):
    """From here on the process builds the yardstick's programs only (the
    second reading's step program, the reference, the comparisons): they are
    kept in a persistent compile cache of their own, `path`, fixed inside the
    checkout. In the program's cache the hybrid's two (48 + 15 MiB) pushed
    the cell's step program out wherever parent and change share one cache
    under a cap (the chip tool's machines: 192 MiB), and `setup_s` paid a
    cold compile in every run (PERF.md section 6, PR 36)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", path)


def _after_the_window(ctx, trainer, model, at_startup, first_cost, moments):
    """The yardstick's turn, once the books are read and the profiler has
    stopped: the trained state is dropped, startup runs again from the seed
    and is held to the first startup's fingerprints, the first step of a
    program that gives out a discrete choice is read once more for it (its
    routers' logits, its kept sets; and startup run a third time, since the
    step consumed the second's weights), and the plain reference gives its
    cost and a sample of each parameter's gradient on those weights and the
    first batch (the reader is a function of the seed), under that choice.
    Returns what `correct` compares."""
    import os

    import jax
    import jax.numpy as jnp

    t_begin = time.time()
    _own_compile_cache(ctx.yardstick_cache_dir)
    trainer.scope.vars.clear()    # parameters and optimizer state, trained
    scope, differ = _startup_again(trainer, at_startup)
    out = {"choice": None, "kept": None, "second_reading": None}
    layers = routed_layers(trainer.main_program)
    choosers = choosing_layers(trainer.main_program)
    ref = ctx.load_module(
        os.path.join(os.path.dirname(ctx.model.__file__), "reference.py"))
    t_startup = t_again = time.time()
    fetched, sets = [], []
    if layers and not hasattr(ref, "chosen"):
        raise SystemExit(
            "chipbench: the program has routed layers (ops that write "
            "`RouterLogits`), so its reference.py has to give `chosen` "
            "and `loss_grads_and_routers(..., choice)` (README.md)")
    if choosers and not all(hasattr(ref, name) for name in (
            "kept", "scores", "loss_grads_routers_and_keepers")):
        raise SystemExit(
            "chipbench: the program has ops that keep k of a row's candidates "
            "(they write `Chosen`), so its reference.py has to give `kept`, "
            "`scores` and `loss_grads_routers_and_keepers(..., choice, kept)` "
            "(README.md)")
    if layers or choosers:
        cost2, moments2, fetched, sets = _first_step_again(
            trainer, model, scope, layers, choosers)
        both = [n for n in moments if n in moments2]
        off = jax.jit(relative_errors)([moments2[n][0] for n in both],
                                       [moments[n][0] for n in both])
        out["second_reading"] = {
            "cost_off_timed": abs(cost2 - first_cost) / max(1.0, abs(first_cost)),
            "moments_off_timed": dict(zip(both, (float(e) for e in off))),
            "moments_differing_in_a_bit": sum(
                not (moments[n][0] == moments2[n][0]).all() for n in both)}
        del scope                 # the step's new state goes
        scope, again = _startup_again(trainer, at_startup)
        differ = sorted(set(differ) | set(again))
        t_again = time.time()
    names = [p.name for p in trainer.main_program.parameters()]
    params = _parameters(trainer, scope)
    del scope                     # Adam's fresh moments go, the weights stay
    first = ref.prepare(next(iter(model["reader"]())))

    def reference(params, first, fetched, sets):
        if not layers and not choosers:
            cost, grads = ref.loss_and_grads(ctx.config, params, first)
            return cost, [_sample(g) for g in grads], [], []
        handed = ref.chosen(ctx.config, params,
                            [z for z, _ in fetched]) if layers else None
        if not choosers:
            cost, grads, routers = ref.loss_grads_and_routers(
                ctx.config, params, first, handed)
            keepers = []
        else:
            cost, grads, routers, keepers = ref.loss_grads_routers_and_keepers(
                ctx.config, params, first, choice=handed, kept=sets)
        own = ref.chosen(ctx.config, params,
                         [z for _, _, z in routers]) if layers else []
        numbers = [choice_numbers(router, mine, theirs,
                                  z.astype(jnp.float32), counts)
                   for router, mine, theirs, (z, counts) in zip(
                       routers, own, handed or [], fetched)]
        return (cost, [_sample(g) for g in grads], numbers,
                kept_numbers_by_layer(ref, ctx.config, keepers, sets))

    cost, grads, numbers, held = jax.jit(reference)(
        params, first, fetched, sets)
    out.update(reference_first_cost=float(cost), startup_differs=differ,
               gradient_errors=None)
    for key, by_layer in (("choice", numbers), ("kept", held)):
        if by_layer:
            out[key] = [{k: float(v) for k, v in layer.items()}
                        for layer in jax.device_get(by_layer)]
    if moments:
        refs = [g for n, g in zip(names, grads) if n in moments]
        scales = [s for _, s in moments.values()]

        def errors(samples, refs):
            return relative_errors([m.astype(jnp.float32) / s
                                    for m, s in zip(samples, scales)], refs)

        errs = jax.jit(errors)([m for m, _ in moments.values()], refs)
        out["gradient_errors"] = dict(zip(moments, (float(e) for e in errs)))
    out["after_window_s"] = {"second_startup": t_startup - t_begin,
                             "first_step_again": t_again - t_startup,
                             "reference": time.time() - t_again}
    return out


def relative_errors(grads, refs):
    """Per tensor, rms(gradient - reference) over the reference's rms, the
    latter no smaller than a tenth of the median tensor's."""
    import jax.numpy as jnp

    ref_rms = jnp.stack([_rms(g) for g in refs])
    diff = jnp.stack([_rms(g - r) for g, r in zip(grads, refs)])
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
    check = _after_the_window(ctx, trainer, model, at_startup,
                              win.first_cost, win.moments)
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
            "lowest_of_last_costs": lowest_of_last_costs(run["costs"]),
            "reference_first_cost": run["reference_first_cost"],
            "gradient_error_worst": worst and [worst, errs[worst]],
            "gradient_error_median": errs and sorted(errs.values())[len(errs) // 2],
            "gradient_errors_largest": errs and sorted(
                errs.items(), key=lambda kv: -kv[1])[:4],
            "choice_by_layer": run.get("choice"),
            "kept_by_layer": run.get("kept"),
            "second_reading": run.get("second_reading") and dict(
                run["second_reading"], moments_off_timed=sorted(
                    run["second_reading"]["moments_off_timed"].items(),
                    key=lambda kv: -kv[1])[:4]),
            "startup_differs": run["startup_differs"],
            "setup_split_s": run["setup_split_s"],
            "after_window_s": run["after_window_s"],
            "peak_after_startup": run["peak_after_startup"],
            "peak_final": run.get("memory_peaks")}


LAST_COSTS = 3


def lowest_of_last_costs(costs):
    """The smallest of the window's last LAST_COSTS fenced cost reads (of
    those there are, where fewer): what "the loss fell" holds against the
    first cost (`last_cost_over_first`). Until PR 59 it was the last read
    alone, and a window's step count follows the host's timing, so a sound
    run that happened to end on a step whose cost spikes read NOT `correct`:
    `gpt2-small.train`, seed 2147400121, traced, 43 steps ending at 14.83
    where 44 end at 6.214, parent and change alike (my chip run, PR 40); the
    PARENT's traced run in PR 52's check, `last_cost_over_first` 1.2353 with
    every other number sound (ledger, PR 52: `outputs_incorrect`). A spike
    is one step's; a loss that does not fall stands over the first cost in
    every read."""
    return min(costs[-LAST_COSTS:])


def compared(run):
    """{name: [number, limit]}: every number `correct` holds to a limit, for
    the run's last lines. The gradient is the worst tensor's (every tensor
    has the one limit); a program that gives out a discrete choice adds the
    second reading's tie to the timed step and the numbers that hold each
    kind of choice, each the worst layer's."""
    want, tol = run["reference_first_cost"], run["tolerances"]
    errs = run["gradient_errors"] or {}
    out = {"startup_tensors_differing": [len(run["startup_differs"]), 0],
           "cost_reads_not_finite": [run["bad_intervals"], 0],
           "last_cost_over_first": [
               lowest_of_last_costs(run["costs"]) / run["first_cost"], 1.0],
           "first_cost_off_reference": [
               abs(run["first_cost"] - want) / max(1.0, abs(want)),
               tol["reference_tol"]],
           "programs_built_in_window": [run["counters"]["programs_built"], 0],
           "cache_misses_in_window": [run["counters"]["cache_misses"], 0]}
    if errs:
        out["gradient_error_nearest_limit"] = [max(errs.values()),
                                               tol["grad_tol"]]
    second, choice = run.get("second_reading"), run.get("choice")
    kept = run.get("kept")
    if second:
        out["second_reading_cost_off_timed"] = [
            second["cost_off_timed"], SECOND_COST_TOL]
        out["second_reading_moments_off_timed"] = [
            max(second["moments_off_timed"].values()), SECOND_GRAD_TOL]
    if choice:
        def worst(key):
            return max(abs(layer[key]) for layer in choice)

        out["choice_counts_off_program"] = [worst("counts_off_program"), 0]
        out["router_weight_rounding_share"] = [
            worst("weight_rounding_share"), ROUTER_TOL]
        out["turned_rows_not_near_tie"] = [worst("turned_not_near_tie"), 0]
        share = max(choice, key=lambda layer: layer["turned_share"]
                    - layer["near_tie_share"])
        out["turned_row_share"] = [share["turned_share"],
                                   share["near_tie_share"]]
    if kept:
        out["kept_sets_off_rule"] = [
            max(layer["sets_off_rule"] for layer in kept), 0]
        out["kept_turned_not_near_tie"] = [
            max(layer["turned_not_near_tie"] for layer in kept), 0]
    return out


# what `correct` says of a compared number that is over its limit
_SAYS = {
    "first_cost_off_reference":
        "the first cost is off the plain reference's",
    "second_reading_cost_off_timed":
        "the first step read again for its discrete choices gave another "
        "cost than the timed first step",
    "second_reading_moments_off_timed":
        "the first step read again gave other first moments than the timed "
        "first step: the worst tensor's",
    "choice_counts_off_program":
        "the published top-k rule on the program's own router logits gives "
        "other pair counts an expert than the program's TokensPerExpert",
    "router_weight_rounding_share":
        "the program's router logits hold this share of what rounding the "
        "router's weight to bf16 does to them",
    "turned_rows_not_near_tie":
        "rows chose other experts than the reference's own router where the "
        "reference is not near a tie",
    "turned_row_share":
        "more rows chose other experts than the reference's router has near "
        "ties",
    "kept_sets_off_rule":
        "rows whose `Chosen` is not exactly min(k, valid candidates) distinct "
        "valid candidates",
    "kept_turned_not_near_tie":
        "rows kept other candidates than the reference's own top k where the "
        "reference's scores are not near a tie",
}


def correct(run):
    """What a train cell owes: finite costs, a loss that fell, a first step
    that agrees with the plain reference on weights the seed gives again
    (under the program's own choice of experts and its own kept sets, each
    held on its own: see above), and nothing built inside the window.
    Returns a list of what failed (empty = ok)."""
    bad = []
    if run["startup_differs"]:
        bad.append(f"startup did not reproduce the weights: run again from "
                   f"the same seed after the window, {run['startup_differs']} "
                   f"differ, so the plain reference saw other weights than "
                   f"the first step")
    costs = run["costs"]
    if not costs or run["bad_intervals"] or not math.isfinite(run["first_cost"]):
        bad.append("a cost read was not finite")
    elif not lowest_of_last_costs(costs) < run["first_cost"]:
        bad.append(f"the loss did not fall: first {run['first_cost']}, "
                   f"the last reads {costs[-LAST_COSTS:]}")
    numbers = compared(run)
    for name, says in _SAYS.items():
        value, limit = numbers.get(name, (0, 0))
        if not value <= limit:
            bad.append(f"{says}: {name} {value} (> {limit})")
    limit = run["tolerances"]["grad_tol"]
    for name, err in (run["gradient_errors"] or {}).items():
        if not err <= limit:
            bad.append(f"the first step's gradient of {name} is off the plain "
                       f"reference's by {err} of its rms (> {limit})")
    if run["counters"]["programs_built"] or run["counters"]["cache_misses"]:
        bad.append(f"programs were built inside the window: {run['counters']}")
    return bad
