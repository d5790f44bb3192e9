"""Executor: compile a Program into one XLA computation and run it.

Reference: paddle/framework/executor.cc:78-146 interprets a BlockDesc op by op
(create vars :86-112, dispatch loop :117-146) with per-op kernels. That
imperative semantics is kept as the *spec*; the TPU implementation traces the
whole block into a single jitted function (per feed-shape bucket) so XLA can
fuse across ops — the op-by-op interpreter would serialize the TPU.

Scope (name → value) mirrors paddle/framework/scope.h:38; persistable vars
(parameters, optimizer state, BN stats) live in the Scope across run() calls,
temporaries live only inside the traced function.

Autodiff: the `autodiff` meta-op (inserted by core/backward.py, the
counterpart of fluid backward.py:338 append_backward) is executed by tracing
the forward op slice in front of it ONCE, as a function of the parameters
under jax.value_and_grad, which hands back the gradients and the forward's
environment — replacing the reference's per-op grad-desc rewriting
(framework/backward.cc, grad_op_desc_maker.h) with one functional transform.
"""

from __future__ import annotations

import contextlib
import logging
import weakref
from typing import Any, Dict, FrozenSet, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import build as builds
from . import provenance, registry
from .. import profiler
from ..flags import FLAGS
from ..obs import metrics
from ..tune import overrides as tune_overrides
from .lod import LoDArray
from .place import Place, default_place
from .program import Program, Variable, default_main_program, grad_var_name
from .sparse import SelectedRows, SparseGradTape

logger = logging.getLogger("paddle_tpu.executor")


# remat policies: "full" recomputes everything in the backward pass;
# "dots" keeps matmul/conv results (cheap to store, expensive to recompute)
_REMAT_POLICIES = {
    "full": None,
    "dots": jax.checkpoint_policies.dots_saveable,
    "dots_no_batch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


def memory_optimize(program=None, policy: str = "dots") -> None:
    """Reference API: fluid memory_optimization_transpiler.memory_optimize

    (liveness-based forward-activation reuse). TPU equivalent: enable
    rematerialization of the forward slice inside the backward pass.

    What it does not do: the whole forward closure becomes ONE
    `jax.checkpoint` region (`_run_autodiff`), so the backward pass first
    runs the whole forward again and then holds all of its activations at
    once: with policy "full" the peak is no lower than without it, and the
    policies only choose what of the first run is kept as well. Nothing is
    rematerialised a region at a time. A model whose memory is K passes over
    one stack of layers gets that from `layers.Repeat(remat=True)`: the loop
    saves each turn's carry and recomputes one turn at a time, all but the
    last."""
    program = program or default_main_program()
    if policy not in _REMAT_POLICIES:
        raise ValueError(
            f"unknown remat policy {policy!r}; choose from "
            f"{sorted(_REMAT_POLICIES)}"
        )
    program.remat_policy = policy


def _check_finite(values: Dict[str, Any]) -> None:
    bad = []
    for name, v in values.items():
        arrs = jax.tree_util.tree_leaves(v)
        for a in arrs:
            if hasattr(a, "dtype") and np.issubdtype(np.dtype(a.dtype), np.floating):
                if not bool(jnp.all(jnp.isfinite(a))):
                    bad.append(name)
                    break
    if bad:
        raise FloatingPointError(
            f"check_nan_inf: non-finite values in {sorted(bad)}"
        )


class Scope:
    """name → runtime value store (reference: paddle/framework/scope.h:38).

    A program that rebinds a persistable consumes its old buffer (the
    Executor donates it, as the reference updates a scope tensor in
    place): an array read with `get` is dead once such a step has run.
    To keep a value, copy it: `np.asarray(scope.get(name))`."""

    def __init__(self):
        self.vars: Dict[str, Any] = {}
        # what an Executor's step plan is valid against: `layout` moves
        # when `set` adds a name (replacing a value leaves it), `writes`
        # with every `set`
        self.layout = 0
        self.writes = 0

    def set(self, name: str, value) -> None:
        if name not in self.vars:
            self.layout += 1
        self.writes += 1
        self.vars[name] = value

    def get(self, name: str):
        return self.vars[name]

    def has(self, name: str) -> bool:
        return name in self.vars

    def keys(self):
        return self.vars.keys()


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


def reset_global_scope() -> None:
    global _global_scope
    _global_scope = Scope()


def accum_fold(state, cost, metrics, skip_nonfinite):
    """One on-device cost/metric accumulator fold — THE shared definition
    of the pass-stats math. The Trainer's per-step jitted `_accum_update`
    and the windowed executor's in-scan fold both call this, so the two
    cadences cannot drift numerically (the fixed-seed A/B demands equal
    pass metrics, not just equal params).

    state: (n_good, cost_sum, [metric_sums...], n_bad) — int32/float32
    scalars; a metric sum may also be a vector, of any dtype (a Program's
    step statistics, `Program.add_step_statistic`: int32 counts), and the
    step's value is then added in the sum's own shape and dtype.
    skip_nonfinite (StepGuard armed) gates a non-finite step's
    cost/metrics out of the stats; the `bad` counter is what the guard
    reads on its sync cadence."""
    n, cost_sum, metric_sums, bad = state
    c = jnp.reshape(jnp.asarray(cost, jnp.float32), ())
    finite = jnp.isfinite(c)
    good = finite if skip_nonfinite else jnp.asarray(True)
    n = n + good.astype(jnp.int32)
    cost_sum = cost_sum + jnp.where(good, c, 0.0)
    metric_sums = [
        m + jnp.where(good, jnp.reshape(jnp.asarray(v, m.dtype), m.shape),
                      jnp.zeros((), m.dtype))
        for m, v in zip(metric_sums, metrics)
    ]
    bad = bad + (~finite).astype(jnp.int32)
    return n, cost_sum, metric_sums, bad


def _feed_signature(feed: Dict[str, Any]):
    sig = []
    for k in sorted(feed):
        v = feed[k]
        leaves, treedef = jax.tree_util.tree_flatten(v)
        sig.append(
            (
                k,
                str(treedef),
                tuple((tuple(l.shape), np.dtype(l.dtype).name) for l in leaves),
            )
        )
    return tuple(sig)


def _device_feed(feed) -> Dict[str, Any]:
    """A copy of `feed` with its numpy values as jax arrays. Committed jax
    arrays (the DevicePrefetcher path puts every batch on device ahead of
    time) pass through untouched: re-wrapping them in jnp.asarray would
    re-hash and re-place each one every batch."""
    return {k: jnp.asarray(v) if isinstance(v, np.ndarray) else v
            for k, v in (feed or {}).items()}


def _fetch_names(fetch_list) -> tuple:
    return tuple(v.name if isinstance(v, Variable) else v
                 for v in (fetch_list or ()))


def _op_scope(op) -> str:
    """`<op type>.<first output variable>`: what an op's kernels are
    called in a profile."""
    first = next((n for names in op.outputs.values() for n in names), "")
    return f"{op.type}.{first}" if first else op.type


def _is_array_tree(name, value) -> bool:
    """An array or a pytree of arrays (a LoD tensor, a list, None): what a
    traced closure can return. A Python number, string or object is static."""
    kinds = {isinstance(leaf, (jax.Array, np.ndarray, np.generic))
             for leaf in jax.tree_util.tree_leaves(value)}
    if len(kinds) == 2:  # it could leave the closure neither way
        raise TypeError(f"{name!r} is bound to a pytree that mixes arrays "
                        f"with static leaves: {value!r}")
    return kinds != {False}


class _BlockRunner:
    """Trace-time walk over a block's ops. Also handed to control-flow
    kernels (via ctx.executor) so sub-blocks can be traced into
    lax.scan/while_loop bodies. `place_grad(program, name, grad)` says where
    a dense gradient lives (the ParallelExecutor's mesh)."""

    def __init__(self, program: Program, place_grad=None):
        self.program = program
        self.place_grad = place_grad or (lambda program, name, grad: grad)

    def run_ops(self, ops, env: Dict[str, Any], entry_env: Dict[str, Any], block):
        # the ops in front of the (last) autodiff op are traced once, inside
        # its differentiation: `_run_autodiff` binds in `env` what they bound
        first = max((i + 1 for i, op in enumerate(ops)
                     if op.type == "autodiff"), default=0)
        if first:
            self._run_autodiff(ops[:first - 1], ops[first - 1], env,
                               entry_env, block)
        for i, op in enumerate(ops[first:], first):
            kernel = registry.get_kernel(op.type)
            ctx = registry.OpContext(op, env, executor=self, block=block)
            try:
                # trace time only: the op's name in every HLO
                # instruction's op_name metadata, forward and (for the
                # ops `_run_autodiff` differentiates) transposed
                with jax.named_scope(_op_scope(op)):
                    kernel(ctx)
            except Exception as e:
                # CustomStackTrace parity (utils/CustomStackTrace.h:51):
                # name the failing op and its I/O so trace errors point at
                # the model line, not the kernel internals. RuntimeError
                # (not type(e)) — arbitrary exception ctors don't take a
                # message string; the original stays chained below.
                raise RuntimeError(
                    f"{e}\n  while executing op #{i} {op.type!r} "
                    f"(block {block.idx}) inputs={op.inputs} "
                    f"outputs={op.outputs}"
                ) from e
        return env

    def run_block(self, block_idx: int, env: Dict[str, Any]):
        block = self.program.blocks[block_idx]
        return self.run_ops(block.ops, env, dict(env), block)

    def _run_autodiff(self, fwd_ops, op, env, entry_env, block):
        """Trace `fwd_ops` once, under differentiation, from the block's
        entry. `env` gets the gradients and everything the forward ops bound
        (the cost, activations, step statistics, rebound persistables): the
        ops behind the autodiff op and the fetches read the differentiated
        forward's values. Arrays leave the closure as its auxiliary output;
        what is static (the `@RNG_COUNTER@` dropout advanced) through `static`.
        An earlier autodiff op among `fwd_ops` is differentiated inside this
        one's closure, by the `run_ops` there."""
        loss_name = op.inputs["Loss"][0]
        param_names = list(op.attrs["params"])

        # params marked sparse_update get SelectedRows grads: their lookup
        # sites route through a SparseGradTape so no dense [vocab, dim]
        # gradient is ever materialized (framework/selected_rows.h parity)
        sparse_names = [p for p in param_names if getattr(
            self._var_or_none(block, p), "sparse_update", False)]
        dense_names = [p for p in param_names if p not in sparse_names]
        pvals = {p: env[p] for p in dense_names}
        static: Dict[str, Any] = {}

        def closure(pv: Dict[str, Any], slots, tape=None):
            tape = tape or SparseGradTape(sparse_names, slots=list(slots))
            start = {**entry_env, **pv, "@SPARSE_TAPE@": tape}
            env2 = dict(start)
            self.run_ops(fwd_ops, env2, dict(entry_env), block)
            loss = env2[loss_name]
            if getattr(loss, "size", 1) != 1:
                raise ValueError(f"loss {loss_name!r} must be scalar for "
                                 f"append_backward; got shape {loss.shape}")
            bound = {k: v for k, v in env2.items()
                     if k not in start or v is not start[k]}
            arrays = {k: v for k, v in bound.items() if _is_array_tree(k, v)}
            static.update({k: bound[k] for k in bound.keys() - arrays.keys()})
            return jnp.reshape(loss, ()), (
                arrays, [r for (_, r) in tape.ids_out])

        sites = []
        if sparse_names:
            # a sparse_update param may ONLY be consumed by lookup_table ops:
            # any other use (e.g. a tied-embedding output projection through
            # mul) would silently contribute zero gradient, because the param
            # is stop_gradient'ed at lookup sites and excluded from the
            # differentiated inputs. Static walk over every block catches it.
            for o in (o for blk in self.program.blocks for o in blk.ops):
                # optimizer update ops legitimately consume the param and
                # its SelectedRows grad (ops/optimizer_ops.py handles both)
                if o.type in ("lookup_table", "autodiff") or \
                        o.attrs.get("is_optimizer_op"):
                    continue
                used = [n for ns in o.inputs.values() for n in ns
                        if n in sparse_names]
                if used:
                    raise ValueError(
                        f"sparse_update param(s) {used} consumed by op "
                        f"{o.type!r}: SelectedRows gradients only support "
                        "lookup_table uses — rebuild the embedding with "
                        "is_sparse=False for tied/shared-weight patterns"
                    )
            # abstract pass (no FLOPs): discover gather sites and shapes
            disco = SparseGradTape(sparse_names)
            jax.eval_shape(lambda pv: closure(pv, None, disco), pvals)
            sites = disco.sites
            missing = sorted(set(sparse_names) - {s[0] for s in sites})
            if missing:
                raise ValueError(
                    f"sparse_update params {missing} have no lookup_table "
                    "site in the program — only embedding gathers support "
                    "SelectedRows gradients")

        # the one trace: differentiate w.r.t. the dense params AND the per-site
        # row slots (none in a dense program): their cotangents are the rows
        policy = getattr(self.program, "remat_policy", None)
        if policy:
            closure = jax.checkpoint(closure, policy=_REMAT_POLICIES[policy])
        slots0 = [jnp.zeros(shape, dt) for (_, shape, dt) in sites]
        (_, (bound, rows_aux)), (grads, slot_grads) = jax.value_and_grad(
            closure, argnums=(0, 1), has_aux=True)(pvals, slots0)
        env.update({**bound, **static})
        for p in dense_names:
            env[grad_var_name(p)] = self.place_grad(self.program, p, grads[p])
        site_params = [s[0] for s in sites]
        for p in sparse_names:
            num_rows, dim = env[p].shape
            rows = [r.reshape(-1) for sp, r in zip(site_params, rows_aux)
                    if sp == p]
            vals = [g.reshape(-1, dim)
                    for sp, g in zip(site_params, slot_grads) if sp == p]
            env[grad_var_name(p)] = SelectedRows(
                jnp.concatenate(rows), jnp.concatenate(vals), num_rows)

    @staticmethod
    def _var_or_none(block, name):
        try:
            return block.var(name)
        except KeyError:
            return None


def rebound_persistables(program: Program) -> FrozenSet[str]:
    """The persistables `program` rebinds (parameters, optimizer moments,
    beta powers, batch-norm statistics, `@AVG@` sums, a scheduled
    learning rate): named by a declared op output or by an input slot
    the op's kernel registered as written (`register_op(writes=...)`),
    in any block. One walk per program version. This is the set the
    Executor donates; a program that rebinds nothing (inference,
    `clone(for_test=True)`, generation) donates nothing."""
    cached = getattr(program, "_rebound_persistables", None)
    if cached is not None and cached[0] == program.version:
        return cached[1]
    persist = {v.name for v in program.persistables()}
    names = frozenset(
        n for block in program.blocks for op in block.ops
        for n in registry.written_names(op) if n in persist
    )
    program._rebound_persistables = (program.version, names)
    return names


def _own_buffers(donated, kept=()):
    """Give every donated leaf a buffer no other argument of the call
    shares: PJRT refuses a buffer donated twice, or donated and read, in
    one call, and a name left bound to a donated array would read a
    dead one. A second name of an array (`scope.set(b, scope.get(a))`)
    gets a copy once; from then on each name has its own output. Host
    values pass: jit donates the temporary it makes of them."""
    leaves, treedef = jax.tree_util.tree_flatten(donated)
    others = jax.tree_util.tree_leaves(kept)
    if len({id(a) for a in leaves} | {id(a) for a in others}) == \
            len(leaves) + len(others):
        return donated
    seen = {id(a) for a in others}
    for i, a in enumerate(leaves):
        if id(a) in seen and isinstance(a, jax.Array):
            leaves[i] = jnp.array(a, copy=True)
        seen.add(id(a))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _tree_bytes(tree) -> tuple:
    leaves = jax.tree_util.tree_leaves(tree)
    return len(leaves), sum(
        int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize for a in leaves)


class _StepPlan:
    """What `Executor.run` / `run_window` derive from the program, the
    names the scope holds, the feed's signature and the fetch list, and
    not from a step's values: the persistable names in the order they are
    passed (sorted, as the state dicts flatten), split into the donated
    and the kept, and the compiled function. Valid while the scope's
    layout is `stamp`; every other input is in the plan's key. It holds
    names, never an array: the scope's reference to a donated buffer stays
    the last one.

    `clean_at` is the scope's `writes` when this plan's last call had
    committed, None before one: while the scope still reads that, every
    name holds what this executor put there (a step's own outputs, each
    its own array; the kept ones placed), so the proof that no buffer is
    donated twice and the placing of the kept need not be made again."""

    __slots__ = ("stamp", "program", "donated", "kept", "fn", "clean_at")

    def __init__(self, stamp, program, donated, kept):
        self.stamp, self.program = stamp, program
        self.donated, self.kept = donated, kept
        self.fn = self.clean_at = None


_PLANS_COUNTER = "pt_executor_plans_total"
_PLANS_HELP = ("Executor.run / run_window calls by what became of the step "
               "plan: built (the first call, or the scope's names or the "
               "program moved) or reused")
_DONATION_KEYS = ("donated_buffers", "donated_bytes", "kept_buffers",
                  "kept_bytes", "mismatches")
# every live Executor, for the registry's pt_executor_* gauges
_executors = weakref.WeakSet()


def donation_totals() -> Dict[str, int]:
    """`Executor.donation_stats` summed over the live executors, empty
    while there is none (read by the obs.metrics collector; no device
    access)."""
    live = list(_executors)
    if not live:
        return {}
    totals = dict.fromkeys(_DONATION_KEYS, 0)
    for exe in live:
        for k, v in exe.donation_stats.items():
            totals[k] += v
    return totals


def provenance_tables() -> List[dict]:
    """The live executors' `provenance.table`s, cut to their `published` rows,
    one a step program compiled while `FLAGS.enable_timers` was on (read by
    the obs.metrics collector; no device access)."""
    return [t for exe in list(_executors) for t in exe._provenance]


class Executor:
    """Reference API: fluid executor.py:71 `Executor(place).run(program,

    feed, fetch_list)`. Compilation is cached per (program version, feed
    shapes, fetch list), and so is a call's bookkeeping: which persistables
    the scope holds, in which order they are passed, which are donated
    (`_StepPlan`). A call whose plan is valid gathers the arrays, draws
    the seed, calls and writes back; the plan is rebuilt when a name is
    added to the scope or the program's version moves.

    The buffers of the persistables a program rebinds
    (`rebound_persistables`) are donated to the step, so a training step
    updates its parameters and optimizer state in place: their previous
    arrays are deleted by the run, and `np.asarray(scope.get(name))` is
    the way to keep a value across one. A program that rebinds nothing
    donates nothing. `donation_stats` says what engaged."""

    # consulted by the Trainer's pipelined loop: the base executor wants
    # the default DevicePrefetcher (host->device copies overlap compute)
    # and its fetches can feed the jitted on-device metric accumulator.
    # The ParallelExecutor overrides both — it owns input placement via
    # _place_inputs, and its mesh-committed fetches cannot be folded into
    # a single-device accumulator without a gather.
    prefetch_by_default = True
    device_metric_accumulation = True
    # run_window (K fused steps under one lax.scan) assumes single-device
    # carries; the ParallelExecutor disables it until the window path is
    # explicitly threaded through the mesh (ISSUE 6 scope note)
    scan_window_supported = True

    def __init__(self, place: Optional[Place] = None):
        self.place = place or default_place()
        self._cache: Dict[Any, Any] = {}
        # jit-cache accounting (the serving layer surfaces these in
        # /metrics): a miss = one whole-program trace + XLA compile
        # `plans_built` / `plans_reused`: calls that listed the scope's
        # persistables anew / that found their step plan valid
        self.cache_stats: Dict[str, int] = {
            "hits": 0, "misses": 0, "plans_built": 0, "plans_reused": 0}
        # scope -> {plan key: _StepPlan}; a plan dies with its scope
        self._plans = weakref.WeakKeyDictionary()
        for outcome in ("built", "reused"):
            metrics.registry().declare_counter(
                _PLANS_COUNTER, help=_PLANS_HELP, labels={"outcome": outcome})
        # one record per compiled step program, written when it traces
        self._donation: List[Dict[str, int]] = []
        # one `provenance.table`, its published rows, per step program compiled
        # with timers on
        self._provenance: List[dict] = []
        # one `build.Build` per step program built, in order, and what the
        # last build of each Program depended on (keyed by its id(): the
        # cache keeps the Program alive)
        self.builds: List[builds.Build] = []
        self._built: Dict[int, tuple] = {}
        _executors.add(self)

    @property
    def donation_stats(self) -> Dict[str, int]:
        """Over the step programs this executor compiled: persistable
        buffers and bytes donated (rebound by the program) and kept
        (only read), and `mismatches`: persistables a trace rebound
        though `rebound_persistables` did not name them (left undonated;
        a kernel lacks its `register_op(writes=...)`)."""
        return {k: sum(d.get(k, 0) for d in self._donation)
                for k in _DONATION_KEYS}

    def cache_size(self) -> int:
        """Number of compiled (program, feed-signature) entries held."""
        return len(self._cache)

    # -- subclass hooks (ParallelExecutor overrides these) -------------
    def _cache_key_prefix(self) -> tuple:
        return ()

    @staticmethod
    def _program_trace_key(program: Program) -> tuple:
        """Everything program-side that affects the trace — shared by the
        per-step and windowed compile caches."""
        return (
            id(program),
            program.version,
            program.amp_dtype,
            program.remat_policy,
            # trace-affecting flags (all feed fused-kernel dispatch)
            FLAGS.use_fused_rnn,
            FLAGS.fused_rnn_interpret,
            FLAGS.use_fused_attention,
            FLAGS.fused_attention_interpret,
            FLAGS.use_fused_conv,
            # the kernel configs a sweep or a test has forced, () when
            # none: a change re-traces instead of reusing the old tile
            tune_overrides.forced_key(),
        )

    def _compile(self, program: Program, feed, fetch_names, persist_names):
        """Build + wrap the traced block walk. Base: plain jax.jit, the
        donated state its donated argument."""
        return jax.jit(self._raw_step(program, fetch_names),
                       donate_argnums=(0,))

    def _read_provenance(self, fn, *args) -> str:
        """A traced run's record of a freshly compiled step program: which
        program op each instruction of its optimized HLO belongs to
        (`core/provenance.py`), for the readers of a device trace. The
        lowering and the compile (or cache read) happen here, ahead of the
        first call and while the arguments are alive; `fn` itself is called
        as ever and finds jit's caches full. Returns the table's label of
        the program."""
        text = fn.lower(*args).compile().as_text()
        table = provenance.table(text)
        # kept for the process's life: only what a trace cannot name itself
        self._provenance.append(
            dict(table, rows=provenance.published(table["rows"])))
        return table["program"]

    def _device_context(self):
        return jax.default_device(self.place.device)

    def _trace_context(self):
        """Hook: context active while the jitted step traces/runs. The
        ParallelExecutor overrides this to declare its mesh to the
        fused-kernel dispatch layer (ops/mesh_dispatch.py), which then
        shard_maps eligible pallas calls over the dp axis."""
        return contextlib.nullcontext()

    # -- the step plan --------------------------------------------------
    def _plan_key(self, program: Program, mode: tuple, feed,
                  fetch_names: tuple) -> tuple:
        """What a step plan and its compiled function depend on besides
        the names the scope holds. `mode` tells a window from a step."""
        return self._cache_key_prefix() + self._program_trace_key(program) \
            + mode + (_feed_signature(feed), fetch_names)

    def _planned_state(self, program: Program, scope: Scope, key: tuple):
        """(plan, donated, kept): the call's `_StepPlan`, built if `scope`
        has none under `key` or has gained or lost a name since, and the
        persistables gathered by it. Only a scope somebody else wrote since
        this plan's last call is proven again to hand no buffer over twice
        (`_own_buffers`) and walked for host values (`_place_kept`)."""
        plans = self._plans.get(scope)
        if plans is None:
            plans = self._plans[scope] = {}
        plan = plans.get(key)
        # the length: a caller may empty `scope.vars` behind `set`'s back
        stamp = (scope.layout, len(scope.vars))
        if plan is None or plan.stamp != stamp:
            rebound = rebound_persistables(program)
            names = sorted(v.name for v in program.persistables()
                           if scope.has(v.name))
            # keeps the program alive: the key holds its id(), which
            # could be recycled if the program were garbage collected
            plan = plans[key] = _StepPlan(
                stamp, program,
                tuple(n for n in names if n in rebound),
                tuple(n for n in names if n not in rebound))
            outcome = "built"
        else:
            outcome = "reused"
        self.cache_stats["plans_" + outcome] += 1
        metrics.registry().counter_inc(_PLANS_COUNTER,
                                       labels={"outcome": outcome})
        held = scope.vars
        donated = {n: held[n] for n in plan.donated}
        kept = {n: held[n] for n in plan.kept}
        if plan.clean_at != scope.writes:
            donated = _own_buffers(donated, kept)
            kept = self._place_kept(program, scope, kept)
        plan.clean_at = None  # until this call has committed
        return plan, donated, kept

    def _planned_fn(self, plan: _StepPlan, key: tuple, build,
                    kind: str = "step"):
        """The plan's compiled function, looked up (or built by
        `build(persist_names)`) the first time. A function just built comes
        back inside `_first_call`, which is what the caller then calls."""
        if plan.fn is None:
            names = sorted(plan.donated + plan.kept)
            full = key + (tuple(names),)
            cached = self._cache.get(full)
            if cached is None:
                self.cache_stats["misses"] += 1
                plan.fn = build(names)
                self._cache[full] = (plan.program, plan.fn)
                return self._first_call(plan, key, full[-1], kind)
            plan.fn = cached[1]
        self.cache_stats["hits"] += 1
        return plan.fn

    def _first_call(self, plan: _StepPlan, key: tuple, names: tuple,
                    kind: str):
        """`plan.fn`, for its first call only, as a build (`core/build.py`):
        the call traces, lowers and compiles inside an `executor.build` span
        that says which program this is and why it was built, and a traced
        run reads the program's provenance there, ahead of the call. `key`
        ends in the feed's signature and the fetch names; a Program run with
        neither is a startup program."""
        depends = (names, key[-2], key[-1], plan.program.version, key[:-2])
        if kind == "step" and not (key[-2] or key[-1]):
            kind = "startup"
        record = builds.Build(
            self.builds, kind,
            builds.cause(self._built.get(id(plan.program)), depends),
            getattr(plan.fn, "__name__", ""))
        self._built[id(plan.program)] = depends
        fn = plan.fn

        def first_call(*args):
            with record:
                if FLAGS.enable_timers:
                    with record.provenance():
                        record.args["module"] = self._read_provenance(
                            fn, *args)
                return fn(*args)

        return first_call

    # ------------------------------------------------------------------
    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        as_numpy: Optional[bool] = None,
    ):
        """as_numpy=False keeps fetches as device arrays so the run does
        NOT fence XLA's async dispatch queue — the pipelined Trainer loop
        reads them back only on its sync cadence. Default (None) follows
        return_numpy (the reference fluid API name)."""
        if as_numpy is None:
            as_numpy = return_numpy
        with profiler.timer("executor.prepare"):
            program = program or default_main_program()
            scope = scope or global_scope()
            feed = _device_feed(feed)
            fetch_names = _fetch_names(fetch_list)
            key = self._plan_key(program, (), feed, fetch_names)
            plan, donated, kept = self._planned_state(program, scope, key)
            # a host scalar: the call places it with its other arguments
            seed = np.uint32(self._draw_seed(program))
            donated, feed, seed = self._place_inputs(
                program, donated, feed, seed)
            fn = self._planned_fn(
                plan, key,
                lambda names: self._compile(program, feed, fetch_names, names))
        with self._device_context(), self._trace_context(), \
                profiler.timer("executor.call"):
            fetches, new_state, extras = fn(donated, kept, feed, seed)
        with profiler.timer("executor.commit"):
            new_state.update(extras)
            if FLAGS.check_nan_inf:
                # reference: CheckTensorNANOrInf per op output behind
                # FLAGS_check_nan_inf (fluid executor.cc:60-72,125-133).
                # Under whole-program jit the checkable boundary is the
                # run: every persistable output + fetch (costs a host
                # sync — debug flag).
                _check_finite(
                    {**new_state,
                     **{n: f for n, f in zip(fetch_names, fetches)}}
                )
            for n, v in new_state.items():
                scope.set(n, v)
            plan.clean_at = scope.writes
            # the scope held the other reference to every replaced
            # (donated: deleted by the call) array: drop the last one
            # here, so that it is timed in this span and not in the return
            del donated
            if as_numpy:
                fetches = [
                    np.asarray(f) if not isinstance(f, LoDArray) else f
                    for f in fetches
                ]
        return fetches

    # ------------------------------------------------------------------
    def _draw_seed(self, program) -> int:
        """Per-run RNG seed for dropout etc. (fresh when random_seed==0).
        Hook: the multi-process ParallelExecutor must return the SAME
        value on every process — SPMD programs diverge otherwise."""
        return (
            np.random.randint(0, 2**31 - 1) if program.random_seed == 0
            else program.random_seed
        )

    # ------------------------------------------------------------------
    def run_startup(self, program, scope=None):
        """Run a startup (init) program. Same as run() here; the
        ParallelExecutor overrides this to init on the local device —
        parameters land on the mesh via _place_inputs at the first
        parallel step, and a mesh-shaped compile of the init program
        would have to declare output shardings for values that do not
        exist yet."""
        return self.run(program, scope=scope)

    # ------------------------------------------------------------------
    def _place_inputs(self, program, state, feed, seed):
        """Hook: place host values onto devices before the jitted call.

        The base executor lets jit commit single-device inputs; the
        multi-process ParallelExecutor overrides this with explicit
        device_puts (jit cannot reshard onto devices it cannot address)."""
        return state, feed, seed

    def _place_kept(self, program, scope, kept: Dict[str, Any]):
        """Hook: the step does not return the persistables it only reads,
        so a host value among them (`load_checkpoint`, a served artifact:
        every parameter of an inference program) would be uploaded again
        by every run. Commit it to the device once and leave it in the
        scope. The ParallelExecutor places them on its mesh."""
        for n, v in kept.items():
            if not isinstance(v, jax.Array):
                kept[n] = jax.device_put(v, self.place.device)
                scope.set(n, kept[n])
        return kept

    @staticmethod
    def _split_state(program: Program, state: Dict[str, Any]):
        """(donated, kept): the persistables the program rebinds, whose
        buffers the step consumes, and the ones it only reads."""
        rebound = rebound_persistables(program)
        donated = {n: v for n, v in state.items() if n in rebound}
        kept = {n: v for n, v in state.items() if n not in rebound}
        return _own_buffers(donated, kept), kept

    # ------------------------------------------------------------------
    def _state_outputs(self, program: Program, env, donated, kept,
                       record: Dict[str, int]):
        """Trace time, after the block walk: (new_state, extras).
        new_state holds exactly the donated names (one the trace did not
        rebind aliases its input: harmless). extras holds what the split
        could not know: a persistable the run created, and a kept one
        the trace rebound all the same; that one is counted in `record`,
        warned about and stays undonated (a lost saving, not a wrong
        answer)."""
        new_state = {n: env[n] for n in donated}
        mismatched = sorted(n for n, v in kept.items() if env[n] is not v)
        extras = {n: env[n] for n in mismatched}
        for v in program.persistables():
            if v.name in env and v.name not in donated and v.name not in kept:
                extras[v.name] = env[v.name]
        if mismatched:
            logger.warning(
                "executor: the step rebinds %s, which no op names as "
                "written (register_op(writes=...)): left undonated",
                mismatched)
        buffers, nbytes = _tree_bytes(donated)
        kept_buffers, kept_bytes = _tree_bytes(kept)
        record.update(donated_buffers=buffers, donated_bytes=nbytes,
                      kept_buffers=kept_buffers, kept_bytes=kept_bytes,
                      mismatches=len(mismatched))
        return new_state, extras

    def _donation_record(self) -> Dict[str, int]:
        """A step program's entry in `donation_stats`: `_state_outputs`
        fills it when the program traces (again when it retraces)."""
        record: Dict[str, int] = {}
        self._donation.append(record)
        return record

    def _raw_step(self, program: Program, fetch_names):
        """The traced block walk as a pure function of (donated, kept,
        feed, seed) -> (fetches, new_state, extras) — the unit both
        `_compile` (one jitted step) and `_build_window` (K steps under
        one lax.scan) compile. The state arrives split as `_split_state`
        splits it; see `_state_outputs` for what comes back."""
        runner = _BlockRunner(program, getattr(self, "_place_grad", None))
        record = self._donation_record()

        def raw(donated: Dict[str, Any], kept: Dict[str, Any],
                feed: Dict[str, Any], seed):
            env: Dict[str, Any] = {}
            env.update(kept)
            env.update(donated)
            env.update(feed)
            env["@RNG@"] = jax.random.PRNGKey(seed)
            env["@RNG_COUNTER@"] = 0
            env["@AMP@"] = program.amp_dtype
            runner.run_block(0, env)
            fetches = [env[n] for n in fetch_names]
            return (fetches,) + self._state_outputs(
                program, env, donated, kept, record)

        return raw

    # -- windowed (multi-step fused) execution -------------------------
    def _build_window(self, program: Program, fetch_names,
                      skip_nonfinite: bool, with_acc: bool):
        """Compile K training steps into ONE program: a lax.scan of the
        traced step over a leading window axis of the feed, with the
        persistable state AND the on-device metric accumulator riding in
        the scan carry. One host dispatch per window instead of K — the
        ISSUE 6 answer to PERF.md's per-step dispatch floor.

        The donated state and the accumulator ride the scan carry and
        are donated to the window; the kept state rides it too (XLA
        drops a carry the body passes through), so that a kept name the
        body rebinds all the same (`_state_outputs`' mismatch) still
        reads its own last value at the next step.

        Persistables that first materialize inside the step (rare: the
        usual flow initializes everything in startup) cannot join the
        carry (its pytree structure is fixed before the first iteration),
        so they ride the stacked scan outputs and the caller keeps the
        last step's value."""
        raw = self._raw_step(program, fetch_names)
        skip = bool(skip_nonfinite)

        def win(donated, kept, feeds, seeds, acc):
            rebound_kept = set()  # filled while the body traces

            def body(carry, xs):
                st, ro, ac = carry
                feed_t, seed_t = xs
                fetches, st, extras = raw(st, ro, feed_t, seed_t)
                if with_acc:
                    ac = accum_fold(ac, fetches[0], list(fetches[1:]), skip)
                rebound_kept.update(n for n in extras if n in ro)
                ro = {n: extras.pop(n, v) for n, v in ro.items()}
                return (st, ro, ac), (fetches, extras)

            (state, ro, acc), (ys, created) = jax.lax.scan(
                body, (donated, kept, acc), (feeds, seeds))
            return ys, state, acc, {n: ro[n] for n in rebound_kept}, created

        return jax.jit(win, donate_argnums=(0, 4))

    def run_window(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        acc_state=None,
        skip_nonfinite: bool = False,
    ):
        """Run K fused training steps in one dispatch.

        feed values are stacked along a leading window axis (K = the
        leading dim, same step-level signature for every slice — the
        DevicePrefetcher's window mode builds these). acc_state, when
        given, is the on-device accumulator tuple (`accum_fold` layout,
        fetch_list[0] must be the cost) carried INSIDE the scan; the
        updated accumulator is returned without any host sync.

        Returns (ys, acc_out): ys aligned with fetch_list, each a device
        array with leading axis K (per-step values — still async; reading
        them is the caller's sync decision)."""
        with profiler.timer("executor.prepare"):
            program = program or default_main_program()
            scope = scope or global_scope()
            feed = _device_feed(feed)
            fetch_names = _fetch_names(fetch_list)
            if acc_state is not None and not fetch_names:
                raise ValueError(
                    "run_window with acc_state needs fetch_list[0] = cost")
            leaves = jax.tree_util.tree_leaves(feed)
            if not leaves:
                raise ValueError("run_window needs at least one feed slot")
            k_steps = int(leaves[0].shape[0])  # the window: the leading dim
            with_acc = acc_state is not None
            key = self._plan_key(
                program, ("scan_window", bool(skip_nonfinite), with_acc),
                feed, fetch_names)
            plan, donated, kept = self._planned_state(program, scope, key)
            # the window donates the accumulator with the state, and a
            # fresh pass's holds one zero under several leaves
            acc_state = _own_buffers(acc_state, (donated, kept))
            # commit carries to THE device before the call: jit
            # specializes its executable on input shardings, so an
            # uncommitted leaf (the startup outputs on the first window,
            # a fresh pass's accumulator zeros) would silently
            # double-compile every window program. A device_put of an
            # already-resident array is a cheap no-copy.
            donated, kept, acc_state = jax.device_put(
                (donated, kept, acc_state), self.place.device)
            seeds = np.asarray(
                [self._draw_seed(program) for _ in range(k_steps)],
                dtype=np.uint32)
            fn = self._planned_fn(
                plan, key,
                lambda names: self._build_window(
                    program, fetch_names, skip_nonfinite, with_acc),
                "window")
        with self._device_context(), self._trace_context(), \
                profiler.timer("executor.call"):
            ys, new_state, acc_out, rebound_kept, created = fn(
                donated, kept, feed, seeds, acc_state)
        with profiler.timer("executor.commit"):
            new_state.update(rebound_kept)
            if FLAGS.check_nan_inf:
                _check_finite(
                    {**new_state, **{n: f for n, f in zip(fetch_names, ys)}}
                )
            for n, v in new_state.items():
                scope.set(n, v)
            for n, v in created.items():
                # stacked K copies of a step-created persistable: keep the
                # last step's value (what the step loop's scope would hold)
                scope.set(n, jax.tree_util.tree_map(lambda a: a[-1], v))
            plan.clean_at = scope.writes
            del donated  # as in run(): it dies in the span
        return ys, acc_out
