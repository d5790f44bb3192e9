"""Global flag registry (gflags parity).

Reference: paddle/utils/Flags.cpp:18-110 defines ~40 gflags consumed across
the runtime (use_gpu, trainer_count, check_nan_inf behavior via
FLAGS_check_nan_inf in fluid executor.cc:60-72, log_period, ...). Here:
a typed registry with env-var overrides (`PT_FLAGS_<NAME>`) and an argv
parser, read through the `FLAGS` namespace object.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

_REGISTRY: Dict[str, dict] = {}


class _Flags:
    """Attribute access over the registry: `FLAGS.check_nan_inf`."""

    def __getattr__(self, name: str):
        try:
            return _REGISTRY[name]["value"]
        except KeyError:
            raise AttributeError(f"undefined flag {name!r}") from None

    def __setattr__(self, name: str, value):
        if name not in _REGISTRY:
            raise AttributeError(f"undefined flag {name!r}")
        _REGISTRY[name]["value"] = _coerce(value, _REGISTRY[name]["default"])


FLAGS = _Flags()


def _coerce(value, default):
    if isinstance(default, bool):
        if isinstance(value, str):
            return value.lower() in ("1", "true", "yes", "on")
        return bool(value)
    if default is None:
        return value
    return type(default)(value)


def define_flag(name: str, default, help: str = "") -> None:
    """Register a flag; env var PT_FLAGS_<NAME> overrides the default."""
    value = default
    env = os.environ.get(f"PT_FLAGS_{name.upper()}")
    if env is not None:
        value = _coerce(env, default)
    _REGISTRY[name] = {"default": default, "value": value, "help": help}


def parse_flags(argv: Optional[List[str]] = None) -> List[str]:
    """Parse --name=value / --name value pairs; returns unconsumed args."""
    argv = list(argv or [])
    rest: List[str] = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a.startswith("--") and "=" in a:
            name, val = a[2:].split("=", 1)
            name = name.replace("-", "_")
            if name in _REGISTRY:
                _set_parsed(name, val)
            else:
                rest.append(a)
            i += 1
            continue
        name = a[2:].replace("-", "_") if a.startswith("--") else None
        if name in _REGISTRY:
            if isinstance(_REGISTRY[name]["default"], bool):
                # gflags semantics: a bare boolean flag means True; never
                # consume the next token as its value
                setattr(FLAGS, name, True)
            elif i + 1 < len(argv) and not argv[i + 1].startswith("--"):
                _set_parsed(name, argv[i + 1])
                i += 1
            else:
                # no value available (end of argv, or the next token is
                # itself a flag) — leave it for the caller to reject
                rest.append(a)
        else:
            rest.append(a)
        i += 1
    return rest


def _set_parsed(name: str, val: str) -> None:
    """setattr with a flag-parse error message instead of a bare
    coercion ValueError."""
    try:
        setattr(FLAGS, name, val)
    except (TypeError, ValueError) as e:
        raise ValueError(
            f"invalid value {val!r} for flag --{name}: {e}"
        ) from None


def flags_help() -> str:
    lines = []
    for name in sorted(_REGISTRY):
        f = _REGISTRY[name]
        lines.append(f"--{name} (default {f['default']!r}): {f['help']}")
    return "\n".join(lines)


# -- core flags (the subset of Flags.cpp that survives the TPU redesign) ----
define_flag("check_nan_inf", False,
            "after each executor run, verify all persistable outputs are "
            "finite (reference: FLAGS_check_nan_inf, fluid executor.cc:60)")
define_flag("seed", 0, "global random seed (0 = nondeterministic)")
define_flag("step_guard", False,
            "trainer: enable the resilience.StepGuard default policy — "
            "skip non-finite steps, roll back to the last checkpoint "
            "after 3 consecutive, reduced-LR cool-down (the production "
            "counterpart of check_nan_inf's debug abort; README 'Fault "
            "tolerance')")
define_flag("log_period", 100, "trainer: log every N batches")
define_flag("sync_every", 0,
            "trainer: host-sync cadence of the pipelined step loop — "
            "materialize the on-device cost/metric accumulator every N "
            "steps (env: PT_FLAGS_SYNC_EVERY). 1 = the fully synchronous "
            "legacy loop (every step fences XLA's async dispatch queue); "
            "0 = auto: follow log_period, except a StepGuard-armed run "
            "keeps the exact per-step check unless a cadence is set "
            "explicitly (PERF.md 'Async dispatch and the host-sync "
            "budget')")
define_flag("scan_window", 0,
            "trainer: fuse K training steps into ONE jitted lax.scan "
            "program over a device-resident window of K stacked batches "
            "(env: PT_FLAGS_SCAN_WINDOW, CLI --scan_window). One host "
            "dispatch per window instead of K — removes, not just hides, "
            "the per-step dispatch floor PERF.md measures; the on-device "
            "metric accumulator and non-finite counter ride inside the "
            "scan carry and sync only at window edges. 0 = off (the "
            "per-step pipelined loop); requires an executor with "
            "scan_window_supported (the mesh ParallelExecutor is not, "
            "yet). Checkpoint cadence and StepGuard detection quantize "
            "to window boundaries (PERF.md 'Breaking the dispatch "
            "floor')")
define_flag("microbatches", 0,
            "pipeline executor: micro-batches M per global batch (CLI "
            "--microbatches, env: PT_FLAGS_MICROBATCHES). Each step "
            "splits the batch into M slices driven through the K-stage "
            "GPipe tick grid (paddle_tpu/pipeline); bubble fraction is "
            "(K-1)/(M+K-1), so more micro-batches amortize the "
            "fill/drain ticks. 0 = default 2x the stage count")
define_flag("pipeline_stages", 0,
            "pipeline executor: stage count K for `train --mesh` runs "
            "(CLI --pipeline_stages, env: PT_FLAGS_PIPELINE_STAGES). "
            "0 = follow the mesh's pp axis size (meshless: no "
            "pipelining). Must be a multiple of the pp axis; the "
            "program is cut at stage_boundary() markers when their "
            "count matches K-1, else auto-balanced by op cost")
define_flag("prefetch_to_device", 2,
            "trainer: default DevicePrefetcher queue depth — batch N+1's "
            "host->device transfer overlaps batch N's compute "
            "(DataProvider.h:375 double-buffer parity). 0 disables; "
            "Trainer.train(prefetch_to_device=...) overrides per run. "
            "Executors that own input placement (ParallelExecutor) "
            "ignore the default")
define_flag("show_param_stats_period", 0,
            "trainer: dump per-parameter value/gradient stats every N "
            "batches (reference: TrainerInternal.cpp:81-109); 0 = off")
define_flag("stats_period", 0,
            "trainer: emit a one-line runtime-stats log (step, "
            "dispatches, syncs, checkpoint commits, guard skips, trace "
            "drops — the paddle_tpu.stats logger) every N steps; the "
            "training-side view of the unified metrics registry that "
            "serving exposes on /metrics. 0 = off")
define_flag("dump_stats", False,
            "CLI train: print the unified metrics registry (Prometheus "
            "text) and the global timer table at exit — the dump-at-exit "
            "counterpart of scraping a serving process's /metrics")
define_flag("enable_timers", False,
            "accumulate REGISTER_TIMER-style stat timers "
            "(reference: utils/Stat.h, WITH_TIMER)")
define_flag("use_fused_rnn", True,
            "use pallas fused LSTM/GRU sequence kernels when shapes are "
            "eligible and the backend is TPU (reference: "
            "hl_lstm_parallel_forward fused CUDA kernels, "
            "cuda/include/hl_lstm.h:42). On by default: measured on v5e "
            "the fused train recurrence beats lax.scan 1.1-1.5x across "
            "T/B/H/dtype (lstm_kernel_microbench: old link, rounds <= 5, "
            "not re-measured on this chip; record in git history)")
define_flag("fused_rnn_interpret", False,
            "testing only: allow the fused RNN kernels in pallas interpret "
            "mode on non-TPU backends")
define_flag("use_fused_conv", True,
            "build conv+BN+ReLU towers through the fused raw-stats protocol "
            "(ops/fused_conv_ops.py: each 1x1 conv applies the previous BN "
            "to its operand and emits its own output's statistics — the "
            "reference's cuDNN fused-conv analogue, "
            "gserver/layers/CudnnConvBaseLayer.cpp)")
define_flag("use_fused_attention", True,
            "use the fused Bahdanau attention decoder kernels when shapes "
            "are eligible and the backend is TPU (ops/bahdanau_kernels.py "
            "— the hand-written-fused-kernel philosophy of the reference's "
            "hl_lstm.h:42 applied to the NMT decoder scan, 51% of that "
            "step)")
define_flag("fused_attention_interpret", False,
            "testing only: allow the fused attention decoder kernels in "
            "pallas interpret mode on non-TPU backends")
