"""Model / checkpoint IO.

Reference surface being rebuilt:
- fluid/io.py: save_vars / save_params / save_persistables, save_inference_model
  (prunes the program to the feed→fetch slice and serializes ProgramDesc +
  params; model format doc doc/design/model_format.md), load_* counterparts.
- Gen-1 ParamUtil (paddle/trainer/ParamUtil.h:58-93): per-pass checkpoint dirs
  with cadence flags, resume via init_model_path/start_pass.
- v2 Parameters.to_tar/from_tar (python/paddle/v2/parameters.py:328,358).
- framework/prune.cc: dataflow-slice of a ProgramDesc.

TPU design: the Scope already holds every persistable value (parameters,
optimizer accumulators, BN statistics, LR/step counters) as host-transferable
arrays, so a checkpoint is one `.npz` of the persistable slice of the Scope
plus a JSON sidecar (program + metadata). Sharded arrays come back to host
via np.asarray (an all-gather under jit-less access), which matches orbax's
restore-to-host semantics at the scale this framework targets; the format is
deliberately single-file so a checkpoint is also the deployment artifact
(MergeModel.cpp parity).
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import tempfile
import warnings
import zipfile
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .core.executor import Executor, Scope, global_scope
from .core.lod import LoDArray
from .core.program import Program, Variable, default_main_program
from .resilience import faults

__all__ = [
    "CheckpointCorruptError",
    "QuantMetaError",
    "GENERATION_SCHEMA_VERSION",
    "generation_state_fingerprint",
    "program_fingerprint",
    "quant_scales_digest",
    "save_vars",
    "save_params",
    "save_persistables",
    "load_vars",
    "load_params",
    "load_persistables",
    "save_inference_model",
    "load_inference_model",
    "apply_sharding_meta",
    "save_checkpoint",
    "load_checkpoint",
    "clean_checkpoint",
    "get_latest_checkpoint_serial",
    "verify_checkpoint",
    "save_sharded_checkpoint",
    "load_sharded_checkpoint",
]

PARAMS_FILE = "params.npz"
PROGRAM_FILE = "program.json"
META_FILE = "meta.json"
CHECKPOINT_PREFIX = "checkpoint"

# DecodeState wire-schema version: bump when the serialized decode-state
# layout (what generation_state_fingerprint hashes, or how disagg
# handoff payloads interpret it) changes incompatibly. Prefill/decode
# replicas exchange device state across processes, so the schema is part
# of the artifact's identity, not an implementation detail.
GENERATION_SCHEMA_VERSION = 1


def generation_state_fingerprint(gen: Dict[str, Any]) -> str:
    """Layout identity of the decode state a generation artifact boots:
    beam geometry + per-state/per-example dtypes and trailing shapes,
    hashed over canonical JSON. Two artifacts with equal fingerprints
    allocate bit-compatible DecodeState pools, so a prefill replica's
    handoff payload can be admitted by a decode replica iff the
    fingerprints match (serving/disagg validates exactly this).
    Deliberately EXCLUDES the program fingerprint: a retrained model
    with unchanged state geometry still hands off cleanly mid-rollout —
    only layout breaks are rejected."""
    layout = {
        "schema_version": int(gen.get("schema_version",
                                      GENERATION_SCHEMA_VERSION)),
        "beam_size": int(gen["beam_size"]),
        "max_len": int(gen["max_len"]),
        "bos_id": int(gen["bos_id"]),
        "eos_id": int(gen["eos_id"]),
        "length_normalize": bool(gen.get("length_normalize", False)),
        "state": [[s["name"], s["dtype"], s["shape"]]
                  for s in gen.get("state", [])],
        "per_example": [[s["name"], s["dtype"], s["shape"]]
                        for s in gen.get("per_example", [])],
    }
    blob = json.dumps(layout, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


class CheckpointCorruptError(RuntimeError):
    """A checkpoint's payload does not match the integrity record in its
    meta (or the payload is unreadable)."""


class QuantMetaError(ValueError):
    """A quantized artifact's quant sidecar does not match its payload:
    the program changed after the scales were calibrated (stale-scale
    artifact) or the int8 payload/scales were swapped out from under
    the program. Serving such an artifact would produce garbage at full
    throughput — fail at load instead."""


def program_fingerprint(program: Program) -> str:
    """Content hash of a program's serialized form (to_dict is already
    the canonical round-trip surface, and version is deliberately NOT
    part of it, so the fingerprint of a freshly-saved program equals
    the fingerprint of its re-loaded self). The quant meta block pins
    scales to this — a rewrite after calibration changes the hash."""
    blob = json.dumps(program.to_dict(), sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def quant_scales_digest(scope: Scope, param_names: Sequence[str]) -> str:
    """Digest over the quant-bearing payload of an artifact: every int8
    parameter and every @quant_scale var, hashed with name/dtype/shape
    so a scale swapped between two weights of the same size is still
    caught. Calibration is deterministic (quant/calibrate.py), so equal
    inputs produce equal digests."""
    from .quant.convert import SCALE_SUFFIX

    h = hashlib.sha256()
    for name in sorted(param_names):
        if not scope.has(name):
            continue
        a = np.asarray(scope.get(name))
        if a.dtype != np.int8 and not name.endswith(SCALE_SUFFIX):
            continue
        h.update(name.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _write_json_atomic(path: str, obj) -> None:
    """tmp + os.replace so a preempted writer can never leave a torn
    JSON file (the same discipline save_vars applies to the npz)."""
    d = os.path.dirname(os.path.abspath(path))
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(obj, f)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


# ---------------------------------------------------------------------------
# variable-level save/load (fluid io.py save_vars/load_vars)
# ---------------------------------------------------------------------------

def _to_host(value) -> np.ndarray:
    if isinstance(value, LoDArray):
        raise TypeError("cannot checkpoint a LoDArray variable")
    return np.asarray(value)


def save_vars(
    dirname: str,
    var_names: Sequence[str],
    scope: Optional[Scope] = None,
    filename: str = PARAMS_FILE,
) -> str:
    """Save named scope values as one npz under `dirname`. Atomic (tmp+rename)
    so a preempted save never corrupts the previous checkpoint
    (go/pserver checkpoint design parity, service.go:346)."""
    scope = scope or global_scope()
    os.makedirs(dirname, exist_ok=True)
    arrays = {n: _to_host(scope.get(n)) for n in var_names}
    path = os.path.join(dirname, filename)
    fd, tmp = tempfile.mkstemp(dir=dirname, suffix=".tmp")
    os.close(fd)
    try:
        with open(tmp, "wb") as f:
            np.savez(f, **arrays)
        # fault point: "raise" simulates a failed write (tmp removed,
        # previous file intact); "corrupt" publishes a torn npz — the
        # scenario the loader's quarantine-and-fall-back path must
        # survive even when a meta marker lands after it
        if faults.fire("ckpt.write", path=path) == "corrupt":
            with open(tmp, "r+b") as f:
                f.truncate(max(os.path.getsize(tmp) // 2, 1))
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return path


def load_vars(
    dirname: str,
    scope: Optional[Scope] = None,
    filename: str = PARAMS_FILE,
    var_names: Optional[Sequence[str]] = None,
) -> List[str]:
    scope = scope or global_scope()
    path = os.path.join(dirname, filename)
    # materialize every array BEFORE touching the scope: decompression
    # forces truncation/corruption to surface here, so a bad file can
    # never leave the scope half-updated
    with np.load(path) as data:
        names = list(data.files) if var_names is None else list(var_names)
        arrays = {}
        for n in names:
            if n not in data:
                raise KeyError(f"variable {n!r} not found in {path}")
            arrays[n] = data[n]
    loaded = []
    for n, a in arrays.items():
        scope.set(n, a)
        loaded.append(n)
    return loaded


def save_params(dirname, main_program: Optional[Program] = None, scope=None):
    """Parameters only (no optimizer state) — fluid io.py save_params."""
    program = main_program or default_main_program()
    scope = scope or global_scope()
    missing = sorted(
        v.name for v in program.parameters() if not scope.has(v.name)
    )
    if missing:
        raise ValueError(
            f"save_params: parameters {missing} are not in the scope — "
            f"did the startup program run?"
        )
    names = sorted(v.name for v in program.parameters())
    return save_vars(dirname, names, scope)


def save_persistables(dirname, main_program: Optional[Program] = None, scope=None):
    """Full persistable state: params + optimizer accumulators + BN stats +
    step/LR counters — fluid io.py save_persistables."""
    program = main_program or default_main_program()
    scope = scope or global_scope()
    names = sorted(
        v.name for v in program.persistables() if scope.has(v.name)
    )
    return save_vars(dirname, names, scope)


def load_params(dirname, main_program: Optional[Program] = None, scope=None):
    program = main_program or default_main_program()
    names = sorted(v.name for v in program.parameters())
    return load_vars(dirname, scope, var_names=names)


def load_persistables(dirname, main_program: Optional[Program] = None, scope=None):
    # load whatever the file has; missing-from-program names are fine (the
    # program may have been re-built with the same var names)
    return load_vars(dirname, scope)


# ---------------------------------------------------------------------------
# inference model (prune + serialize)  — fluid io.py save_inference_model,
# framework/prune.cc, paddle/inference/inference.h
# ---------------------------------------------------------------------------

def _prune_for_inference(
    program: Program, feed_names: Sequence[str], target_names: Sequence[str]
) -> Program:
    """Dataflow-slice block 0 to the ops needed to compute `target_names`
    from `feed_names`. clone(for_test=True) drops the backward+optimizer
    pass and flips is_test; the walk here only slices the forward graph."""
    pruned = program.clone(for_test=True)
    block = pruned.global_block()

    def sub_block_refs(op) -> set:
        """Names an op's sub-block(s) read from the enclosing scope —
        a beam_search_group / control-flow step body consumes
        parameters and closures by name without listing them as op
        inputs, so the dataflow slice must treat them as consumed or
        their producing ops (and the params themselves) get pruned."""
        refs: set = set()
        idx = op.attrs.get("sub_block")
        if not isinstance(idx, int):
            return refs
        stack = [idx]
        while stack:
            b = pruned.blocks[stack.pop()]
            produced: set = set()
            for sop in b.ops:
                refs.update(n for n in sop.input_names()
                            if n not in produced)
                produced.update(sop.output_names())
                inner = sop.attrs.get("sub_block")
                if isinstance(inner, int):
                    stack.append(inner)
        return refs

    needed = set(target_names)
    kept = []
    for op in reversed(block.ops):
        if any(o in needed for o in op.output_names()):
            kept.append(op)
            needed.update(op.input_names())
            needed.update(n for n in sub_block_refs(op)
                          if n in block.vars)
    kept.reverse()
    block.ops = kept

    referenced = set(feed_names) | set(target_names)
    for op in kept:
        referenced.update(op.input_names())
        referenced.update(op.output_names())
        referenced.update(n for n in sub_block_refs(op)
                          if n in block.vars)
    block.vars = {n: v for n, v in block.vars.items() if n in referenced}
    # every declared feed must actually be consumed by the slice
    missing = [n for n in feed_names if n not in needed]
    if missing:
        raise ValueError(
            f"feed vars {missing} are not inputs of the pruned inference "
            f"slice for targets {list(target_names)}"
        )
    return pruned


def save_inference_model(
    dirname: str,
    feeded_var_names: Sequence[str],
    target_vars: Sequence,
    executor: Optional[Executor] = None,
    main_program: Optional[Program] = None,
    scope: Optional[Scope] = None,
    draft_model: Optional[str] = None,
) -> None:
    """fluid io.py save_inference_model: pruned program + params in `dirname`.

    `draft_model` records a speculative-decoding companion in the
    meta.json sidecar: the directory (relative paths resolve against
    THIS artifact's dirname at load) of a small generation model the
    serving scheduler drafts with by default (`serve --draft_model`
    overrides it)."""
    program = main_program or default_main_program()
    scope = scope or global_scope()
    target_names = [
        v.name if isinstance(v, Variable) else v for v in target_vars
    ]
    pruned = _prune_for_inference(program, feeded_var_names, target_names)
    os.makedirs(dirname, exist_ok=True)
    param_names = sorted(
        v.name
        for v in pruned.global_block().vars.values()
        if v.persistable and scope.has(v.name)
    )
    save_vars(dirname, param_names, scope)
    # feed dtypes/shapes travel with the artifact so a serving front-end
    # can coerce JSON inputs (int32 ids vs float32 features) without
    # reconstructing them from the program graph
    feed_specs = {}
    for n in feeded_var_names:
        try:
            v = pruned.global_block().var(n)
            feed_specs[n] = {"dtype": np.dtype(v.dtype).name,
                             "shape": [int(d) for d in v.shape]}
        except KeyError:
            pass
    # generation-state specs travel with the artifact: beam geometry +
    # decode-state dtypes/shapes, so the serving scheduler can allocate
    # its device-resident slot pool (and pre-compile the pool step at
    # warmup) without re-tracing the model source
    generation = _generation_meta(pruned)
    # sharding sidecar: partition specs of mesh-sharded parameters
    # (parallel/sharded_embedding.py sets var.sharding) so a serving
    # replica can BE a mesh — load_inference_model re-attaches the
    # specs and ServingEngine(mesh=...) places params accordingly
    sharding = _sharding_meta(pruned)
    # quant sidecar (quant/convert.py sets _quant_meta): mode + site
    # counts travel as-is; the program fingerprint and scales digest are
    # computed HERE, over the pruned program and the params actually
    # saved, so load-time validation checks the artifact's own content
    quant = None
    qmeta = getattr(program, "_quant_meta", None)
    if qmeta:
        quant = dict(qmeta)
        quant["program_fingerprint"] = program_fingerprint(pruned)
        quant["scales_digest"] = quant_scales_digest(scope, param_names)
    with open(os.path.join(dirname, PROGRAM_FILE), "w") as f:
        json.dump(pruned.to_dict(), f)
    with open(os.path.join(dirname, META_FILE), "w") as f:
        json.dump(
            {
                "feed_names": list(feeded_var_names),
                "fetch_names": target_names,
                "param_names": param_names,
                "feed_specs": feed_specs,
                # the artifact's identity for fleet rollout: a replica
                # reports this hash on /healthz so a rollout can verify
                # every standby actually loaded the new version before
                # the router flips (fleetctl/rollout.py)
                "program_fingerprint": program_fingerprint(pruned),
                **({"generation": generation} if generation else {}),
                **({"sharding": sharding} if sharding else {}),
                **({"quant": quant} if quant else {}),
                **({"draft_model": {"dir": draft_model}}
                   if draft_model else {}),
            },
            f,
        )


def _sharding_meta(pruned: Program) -> Optional[dict]:
    """meta.json sidecar for mesh-sharded models: per-variable partition
    specs (one entry per dim: axis name, list of axis names, or null =
    replicated) plus the mesh axes they reference, JSON-shaped so the
    artifact stays backend-agnostic. Only vars carrying an explicit
    `.sharding` PartitionSpec (e.g. parallel.sharded_embedding tables)
    are recorded — everything else is replicated at serving time."""
    specs: Dict[str, list] = {}
    axes: set = set()
    for block in pruned.blocks:
        for v in block.vars.values():
            spec = getattr(v, "sharding", None)
            if spec is None:
                continue
            entry = []
            for dim in tuple(spec):
                if dim is None:
                    entry.append(None)
                elif isinstance(dim, (tuple, list)):
                    entry.append([str(a) for a in dim])
                    axes.update(str(a) for a in dim)
                else:
                    entry.append(str(dim))
                    axes.add(str(dim))
            specs[v.name] = entry
    if not specs:
        return None
    return {"specs": specs, "mesh_axes": sorted(axes)}


def apply_sharding_meta(program: Program, meta: Optional[dict]) -> int:
    """Re-attach partition specs from a sharding sidecar onto the
    program's variables (the load-side inverse of `_sharding_meta`).
    Returns the number of vars annotated. Idempotent; unknown var names
    are skipped (the pruned slice may have dropped them)."""
    if not meta:
        return 0
    from jax.sharding import PartitionSpec

    n = 0
    for block in program.blocks:
        for name, entry in meta.get("specs", {}).items():
            v = block.vars.get(name)
            if v is None:
                continue
            v.sharding = PartitionSpec(
                *[tuple(d) if isinstance(d, list) else d for d in entry])
            n += 1
    return n


def _generation_meta(pruned: Program) -> Optional[dict]:
    """meta.json sidecar for generation models: the beam_search_group
    geometry plus per-state trailing shapes/dtypes (batch axis
    dropped — that's the slot axis at serving time)."""
    block = pruned.global_block()
    op = next((o for o in block.ops if o.type == "beam_search_group"),
              None)
    if op is None:
        return None

    def vspec(name):
        try:
            v = block.var(name)
        except KeyError:
            return {"name": name, "dtype": "float32", "shape": None}
        trailing = [int(d) for d in v.shape[1:]]
        return {"name": name, "dtype": np.dtype(v.dtype).name,
                "shape": trailing if all(d > 0 for d in trailing)
                else None}

    gen = {
        "beam_size": int(op.attrs.get("beam_size", 4)),
        "max_len": int(op.attrs.get("max_len", 32)),
        "bos_id": int(op.attrs.get("bos_id", 0)),
        "eos_id": int(op.attrs.get("eos_id", 1)),
        "length_normalize": bool(op.attrs.get("length_normalize", False)),
        "state": [vspec(n) for n in op.inputs.get("Boot", [])],
        "per_example": [vspec(n) for n in op.inputs.get("PerExample", [])],
        "outputs": {
            "ids": op.outputs["Ids"][0],
            "scores": op.outputs["Scores"][0],
            "lengths": op.outputs["Lengths"][0],
        },
    }
    # the DecodeState wire-schema identity travels with the artifact so
    # a disagg handoff can be validated BEFORE any state touches a pool
    gen["schema_version"] = GENERATION_SCHEMA_VERSION
    gen["state_fingerprint"] = generation_state_fingerprint(gen)
    return gen


def load_inference_model(dirname: str, scope: Optional[Scope] = None):
    """Returns (program, feed_names, fetch_names); params are loaded into
    the scope so `Executor().run(program, feed, fetch_list)` works directly."""
    scope = scope or global_scope()
    with open(os.path.join(dirname, PROGRAM_FILE)) as f:
        program = Program.from_dict(json.load(f))
    with open(os.path.join(dirname, META_FILE)) as f:
        meta = json.load(f)
    load_vars(dirname, scope, var_names=meta["param_names"])
    # serving sidecar (absent in pre-serving artifacts): per-feed
    # dtype/shape specs, consumed by serving.ServingEngine
    program._serving_meta = meta.get("feed_specs") or None
    # artifact identity (absent in pre-fleet artifacts): the exporter's
    # program fingerprint; ServingEngine recomputes it when missing so
    # /healthz "versions" is populated for every artifact age
    program._program_fingerprint = meta.get("program_fingerprint") or None
    # generation sidecar (absent for feed-forward models / pre-gen
    # artifacts): beam geometry + decode-state specs, consumed by
    # serving.scheduler.ContinuousScheduler warmup
    program._generation_meta = meta.get("generation") or None
    # pre-disagg artifacts lack the DecodeState schema identity: backfill
    # it from the state specs already in the sidecar, so handoff
    # validation has a fingerprint to compare for every artifact age
    if program._generation_meta is not None \
            and not program._generation_meta.get("state_fingerprint"):
        g = program._generation_meta
        g.setdefault("schema_version", GENERATION_SCHEMA_VERSION)
        g["state_fingerprint"] = generation_state_fingerprint(g)
    # draft-model sidecar (absent unless exported with draft_model=...):
    # the speculative-decoding companion dir, consumed by the serving
    # scheduler (relative paths resolve against the artifact dir)
    program._draft_meta = meta.get("draft_model") or None
    # sharding sidecar (absent for unsharded models): partition specs of
    # mesh-sharded parameters, re-attached to the restored vars so a
    # mesh ServingEngine (or ParallelExecutor) places them sharded
    program._sharding_meta = meta.get("sharding") or None
    apply_sharding_meta(program, program._sharding_meta)
    # quant sidecar (absent for fp artifacts): validate scales against
    # the program BEFORE anything can serve — a stale-scale artifact
    # (program edited after calibration, or payload swapped) fails
    # loudly here instead of serving garbage at full throughput
    program._quant_meta = meta.get("quant") or None
    if program._quant_meta:
        q = program._quant_meta
        fp = program_fingerprint(program)
        if q.get("program_fingerprint") not in (None, fp):
            raise QuantMetaError(
                f"{dirname}: quantized artifact is stale — the program "
                f"({fp}) no longer matches the one its scales were "
                f"calibrated for ({q['program_fingerprint']}); re-run "
                "calibrate + convert and re-export")
        digest = quant_scales_digest(scope, meta["param_names"])
        if q.get("scales_digest") not in (None, digest):
            raise QuantMetaError(
                f"{dirname}: quantized payload/scales digest {digest} "
                f"does not match the recorded {q['scales_digest']} — "
                "the int8 weights or their scales were modified after "
                "export; refusing to serve mismatched scales")
    return program, meta["feed_names"], meta["fetch_names"]


# ---------------------------------------------------------------------------
# training checkpoints (ParamUtil / fluid io.py checkpoint API)
# ---------------------------------------------------------------------------

def _serial_dir(checkpoint_dir: str, serial: int) -> str:
    return os.path.join(checkpoint_dir, f"{CHECKPOINT_PREFIX}_{serial}")


def _complete_serials(checkpoint_dir: str) -> List[int]:
    """Ascending serials whose completion marker (meta) is present.
    Quarantined `checkpoint_N.corrupt` dirs never match."""
    if not os.path.isdir(checkpoint_dir):
        return []
    out = []
    for name in os.listdir(checkpoint_dir):
        m = re.fullmatch(rf"{CHECKPOINT_PREFIX}_(\d+)", name)
        if m and os.path.exists(
            os.path.join(checkpoint_dir, name, META_FILE)
        ):
            out.append(int(m.group(1)))
    return sorted(out)


def get_latest_checkpoint_serial(checkpoint_dir: str,
                                 verify: bool = False) -> int:
    """Largest *complete* (meta present) checkpoint serial, or -1.
    verify=True additionally demands the payload match the integrity
    hashes in meta, returning the newest serial that would actually
    load (read-only: nothing is quarantined — load_checkpoint does
    that when it takes the fallback for real)."""
    serials = _complete_serials(checkpoint_dir)
    if not verify:
        return serials[-1] if serials else -1
    for serial in reversed(serials):
        try:
            verify_checkpoint(_serial_dir(checkpoint_dir, serial))
            return serial
        except CheckpointCorruptError:
            continue
    return -1


def verify_checkpoint(dirname: str) -> None:
    """Raise CheckpointCorruptError unless the directory's meta parses
    and every payload file hashed into it (`integrity`) is present and
    matches. Pre-hardening checkpoints (no integrity record) pass —
    their corruption is still caught at load time by the materialize-
    before-commit read."""
    meta_path = os.path.join(dirname, META_FILE)
    try:
        with open(meta_path) as f:
            meta = json.load(f)
    except (OSError, ValueError) as e:
        raise CheckpointCorruptError(
            f"{dirname}: unreadable meta ({e})") from e
    integrity = meta.get("integrity")
    if not isinstance(integrity, dict):
        # pre-hardening save. For the sharded format we can still check
        # STRUCTURE: every shard file the manifest references must exist
        # (a retention sweep or partial copy that dropped one shard file
        # would otherwise pass verify and fail mid-restore)
        smeta_path = os.path.join(dirname, SHARDED_META)
        if os.path.exists(smeta_path):
            try:
                with open(smeta_path) as f:
                    smeta = json.load(f)
            except (OSError, ValueError) as e:
                raise CheckpointCorruptError(
                    f"{dirname}: unreadable sharded meta ({e})") from e
            procs = {0} | {
                e["process"]
                for info in smeta.get("vars", {}).values()
                if info.get("kind") == "sharded"
                for e in info.get("shards", [])
            }
            for p in sorted(procs):
                if not os.path.exists(
                        os.path.join(dirname, f"shards_p{p}.npz")):
                    raise CheckpointCorruptError(
                        f"{dirname}: shard file shards_p{p}.npz referenced "
                        "by the manifest is missing")
        return
    for fname, want in sorted(integrity.items()):
        path = os.path.join(dirname, fname)
        if not os.path.exists(path):
            raise CheckpointCorruptError(
                f"{dirname}: payload {fname} missing")
        got = _sha256_file(path)
        if got != want:
            raise CheckpointCorruptError(
                f"{dirname}: payload {fname} sha256 {got[:12]}… does not "
                f"match the recorded {str(want)[:12]}…")


def _quarantine_dir(dirname: str) -> str:
    """Move a corrupt checkpoint aside (same pattern as tune/cache.py's
    corrupt-table quarantine) so the serial scan never sees it again
    but a human still can."""
    q = dirname + ".corrupt"
    i = 1
    while os.path.exists(q):
        q = f"{dirname}.corrupt.{i}"
        i += 1
    os.replace(dirname, q)
    return q


def _payload_files(dirname: str) -> List[str]:
    """Checkpoint payload files subject to integrity hashing."""
    return sorted(
        n for n in os.listdir(dirname)
        if n == PARAMS_FILE or n == SHARDED_META
        or re.fullmatch(r"shards_p\d+\.npz", n)
    )


def save_checkpoint(
    checkpoint_dir: str,
    trainer_args: Optional[Dict[str, Any]] = None,
    main_program: Optional[Program] = None,
    scope: Optional[Scope] = None,
    max_num_checkpoints: int = 3,
    sharded: bool = False,
) -> int:
    """Save persistables + trainer metadata as a new numbered checkpoint,
    keeping only the newest `max_num_checkpoints` (ParamUtil cadence +
    `save_only_one` generalized). Returns the new serial.

    sharded=True uses the orbax-style per-shard format (each process
    writes only shards it owns — no all-gather; see the sharded section
    below) instead of the single gathered npz.

    Threading contract: serial allocation re-lists the directory, so
    concurrent saves into one checkpoint_dir from MULTIPLE threads of a
    process could race onto the same serial. The Trainer's background
    checkpointing therefore funnels every save through ONE writer thread
    (trainer._CheckpointWriter) and hands it a host snapshot scope —
    this function itself never touches the device when given one."""
    serial = get_latest_checkpoint_serial(checkpoint_dir) + 1
    if sharded:
        import jax

        chief = jax.process_index() == 0
        if jax.process_count() > 1:
            # every process must agree on the serial: re-deriving it from
            # an unsynchronized filesystem listing can split one save
            # across two serial directories — the chief decides
            from jax.experimental import multihost_utils

            serial = int(
                multihost_utils.broadcast_one_to_all(np.int32(serial))
            )
        d = _serial_dir(checkpoint_dir, serial)
        os.makedirs(d, exist_ok=True)
        save_sharded_checkpoint(d, main_program, scope)  # barriers inside
        # completion marker: chief only, AFTER the fold, then a barrier so
        # no process returns before the checkpoint is actually loadable.
        # The meta records a sha256 per payload file (every shard is
        # complete and visible to the chief past the fold barrier) so the
        # loader can tell a bit-rotted shard from a good one.
        if chief:
            faults.fire("ckpt.meta", serial=serial)
            _write_json_atomic(
                os.path.join(d, META_FILE),
                {"serial": serial, "trainer_args": trainer_args or {},
                 "integrity": {n: _sha256_file(os.path.join(d, n))
                               for n in _payload_files(d)}},
            )
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("ptpu_ckpt_meta")
        if not chief:
            # retention runs on the chief only: a peer sweeping on its
            # own filesystem view could delete a serial the chief still
            # considers in flight
            return serial
    else:
        d = _serial_dir(checkpoint_dir, serial)
        os.makedirs(d, exist_ok=True)
        save_persistables(d, main_program, scope)
        # meta written last: its presence marks the checkpoint complete,
        # and it carries the payload hashes so load can verify integrity
        faults.fire("ckpt.meta", serial=serial)
        _write_json_atomic(
            os.path.join(d, META_FILE),
            {"serial": serial, "trainer_args": trainer_args or {},
             "integrity": {n: _sha256_file(os.path.join(d, n))
                           for n in _payload_files(d)}},
        )
    # retention sweeps only COMPLETE serials (meta present): an
    # incomplete directory may belong to a save another process is
    # still writing — deleting it under them corrupts that save
    for s in _complete_serials(checkpoint_dir)[:-max_num_checkpoints]:
        shutil.rmtree(_serial_dir(checkpoint_dir, s), ignore_errors=True)
    return serial


# errors that mean "this checkpoint is damaged, try the previous one"
# rather than "the caller made a mistake": integrity mismatches, torn
# zip containers, short reads, members missing after truncation
_RECOVERABLE_LOAD_ERRORS = (
    CheckpointCorruptError,
    OSError,
    ValueError,  # covers json.JSONDecodeError and npz parse errors
    KeyError,
    EOFError,
    zipfile.BadZipFile,
)


def load_checkpoint(
    checkpoint_dir: str,
    main_program: Optional[Program] = None,
    scope: Optional[Scope] = None,
) -> Dict[str, Any]:
    """Restore the newest VALID checkpoint; returns its trainer_args.

    A serial whose integrity hashes mismatch — or whose payload fails
    to deserialize despite the meta marker being present (torn write,
    bit rot) — is quarantined to `<dir>.corrupt` and the previous
    serial is tried, so one damaged checkpoint costs one checkpoint
    interval, never the run."""
    quarantined = 0
    while True:
        serial = get_latest_checkpoint_serial(checkpoint_dir)
        if serial < 0:
            extra = (f" ({quarantined} corrupt serial(s) quarantined)"
                     if quarantined else "")
            raise FileNotFoundError(
                f"no valid checkpoint under {checkpoint_dir}{extra}")
        d = _serial_dir(checkpoint_dir, serial)
        try:
            verify_checkpoint(d)
            if os.path.exists(os.path.join(d, SHARDED_META)):
                load_sharded_checkpoint(d, main_program, scope)
            else:
                load_persistables(d, main_program, scope)
            with open(os.path.join(d, META_FILE)) as f:
                return json.load(f)["trainer_args"]
        except _RECOVERABLE_LOAD_ERRORS as e:
            quarantined += 1
            q = _quarantine_dir(d)
            warnings.warn(
                f"checkpoint {d} is corrupt ({type(e).__name__}: {e}); "
                f"quarantined to {q}, falling back to the previous "
                "serial", stacklevel=2)


def clean_checkpoint(checkpoint_dir: str) -> None:
    shutil.rmtree(checkpoint_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# sharded checkpoints (orbax-style; SURVEY §5.4 "sharded checkpoint of
# params+opt state"; replaces the pserver's parameter-block persistence,
# go/pserver/service.go:346)
# ---------------------------------------------------------------------------
#
# The single-file path above gathers every sharded array to one host
# (np.asarray = implicit all-gather) — fine on one chip, wrong at scale:
# a ZeRO-sharded optimizer state or an mp-sharded embedding would spike
# HBM/ICI and write dp-redundant bytes. The sharded format instead has
# each PROCESS write only the shards it owns (replica 0 of each), so save
# traffic is exactly one device→host copy of each unique shard:
#
#   dir/
#     sharded_meta.json          # global shapes/dtypes + shard index map
#     shards_p{K}.npz            # process K's unique shards, keyed
#                                # "<var>::<linear shard idx>"
#
# Restore assembles global host arrays from all shard files (every
# process reads the manifest + files it can see — a shared filesystem,
# like the reference's cluster save path) and sets them into the Scope;
# the next ParallelExecutor step re-shards them onto the mesh via its
# in_shardings. Mid-pass resume, cadence, and latest-pointer semantics
# come from the serial-checkpoint layer above, which delegates here when
# `sharded=True`.

SHARDED_META = "sharded_meta.json"


def save_sharded_checkpoint(
    dirname: str,
    main_program: Optional[Program] = None,
    scope: Optional[Scope] = None,
) -> str:
    import jax

    program = main_program or default_main_program()
    scope = scope or global_scope()
    os.makedirs(dirname, exist_ok=True)
    pid = jax.process_index()
    names = sorted(v.name for v in program.persistables() if scope.has(v.name))

    # the saving world travels with the manifest: a restore on a
    # different chip/process count is legitimate (elastic resume — the
    # loader assembles GLOBAL arrays either way) but must be observable
    # (pipeline.elastic counts it as pt_ckpt_reshard_total)
    meta: Dict[str, Any] = {
        "vars": {},
        "num_processes": jax.process_count(),
        "world": {
            "device_count": int(jax.device_count()),
            "process_count": int(jax.process_count()),
        },
    }
    local: Dict[str, np.ndarray] = {}
    for n in names:
        val = scope.get(n)
        shards = getattr(val, "addressable_shards", None)
        if shards is None or getattr(val, "is_fully_replicated", True):
            # replicated / host value: chief saves one copy
            meta["vars"][n] = {"kind": "replicated"}
            if pid == 0:
                local[f"{n}::r"] = _to_host(val)
            continue
        entries = []
        for s in shards:
            if s.replica_id != 0:
                continue  # exactly one owner per unique shard
            # record the global slice this shard covers
            idx = [
                [0 if sl.start is None else int(sl.start),
                 dim if sl.stop is None else int(sl.stop)]
                for sl, dim in zip(s.index, val.shape)
            ]
            key = f"{n}::{len(entries)}"
            local[key] = np.asarray(s.data)
            entries.append({"key": key, "slice": idx, "process": pid})
        meta["vars"][n] = {
            "kind": "sharded",
            "shape": list(val.shape),
            "dtype": np.dtype(val.dtype).name,
            "shards": entries,
        }

    # a reused dirname must not leak a previous save's files into this
    # one: each process clears its own stale outputs first (and the chief
    # clears any leftover merged manifest)
    for stale in (f"shards_p{pid}.npz", f"manifest_p{pid}.json"):
        path = os.path.join(dirname, stale)
        if os.path.exists(path):
            os.remove(path)
    if pid == 0 and os.path.exists(os.path.join(dirname, SHARDED_META)):
        os.remove(os.path.join(dirname, SHARDED_META))

    fd, tmp = tempfile.mkstemp(dir=dirname, suffix=".tmp")
    os.close(fd)
    try:
        with open(tmp, "wb") as f:
            np.savez(f, **local)
        # same fault point as the single-file path: shard writes are
        # exactly where a preempted/bit-rotted save manifests at scale
        if faults.fire("ckpt.write", shard=pid) == "corrupt":
            with open(tmp, "r+b") as f:
                f.truncate(max(os.path.getsize(tmp) // 2, 1))
        os.replace(tmp, os.path.join(dirname, f"shards_p{pid}.npz"))
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    # multi-process: every process contributes its shard entries; merge by
    # writing per-process manifests and letting the chief fold them AFTER
    # a cross-process barrier — folding early would silently drop peers'
    # shards and the loader would zero-fill their slices
    with open(os.path.join(dirname, f"manifest_p{pid}.json"), "w") as f:
        json.dump(meta, f)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("ptpu_sharded_ckpt_save")
    if pid == 0:
        _fold_sharded_manifests(dirname, meta)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        # nobody leaves before sharded_meta.json exists — a caller (e.g.
        # save_checkpoint) must be able to treat the dir as loadable
        multihost_utils.sync_global_devices("ptpu_sharded_ckpt_fold")
    return dirname


def _fold_sharded_manifests(dirname: str, chief_meta: Dict[str, Any]) -> None:
    """Chief merges every process's shard entries into sharded_meta.json.
    Only manifests from the CURRENT job's process ids are folded (stale
    higher-numbered files from an earlier, larger job are ignored); a
    missing expected manifest is an error, not a silent omission."""
    merged = json.loads(json.dumps(chief_meta))
    nproc = chief_meta["num_processes"]
    for p in range(1, nproc):
        path = os.path.join(dirname, f"manifest_p{p}.json")
        if not os.path.exists(path):
            raise FileNotFoundError(
                f"sharded save: manifest for process {p}/{nproc} missing "
                f"({path}) — did the save barrier run on every process?"
            )
        with open(path) as f:
            other = json.load(f)
        for var, info in other["vars"].items():
            if info.get("kind") == "sharded":
                mine = merged["vars"].setdefault(var, info)
                if mine is not info:
                    mine["shards"].extend(info["shards"])
    _write_json_atomic(os.path.join(dirname, SHARDED_META), merged)


def load_sharded_checkpoint(
    dirname: str,
    main_program: Optional[Program] = None,
    scope: Optional[Scope] = None,
) -> List[str]:
    """Assemble global host arrays from the shard files and set them into
    the scope (re-sharding onto a mesh happens on the next parallel run)."""
    scope = scope or global_scope()
    with open(os.path.join(dirname, SHARDED_META)) as f:
        meta = json.load(f)
    if main_program is not None:
        # match the single-file path's semantics: touch only the
        # program's persistables, not every name the manifest carries
        keep = {v.name for v in main_program.persistables()}
        meta["vars"] = {n: i for n, i in meta["vars"].items() if n in keep}
    # open only files the manifest references (a reused directory may
    # hold stale shards_pK.npz from an older, larger job). A single torn
    # shard file raises a TYPED CheckpointCorruptError naming it, so
    # load_checkpoint's newest-VALID-serial loop quarantines this serial
    # and falls back — one damaged shard costs one checkpoint interval,
    # never the restore.
    procs = {0} | {
        e["process"]
        for info in meta["vars"].values() if info["kind"] == "sharded"
        for e in info["shards"]
    }
    files = {}
    try:
        for p in sorted(procs):
            fname = f"shards_p{p}.npz"
            try:
                files[p] = np.load(os.path.join(dirname, fname))
            except _SHARD_READ_ERRORS as e:
                raise CheckpointCorruptError(
                    f"{dirname}: shard file {fname} is unreadable "
                    f"({type(e).__name__}: {e})") from e
        # stage everything on host BEFORE committing to the scope: a
        # corrupt shard surfaces during assembly and leaves the scope
        # untouched (load_checkpoint then falls back)
        staging: Dict[str, np.ndarray] = {}
        for var, info in meta["vars"].items():
            if info["kind"] == "replicated":
                staging[var] = _read_shard(files, 0, f"{var}::r", dirname)
            else:
                out = np.zeros(info["shape"], np.dtype(info["dtype"]))
                covered = np.zeros(info["shape"], bool)
                for e in info["shards"]:
                    sl = tuple(slice(a, b) for a, b in e["slice"])
                    out[sl] = _read_shard(
                        files, e["process"], e["key"], dirname)
                    covered[sl] = True
                if not covered.all():
                    raise CheckpointCorruptError(
                        f"sharded checkpoint: {var} has uncovered slices "
                        f"({int((~covered).sum())} of {covered.size} "
                        "elements) — incomplete save?"
                    )
                staging[var] = out
    finally:
        for f in files.values():
            f.close()
    loaded = []
    for var, val in staging.items():
        scope.set(var, val)
        loaded.append(var)
    # elastic resume: restoring into a different world than the one that
    # saved is the resharding path — count it (pipeline.elastic declares
    # the family at construction; lazy import avoids an io<->pipeline
    # import cycle at package-init time)
    world = meta.get("world")
    if world:
        import jax

        cur = {"device_count": int(jax.device_count()),
               "process_count": int(jax.process_count())}
        if any(int(world.get(k, v)) != v for k, v in cur.items()):
            from .pipeline.elastic import count_reshard

            count_reshard()
    return loaded


# shard files are read lazily by np.load: a torn zip can surface at
# open OR at member access, with container-format errors (BadZipFile,
# short reads) or npy-payload errors (ValueError)
_SHARD_READ_ERRORS = (OSError, ValueError, EOFError, zipfile.BadZipFile)


def _read_shard(files, process: int, key: str, dirname: str) -> np.ndarray:
    try:
        return files[process][key]
    except KeyError as e:
        raise CheckpointCorruptError(
            f"{dirname}: shards_p{process}.npz is missing member {key!r} "
            "(truncated or stale shard file)") from e
    except _SHARD_READ_ERRORS as e:
        raise CheckpointCorruptError(
            f"{dirname}: shard {key!r} in shards_p{process}.npz is "
            f"unreadable ({type(e).__name__}: {e})") from e
