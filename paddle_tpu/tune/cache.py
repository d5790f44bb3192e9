"""Persistent tuned-config table: JSON on disk, LRU in process.

Key model (the CLBlast lesson, arXiv:1705.05249 §4): a tuned config is
only valid for the exact (kernel family, shape signature, dtype, device
kind) it was measured on — a v5e-optimal tile is a guess on v4, and a
bf16 tile model doubles its VMEM take at f32. The table therefore keys
on all four, and lookups from a different device kind simply miss (the
runtime then uses its analytic default — the same code path as an
untuned machine, so shipping a table can never CHANGE behavior on
hardware it wasn't measured on).

Fleet sharing (Autotuner v2): the same file format is the EXCHANGE
format — `paddle_tpu tune export/import/merge` move tables between
hosts, and pre-tuned per-device tables ship with the package under
`paddle_tpu/tune/tables/<device_kind>.json` (auto-consulted as a
read-through base layer beneath the user's local table; see
tune/overrides.py). To make merging well-defined, every entry's meta
carries its PROVENANCE ("measured" from the timing harness,
"interpolated" from a nearest-shape materialization) and an
`updated_at` epoch stamp; `merge_entry` resolves conflicts as
measured-beats-interpolated first, newest-wins second — a fleet member
can therefore blindly merge a colleague's table without ever letting a
guessed config shadow a measured one.

Durability discipline:
- writes are atomic (tempfile in the target dir + os.replace), so a
  killed tune run can't leave a half-written table for every later
  process to choke on;
- the file carries a schema version; a version mismatch is ignored with
  a warning (forward-compat: an old runtime reading a new table must
  fall back to analytic defaults, not crash) — `tune import` REJECTS
  it loudly instead (an operator merging tables wants the error, not a
  silent no-op);
- a corrupt file (truncated, hand-edited, wrong types) is moved aside
  to `<path>.corrupt` and an empty table takes its place — the tuner
  must never be able to break model execution;
- reads go through a small in-process LRU front so the per-trace lookup
  cost is a dict hit, not repeated signature formatting.
"""

from __future__ import annotations

import collections
import hashlib
import json
import os
import tempfile
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

TABLE_VERSION = 1
_LRU_CAP = 512

# entry provenance vocabulary (meta["provenance"]): measured entries
# come from the timing harness, interpolated ones from a materialized
# nearest-shape match. Unknown/missing provenance merges as weakest.
MEASURED = "measured"
INTERPOLATED = "interpolated"
_PROVENANCE_RANK = {MEASURED: 2, INTERPOLATED: 1}

# itemsize -> dtype name for kernels whose shape model only sees the io
# itemsize (bahdanau _bblk, the RNN eligibility): the fused families
# admit exactly bf16/f32, so the mapping is bijective
ITEMSIZE_DTYPE = {2: "bfloat16", 4: "float32"}


def device_kind() -> str:
    """Canonical device identity for table keys: jax's device_kind
    string (e.g. 'TPU v5 lite'), lowercased with spaces collapsed so the
    key survives JSON round-trips and shell quoting. 'cpu' off-TPU —
    which is exactly why CPU test runs can never hit TPU-tuned entries.
    A process with no backend at all fails here, loudly: a made-up key
    would file measurements under a device that does not exist."""
    import jax

    return "-".join(str(jax.devices()[0].device_kind).lower().split())


def make_sig(params: Dict[str, Any]) -> str:
    """Canonical shape signature: sorted k=v pairs. Params must be
    scalars (ints/strs) — the signature is a JSON object key. A 'dtype'
    key is excluded: dtype is its own key dimension (space.normalize
    carries it inside params for the candidate generators, runtime
    lookups pass pure shape dicts — both must map to one signature)."""
    return ",".join(f"{k}={params[k]}" for k in sorted(params)
                    if k != "dtype")


def entry_key(kernel: str, sig: str, dtype: str, device: str) -> str:
    return "|".join((kernel, sig, dtype, device))


def parse_key(key: str) -> Optional[Tuple[str, str, str, str]]:
    """entry_key inverse: (kernel, sig, dtype, device), or None for a
    malformed key (hand-edited tables must degrade, not crash)."""
    parts = key.split("|")
    if len(parts) != 4:
        return None
    return parts[0], parts[1], parts[2], parts[3]


def sig_to_params(sig: str) -> Optional[Dict[str, int]]:
    """Shape signature back to its params dict (int-valued keys only —
    exactly what make_sig emits for the kernel families)."""
    if not sig:
        return None
    out: Dict[str, int] = {}
    for kv in sig.split(","):
        k, eq, v = kv.partition("=")
        if not eq:
            return None
        try:
            out[k] = int(v)
        except ValueError:
            return None
    return out


def merge_entry(mine: Optional[Dict[str, Any]],
                theirs: Dict[str, Any]) -> Dict[str, Any]:
    """Conflict resolution for one key: measured beats interpolated,
    then newest `updated_at` wins (a fresh re-measurement supersedes an
    old one; ties keep the incumbent — merging a table into itself is a
    no-op). Entries without provenance/updated_at rank weakest/oldest,
    so a modern entry always survives a legacy one."""
    if mine is None:
        return theirs
    rank_m = _PROVENANCE_RANK.get(
        (mine.get("meta") or {}).get("provenance"), 0)
    rank_t = _PROVENANCE_RANK.get(
        (theirs.get("meta") or {}).get("provenance"), 0)
    if rank_t != rank_m:
        return theirs if rank_t > rank_m else mine
    at_m = float((mine.get("meta") or {}).get("updated_at", 0) or 0)
    at_t = float((theirs.get("meta") or {}).get("updated_at", 0) or 0)
    return theirs if at_t > at_m else mine


class TunedTable:
    """entries: key -> {"config": {...}, "meta": {...}}."""

    def __init__(self, path: Optional[str] = None, autoload: bool = True):
        self.path = path
        self.entries: Dict[str, Dict[str, Any]] = {}
        self._lru: "collections.OrderedDict[str, Any]" = (
            collections.OrderedDict())
        self._fp: Optional[str] = None
        if path and autoload:
            self.load(path)

    # -------------------------------------------------------- lookups --
    def get(self, kernel: str, params: Dict[str, Any], dtype: str,
            device: Optional[str] = None) -> Optional[Dict[str, Any]]:
        key = entry_key(kernel, make_sig(params), dtype,
                        device if device is not None else device_kind())
        if key in self._lru:
            self._lru.move_to_end(key)
            cfg = self._lru[key]
        else:
            e = self.entries.get(key)
            cfg = dict(e["config"]) if e else None
            self._lru[key] = cfg
            if len(self._lru) > _LRU_CAP:
                self._lru.popitem(last=False)
        # fresh dict per caller: a consumer mutating its config must not
        # corrupt the cached copy
        return dict(cfg) if cfg is not None else None

    def put(self, kernel: str, params: Dict[str, Any], dtype: str,
            config: Dict[str, Any], device: Optional[str] = None,
            meta: Optional[Dict[str, Any]] = None,
            provenance: Optional[str] = None) -> str:
        key = entry_key(kernel, make_sig(params), dtype,
                        device if device is not None else device_kind())
        m = dict(meta or {})
        if provenance is not None:
            m["provenance"] = provenance
            m.setdefault("updated_at", int(time.time()))
        self.entries[key] = {"config": dict(config), "meta": m}
        self._lru.pop(key, None)
        self._fp = None
        return key

    def __len__(self) -> int:
        return len(self.entries)

    def entries_for(self, kernel: str, dtype: str,
                    device: Optional[str] = None
                    ) -> List[Tuple[Dict[str, int], Dict[str, Any],
                                    Dict[str, Any]]]:
        """All (params, config, meta) tuned for this kernel/dtype/device
        — the interpolation neighbor pool (tune/overrides.py). Malformed
        keys/signatures are skipped, never fatal."""
        device = device if device is not None else device_kind()
        out = []
        for key, e in self.entries.items():
            parsed = parse_key(key)
            if parsed is None:
                continue
            k, sig, dt, dev = parsed
            if k != kernel or dt != dtype or dev != device:
                continue
            params = sig_to_params(sig)
            if params is None or not isinstance(e.get("config"), dict):
                continue
            out.append((params, dict(e["config"]),
                        dict(e.get("meta") or {})))
        return out

    def merge_from(self, other: "TunedTable") -> Dict[str, int]:
        """Merge `other`'s entries into this table under the
        measured-beats-interpolated / newest-wins policy. Returns
        {"added", "replaced", "kept"} counts for the CLI report."""
        stats = {"added": 0, "replaced": 0, "kept": 0}
        for key, theirs in other.entries.items():
            if not isinstance(theirs, dict) \
                    or not isinstance(theirs.get("config"), dict):
                continue
            mine = self.entries.get(key)
            winner = merge_entry(mine, theirs)
            if mine is None:
                stats["added"] += 1
            elif winner is theirs:
                stats["replaced"] += 1
            else:
                stats["kept"] += 1
                continue
            self.entries[key] = {"config": dict(theirs["config"]),
                                 "meta": dict(theirs.get("meta") or {})}
            self._lru.pop(key, None)
            self._fp = None
        return stats

    def fingerprint(self) -> str:
        """Content hash over the entry set — folded into the Executor's
        jit cache key (a reloaded/retuned table must re-trace) and
        recorded in saved-model metadata (serving detects staleness)."""
        if self._fp is None:
            blob = json.dumps(self.entries, sort_keys=True).encode()
            self._fp = hashlib.sha1(blob).hexdigest()[:16]
        return self._fp

    # ------------------------------------------------------------- io --
    def load(self, path: Optional[str] = None) -> "TunedTable":
        path = path or self.path
        self.path = path
        self.entries = {}
        self._lru.clear()
        self._fp = None
        if not path or not os.path.exists(path):
            return self
        try:
            with open(path) as f:
                doc = json.load(f)
            if not isinstance(doc, dict):
                raise ValueError("table root must be an object")
            if doc.get("version") != TABLE_VERSION:
                warnings.warn(
                    f"tuned table {path} has schema version "
                    f"{doc.get('version')!r} (this runtime reads "
                    f"{TABLE_VERSION}); ignoring it — analytic defaults "
                    "apply", stacklevel=2)
                return self
            entries = doc.get("entries", {})
            if not isinstance(entries, dict) or not all(
                    isinstance(e, dict) and isinstance(e.get("config"), dict)
                    for e in entries.values()):
                raise ValueError("malformed entries")
            self.entries = entries
        except (json.JSONDecodeError, ValueError, KeyError, TypeError) as e:
            quarantine = path + ".corrupt"
            try:
                os.replace(path, quarantine)
                moved = f"; moved aside to {quarantine}"
            except OSError:
                moved = ""
            warnings.warn(
                f"tuned table {path} is corrupt ({e}){moved}; starting "
                "empty — analytic defaults apply", stacklevel=2)
        return self

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path
        if not path:
            raise ValueError("TunedTable.save: no path configured")
        self.path = path
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        doc = {"version": TABLE_VERSION, "device_kind": device_kind(),
               "entries": self.entries}
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".tuned-", suffix=".json")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, path)  # atomic on POSIX
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return path


class TableFormatError(ValueError):
    """A table file that must not be silently ignored (tune import /
    merge): wrong schema version, malformed JSON, bad entry shape."""


def load_strict(path: str) -> TunedTable:
    """Load a table for import/merge: unlike TunedTable.load (runtime
    read-path, degrades to empty with a warning), this RAISES
    TableFormatError on schema-version mismatch or corruption — an
    operator moving tables between hosts wants the loud failure."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError as e:
        raise TableFormatError(f"cannot read table {path}: {e}") from e
    except json.JSONDecodeError as e:
        raise TableFormatError(f"table {path} is not JSON: {e}") from e
    if not isinstance(doc, dict):
        raise TableFormatError(f"table {path}: root must be an object")
    if doc.get("version") != TABLE_VERSION:
        raise TableFormatError(
            f"table {path} has schema version {doc.get('version')!r}; "
            f"this build reads version {TABLE_VERSION} — re-export it "
            "from a matching build")
    entries = doc.get("entries", {})
    if not isinstance(entries, dict) or not all(
            isinstance(e, dict) and isinstance(e.get("config"), dict)
            for e in entries.values()):
        raise TableFormatError(f"table {path}: malformed entries")
    t = TunedTable(path, autoload=False)
    t.entries = entries
    return t


def default_path() -> str:
    """PT_TUNE_CACHE env, else the XDG-ish per-user location."""
    env = os.environ.get("PT_TUNE_CACHE")
    if env:
        return env
    base = os.environ.get("XDG_CACHE_HOME",
                          os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(base, "paddle_tpu", "tuned.json")


def base_table_dir() -> str:
    """Where the pre-tuned fleet tables live: PT_TUNE_TABLES_DIR env
    (tests point it at a tmpdir; empty string disables the base layer
    entirely), else the package's shipped `tune/tables/` directory."""
    env = os.environ.get("PT_TUNE_TABLES_DIR")
    if env is not None:
        return env
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tables")


def base_table_path(device: Optional[str] = None) -> Optional[str]:
    """The shipped table for this device kind, or None when the package
    carries none (every non-TPU dev box): `tables/<device_kind>.json`,
    device_kind already filename-safe (lowercased, '-'-joined)."""
    d = base_table_dir()
    if not d:
        return None
    path = os.path.join(d, f"{device or device_kind()}.json")
    return path if os.path.exists(path) else None
