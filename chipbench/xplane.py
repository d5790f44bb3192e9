"""From the profiler's trace (`*.xplane.pb`) to numbers, with nothing but
`jax.profiler.ProfileData` and, for the one thing it does not hand out (the
stats of an event's metadata, where the compiler's `op_name` lives), forty
lines that read the protobuf's wire format.

Two stages, so that the second can be checked without a chip
(`selftest.py`, `testdata/`):

1. `load(path)` -> `{"planes": [{"name", "lines": [{"name", "events":
   [[name, start_ns, duration_ns], ...]}], "op_names": {instruction text:
   op_name}}]}` — plain lists; `op_names` on device planes only.
2. `reduce(trace, chips)` -> busy union, idle share, custom-call and
   collective time per device plane, the ten device ops with most time,
   the idle gaps by what the host was doing, and `ops`: the first device's
   whole op table, one row per distinct instruction (see `op_table`).

Where an op's scope comes from (looked at on the chip in PR 26, jax 0.9.0,
TPU v5 lite): `ProfileData`'s events on `XLA Ops` carry three stats
(`device_offset_ps`, `device_duration_ps`, `Time Scale Multiplier`) and no
name of the op that made them. The file does hold it: every event points at
an `XEventMetadata` of its plane whose name is the instruction's text and
whose stats include `tf_op` = the HLO metadata's `op_name`, such as
`jit(raw)/transpose(jvp(mul.fc_394.tmp_395))/dot_general:` (beside
`hlo_category`, `flops`, `bytes_accessed`, `source`). `event_op_names`
reads exactly that, so no HLO text of the compiled step is needed. Async
copies, slices and XLA's own `ConcatBitcast` custom-calls have no `tf_op`:
their rows have the scope "".

What a TPU v5e trace looks like (read by hand in PR 23, jax 0.9.0): one
plane per chip named `/device:TPU:<n>` with the lines `Steps`, `XLA
Modules` (one event per executed program), `XLA Ops` (one event per
executed HLO op, named by the instruction's whole text; a `while` covers
its body's ops, which are on the line too) and `Async XLA Ops` (DMAs in
flight: copy-start/slice-start to their done; not device busy time).
Host threads are lines of the plane `/host:CPU`;
`jax.profiler.TraceAnnotation`s are events on the line of the thread that
wrote them (`python3`), in ns on the same clock as the device lines.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "chipbench."
# an op is a collective by the HLO opcode its name starts with
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
# control-flow ops cover their bodies' ops: they are on the line for the
# whole of the loop, so they say nothing about which op was running
CONTAINERS = ("while", "conditional", "call")


# ------------------------------------------------------------------ load ---
def load(path: str) -> dict:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        buf = f.read()
    data = ProfileData.from_serialized_xspace(buf)
    op_names = event_op_names(buf)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                events.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
        if plane.name.startswith(DEVICE_PLANE):
            planes[-1]["op_names"] = op_names.get(plane.name, {})
    return {"planes": planes}


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo, hi):
    """One protobuf message's fields: (number, value) with a varint's value,
    or the (start, end) of a length-delimited field; fixed-width fields are
    passed over."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane.pb")


def event_op_names(buf: bytes, stat: str = "tf_op") -> dict:
    """{plane name: {event metadata name: its `tf_op` stat}} of a serialized
    `XSpace`, for the planes that have any. Field numbers (tsl's
    xplane.proto): XSpace.planes 1; XPlane.name 2, .event_metadata 4,
    .stat_metadata 5 (maps: key 1, value 2); XEventMetadata.name 2, .stats
    5; XStatMetadata.id 1, .name 2; XStat.metadata_id 1, .str_value 5,
    .ref_value 7 (a string kept as a stat metadata's name). The lines and
    their events are passed over unread."""
    def text(span):
        return buf[span[0]:span[1]].decode("utf-8", "replace")

    out = {}
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for number, span in _fields(buf, *plane):
            if number == 2:
                name = text(span)
            elif number == 4:
                events += [v for k, v in _fields(buf, *span) if k == 2]
            elif number == 5:
                for k, v in _fields(buf, *span):
                    if k == 2:
                        meta = dict(_fields(buf, *v))
                        stat_names[meta.get(1)] = text(meta[2]) if 2 in meta else ""
        found = {}
        for span in events:
            meta_name, op_name = "", None
            for number, value in _fields(buf, *span):
                if number == 2:
                    meta_name = text(value)
                elif number == 5:
                    st = dict(_fields(buf, *value))
                    if stat_names.get(st.get(1)) == stat:
                        op_name = text(st[5]) if 5 in st \
                            else stat_names.get(st.get(7), "")
            if op_name:
                found[meta_name] = op_name
        if found:
            out[name] = found
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


# ---------------------------------------------------------------- reduce ---
def union(intervals):
    """Merged, sorted [start, end) list of possibly overlapping or nested
    intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(merged) -> int:
    return sum(e - s for s, e in merged)


_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")
_SHAPE = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")


def opcode(name: str) -> str:
    """The HLO opcode of an `XLA Ops` event. The event's name is the whole
    instruction as the compiler prints it,
    `%fusion.10 = bf16[128000,2048]{1,0:T(8,128)(2,1)} fusion(...), kind=...`:
    the opcode is the first lower-case word followed by `(` after the
    ` = ` (shapes put `[` or an upper-case letter there). A bare name
    (`fusion.12`, as older traces have it) gives its stem."""
    _, eq, rest = name.partition(" = ")
    if eq:
        m = _OPCODE.search(" " + rest)
        if m:
            return m.group(1)
    base = name.lstrip("%").split(" ")[0]
    head, _, tail = base.rpartition(".")
    return head if head and tail.isdigit() else base


def short(name: str) -> str:
    """`%fusion.10 fusion bf16[128000,2048]`: instruction, opcode, first
    output shape — what the breakdown prints instead of the full text."""
    inst, eq, rest = name.partition(" = ")
    if not eq:
        return name[:96]
    m = _SHAPE.match(rest)
    return f"{inst} {opcode(name)} {m.group(1) if m else ''}".strip()[:96]


def is_collective(name: str) -> bool:
    return opcode(name).startswith(COLLECTIVES)


def is_container(name: str) -> bool:
    return opcode(name) in CONTAINERS


def is_custom_call(name: str) -> bool:
    """A compiled Pallas / Mosaic kernel: a `custom-call` whose target is
    `tpu_custom_call` (read in PR 23's traces: the target is part of the
    event's name; XLA's own `ConcatBitcast` custom-calls are not kernels)."""
    return (opcode(name) == "custom-call"
            and 'custom_call_target="tpu_custom_call"' in name)


_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_WRAPPED = re.compile(r"^((?:[A-Za-z_]+\()*)([^()]*)\)*$")


def scope_of(op_name: str):
    """(scope, transform) of an HLO `op_name` such as
    `jit(raw)/transpose(jvp(mul.fc_394.tmp_395))/dot_general:` ->
    (`mul.fc_394.tmp_395`, `transpose(jvp`): the first element of the path
    that is not a `jit(...)`, which is the `jax.named_scope` the program
    put on the op (`Executor`: `<op type>.<first output>`), without the
    transformations JAX wrapped it in. `jvp(` alone is the forward half of
    a differentiated op, `transpose(` its backward half. ("", "") where
    there is no such element."""
    parts = op_name.rstrip(":").split("/")
    for part in parts[:-1] if len(parts) > 1 else parts:
        if part.startswith(("jit(", "pjit(")):
            continue
        m = _WRAPPED.match(part)
        if m:
            return m.group(2), m.group(1).rstrip("(")
        return part, ""
    return "", ""


def op_table(ranked: list, kinds: dict, op_names: dict) -> list:
    """One row per distinct instruction of `ranked` ([(instruction text,
    [count, ns])], already cut to the window and longest first):

    name       the instruction's name, `%fusion.10`
    opcode     the HLO opcode, `fusion`
    shape      the first output's type and shape, `bf16[128000,2048]`
    target     a custom-call's `custom_call_target`, else None
    container  True for `while` / `conditional` / `call`: its ns cover its
               body's ops, which have rows of their own; every sum over
               device time leaves containers out
    count, ns  events inside the window and their summed duration there
    op_name    the compiler's `op_name` for it, "" where it has none
    scope, transform   `scope_of(op_name)`
    """
    rows = []
    for text, (count, ns) in ranked:
        inst, _, rest = text.partition(" = ")
        shape = _SHAPE.match(rest)
        target = _TARGET.search(text) if opcode(text) == "custom-call" else None
        op_name = op_names.get(text, "").rstrip(":")
        scope, transform = scope_of(op_name) if op_name else ("", "")
        rows.append({"name": inst.split(" ")[0][:96], "opcode": opcode(text),
                     "shape": shape.group(1) if shape else "",
                     "target": target.group(1) if target else None,
                     "container": kinds[text][0], "count": count, "ns": ns,
                     "op_name": op_name, "scope": scope,
                     "transform": transform})
    return rows


def _annotations(trace):
    """(annotations, host): the host events this harness wrote, and every
    event of the threads that wrote them (JAX's own among them:
    `PjitFunction(raw)` is a dispatch, `np.asarray(jax.Array)` a read),
    both [(name, start, end)] sorted by start."""
    ann, host = [], []
    for plane in trace["planes"]:
        if not plane["name"].startswith(HOST_PLANE):
            continue
        for line in plane["lines"]:
            evs = [(ev[0], ev[1], ev[1] + ev[2]) for ev in line["events"]]
            mine = [e for e in evs if e[0].startswith(ANNOTATION_PREFIX)]
            if mine:
                ann += mine
                host += evs
    return sorted(ann, key=lambda a: a[1]), sorted(host, key=lambda a: a[1])


def _label_gaps(gaps, host):
    """{label: ns}: each idle gap [s, e) under what the host was doing in
    it — the innermost (shortest) event of the harness's thread that
    covers at least half of the gap, or 'unattributed'. One sweep: gaps
    and host events both come sorted by start."""
    out, live, i = {}, [], 0
    for s, e in gaps:
        while i < len(host) and host[i][1] < e:
            live.append(host[i])
            i += 1
        live = [h for h in live if h[2] > s]
        best, best_len = "unattributed", None
        for name, a, b in live:
            if (min(b, e) - max(a, s)) * 2 >= (e - s) and (
                    best_len is None or b - a < best_len):
                best, best_len = name, b - a
        if best.startswith(ANNOTATION_PREFIX):
            best = best[len(ANNOTATION_PREFIX):]
        out[best] = out.get(best, 0) + (e - s)
    return out


def reduce(trace: dict, chips: int = 1) -> dict:
    ann, host = _annotations(trace)
    device_planes = sorted(
        (p for p in trace["planes"] if p["name"].startswith(DEVICE_PLANE)),
        key=lambda p: p["name"])[:chips]
    if not device_planes:
        raise ValueError("the trace has no device plane: planes are " +
                         ", ".join(p["name"] for p in trace["planes"]))
    ops_by_plane = []
    for p in device_planes:
        line = next((ln for ln in p["lines"] if ln["name"] == OPS_LINE), None)
        if line is None or not line["events"]:
            raise ValueError(f"{p['name']} has no '{OPS_LINE}' events")
        ops_by_plane.append(line["events"])
    # the window: the harness's own annotations (the trace starts after
    # the warm-up, so every one lies in the window), on the trace's clock,
    # from the first to the last; without them (a foreign trace), the
    # span of the device's own events
    if ann:
        lo, hi = ann[0][1], max(a[2] for a in ann)
    else:
        lo = min(ev[1] for evs in ops_by_plane for ev in evs)
        hi = max(ev[1] + ev[2] for evs in ops_by_plane for ev in evs)
    window = hi - lo
    kinds = {}   # an instruction's text -> (container, kernel, collective)

    def kind(name):
        k = kinds.get(name)
        if k is None:
            k = kinds[name] = (is_container(name), is_custom_call(name),
                               is_collective(name))
        return k

    planes, op_rows = [], {}   # first device: instruction -> [count, ns]
    for p, events in zip(device_planes, ops_by_plane):
        leaf, cust, coll = [], [], []
        for name, start, dur, *_ in events:
            s, e = max(start, lo), min(start + dur, hi)
            if e <= s:
                continue
            container, kernel, collective = kind(name)
            if p is device_planes[0]:
                # the table lists loops too: a loop's time is its body's
                # ops (also listed) plus the gaps between them
                row = op_rows.setdefault(name, [0, 0])
                row[0] += 1
                row[1] += e - s
            if container:
                continue
            leaf.append((s, e))
            if kernel:
                cust.append((s, e))
            if collective:
                coll.append((s, e))
        planes.append({"name": p["name"], "busy_ns": total(union(leaf)),
                       "custom_call_ns": total(union(cust)),
                       "collective_ns": total(union(coll)),
                       "op_events": len(leaf)})
        if p is device_planes[0]:
            busy = union(leaf)
    # idle gaps of the first device, by what the host was doing
    gaps, prev = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    ranked = sorted(op_rows.items(), key=lambda kv: -kv[1][1])
    top = [(short(n) + (" (body included)" if kinds[n][0] else ""), t)
           for n, (_, t) in ranked[:10]]
    idle = sorted(_label_gaps(gaps, host).items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window / 1e9,
        "busy_s_mean": sum(p["busy_ns"] for p in planes) / len(planes) / 1e9,
        "planes": planes,
        "ops": op_table(ranked, kinds, device_planes[0].get("op_names", {})),
        "breakdown": {
            "device_ops": [[n, t / 1e9] for n, t in top],
            "idle_gaps": [[n, t / 1e9] for n, t in idle],
        },
    }


def reduce_dir(trace_dir: str, chips: int = 1) -> dict:
    return reduce(load(find_xplane(trace_dir)), chips)
