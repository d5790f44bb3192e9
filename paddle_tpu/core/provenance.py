"""Which program op each instruction of a compiled step program belongs to.

A device trace names a row by the compiler's `op_name` of the instruction
that ran: `jit(raw)/transpose(jvp(mul.fc_3.tmp_4))/dot_general`, in which
`mul.fc_3.tmp_4` is the `jax.named_scope` the Executor traced the op under
(`core/executor.py:_op_scope`). Two kinds of row it cannot name: what XLA
itself put between the program's ops (layout copies, `copy-start` /
`copy-done`, slices, bitcast custom calls, fusions rooted in a `tuple`),
whose `op_name` is empty; and the ops a fusion carries besides its root's (an
Adam update fused into the GEMM that makes its gradient). Both are in the
optimized HLO text of the compiled program, which only the program holds.
`table(text)` reduces that text to one row for every instruction the device
runs as an op of its own (the entry computation and the bodies of `while` /
`conditional` / `call`), by three rules in this order:

root       the instruction's own `op_name` gives a scope. A fusion also lists
           the scopes of its fused computation's members where they differ
           from the root's (`member`), each with a weight: the members' output
           elements under that scope over those under any scope. A listing
           weight, never a share of a time.
fused      no scope of its own, but members of the computation it calls have:
           the members' scopes with the same weights, heaviest first.
neighbour  no scope anywhere: the operands are walked back and the users
           forward through other unnamed instructions, `get-tuple-element`,
           `bitcast` and `tuple`, to the first named ones on every path. One
           op among the users, in whichever pass -> the nearest user's scope
           (`consumer`: a layout copy exists for the op that reads it); else
           the nearest named producer (`producer`); else an entry parameter
           among the producers, under `argument.<variable>` (`argument`: the
           copy of a donated `moe_3.w_up`), the variable read from the
           parameter's own `op_name`, where JAX writes the argument's path
           (`donated['moe_3.w_up']`; jit leaves unused arguments out of the
           compiled program, so a parameter's number is not its place in the
           flattened arguments, and its path is in the text); else, inside a
           body, the scope of the `while` / `conditional` / `call` that runs
           it (`caller`: what a loop fetches for its next turn has its user
           behind the body's root).

An instruction none of the rules names has no row. A scope is the first
element of the `op_name`'s path that is not a `jit(...)`, as
`chipbench/xplane.py:scope_of` reads it, WITH the transformations JAX wrapped
it in (`transpose(jvp(mul.fc_3.tmp_4))`), so that a reader gets the pass of a
row the trace left without a name too; `scope_of` splits the two.

Pure text in, plain lists out; no JAX.
"""

from __future__ import annotations

import hashlib
import re
from typing import Dict, List, NamedTuple, Optional, Tuple

# cover their bodies' instructions, which have rows of their own
CONTAINERS = ("while", "conditional", "call")
# what a walk passes through whether it carries an `op_name` or not
PLUMBING = ("get-tuple-element", "bitcast", "tuple")
# never an op of its own on the device
_NOT_OPS = ("parameter", "constant") + PLUMBING + CONTAINERS

_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")
_ARRAY = re.compile(r"[a-z][a-z0-9]*\[([0-9,]*)\]")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_WRAPPED = re.compile(r"^((?:[A-Za-z_]+\()*)([^()]*)\)*$")
_ARGUMENT = re.compile(r"\[\\?'([^'\\]+)\\?'\]")
_COMMENT = re.compile(r"/\*.*?\*/")


def scope_of(op_name: str) -> Tuple[str, str]:
    """(scope, transform) of an `op_name` or of a table's scope, by
    `chipbench/xplane.py:scope_of`'s rule (a test holds the two together):
    `jit(raw)/transpose(jvp(mul.fc_3.tmp_4))/dot_general` and
    `transpose(jvp(mul.fc_3.tmp_4))` both give (`mul.fc_3.tmp_4`,
    `transpose(jvp`)."""
    m = _WRAPPED.match(_element(op_name))
    return (m.group(2), m.group(1).rstrip("(")) if m else (_element(op_name), "")


def _element(op_name: str) -> str:
    """The path element that holds the scope, transformations kept: the first
    that is not a `jit(...)`, the last element (the primitive) aside."""
    parts = op_name.rstrip(":").split("/")
    for part in parts[:-1] if len(parts) > 1 else parts:
        if not part.startswith(("jit(", "pjit(")):
            return part
    return ""


class Instruction(NamedTuple):
    """One line of a computation."""

    name: str
    opcode: str
    elements: int          # of the output, a tuple's summed
    operands: List[str]
    called: List[str]      # computations: `calls=`, `body=`, `to_apply=`, ...
    op_name: str


def _split_operands(text: str) -> List[str]:
    """Names of the operands in `text`, the inside of an opcode's brackets:
    `%a, %b`, `a, b` or `bf16[8,128]{1,0:T(8,128)(2,1)} %a`, commas inside
    brackets left alone; the printer's `/*index=5*/` before every fifth
    operand of a long list is no part of a name."""
    text = _COMMENT.sub("", text)
    names, depth, start = [], 0, 0
    for i, ch in enumerate(text + ","):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            piece = text[start:i].split()
            if piece:
                names.append(piece[-1].lstrip("%"))
            start = i + 1
    return names


def _closing(text: str, opened: int) -> int:
    """Index of the bracket that closes the one at `opened`."""
    depth = 0
    for i in range(opened, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i
    return len(text)


def _elements(shape: str) -> int:
    """Output elements of a shape's text, a tuple's summed."""
    total = 0
    for dims in _ARRAY.findall(shape):
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        total += n
    return total


def parse(text: str):
    """(module name, entry computation's name, {computation: [Instruction]})
    of an HLO module's text as `compiled.as_text()` prints it."""
    module = re.match(r"HloModule\s+([\w.\-]+)", text)
    computations: Dict[str, List[Instruction]] = {}
    entry, current = None, None
    for line in text.splitlines():
        if current is None:
            header = _HEADER.match(line)
            if header and " -> " in line:
                current = computations.setdefault(header.group(2), [])
                if header.group(1):
                    entry = header.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = " " + m.group(2)
        op = _OPCODE.search(rest)
        if not op:
            continue
        close = _closing(rest, op.end() - 1)
        attrs = rest[close + 1:]
        called = _CALLED.findall(attrs)
        for group in _BRANCHES.findall(attrs):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        op_name = _OP_NAME.search(attrs)
        current.append(Instruction(
            m.group(1), op.group(1), _elements(rest[:op.start()]),
            _split_operands(rest[op.end():close]), called,
            op_name.group(1) if op_name else ""))
    return (module.group(1) if module else ""), entry, computations


def _member_elements(computations, name, into) -> None:
    """Adds to `into` ({scope: elements}) the output elements of the named
    members of computation `name`; an unnamed member that calls a computation
    of its own (a fusion inside an async wrapper) gives its members'."""
    for inst in computations.get(name, ()):
        if inst.opcode == "parameter":
            continue
        scope = _element(inst.op_name)
        if scope:
            into[scope] = into.get(scope, 0) + max(inst.elements, 1)
        else:
            for called in inst.called:
                _member_elements(computations, called, into)


def _weights(elements: Dict[str, int]) -> List[Tuple[str, float]]:
    """[(scope, its share of the elements)], heaviest first."""
    total = float(sum(elements.values()))
    return sorted(((s, n / total) for s, n in elements.items()),
                  key=lambda kv: (-kv[1], kv[0]))


class _Walk:
    """The neighbour rule inside one computation."""

    def __init__(self, instructions, named: Dict[str, str], is_entry: bool):
        self.by_name = {i.name: i for i in instructions}
        self.named, self.is_entry = named, is_entry
        self.users: Dict[str, List[str]] = {}
        for inst in instructions:
            for operand in inst.operands:
                self.users.setdefault(operand, []).append(inst.name)

    def _first_named(self, start: str, forward: bool) -> List[str]:
        """The first named instructions on every path from `start`, nearest
        first; parameters of the entry computation count on the way back."""
        seen, frontier, found = {start}, [start], []
        while frontier:
            reached = []
            for name in frontier:
                nexts = self.users.get(name, ()) if forward \
                    else self.by_name[name].operands
                for n in nexts:
                    if n in seen or n not in self.by_name:
                        continue
                    seen.add(n)
                    inst = self.by_name[n]
                    if n in self.named and inst.opcode not in PLUMBING:
                        found.append(n)
                    elif inst.opcode == "parameter":
                        if not forward and self.is_entry:
                            found.append(n)
                    elif inst.opcode not in CONTAINERS:
                        reached.append(n)
            frontier = reached
        return found

    def scope(self, name: str) -> Optional[Tuple[str, str]]:
        """(scope, via) for an instruction with no name anywhere."""
        consumers = [self.named[n] for n in self._first_named(name, True)]
        if len({scope_of(s)[0] for s in consumers}) == 1:
            return consumers[0], "consumer"
        producers = self._first_named(name, False)
        for n in producers:
            if n in self.named:
                return self.named[n], "producer"
        if not producers:
            return None
        # what is left on the way back is entry parameters
        inst = self.by_name[producers[0]]
        variable = _ARGUMENT.search(inst.op_name)
        return "argument." + (variable.group(1) if variable
                              else inst.op_name or inst.name), "argument"


def table(text: str) -> dict:
    """`{"program": <module name>.<8 hex of the text's sha1>, "rows": [...]}`
    of a compiled program's optimized HLO text; a row is `{"instruction",
    "opcode", "rule", "scopes": [[scope, via, weight], ...]}`: under `root`
    the root's scope first (`via` `root`) and then the other members'
    (`member`); under `fused` the members' (`fused`), heaviest first: the row
    goes to the first; under `neighbour` the one scope found, its `via`
    `consumer`, `producer`, `argument` or `caller`, weight 1."""
    module, entry, computations = parse(text)
    rows: List[dict] = []
    # (computation, the scope of the instruction that calls it)
    pending, done = [(entry, "")] if entry else [], set()
    while pending:
        comp, caller = pending.pop()
        if comp in done or comp not in computations:
            continue
        done.add(comp)
        instructions = computations[comp]
        named: Dict[str, str] = {}    # instruction -> the scope it goes to
        listed: Dict[str, list] = {}
        for inst in instructions:
            if inst.opcode in CONTAINERS:
                inside = _element(inst.op_name) or caller
                pending += [(c, inside) for c in inst.called]
            if inst.opcode == "parameter":
                continue
            own = _element(inst.op_name)
            members: Dict[str, int] = {}
            if inst.opcode == "fusion" or (
                    not own and inst.opcode not in CONTAINERS):
                for called in inst.called:
                    _member_elements(computations, called, members)
            weights = _weights(members)
            if own:
                named[inst.name] = own
                others = [[s, "member", w] for s, w in weights if s != own]
                weight = dict(weights).get(own, 0.0) if others else 1.0
                listed[inst.name] = ["root", [[own, "root", weight]] + others]
            elif weights:
                named[inst.name] = weights[0][0]
                listed[inst.name] = ["fused",
                                     [[s, "fused", w] for s, w in weights]]
        walk = _Walk(instructions, named, comp == entry)
        for inst in instructions:
            if inst.opcode in _NOT_OPS:
                continue
            if inst.name in listed:
                rule, scopes = listed[inst.name]
            else:
                found = walk.scope(inst.name) or (
                    caller and (caller, "caller"))
                if not found:
                    continue
                rule, scopes = "neighbour", [[found[0], found[1], 1.0]]
            rows.append({"instruction": inst.name, "opcode": inst.opcode,
                         "rule": rule, "scopes": scopes})
    digest = hashlib.sha1(text.encode()).hexdigest()[:8]
    return {"program": f"{module}.{digest}", "rows": rows}


def published(rows) -> List[dict]:
    """The rows a trace cannot name from its own `op_name`s: the `fused` and
    `neighbour` ones, and the `root` fusions that carry another op's members
    besides their root's."""
    return [r for r in rows if r["rule"] != "root" or len(r["scopes"]) > 1]
