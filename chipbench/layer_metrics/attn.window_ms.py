"""attn.window_ms (layer: Window attention). Device time per step in the
attention kernels of the WINDOW layers of a model that mixes window and global
layers: the `tpu_custom_call` rows under the scope of a `flash_attention` op
that is a window layer's, forward and backward, ms a step. The reader gets no
attributes (`run["program_ops"]` has each op's type, scope, inputs and
outputs), so the layers are told apart by STRUCTURE, by the published rule
itself (rotary on window layers only): of the gated attention layers (the
kernels' output meets a `sigmoid`'s in an `elementwise_mul`), the one whose Q
is the output of a `rotary_embedding` op is a window layer's, the one whose Q
comes straight from a norm is a global layer's. `info` gives the global
layers' kernels beside it and the time of one window layer over one global
layer's: the query-key pairs say 0.4375 at T 8192 and W 2048, the key blocks
visited at 1024 x 1024 say 0.67 (3 against 4.5 a query block); near 1 the
window is not in the kernels. `attn.assemble_ms` reads the rest of these
layers. Nothing to read where the Program has no gated attention layer (every
other configuration; a parent of the PR that added it) or the trace no
scopes."""

KERNELS, ROTARY = "flash_attention", "rotary_embedding"


def layers(program_ops):
    """One dict per gated attention layer: its `kind` ("window" | "global")
    and {scope: part} of its ops, found from each `flash_attention` op."""
    made_by = {n: op for op in program_ops
               for names in op["outputs"].values() for n in names}

    def behind(op, slot="X"):
        names = (op or {}).get("inputs", {}).get(slot) or ()
        return made_by.get(names[0]) if names else None

    def reads(name):
        return [op for op in program_ops
                if any(name in names for names in op["inputs"].values())]

    found = []
    for fa in (op for op in program_ops if op["type"] == KERNELS):
        out = fa["outputs"]["Out"][0]
        gate_mul = next((op for op in reads(out)
                         if op["type"] == "elementwise_mul"), None)
        if gate_mul is None:
            continue
        sigmoid = next((made_by[n] for names in gate_mul["inputs"].values()
                        for n in names if n != out and n in made_by
                        and made_by[n]["type"] == "sigmoid"), None)
        if sigmoid is None:
            continue
        parts = {fa["scope"]: "kernels", gate_mul["scope"]: "gate_mul",
                 sigmoid["scope"]: "gate_sigmoid"}
        proj = behind(sigmoid)
        if proj is not None:
            parts[proj["scope"]] = "gate_proj"
        for op in reads(gate_mul["outputs"]["Out"][0]):
            parts[op["scope"]] = "out_proj"
        kind = "global"
        for slot, name in (("Q", "q"), ("K", "k")):
            op = behind(fa, slot)
            if op is not None and op["type"] == ROTARY:
                parts[op["scope"]] = name + "_rotary"
                if slot == "Q":
                    kind = "window"
                op = behind(op)
            if op is not None and op["type"] == "rms_norm":
                parts[op["scope"]] = name + "_norm"
                op = behind(op)
            if op is not None:
                parts[op["scope"]] = name + "_proj"
        v = behind(fa, "V")
        if v is not None:
            parts[v["scope"]] = "v_proj"
        found.append({"kind": kind, "parts": parts})
    return found


def kernel_rows(run):
    """{"window": [rows], "global": [rows]}: the `tpu_custom_call` rows under
    each kind's `flash_attention` scopes; {} where there is nothing."""
    ops = (run.get("trace") or {}).get("ops")
    if not ops or not run.get("program_ops"):
        return {}
    kind_of = {scope: layer["kind"] for layer in layers(run["program_ops"])
               for scope, part in layer["parts"].items() if part == "kernels"}
    out = {}
    for r in ops:
        if r["target"] == "tpu_custom_call" and r["scope"] in kind_of:
            out.setdefault(kind_of[r["scope"]], []).append(r)
    return out


def _ms(rows, run):
    return sum(r["ns"] for r in rows) / 1e6 / run["steps"]


def compute(run):
    mine = kernel_rows(run).get("window")
    return _ms(mine, run) if mine else None


def info(run):
    rows = kernel_rows(run)
    count = {"window": 0, "global": 0}
    for layer in layers(run["program_ops"]):
        count[layer["kind"]] += 1
    out = {"window_layers": count["window"], "global_layers": count["global"],
           "window_kernels_ms": _ms(rows.get("window", ()), run),
           "global_kernels_ms": _ms(rows.get("global", ()), run)}
    if count["window"] and count["global"] and out["global_kernels_ms"]:
        out["one_window_layer_over_one_global"] = (
            out["window_kernels_ms"] / count["window"]
            / (out["global_kernels_ms"] / count["global"]))
    by_pass = {}
    for kind, mine in rows.items():
        for r in mine:
            which = ("backward" if r["transform"].startswith("transpose")
                     else "forward")
            key = f"{kind}_{which}_ms"
            by_pass[key] = by_pass.get(key, 0.0) + r["ns"] / 1e6 / run["steps"]
    out["by_pass_ms"] = by_pass
    return out
