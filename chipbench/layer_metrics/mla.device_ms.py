"""mla.device_ms (layer: Latent attention). Device time per step in the latent
attention layers: the leaf rows of the trace's op table (`run["trace"]["ops"]`)
whose scope is one of a latent-attention layer's ops, forward and backward,
over the window's steps. The layer's ops are read off the Program
(`run["program_ops"]`) by STRUCTURE, from each `latent_kv_expand` op (the one
op type only this layer appends), and not by a name a configuration spells:

    kernels   the `flash_attention` op that reads the expand op's K
    out       the op that reads the kernels' output (the output projection)
    assemble  the `latent_kv_expand` op: k_r laid beside every head's k_n,
              the up-projection's output split into k_n and v
    rotary    the ops that made the kernels' Q and the expand op's KRope
    q_up, q_norm, q_down      the chain behind the Q rotary, by its X inputs
    kv_up, kv_norm, split, kv_down   the chain behind the expand op's KV

`info` splits the time by part and by pass. Nothing to read where the Program
has no `latent_kv_expand` op (every other configuration; a parent of the PR
that added it) or the trace no scopes."""

EXPAND = "latent_kv_expand"
Q_CHAIN = ("q_up", "q_norm", "q_down")
KV_CHAIN = ("kv_up", "kv_norm", "split", "kv_down")


def parts(program_ops):
    """{scope: part} of every latent-attention layer's ops."""
    made_by = {n: op for op in program_ops
               for names in op["outputs"].values() for n in names}

    def behind(op, slot="X"):
        names = op["inputs"].get(slot) or ()
        return made_by.get(names[0]) if names else None

    found = {}
    for expand in (op for op in program_ops if op["type"] == EXPAND):
        found[expand["scope"]] = "assemble"
        k = expand["outputs"]["K"][0]
        kernels = next((op for op in program_ops
                        if k in (op["inputs"].get("K") or ())), None)
        if kernels is None:
            continue
        found[kernels["scope"]] = "kernels"
        o = kernels["outputs"]["Out"][0]
        found.update({op["scope"]: "out" for op in program_ops
                      if o in (op["inputs"].get("X") or ())})
        for start, slot, chain in ((kernels, "Q", Q_CHAIN),
                                   (expand, "KRope", ())):
            op = behind(start, slot)
            if op is not None:
                found[op["scope"]] = "rotary"
                for part in chain:
                    op = behind(op)
                    if op is None:
                        break
                    found[op["scope"]] = part
        op = expand
        for part, slot in zip(KV_CHAIN, ("KV", "X", "X", "X")):
            op = behind(op, slot)
            if op is None:
                break
            found[op["scope"]] = part
    return found


def rows(run):
    """[(row, part)] of the leaf rows under a latent-attention op's scope."""
    ops = (run.get("trace") or {}).get("ops")
    if not ops or not run.get("program_ops"):
        return []
    mine = parts(run["program_ops"])
    return [(r, mine[r["scope"]]) for r in ops
            if not r["container"] and r["scope"] in mine]


def which_pass(row):
    return ("transpose" if row["transform"].startswith("transpose")
            else row["transform"] or "plain")


def compute(run):
    mine = rows(run)
    if not mine:
        return None
    return sum(r["ns"] for r, _ in mine) / 1e6 / run["steps"]


def info(run):
    """ms a step by part and by pass (`jvp`: the forward; `transpose`:
    backward)."""
    by_part, by_pass = {}, {}
    for r, part in rows(run):
        ms = r["ns"] / 1e6 / run["steps"]
        by_part[part] = by_part.get(part, 0.0) + ms
        by_pass[which_pass(r)] = by_pass.get(which_pass(r), 0.0) + ms
    return {"by_part_ms": by_part, "by_pass_ms": by_pass}
