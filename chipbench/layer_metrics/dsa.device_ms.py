"""dsa.device_ms (layer: Sparse attention). Device time per step in the learned
sparse-attention layers, whole: the leaf rows of the trace's op table whose
scope is one of a layer's ops (`layers.sparse_attention`: the three
projections, the per-head norms and the rotary launches with their fed
tables, the indexer's three projections, `sparse_keep` (the indexer's scores
in tiles and the selection), `sparse_attention` (the attention kernels under
the keep operand), the out-projection), forward and backward, over the
window's steps. A layer is found from `run["program_ops"]`: its
`sparse_attention` op names its output `<layer>.kernels.tmp_N`, and the
layer's ops are those whose first output starts with `<layer>.`. A row's part
is its op's (`qkv`, `q_norm`, `k_norm`, `q_rope`, `k_rope`, `indexer`,
`select`, `kernels`, `out_proj`); inside `sparse_keep` the rows the compiler
names under the inner scope `indexer` are the indexer's and the others the
selection's. Its `info` splits the time by part, by pass and by layer and
gives the kernels' part. Nothing to read where the Program has no such op (a
parent of the PR that added it) or the trace no scopes."""

from chipbench.readers import load_reader

KERNELS, MARK = "sparse_attention", ".kernels.tmp_"
KEEP = "sparse_keep"
UNITS = "gmu.device_ms"


def layers(program_ops):
    """The name prefixes of the Program's sparse-attention layers."""
    out = []
    for op in program_ops:
        if op["type"] == KERNELS:
            name = op["outputs"]["Out"][0]
            if MARK in name:
                out.append(name[:name.index(MARK)] + ".")
    return out


def rows(run):
    """[(row, part, layer)]."""
    ops = (run.get("trace") or {}).get("ops")
    if not ops or not run.get("program_ops"):
        return []
    prefixes = layers(run["program_ops"])
    scopes = load_reader(UNITS).scopes_of(run["program_ops"], prefixes)
    layer_of, keeps = {}, set()
    for op in run["program_ops"]:
        if op["scope"] in scopes:
            first = next(n for names in op["outputs"].values() for n in names)
            layer_of[op["scope"]] = next(p for p in prefixes
                                         if first.startswith(p))
            if op["type"] == KEEP:
                keeps.add(op["scope"])

    def part(r):
        if r["scope"] in keeps:
            return "indexer" if "/indexer/" in (r.get("op_name") or "") \
                else "select"
        return scopes[r["scope"]]

    return [(r, part(r), layer_of[r["scope"]]) for r in ops
            if not r["container"] and r["scope"] in scopes]


def part_ms(run, parts):
    """ms a step of the rows whose part is one of `parts`; None where the
    layer has no row at all."""
    mine = rows(run)
    if not mine:
        return None
    return sum(r["ns"] for r, p, _ in mine if p in parts) / 1e6 / run["steps"]


def compute(run):
    mine = rows(run)
    if not mine:
        return None
    return sum(r["ns"] for r, _, _ in mine) / 1e6 / run["steps"]


def info(run):
    by_part, by_pass, by_layer, kernels = {}, {}, {}, 0.0
    for r, part, layer in rows(run):
        ms = r["ns"] / 1e6 / run["steps"]
        which = ("transpose" if r["transform"].startswith("transpose")
                 else r["transform"] or "plain")
        by_part[part] = by_part.get(part, 0.0) + ms
        by_pass[which] = by_pass.get(which, 0.0) + ms
        by_layer[layer] = by_layer.get(layer, 0.0) + ms
        if r["target"] == "tpu_custom_call":
            kernels += ms
    return {"by_scope_ms": by_part, "by_pass_ms": by_pass,
            "by_layer_ms": by_layer, "kernels_ms": kernels}
