"""diffattn.device_ms (layer: Differential attention). Device time per step in
the differential-attention layers: the leaf rows of the trace's op table whose
scope is one of a layer's ops (`layers.differential_attention`: the fused
projection and its bias, the split into q, k, v and into the pairs' first and
second heads, the four `flash_attention` ops, `diff_combine`, the
out-projection and its bias), forward and backward, over the window's steps. A
layer is found from `run["program_ops"]`: its `diff_combine` op names its
output `<layer>.combine.tmp_N`, and the layer's ops are those whose first
output starts with `<layer>.` (a cross layer's keys and values are another
layer's, and are counted there). Its `info` splits the time by the layer's
scopes (`qkv`, `kernels`, `combine`, `out_proj`), by pass and by layer, and
gives the kernels' part and the launches the program counted
(`pt_diff_attention_launches_total`). Nothing to read where the Program has no
such op (a parent of the PR that added it) or the trace no scopes."""

from chipbench.readers import load_reader

COMBINE, MARK = "diff_combine", ".combine.tmp_"
PARTS = ("qkv", "kernels", "combine", "out_proj")
UNITS = "gmu.device_ms"


def layers(program_ops):
    """The name prefixes of the Program's differential-attention layers."""
    out = []
    for op in program_ops:
        if op["type"] == COMBINE:
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
    layer_of = {}
    for op in run["program_ops"]:
        if op["scope"] in scopes:
            first = next(n for names in op["outputs"].values() for n in names)
            layer_of[op["scope"]] = next(p for p in prefixes
                                         if first.startswith(p))
    return [(r, scopes[r["scope"]], layer_of[r["scope"]]) for r in ops
            if not r["container"] and r["scope"] in scopes]


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
            "by_layer_ms": by_layer, "kernels_ms": kernels,
            "launches_per_step": (run.get("registry") or {}).get(
                "pt_diff_attention_launches_total")}
