"""attn.masked_pair_share (layer: Kernels). Of the query-key score pairs the
step's attention ops compute, the share the mask throws away: 1 - kept /
computed over the `pt_flash_attention_pairs{path,pairs}` series of the
program's metrics registry at the window's close (`run["registry"]`), summed
over the dispatcher's paths. `ops/flash_ops.py` sets them when an attention op
is traced, from the static shapes: `kept` the pairs inside the causal / window
band, `computed` what the path's forward runs for them (the kernels: the whole
of a block inside the band and, of a block a diagonal crosses, the strips
they run; the XLA formulation: every pair); the backward recomputes the same
pairs, so the share is the step's. 0 would be a kernel that computes no pair
it masks. Nothing to read where the program publishes no such series."""

import re

FAMILY = "pt_flash_attention_pairs"
_LABEL = re.compile(r'(\w+)="([^"]*)"')


def pairs(registry):
    """{path: {"computed": n, "kept": n}} of the ops traced."""
    out = {}
    for series, value in (registry or {}).items():
        if series.startswith(FAMILY + "{"):
            labels = dict(_LABEL.findall(series))
            out.setdefault(labels["path"], {})[labels["pairs"]] = value
    return out


def compute(run):
    by_path = pairs(run.get("registry"))
    computed = sum(p.get("computed", 0.0) for p in by_path.values())
    if computed <= 0:
        return None
    return 1.0 - sum(p.get("kept", 0.0) for p in by_path.values()) / computed


def info(run):
    return {"pairs_by_path": pairs(run.get("registry"))}
