"""trinity.attn_device_ms: `attn.device_ms` on the trinity-mini cell, under a name
of its own: device time per step under the attention ops' scopes, all five
layers: the fused kernels and whatever XLA runs around them (here the sum of a
group's dK and dV over its 8 query heads). That reader's manifest entry lists
the cells that were there, and a `model_config` PR may not edit an entry that
is there (PERF.md section 7): this file only loads `attn.device_ms.py` by path
and returns what it returns. A later `benchmark` PR that drops the `workloads`
lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "attn.device_ms"


def compute(run):
    return load_reader(WRAPS).compute(run)


def info(run):
    return load_reader(WRAPS).info(run)
