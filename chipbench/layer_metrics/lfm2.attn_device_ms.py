"""lfm2.attn_device_ms: `attn.device_ms` on the lfm2-24b-a2b cell, under a name of its own:
device time per step under the attention op's scope, the one `full_attention`
layer of five: the fused kernels at 32 heads of 64 (two heads a lane block) over
T 16 384 and whatever XLA runs around them (K and V repeated to 32 heads in
front of the kernels, the sum of a group's dK and dV behind them). That reader's manifest entry lists the cells that were there, and a
`model_config` PR may not edit an entry that is there (PERF.md section 7): this
file only loads `attn.device_ms.py` by path and returns what it returns. A later
`benchmark` PR that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "attn.device_ms"


def compute(run):
    return load_reader(WRAPS).compute(run)


def info(run):
    return load_reader(WRAPS).info(run)
