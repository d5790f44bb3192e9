"""ouro.opt_device_ms: `opt.device_ms` on the ouro-2.6b cell, under a name of its
own: device time per step in the optimizer's own update ops: Adam over 612 M
parameters, behind the loop (a weight's gradient is whole only when the
backward loop has ended, so no update is fused into a weight-gradient GEMM
here). That reader's manifest entry lists the cells that were there, and a
`model_config` PR may not edit an entry that is there (PERF.md section 7): this
file only loads `opt.device_ms.py` by path and returns what it returns. A later
`benchmark` PR that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "opt.device_ms"


def compute(run):
    return load_reader(WRAPS).compute(run)
