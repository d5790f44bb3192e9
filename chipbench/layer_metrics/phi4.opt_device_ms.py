"""phi4.opt_device_ms: `opt.device_ms` on the phi-4-mini-flash-reasoning cell, under a name of its own:
device time per step in the optimizer's ops: Adam over 697 M parameters. That reader's manifest entry lists the cells that were there, and a
`model_config` PR may not edit an entry that is there (PERF.md section 7): this
file only loads `opt.device_ms.py` by path and returns what it returns. A later
`benchmark` PR that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "opt.device_ms"


def compute(run):
    return load_reader(WRAPS).compute(run)
