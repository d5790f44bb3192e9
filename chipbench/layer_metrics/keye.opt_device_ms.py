"""keye.opt_device_ms: `opt.device_ms` on the keye-vl-2.0-30b-a3b cell, under a
name of its own: device time a step in the optimizer's ops (Adam over 456 M
trained parameters; the frozen indexer has no state). That reader's manifest
entry lists the cells that were there, and a `model_config` PR may not edit an
entry that is there (PERF.md section 7 item 3): this file only loads
`opt.device_ms.py` by path and returns what it returns. A later `benchmark` PR
that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "opt.device_ms"


def compute(run):
    return load_reader(WRAPS).compute(run)
