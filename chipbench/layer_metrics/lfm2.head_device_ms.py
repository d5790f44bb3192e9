"""lfm2.head_device_ms: `head.device_ms` on the lfm2-24b-a2b cell, under a name of its own:
device time per step in the head: the rows found by walking back from the cost
op (the closing norm's output times [2048, 8192] and the cost over the sliced
vocabulary, logits [16 384, 8192] float32). That reader's manifest entry lists the cells that were there, and a
`model_config` PR may not edit an entry that is there (PERF.md section 7): this
file only loads `head.device_ms.py` by path and returns what it returns. A later
`benchmark` PR that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "head.device_ms"


def compute(run):
    return load_reader(WRAPS).compute(run)
