"""phi4.head_device_ms: `head.device_ms` on the phi-4-mini-flash-reasoning cell, under a name of its own:
device time per step in the head: the rows found by walking back from the cost
op (the closing norm's output times the TIED token table [25 008, 2560], transposed, the
cost over the sliced vocabulary, and the table's Adam update, which the walk finds through the
`matmul`'s parameter). That reader's manifest entry lists the cells that were there, and a
`model_config` PR may not edit an entry that is there (PERF.md section 7): this
file only loads `head.device_ms.py` by path and returns what it returns. A later
`benchmark` PR that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "head.device_ms"


def compute(run):
    return load_reader(WRAPS).compute(run)
