"""keye.bounded_step_share: `moe.bounded_step_share` on the keye-vl-2.0-30b-a3b
cell, under a name of its own: of the window's steps, the share whose live
pairs fitted one chunk of the share's row bound
(`pt_moe_row_path_total{path=0}` over both paths), per layer. That reader's
manifest entry lists the cells that were there, and a `model_config` PR may not
edit an entry that is there (PERF.md section 7 item 3): this file only loads
`moe.bounded_step_share.py` by path and returns what it returns. A later
`benchmark` PR that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "moe.bounded_step_share"


def compute(run):
    return load_reader(WRAPS).compute(run)


def info(run):
    return load_reader(WRAPS).info(run)
