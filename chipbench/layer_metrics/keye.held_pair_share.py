"""keye.held_pair_share: `moe.held_pair_share` on the keye-vl-2.0-30b-a3b cell,
under a name of its own: of the (token, slot) pairs the window's routers chose,
the share that chose an expert this chip holds (`pt_moe_held_pairs_total` over
`pt_moe_expert_tokens_total`; 1/8 under even routing: 16 of 128). That reader's
manifest entry lists the cells that were there, and a `model_config` PR may not
edit an entry that is there (PERF.md section 7 item 3): this file only loads
`moe.held_pair_share.py` by path and returns what it returns. A later
`benchmark` PR that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "moe.held_pair_share"


def compute(run):
    return load_reader(WRAPS).compute(run)


def info(run):
    return load_reader(WRAPS).info(run)
