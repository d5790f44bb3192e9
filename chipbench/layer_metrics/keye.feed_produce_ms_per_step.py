"""keye.feed_produce_ms_per_step: `feed.produce_ms_per_step` on the keye-
vl-2.0-30b-a3b cell, under a name of its own: host time a step the reader and
the DataFeeder take to produce a batch (here with a [3, 16 384] position array
built span by span). That reader's manifest entry lists the cells that were
there, and a `model_config` PR may not edit an entry that is there (PERF.md
section 7 item 3): this file only loads `feed.produce_ms_per_step.py` by path
and returns what it returns. A later `benchmark` PR that drops the `workloads`
lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "feed.produce_ms_per_step"


def compute(run):
    return load_reader(WRAPS).compute(run)
