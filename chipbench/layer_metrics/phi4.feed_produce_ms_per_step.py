"""phi4.feed_produce_ms_per_step: `feed.produce_ms_per_step` on the phi-4-mini-flash-reasoning cell, under a name of its own:
the host's time to produce one batch of 8192 ids (the reader and the
DataFeeder). That reader's manifest entry lists the cells that were there, and a
`model_config` PR may not edit an entry that is there (PERF.md section 7): this
file only loads `feed.produce_ms_per_step.py` by path and returns what it returns. A later
`benchmark` PR that drops the `workloads` lists retires this file."""

from chipbench.readers import load_reader

WRAPS = "feed.produce_ms_per_step"


def compute(run):
    return load_reader(WRAPS).compute(run)
