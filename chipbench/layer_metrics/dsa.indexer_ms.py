"""dsa.indexer_ms (layer: Sparse attention). Device time per step in the indexer: of `dsa.device_ms`'s rows, the
indexer's three projections (W^I_q, W^I_k, W^I_w: forward only, they are
frozen) and, inside `sparse_keep`, the rows under the inner scope `indexer`:
the scores I(t, s) = sum_j w[t, j] relu(q^I[t, j] . k^I[s]) of every tile of
512 rows against the sequence's keys.
Nothing to read where `dsa.device_ms` finds nothing."""

from chipbench.readers import load_reader

LAYER = "dsa.device_ms"
PARTS = ("indexer",)


def compute(run):
    return load_reader(LAYER).part_ms(run, PARTS)
