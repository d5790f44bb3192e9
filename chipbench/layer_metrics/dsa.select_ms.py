"""dsa.select_ms (layer: Sparse attention). Device time per step in the selection: of `dsa.device_ms`'s rows, those of
the `sparse_keep` ops outside the inner scope `indexer`: a tile's topk-th
largest score found by counting, the ties' lowest indices, the kept sets packed
a bit a (row, key), and the loop over the tiles.
Nothing to read where `dsa.device_ms` finds nothing."""

from chipbench.readers import load_reader

LAYER = "dsa.device_ms"
PARTS = ("select",)


def compute(run):
    return load_reader(LAYER).part_ms(run, PARTS)
