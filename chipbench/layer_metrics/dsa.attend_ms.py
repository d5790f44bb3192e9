"""dsa.attend_ms (layer: Sparse attention). Device time per step in the attention over the kept keys: of
`dsa.device_ms`'s rows, those of the `sparse_attention` ops (the packed
flash kernels under the keep operand, forward and backward, and whatever XLA
puts around them).
Nothing to read where `dsa.device_ms` finds nothing."""

from chipbench.readers import load_reader

LAYER = "dsa.device_ms"
PARTS = ("kernels",)


def compute(run):
    return load_reader(LAYER).part_ms(run, PARTS)
