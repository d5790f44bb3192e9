"""rope.tables_ms (layer: Sparse attention). Device time per step that feeding
the rotary its positions costs: of `dsa.device_ms`'s rows, those of the
layers' `rotary_embedding` ops (parts `q_rope`, `k_rope`) that are NOT the
`qk_assemble` launches themselves (`tpu_custom_call`): the cos and sin tables
made from the fed [B, 3, T] positions (the inner scope `tables`), their two
128-lane forms a launch, the negated sine of the backward. XLA makes equal
tables once, so this is small; 0 where it folds them into other rows'.
Nothing to read where `dsa.device_ms` finds nothing."""

from chipbench.readers import load_reader

LAYER = "dsa.device_ms"
PARTS = ("q_rope", "k_rope")


def compute(run):
    mine = load_reader(LAYER).rows(run)
    if not mine:
        return None
    return sum(r["ns"] for r, p, _ in mine
               if p in PARTS and r["target"] != "tpu_custom_call"
               ) / 1e6 / run["steps"]
