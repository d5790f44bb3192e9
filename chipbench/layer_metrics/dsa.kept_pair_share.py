"""dsa.kept_pair_share (layer: Sparse attention). Of the (row, key) pairs under
the causal mask of the step's sparse-attention layers, the share the rows
KEEP: `pt_sparse_attention_kept_pairs` over `pt_sparse_attention_causal_pairs`
at the window's close (`run["registry"]`; `ops/sparse_attention_ops.py` sets
them when an op is traced, static arithmetic: min(topk, t + 1) a row). 0.234
at T 16 384 and topk 2048, 1 where T <= topk. The share of the attention
kernels' causal work that the mathematics needs: what a kernel that skips the
rest could save. Nothing to read where the program publishes no such series."""

KEPT = "pt_sparse_attention_kept_pairs"
CAUSAL = "pt_sparse_attention_causal_pairs"


def compute(run):
    registry = run.get("registry") or {}
    kept, causal = registry.get(KEPT), registry.get(CAUSAL)
    if not kept or not causal:
        return None
    return kept / causal


def info(run):
    registry = run.get("registry") or {}
    return {"kept_pairs": registry.get(KEPT),
            "causal_pairs": registry.get(CAUSAL),
            "saved_choice_bytes": registry.get(
                "pt_sparse_attention_saved_choice_bytes")}
