"""Model FLOPs per trained item, from the configuration's sizes alone.

Copied from `bench.py` (`_build_lstm_train`, `_build_transformer_train`) so
that a later edit there cannot move the yardstick. Convention, as there: a
multiply and an add count separately (2 FLOPs per MAC) and a train step is
3x the forward pass; recomputed work does not count. These are the
operations the model needs, not what the compiled step executes.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def lstm2_train_flops_per_token(cfg: dict) -> float:
    """Two stacked LSTM layers: per layer the input projection into the
    4H gates and the recurrent H -> 4H matmul. Embedding gather and the
    last-step classifier are negligible and left out, as in bench.py."""
    hidden, emb = cfg["hidden_size"], cfg["embedding_size"]
    gates = 4 * hidden
    fwd = 2 * gates * (emb + hidden) + 2 * gates * (hidden + hidden)
    return 3.0 * fwd


def transformer_lm_train_flops_per_token(cfg: dict, seqlen: int) -> float:
    """Per layer qkvo 4*d^2 + ffn 8*d^2 MACs and causal attention (two
    matmuls over T*d, halved by the mask); plus the d x vocab output head
    at the configuration's own vocabulary (50257 for GPT-2 small)."""
    dim, depth, vocab = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    fwd = depth * (2 * 12 * dim * dim + 2 * seqlen * dim) + 2 * dim * vocab
    return 3.0 * fwd


_FAMILIES = {
    "lstm2": lambda cfg, cell: lstm2_train_flops_per_token(cfg),
    "transformer_lm": lambda cfg, cell: transformer_lm_train_flops_per_token(
        cfg, cell["seqlen"]),
}


def train_flops_per_item(config: dict, cell: dict) -> float:
    """`config["flops_family"]` names the arithmetic."""
    return float(_FAMILIES[config["flops_family"]](config, cell))


def peak_flops(device_kind: str) -> float:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks or device_kind.startswith("_"):
        raise SystemExit(
            f"chipbench: no published peak for device_kind {device_kind!r}; "
            f"add it to chipbench/peaks.json with its source")
    return float(peaks[device_kind]["bf16_flops"])
