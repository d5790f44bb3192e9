"""Model FLOPs per trained item, from the configuration's sizes alone.

Copied from `bench.py` (`_build_lstm_train`, `_build_transformer_train`) so
that a later edit there cannot move the yardstick. Convention, as there: a
multiply and an add count separately (2 FLOPs per MAC) and a train step is
3x the forward pass; recomputed work does not count. These are the
operations the model needs, not what the compiled step executes.
"""

from __future__ import annotations

import importlib.util
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


def family_arithmetic(config: dict, config_dir: str):
    """The function that counts `config["flops_family"]`: this file's, for a
    family it knows (a configuration may not override those), else
    `train_flops_per_item(config, cell)` of the `flops.py` beside the
    configuration's `config.json`. Called when the manifest is read, before
    any backend starts: a family nobody counts is an error that names the
    file to add."""
    family = config.get("flops_family")
    if family in _FAMILIES:
        return _FAMILIES[family]
    path = os.path.join(config_dir, "flops.py")
    if not os.path.isfile(path):
        raise SystemExit(
            f"chipbench: flops_family {family!r} is not one of "
            f"{sorted(_FAMILIES)} and there is no {os.path.relpath(path)} "
            f"with train_flops_per_item(config, cell)")
    spec = importlib.util.spec_from_file_location("chipbench_config_flops", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn = getattr(mod, "train_flops_per_item", None)
    if not callable(fn):
        raise SystemExit(f"chipbench: {os.path.relpath(path)} defines no "
                         f"train_flops_per_item(config, cell)")
    return fn


def train_flops_per_item(config: dict, cell: dict,
                         config_dir: str = "") -> float:
    """`config["flops_family"]` names the arithmetic (`family_arithmetic`)."""
    return float(family_arithmetic(config, config_dir)(config, cell))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind (`peaks.json`)."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise SystemExit(
            f"chipbench: no published peak for device_kind {device_kind!r}; "
            f"add it to chipbench/peaks.json with its source")
    return table[device_kind]


def peak_flops(device_kind: str) -> float:
    return float(peaks(device_kind)["bf16_flops"])
