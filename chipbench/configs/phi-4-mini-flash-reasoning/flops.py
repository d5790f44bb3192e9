"""Model FLOPs per trained token of the Phi-4-mini-flash family (`flops_family`
"phi4flash"), by `chipbench/flops.py`'s convention: a multiply and an add count
separately, a train step is 3x the forward pass, recomputed work counts
nothing.

Per layer, forward, in FLOPs a token (d = hidden_size, f = intermediate_size,
H query heads, KV K/V heads, D = d / H; d_in = mamba_expand d, N =
mamba_d_state, R = mamba_dt_rank), the layer's kind by its PUBLISHED index
(`reference.py:held_layers`):

- "mamba": the four projections W_in [d, 2 d_in], W_x [d_in, R + 2 N], W_dt
  [R, d_in], W_out [d_in, d]: 2 x (2 d d_in + d_in (R + 2 N) + R d_in + d_in
  d). The taps, the gate and the scan itself (9 d_in N operations a token on
  the vector unit: `kernels/selective_scan.py` counts them for its roofline)
  are left out, as every elementwise pass is.
- "gmu": W_g [d, d_in] and W_o [d_in, d]: 2 x 2 d d_in.
- "window" / "full": W_qkv [d, (H + 2 KV) D] and W_o [d, d]; "cross": W_q [d,
  d] and W_o. The kernels' work over the keys a query SEES: a query pair is
  two score matmuls over D lanes and two probability-value matmuls over 2 D
  lanes, 2 x 2 D + 2 x 4 D = 12 D FLOPs a (query, key), H / 2 pairs: 6 H D a
  (query, key). Keys seen on average: (T + 1) / 2 under the causal mask;
  under a window W < T, (W (W + 1) / 2 + (T - W) W) / T. That the four
  launches compute every score twice counts nothing.
- the MLP, every layer: W_1 [d, 2 f] and W_2 [f, d]: 2 x 3 d f.
- the tied head over this chip's slice: 2 d V.

Norms, softmax, the pair combine and the embedding gather are left out, as
everywhere in `flops.py`.

At the cell's sizes (d 2560; H 40, KV 20, D 64; f 10 240; d_in 5120, N 16, R
160; V 25 008; layers 0, 1, 16, 17, 18, 19; T 8192): a mixer 82 247 680, x 2 =
164 495 360 (10.8 %); the window layer 39 321 600 + 7 619 040; the full layer
39 321 600 + 62 922 240; the cross layer 26 214 400 + 62 922 240, the three
238 321 120 (15.6 %); the memory unit 52 428 800 (3.4 %); the MLPs 6 x 157 286
400 = 943 718 400 (61.8 %); the head 128 040 960 (8.4 %): forward 1 527 004
640, 4 581 013 920 FLOPs a trained token (37.5 TFLOP a step of 8192 tokens).
"""

import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def _held_layers(config):
    spec = importlib.util.spec_from_file_location(
        "phi4flash_reference_kinds", os.path.join(_HERE, "reference.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return ref.held_layers(config)


def keys_seen(seqlen: int, window: int = 0) -> float:
    """The keys a query reads on average."""
    if not window or window >= seqlen:
        return (seqlen + 1) / 2
    return (window * (window + 1) / 2 + (seqlen - window) * window) / seqlen


def mixing_flops_per_token(config: dict, seqlen: int, kind: str) -> float:
    d, H = config["hidden_size"], config["num_attention_heads"]
    d_in = config["mamba_expand"] * d
    if kind == "mamba":
        N, R = config["mamba_d_state"], config["mamba_dt_rank"]
        return 2 * (2 * d * d_in + d_in * (R + 2 * N) + R * d_in + d_in * d)
    if kind == "gmu":
        return 2 * 2 * d * d_in
    D, KV = d // H, config["num_key_value_heads"]
    if kind not in ("window", "full", "cross"):
        raise ValueError(f"unknown layer kind {kind!r}")
    width = d if kind == "cross" else (H + 2 * KV) * D
    window = config["sliding_window"] if kind == "window" else 0
    return 2 * (d * width + d * d) + 6 * H * D * keys_seen(seqlen, window)


def forward_flops_per_token(config: dict, seqlen: int) -> float:
    d = config["hidden_size"]
    kinds = [kind for _, kind in _held_layers(config)]
    return (sum(mixing_flops_per_token(config, seqlen, k) for k in kinds)
            + len(kinds) * 6 * d * config["intermediate_size"]
            + 2 * d * config["vocab_size"])


def train_flops_per_item(config: dict, cell: dict) -> float:
    return 3.0 * forward_flops_per_token(config, int(cell["seqlen"]))
