"""Phi-4-mini-flash-shaped decoder LM (`microsoft/Phi-4-mini-flash-reasoning`,
model_type `phi4flash`; Ren et al. 2025, "Decoder-Hybrid-Decoder Architecture
for Efficient Reasoning with Long Generation": SambaY): a self-decoder of
Mamba-1 mixers and windowed differential attention, and a cross-decoder whose
layers read ONE layer's keys and values and ONE mixer's scan output.

    h_0 = Emb[token]                                     (rows enter unscaled)
    h <- h + Mix_i(ln(h));   h <- h + MLP(ln(h));   logits = ln(h) Emb^T

LayerNorm with scale and shift; no position signal anywhere (the mixers
carry order); the head is the token table itself (`tie_word_embeddings`).
With L = `num_hidden_layers` and i a layer's PUBLISHED index, Mix_i is

    i % mb_per_layer == 0 (a mixer layer):
        i <= L / 2   "mamba"   layers.mamba1_mixer; layer L / 2 also hands
                               on its scan output M (in front of its gate)
        i >  L / 2   "gmu"     layers.gated_memory_unit reading M
    the others (an attention layer), layers.differential_attention:
        i <  L / 2      "window"  under `sliding_window`
        i == L / 2 + 1  "full"    no window; its k and v are handed on
        i >  L / 2 + 1  "cross"   projects q alone and reads those k and v

and lam_init follows i. MLP: [g | y] = v W_1, (y * silu(g)) W_2, no bias.
`layer_ids` builds a part of the model: the published indices of the layers
held, in order; a "gmu" or "cross" layer needs the layer it reads among them.

Built from the layer DSL like the other decoders of this package, so AMP,
Trainer and checkpointing apply unchanged.

phi4flash_lm: tokens [B, T] int32 -> logits [B, T, vocab].
"""

from __future__ import annotations

import paddle_tpu.layers as layers
from ..core.program import default_main_program
from ..initializer import NormalInitializer, XavierInitializer
from ..param_attr import ParamAttr

__all__ = ["phi4flash_lm", "phi4flash_layer_kinds"]

MAMBA, GMU, WINDOW, FULL, CROSS = "mamba", "gmu", "window", "full", "cross"


def phi4flash_layer_kinds(num_hidden_layers: int = 32, mb_per_layer: int = 2):
    """The kind of each published layer, by its index."""
    half = num_hidden_layers // 2
    if half % mb_per_layer:
        raise ValueError(f"layer {half}, which hands on its scan output, is "
                         f"no mixer layer at mb_per_layer {mb_per_layer}")

    def kind(i):
        if i % mb_per_layer == 0:
            return MAMBA if i <= half else GMU
        return WINDOW if i < half else FULL if i == half + 1 else CROSS

    return [kind(i) for i in range(num_hidden_layers)]


def phi4flash_lm(
    tokens,
    vocab_size: int,
    num_hidden_layers: int = 32,
    mb_per_layer: int = 2,
    sliding_window: int = 512,
    dim: int = 2560,
    num_heads: int = 40,
    num_kv_heads: int = 20,
    ffn_dim: int = 10240,
    state_size: int = 16,
    conv_kernel: int = 4,
    expand: int = 2,
    dt_rank=None,
    layer_ids=None,
    tie_word_embeddings: bool = True,
    norm_eps: float = 1e-5,
    table_std: float = 0.02,
    lam_std: float = 0.1,
    out_scale=None,
    name: str = "phi4",
):
    """tokens: dense [B, T] int32 Variable. Returns per-position logits [B, T,
    vocab_size]. The defaults are Phi-4-mini-flash-reasoning's published
    sizes (heads of dim / num_heads = 64; d_in = 2 dim, 16 states, 4 taps,
    dt_rank ceil(dim / 16): the published configuration class's defaults).
    `layer_ids`: None for the whole model, else the published indices held.
    The token table starts from N(0, `table_std`) (the published
    `initializer_range`: the table is the head too, so its scale is the
    logits'); the norms at one and zero; the mixers' small tensors at
    `mamba1_mixer`'s family defaults; every other matrix Glorot uniform, and
    every matrix that WRITES to the residual stream (a mixer's and a memory
    unit's W_out, an attention layer's W_o, every W_2) at `out_scale` times
    its Glorot range, by default 1 / sqrt(num_hidden_layers) whatever part
    is held (`nemotron_h_lm`'s scheme and its reason: a first step a float32
    reference can be held to). Under amp a layer's bf16 output is cast up
    before it is added: the stream is float32. Parameters, in order: the
    table; per layer the mixing norm's scale and shift, the mixing's
    (`mamba1_mixer`'s, `gated_memory_unit`'s or `differential_attention`'s),
    the MLP norm's two, W_1, W_2; the closing norm's two; an untied head
    where `tie_word_embeddings` is off."""
    kinds = phi4flash_layer_kinds(num_hidden_layers, mb_per_layer)
    half = num_hidden_layers // 2
    held = list(range(num_hidden_layers)) if layer_ids is None else [
        int(i) for i in layer_ids]
    if held != sorted(set(held)) or not held or not (
            0 <= held[0] and held[-1] < num_hidden_layers):
        raise ValueError(f"layer_ids {layer_ids}: published indices below "
                         f"{num_hidden_layers}, each once, in order")
    for i in held:
        needs = {GMU: half, CROSS: half + 1}.get(kinds[i])
        if needs is not None and needs not in held:
            raise ValueError(f"layer {i} ({kinds[i]}) reads layer {needs}, "
                             f"which layer_ids {held} leaves out")
    if out_scale is None:
        out_scale = num_hidden_layers ** -0.5

    def scaled():
        return ParamAttr(initializer=XavierInitializer(gain=out_scale))

    def add(x, h):
        return layers.elementwise_add(x, layers.cast(h, "float32"))

    def norm(x, s):
        return layers.layer_norm(x, begin_norm_axis=2, epsilon=norm_eps,
                                 name=s)

    table = ParamAttr(name=f"{name}.tok_emb",
                      initializer=NormalInitializer(0.0, table_std))
    x = layers.embedding(tokens, size=[vocab_size, dim], param_attr=table)
    memory = shared_kv = None
    for i in held:
        prefix, kind = f"{name}.h{i}", kinds[i]
        h = norm(x, f"{prefix}.mix_norm")
        if kind == MAMBA:
            h, scanned = layers.mamba1_mixer(
                h, state_size=state_size, conv_kernel=conv_kernel,
                expand=expand, dt_rank=dt_rank, emit_memory=True,
                param_attr={"out_w": scaled()}, name=f"{prefix}.mamba")
            if i == half:
                memory = scanned
        elif kind == GMU:
            h = layers.gated_memory_unit(
                h, memory, param_attr={"out_w": scaled()},
                name=f"{prefix}.gmu")
        else:
            out = layers.differential_attention(
                h, num_heads, num_kv_heads, depth=i,
                window=sliding_window if kind == WINDOW else None,
                shared_kv=shared_kv if kind == CROSS else None,
                return_kv=kind == FULL, rms_eps=norm_eps, lam_std=lam_std,
                param_attr={"wo": scaled()}, name=f"{prefix}.attn")
            if kind == FULL:
                h, shared_kv = out
            else:
                h = out
        x = add(x, h)
        h = norm(x, f"{prefix}.mlp_norm")
        h = layers.fc(h, size=2 * ffn_dim, num_flatten_dims=2,
                      param_attr=ParamAttr(name=f"{prefix}.mlp.w1"),
                      bias_attr=False, name=f"{prefix}.mlp.up")
        h = layers.fc(layers.silu_gate(h, name=f"{prefix}.mlp.gate"),
                      size=dim, num_flatten_dims=2,
                      param_attr=ParamAttr.derive(scaled(), f"{prefix}.mlp",
                                                  "w2"),
                      bias_attr=False, name=f"{prefix}.mlp.down")
        x = add(x, h)
    x = norm(x, f"{name}.final_norm")
    if tie_word_embeddings:
        emb = default_main_program().global_block().var(table.name)
        return layers.matmul(x, emb, transpose_y=True)
    return layers.fc(x, size=vocab_size, num_flatten_dims=2,
                     param_attr=ParamAttr(name=f"{name}.out_w"),
                     bias_attr=False)
