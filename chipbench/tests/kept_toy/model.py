"""The toy's Program: `op.py`'s learned sparse attention as an op registered
here (`kept_attention`: Out, and `Chosen` int32 [B*T, k]), one or two layers
of it behind an embedding, behind a routed layer of the hybrid's kind where
`config["routed"]`, under bf16 AMP and Adam, fed the other cells' reader.
`config["fault"]` breaks the op underneath (`op.FAULTS`; "other_input": the
last layer's indexer reads the first layer's input).
"""

import importlib.util
import os

import numpy as np

import paddle_tpu as pt
from paddle_tpu.core import registry
from paddle_tpu.initializer import NormalInitializer
from paddle_tpu.layers.helper import LayerHelper
from paddle_tpu.param_attr import ParamAttr


def _beside(name):
    spec = importlib.util.spec_from_file_location(
        "kept_toy_" + name, os.path.join(os.path.dirname(__file__), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


op = _beside("op")
WEIGHTS = ("Wq", "Wk", "Wv", "Wo", "IndexWq", "IndexWk", "IndexWw")

if "kept_attention" not in registry.registered_ops():
    @registry.register_op("kept_attention")
    def _kept_attention(ctx):
        out, chosen = op.kept_attention(
            ctx.input("X"), [ctx.input(slot) for slot in WEIGHTS],
            ctx.attr("k"), ctx.attr("heads"), ctx.attr("index_heads"),
            index_input=ctx.input("IndexX"), fault=ctx.attr("fault"))
        ctx.set_output("Out", out)
        ctx.set_output("Chosen", chosen)


def kept_attention(x, config, name, index_input=None, fault=None):
    helper = LayerHelper("kept_attention", name=name)
    B, T, d = x.shape
    heads, index_heads = config["num_attention_heads"], config["index_n_heads"]
    wide = heads * config["head_dim"]
    shapes = [(d, wide), (d, wide), (d, wide), (wide, d),
              (d, index_heads * config["index_head_dim"]),
              (d, config["index_head_dim"]), (d, index_heads)]
    inputs = {"X": [x]}
    for slot, shape in zip(WEIGHTS, shapes):
        # the sets are discrete: the indexer gets no gradient, and is frozen
        inputs[slot] = [helper.create_parameter(
            ParamAttr(name=f"{name}.{slot}",
                      trainable=not slot.startswith("Index")), shape)]
    if index_input is not None:
        inputs["IndexX"] = [index_input]
    out = helper.create_tmp_variable(np.float32, x.shape)
    chosen = helper.create_tmp_variable(np.int32,
                                        (B * T, config["index_topk"]))
    helper.append_op(
        type="kept_attention", inputs=inputs,
        outputs={"Out": [out], "Chosen": [chosen]},
        attrs={"k": config["index_topk"], "heads": heads,
               "index_heads": index_heads, "fault": fault})
    return out


def get_model(config, cell, seed):
    vocab, eps = config["vocab_size"], config["rms_norm_eps"]
    batch, seqlen = cell["batch"], cell["seqlen"]
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed % (2**31 - 1) + 1
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    x = pt.layers.embedding(
        toks, size=[vocab, config["hidden_size"]],
        param_attr=ParamAttr(name="toy.tok_emb",
                             initializer=NormalInitializer(0.0, 1.0)))

    def normed(x, name):
        return pt.layers.rms_norm(x, epsilon=eps, name=name,
                                  param_attr=ParamAttr(name=name + ".w"))

    def add(x, h):
        return pt.layers.elementwise_add(x, pt.layers.cast(h, "float32"))

    if config["routed"]:
        h, _, _ = pt.layers.moe_ffn(
            normed(x, "toy.moe.ln"), config["router_experts"],
            config["num_experts_per_tok"], config["moe_intermediate_size"],
            norm_topk_prob=config["norm_topk_prob"], scoring="sigmoid",
            router_bias=True, gate_scale=config["routed_scaling_factor"],
            expert_act="relu2", held_experts=tuple(config["held_experts"]),
            shared_expert_dim=config["moe_shared_expert_intermediate_size"],
            name="toy.moe")
        x = add(x, h)
    fault, first = config.get("fault"), None
    for i in range(config["num_hidden_layers"]):
        h = normed(x, f"toy.h{i}.ln")
        first = h if first is None else first
        last = i == config["num_hidden_layers"] - 1
        x = add(x, kept_attention(
            h, config, f"toy.h{i}.attn",
            index_input=first if fault == "other_input" and last and i else None,
            fault=fault if fault in op.FAULTS and last else None))
    logits = pt.layers.fc(normed(x, "toy.ln_f"), size=vocab,
                          num_flatten_dims=2, bias_attr=False,
                          param_attr=ParamAttr(name="toy.out_w"))
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    pt.optimizer.Adam(learning_rate=3e-4).minimize(loss)
    main.set_amp("bfloat16")

    def reader():
        """Endless; the same seed gives the same batches: every sequence
        counts upward from a random start inside a 512-token slice of the
        vocabulary (the other cells' reader)."""
        rng = np.random.RandomState(seed % 2**32)
        span = min(512, vocab)
        while True:
            start = rng.randint(0, span, (batch, 1))
            seq = (start + np.arange(seqlen + 1)) % span
            yield {"toks": seq[:, :-1].astype(np.int32),
                   "labels": seq[:, 1:, None].astype(np.int32)}

    return {"cost": loss, "reader": reader, "feed_order": None,
            "items_per_step": batch * seqlen}
