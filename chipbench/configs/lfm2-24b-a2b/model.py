"""lfm2-24b-a2b: one chip's share of LFM2-24B-A2B at its published widths
through `paddle_tpu.models.lfm2_moe_lm`, built as `configs/lfm2_moe.py` builds
it — copied here so that an edit there cannot move the yardstick. The model
code is the system under test; this file only calls it with the sizes of
`config.json` and the batch and length of the cell.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models


def get_model(config, cell, seed):
    vocab = config["vocab_size"]
    batch, seqlen = cell["batch"], cell["seqlen"]
    if seqlen > config["max_position_embeddings"]:
        raise ValueError(f"seqlen {seqlen} is beyond the published context")
    lo, hi = config["held_experts"]
    if hi - lo != config["num_experts"]:
        raise ValueError("config.json: the held experts disagree with "
                         "num_experts")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("config.json: layer_types disagrees with "
                         "num_hidden_layers")
    if config["conv_bias"]:
        raise ValueError("config.json: the operator's convolution has no "
                         "bias (conv_bias)")
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed % (2**31 - 1) + 1
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    logits, _ = models.lfm2_moe_lm(
        toks, vocab_size=vocab, layer_types=config["layer_types"],
        num_dense_layers=config["num_dense_layers"],
        dim=config["hidden_size"], num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        conv_kernel=config["conv_L_cache"],
        dense_dim=config["intermediate_size"],
        num_experts=config["router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        gate_scale=float(config["routed_scaling_factor"]),
        norm_topk_prob=config["norm_topk_prob"],
        use_expert_bias=config["use_expert_bias"], held_experts=(lo, hi),
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        rms_eps=config["norm_eps"],
        # a part of the published model: its stream-writing matrices' start
        out_scale=config.get("published", config)["num_hidden_layers"] ** -0.5)
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    # config.json `assumed.optimizer`: at the other configurations' 3e-4 two
    # thirds of the share's pairs land on its held experts inside 30 steps
    pt.optimizer.Adam(learning_rate=3e-6).minimize(loss)
    main.set_amp("bfloat16")

    def reader():
        """Endless; the same seed gives the same batches. Learnable: every
        sequence counts upward from a random start inside a 512-token slice
        of the vocabulary (the other cells' reader: at T 16 384 every id
        comes 32 times; the convolutions and the rotary tell two occurrences
        of an id apart)."""
        rng = np.random.RandomState(seed % 2**32)
        span = min(512, vocab)
        while True:
            start = rng.randint(0, span, (batch, 1))
            seq = (start + np.arange(seqlen + 1)) % span
            yield {"toks": seq[:, :-1].astype(np.int32),
                   "labels": seq[:, 1:, None].astype(np.int32)}

    return {"cost": loss, "reader": reader, "feed_order": None,
            "items_per_step": batch * seqlen}
