"""keye-vl-2.0-30b-a3b: one chip's share of Keye-VL-2.0-30B-A3B's language
model at its published widths through `paddle_tpu.models.keye_lm`, built as
`configs/keye_vl.py` builds it — copied here so that an edit there cannot
move the yardstick. The model code is the system under test; this file only
calls it with the sizes of `config.json` and the batch, length and image
spans of the cell, and makes the cell's data.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models


def span_positions(seqlen, starts, grid):
    """int32 [3, seqlen]: (temporal, height, width) of each token by the
    Qwen2-VL rule (`assumed.positions`). A text token advances all three axes
    by one. An image span of grid x grid tokens that begins at token `s` (one
    of `starts`, ascending, spans apart) at position P gives its token at
    grid row r, column c the triple (P, P + r, P + c); the next text token is
    at P + grid."""
    pos = np.zeros((3, seqlen), np.int32)
    at = p = 0
    r, c = np.divmod(np.arange(grid * grid), grid)
    for s in starts:
        if s < at or s + grid * grid > seqlen:
            raise ValueError(f"span at {s}: spans of {grid * grid} tokens "
                             f"apart inside {seqlen}")
        pos[:, at:s] = p + np.arange(s - at)
        p += s - at
        pos[:, s:s + grid * grid] = p + np.stack([0 * r, r, c])
        p, at = p + grid, s + grid * grid
    pos[:, at:] = p + np.arange(seqlen - at)
    return pos


def span_starts(rng, seqlen, spans, grid):
    """`spans` ascending starts from `rng`: the text tokens that are left are
    cut at uniform random places."""
    text = seqlen - spans * grid * grid
    if text < 0:
        raise ValueError(f"{spans} spans of {grid} x {grid} tokens do not "
                         f"fit {seqlen}")
    cuts = np.sort(rng.randint(0, text + 1, spans))
    return [int(c) + i * grid * grid for i, c in enumerate(cuts)]


def get_model(config, cell, seed):
    vocab = config["vocab_size"]
    batch, seqlen = cell["batch"], cell["seqlen"]
    spans, grid = cell["image_spans"], cell["image_grid"]
    if seqlen > config["max_position_embeddings"]:
        raise ValueError(f"seqlen {seqlen} is beyond the published context")
    lo, hi = config["held_experts"]
    if hi - lo != config["num_experts"] \
            or config["num_experts"] != config["num_local_experts"]:
        raise ValueError("config.json: the held experts disagree with "
                         "num_experts / num_local_experts")
    if len(config["layer_ids"]) != config["num_hidden_layers"]:
        raise ValueError("config.json: layer_ids disagrees with "
                         "num_hidden_layers")
    if config["mlp_only_layers"] or config["decoder_sparse_step"] != 1 \
            or config["attention_bias"] or config["tie_word_embeddings"] \
            or config["use_sliding_window"]:
        raise ValueError("config.json: every layer is routed, nothing has a "
                         "bias, the head is untied, no layer has a window")
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("config.json: the indexer has one key head")
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed % (2**31 - 1) + 1
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    positions = pt.layers.data("positions", shape=[3, seqlen], dtype=np.int32)
    logits, _ = models.keye_lm(
        toks, positions, vocab_size=vocab,
        num_layers=config.get("published", config)["num_hidden_layers"],
        layer_ids=tuple(config["layer_ids"]), dim=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
        num_experts=config["router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"], held_experts=(lo, hi),
        rope_theta=float(config["rope_theta"]),
        mrope_section=tuple(config["rope_scaling"]["mrope_section"]),
        rms_eps=config["rms_norm_eps"])
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    pt.optimizer.Adam(learning_rate=config["adam_learning_rate"]).minimize(
        loss)
    main.set_amp("bfloat16")

    def reader():
        """Endless; the same seed gives the same batches. Learnable: every
        sequence counts upward from a random start inside a 512-token slice
        of the vocabulary (the other cells' reader: at T 16 384 every id
        comes 32 times; the causal prefix and the positions tell two
        occurrences of an id apart), and holds `image_spans` image spans of
        `image_grid` x `image_grid` tokens whose starts come from the seed: a
        span's ids are the counting data's (no tower), its positions the
        grid's."""
        rng = np.random.RandomState(seed % 2**32)
        span = min(512, vocab)
        while True:
            start = rng.randint(0, span, (batch, 1))
            seq = (start + np.arange(seqlen + 1)) % span
            pos = np.stack([span_positions(
                seqlen, span_starts(rng, seqlen, spans, grid), grid)
                for _ in range(batch)])
            yield {"toks": seq[:, :-1].astype(np.int32),
                   "labels": seq[:, 1:, None].astype(np.int32),
                   "positions": pos}

    return {"cost": loss, "reader": reader, "feed_order": None,
            "items_per_step": batch * seqlen}
