"""ouro-2.6b: one pipeline stage's worth of Ouro-2.6B at its published widths
through `paddle_tpu.models.looped_lm`, built as `configs/looped_lm.py` builds
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
    if len(config["layer_types"]) != config["num_hidden_layers"] or \
            set(config["layer_types"]) != {"full_attention"}:
        raise ValueError("config.json: layer_types is one `full_attention` a "
                         "layer")
    if config["num_key_value_heads"] != config["num_attention_heads"] or \
            config["use_sliding_window"] or config["tie_word_embeddings"] or \
            config["rope_scaling"] is not None or config["hidden_act"] != "silu":
        raise ValueError("config.json: the model built here is plain "
                         "multi-head, global, untied, silu, unscaled rotary")
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed % (2**31 - 1) + 1
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    loss, _, _ = models.looped_lm(
        toks, labels, vocab_size=vocab, dim=config["hidden_size"],
        num_heads=config["num_attention_heads"], head_dim=config["head_dim"],
        num_layers=config["num_hidden_layers"],
        ffn_dim=config["intermediate_size"], turns=config["total_ut_steps"],
        rope_theta=config["rope_theta"], rms_eps=config["rms_norm_eps"],
        exit_beta=config["exit_beta"])
    pt.optimizer.Adam(learning_rate=3e-4).minimize(loss)
    main.set_amp("bfloat16")

    def reader():
        """Endless; the same seed gives the same batches. Learnable: every
        sequence counts upward from a random start inside a 512-token slice
        of the vocabulary (the other cells' reader: at T 4096 every id comes
        8 times, and the rotary tells two occurrences apart)."""
        rng = np.random.RandomState(seed % 2**32)
        span = min(512, vocab)
        while True:
            start = rng.randint(0, span, (batch, 1))
            seq = (start + np.arange(seqlen + 1)) % span
            yield {"toks": seq[:, :-1].astype(np.int32),
                   "labels": seq[:, 1:, None].astype(np.int32)}

    return {"cost": loss, "reader": reader, "feed_order": None,
            "items_per_step": batch * seqlen}
