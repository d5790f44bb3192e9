"""phi-4-mini-flash-reasoning: six layers of Phi-4-mini-flash-reasoning at its
published widths through `paddle_tpu.models.phi4flash_lm`, built as
`configs/phi4flash.py` builds it — copied here so that an edit there cannot
move the yardstick. The model code is the system under test; this file only
calls it with the sizes of `config.json` and the batch and length of the cell.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models


def get_model(config, cell, seed):
    vocab = config["vocab_size"]
    batch, seqlen = cell["batch"], cell["seqlen"]
    if seqlen > config["max_position_embeddings"]:
        raise ValueError(f"seqlen {seqlen} is beyond the published context")
    if len(config["layer_ids"]) != config["num_hidden_layers"]:
        raise ValueError("config.json: layer_ids disagrees with "
                         "num_hidden_layers")
    if not config["tie_word_embeddings"] or config["mlp_bias"] \
            or config["lm_head_bias"] or not config["mamba_conv_bias"] \
            or config["mamba_proj_bias"]:
        raise ValueError("config.json: the head is the table, the MLP and "
                         "the head have no bias, the mixer's conv has one "
                         "and its projections none")
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed % (2**31 - 1) + 1
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    logits = models.phi4flash_lm(
        toks, vocab_size=vocab,
        # the kind of a layer, its lam_init and the boundary follow the
        # PUBLISHED depth and index
        num_hidden_layers=config.get("published", config)["num_hidden_layers"],
        mb_per_layer=config["mb_per_layer"],
        sliding_window=config["sliding_window"], dim=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        ffn_dim=config["intermediate_size"],
        state_size=config["mamba_d_state"], conv_kernel=config["mamba_d_conv"],
        expand=config["mamba_expand"], dt_rank=config["mamba_dt_rank"],
        layer_ids=config["layer_ids"],
        tie_word_embeddings=config["tie_word_embeddings"],
        norm_eps=config["layer_norm_eps"],
        # config.json `start` (why each: `assumed.initialisers`, `departures`)
        table_std=config["start"]["table_std"],
        out_scale=config["start"]["stream_writer_gain"],
        lam_std=config["start"]["lam_std"])
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    pt.optimizer.Adam(learning_rate=3e-4).minimize(loss)
    main.set_amp("bfloat16")

    def reader():
        """Endless; the same seed gives the same batches. Learnable: every
        sequence counts upward from a random start inside a 512-token slice
        of the vocabulary (the other cells' reader: at T 8192 every id comes
        16 times; the mixers' state tells two occurrences of an id apart)."""
        rng = np.random.RandomState(seed % 2**32)
        span = min(512, vocab)
        while True:
            start = rng.randint(0, span, (batch, 1))
            seq = (start + np.arange(seqlen + 1)) % span
            yield {"toks": seq[:, :-1].astype(np.int32),
                   "labels": seq[:, 1:, None].astype(np.int32)}

    return {"cost": loss, "reader": reader, "feed_order": None,
            "items_per_step": batch * seqlen}
