"""olmoe-1b-7b: OLMoE-1B-7B at its published widths, one layer deep, through
`paddle_tpu.models.olmoe_lm`, built as `configs/olmoe.py` builds it — copied
here so that an edit there cannot move the yardstick. The model code is the
system under test; this file only calls it with the sizes of `config.json`
and the batch and length of the cell.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models


def get_model(config, cell, seed):
    vocab = config["vocab_size"]
    batch, seqlen = cell["batch"], cell["seqlen"]
    if seqlen > config["max_position_embeddings"]:
        raise ValueError(f"seqlen {seqlen} is beyond the published context")
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed % (2**31 - 1) + 1
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    logits, aux = models.olmoe_lm(
        toks, vocab_size=vocab, dim=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_layers=config["num_hidden_layers"],
        num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_dim=config["intermediate_size"],
        rope_theta=config["rope_theta"], rms_eps=config["rms_norm_eps"],
        norm_topk_prob=config["norm_topk_prob"],
        aux_balance_weight=config["aux_balance_weight"],
        aux_z_weight=config["aux_z_weight"])
    ce = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    loss = pt.layers.elementwise_add(ce, aux)
    pt.optimizer.Adam(learning_rate=3e-4).minimize(loss)
    main.set_amp("bfloat16")

    def reader():
        """Endless; the same seed gives the same batches. Learnable: every
        sequence counts upward from a random start inside a 512-token
        slice of the vocabulary."""
        rng = np.random.RandomState(seed % 2**32)
        span = min(512, vocab)
        while True:
            start = rng.randint(0, span, (batch, 1))
            seq = (start + np.arange(seqlen + 1)) % span
            yield {"toks": seq[:, :-1].astype(np.int32),
                   "labels": seq[:, 1:, None].astype(np.int32)}

    return {"cost": loss, "reader": reader, "feed_order": None,
            "items_per_step": batch * seqlen}
