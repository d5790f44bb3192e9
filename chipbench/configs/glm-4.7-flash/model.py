"""glm-4.7-flash: one chip's share of GLM-4.7-Flash at its published widths
through `paddle_tpu.models.glm_moe_lm`, built as `configs/glm_moe.py` builds
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
    if hi - lo != config["n_routed_experts"]:
        raise ValueError("config.json: the held experts disagree with "
                         "n_routed_experts")
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed % (2**31 - 1) + 1
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    logits, _ = models.glm_moe_lm(
        toks, vocab_size=vocab, num_layers=config["num_hidden_layers"],
        first_k_dense=config["first_k_dense_replace"],
        dim=config["hidden_size"], num_heads=config["num_attention_heads"],
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        nope_dim=config["qk_nope_head_dim"],
        rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        dense_dim=config["intermediate_size"],
        num_experts=config["router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        shared_expert_dim=config["n_shared_experts"]
        * config["moe_intermediate_size"],
        gate_scale=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"], held_experts=(lo, hi),
        rope_theta=config["rope_theta"], rms_eps=config["rms_norm_eps"],
        out_scale=config["published"]["num_hidden_layers"] ** -0.5)
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    # config.json `adam_learning_rate` (a rehearsal keeps 3e-4) and
    # `assumed.optimizer`: at the other configurations' 3e-4 the
    # cost is 0 before the window opens and each seed's routing has frozen
    # on one side or the other of the op's chunk of two even shares (a
    # layer's share 0.20-0.26 of its pairs, the bound 0.25); at 3e-7 the
    # window runs on the routing the weights start with, all but even, the
    # same work on every seed
    pt.optimizer.Adam(learning_rate=config["adam_learning_rate"]).minimize(loss)
    main.set_amp("bfloat16")

    def reader():
        """Endless; the same seed gives the same batches. Learnable: every
        sequence counts upward from a random start inside a 512-token slice
        of the vocabulary (the other cells' reader: at T 8192 every id comes
        16 times; with rotary positions a repeated id is not a repeated
        hidden state)."""
        rng = np.random.RandomState(seed % 2**32)
        span = min(512, vocab)
        while True:
            start = rng.randint(0, span, (batch, 1))
            seq = (start + np.arange(seqlen + 1)) % span
            yield {"toks": seq[:, :-1].astype(np.int32),
                   "labels": seq[:, 1:, None].astype(np.int32)}

    return {"cost": loss, "reader": reader, "feed_order": None,
            "items_per_step": batch * seqlen}
