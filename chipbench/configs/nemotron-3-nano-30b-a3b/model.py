"""nemotron-3-nano-30b-a3b: one chip's share of NVIDIA-Nemotron-3-Nano-30B-A3B
at its published widths through `paddle_tpu.models.nemotron_h_lm`, built as
`configs/nemotron_h.py` builds it — copied here so that an edit there cannot
move the yardstick. The model code is the system under test; this file only
calls it with the sizes of `config.json` and the batch and length of the
cell.
"""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models


def get_model(config, cell, seed):
    vocab = config["vocab_size"]
    batch, seqlen = cell["batch"], cell["seqlen"]
    if seqlen > config["max_position_embeddings"]:
        raise ValueError(f"seqlen {seqlen} is beyond the published context")
    pattern = config["hybrid_override_pattern"]
    lo, hi = config["held_experts"]
    if len(pattern) != config["num_hidden_layers"] \
            or hi - lo != config["n_routed_experts"]:
        raise ValueError("config.json: the pattern's length or the held "
                         "experts disagree with the counts")
    main, startup = pt.default_main_program(), pt.default_startup_program()
    main.random_seed = startup.random_seed = seed % (2**31 - 1) + 1
    toks = pt.layers.data("toks", shape=[seqlen], dtype=np.int32)
    labels = pt.layers.data("labels", shape=[seqlen, 1], dtype=np.int32)
    logits = models.nemotron_h_lm(
        toks, vocab_size=vocab, pattern=pattern, dim=config["hidden_size"],
        mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"], n_groups=config["n_groups"],
        state_size=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk=config["chunk_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], num_experts=config["router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        shared_expert_dim=config["moe_shared_expert_intermediate_size"],
        gate_scale=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"], held_experts=(lo, hi),
        out_scale=config["published"]["num_hidden_layers"] ** -0.5,
        rms_eps=config["layer_norm_epsilon"])
    loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, labels))
    # config.json `adam_learning_rate` (a rehearsal keeps 3e-4) and
    # `assumed.optimizer`: at the other configurations' 3e-4 the
    # cost is 0 before the window opens and each seed's routing has frozen
    # somewhere else (a layer's share 0.016-0.100 of its pairs, some steps in
    # two chunks of their bound); at 3e-7 the window runs on the routing the
    # weights start with, all but even, the same work on every seed
    pt.optimizer.Adam(learning_rate=config["adam_learning_rate"]).minimize(loss)
    main.set_amp("bfloat16")

    def reader():
        """Endless; the same seed gives the same batches. Learnable: every
        sequence counts upward from a random start inside a 512-token
        slice of the vocabulary (ISSUE 32's traffic, the other cells'
        reader: at T 8192 every id comes 16 times)."""
        rng = np.random.RandomState(seed % 2**32)
        span = min(512, vocab)
        while True:
            start = rng.randint(0, span, (batch, 1))
            seq = (start + np.arange(seqlen + 1)) % span
            yield {"toks": seq[:, :-1].astype(np.int32),
                   "labels": seq[:, 1:, None].astype(np.int32)}

    return {"cost": loss, "reader": reader, "feed_order": None,
            "items_per_step": batch * seqlen}
