"""NMT (seq2seq-attention) per-component breakdown on the real chip.

VERDICT r2 weak #2: the NMT number needs ResNet-grade rigor. Strategy:
time the FULL train step and ablations in ONE process (relative numbers
hold where absolutes drift between sessions), attributing the
step to encoder / decoder scan / output projection / fused-GRU effect.

Variants:
  full          the bench model (bi-GRU enc + attention GRU dec + 30k out)
  scan_enc      full, FLAGS.use_fused_rnn=0 (encoder GRUs on lax.scan)
  plain_dec     attention decoder replaced by a plain dynamic_gru
                (drops: per-step attention, input-feed concat)
  no_out        full minus the [512, 30k] output projection + 30k CE
  enc_only      encoder + pooled loss only (no decoder, no projection)

Writes benchmarks/nmt_breakdown.json.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax

BATCH = int(os.environ.get("BENCH_BATCH", 128))
SEQLEN = 50
HIDDEN = 512
VOCAB = 30000
STEPS = 30


def build(variant):
    import paddle_tpu as pt
    from paddle_tpu import models
    from paddle_tpu.core.lod import LoDArray

    pt.reset()
    prog, startup = pt.Program(), pt.Program()
    startup.random_seed = 3
    with pt.program_guard(prog, startup):
        src = pt.layers.data("src", shape=[-1], dtype=np.int32, lod_level=1,
                             append_batch_size=False)
        trg_in = pt.layers.data("trg_in", shape=[-1], dtype=np.int32,
                                lod_level=1, append_batch_size=False)
        label = pt.layers.data("label", shape=[-1], dtype=np.int32,
                               lod_level=1, append_batch_size=False)
        if variant in ("full", "scan_enc", "no_out"):
            import paddle_tpu.layers as L
            from paddle_tpu.models.seq2seq import _encoder

            enc, boot_src = _encoder(src, VOCAB, HIDDEN, HIDDEN, SEQLEN, "s2s")
            boot = L.fc(boot_src, size=HIDDEN, act="tanh",
                        param_attr="s2s.boot_w", bias_attr="s2s.boot_b")
            trg_emb = L.embedding(trg_in, size=[VOCAB, HIDDEN],
                                  param_attr="s2s.trg_emb")
            dec_h = L.attention_gru_decoder(
                enc, trg_emb, boot, size=HIDDEN, src_max_len=SEQLEN,
                trg_max_len=SEQLEN, name="s2s.dec")
            if variant == "no_out":
                tok_loss = pt.layers.elementwise_mul(dec_h, dec_h)
            else:
                logits = L.fc(dec_h, size=VOCAB, param_attr="s2s.out_w",
                              bias_attr="s2s.out_b")
                tok_loss = pt.layers.softmax_with_cross_entropy(logits, label)
        elif variant == "plain_dec":
            import paddle_tpu.layers as L
            from paddle_tpu.models.seq2seq import _encoder

            enc, _ = _encoder(src, VOCAB, HIDDEN, HIDDEN, SEQLEN, "s2s")
            trg_emb = L.embedding(trg_in, size=[VOCAB, HIDDEN],
                                  param_attr="s2s.trg_emb")
            proj = L.fc(trg_emb, size=3 * HIDDEN, bias_attr=False)
            dec_h = L.dynamic_gru(proj, size=HIDDEN, max_len=SEQLEN)
            logits = L.fc(dec_h, size=VOCAB, param_attr="s2s.out_w",
                          bias_attr="s2s.out_b")
            tok_loss = pt.layers.softmax_with_cross_entropy(logits, label)
        elif variant == "enc_only":
            from paddle_tpu.models.seq2seq import _encoder

            enc, _ = _encoder(src, VOCAB, HIDDEN, HIDDEN, SEQLEN, "s2s")
            tok_loss = pt.layers.elementwise_mul(enc, enc)
        loss = pt.layers.mean(pt.layers.sequence_pool(tok_loss, "sum"))
        pt.optimizer.Adam(learning_rate=5e-4).minimize(loss)
    prog.set_amp("bfloat16")

    from paddle_tpu.flags import FLAGS

    FLAGS.use_fused_rnn = variant != "scan_enc"

    rng = np.random.RandomState(0)
    pack = lambda seqs: LoDArray.from_sequences(  # noqa: E731
        seqs, capacity=BATCH * SEQLEN, max_seqs=BATCH)
    seqs = lambda: [rng.randint(2, VOCAB, (SEQLEN,)).astype(np.int32)  # noqa: E731
                    for _ in range(BATCH)]
    feed = {"src": pack(seqs()), "trg_in": pack(seqs()),
            "label": pack(seqs())}
    feed = {k: jax.device_put(v) for k, v in feed.items()}
    exe = pt.Executor()
    exe.run(startup)
    return exe, prog, loss, feed


def timeit(variant):
    exe, prog, loss, feed = build(variant)
    for _ in range(3):
        (l,) = exe.run(prog, feed=feed, fetch_list=[loss])
    assert np.isfinite(float(np.asarray(l))), variant
    t0 = time.perf_counter()
    for _ in range(STEPS):
        (l,) = exe.run(prog, feed=feed, fetch_list=[loss],
                       return_numpy=False)
    float(np.asarray(l))  # d2h forces the chain
    dt = (time.perf_counter() - t0) / STEPS
    toks = BATCH * SEQLEN / dt
    print({variant: f"{dt*1e3:.2f} ms/step, {toks/1e3:.0f}k tok/s"},
          flush=True)
    return dt


if __name__ == "__main__":
    rows = {}
    for v in ("full", "scan_enc", "plain_dec", "no_out", "enc_only"):
        rows[v] = timeit(v)
    full = rows["full"]
    out = {
        "config": {"batch": BATCH, "seqlen": SEQLEN, "hidden": HIDDEN,
                   "vocab": VOCAB, "steps": STEPS},
        "ms_per_step": {k: round(v * 1e3, 3) for k, v in rows.items()},
        "attribution_ms": {
            "fused_gru_encoder_saving": round(
                (rows["scan_enc"] - full) * 1e3, 3),
            "attention_plus_input_feed": round(
                (full - rows["plain_dec"]) * 1e3, 3),
            "output_proj_and_30k_ce": round(
                (full - rows["no_out"]) * 1e3, 3),
            "encoder_alone": round(rows["enc_only"] * 1e3, 3),
        },
        "tokens_per_sec_full": round(BATCH * SEQLEN / full, 1),
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "nmt_breakdown.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", path)
