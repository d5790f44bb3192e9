"""In-framework A/B: fused Bahdanau decoder vs XLA scan, NMT train.

Same-process interleaved (PERF.md methodology), bs 128 and 256.
Run on TPU: python experiments/exp_fusedattn.py
"""
import os
import time

import numpy as np

STEPS = int(os.environ.get("STEPS", 60))
SEQLEN = 50


def build(fused, batch):
    import paddle_tpu as pt
    from paddle_tpu import models
    from paddle_tpu.core.lod import LoDArray
    from paddle_tpu.flags import FLAGS

    FLAGS.use_fused_attention = fused
    vocab, hidden = 30000, 512
    prog, startup = pt.Program(), pt.Program()
    startup.random_seed = 7
    with pt.program_guard(prog, startup):
        src = pt.layers.data("src", shape=[-1], dtype=np.int32, lod_level=1,
                             append_batch_size=False)
        trg_in = pt.layers.data("trg_in", shape=[-1], dtype=np.int32,
                                lod_level=1, append_batch_size=False)
        label = pt.layers.data("label", shape=[-1], dtype=np.int32,
                               lod_level=1, append_batch_size=False)
        logits = models.seq2seq_attention(
            src, trg_in, src_vocab=vocab, trg_vocab=vocab,
            emb_dim=hidden, enc_hidden=hidden, dec_hidden=hidden,
            src_max_len=SEQLEN, trg_max_len=SEQLEN)
        tok_loss = pt.layers.softmax_with_cross_entropy(logits, label)
        loss = pt.layers.mean(pt.layers.sequence_pool(tok_loss, "sum"))
        pt.optimizer.Adam(learning_rate=5e-4).minimize(loss)
    prog.set_amp("bfloat16")
    rng = np.random.RandomState(0)
    pack = lambda seqs: LoDArray.from_sequences(  # noqa: E731
        seqs, capacity=batch * SEQLEN, max_seqs=batch)
    seqs = [rng.randint(2, vocab, (SEQLEN,)).astype(np.int32)
            for _ in range(batch)]
    feed = {"src": pack(seqs), "trg_in": pack(seqs), "label": pack(seqs)}
    return prog, startup, loss, feed


def main():
    import jax

    import paddle_tpu as pt

    exe = pt.Executor()
    for batch in (128, 256):
        variants = {}
        for fused in (False, True):
            prog, startup, loss, feed = build(fused, batch)
            feed = {k: jax.device_put(v) for k, v in feed.items()}
            for v in feed.values():
                for leaf in jax.tree.leaves(v):
                    np.asarray(leaf.ravel()[0])
            exe.run(startup)
            for _ in range(3):
                (l,) = exe.run(prog, feed=feed, fetch_list=[loss])
            assert np.isfinite(l), f"fused={fused} loss {l}"
            variants[fused] = (prog, loss, feed, float(l))
        print(f"bs={batch} warm losses: unfused={variants[False][3]:.3f} "
              f"fused={variants[True][3]:.3f}", flush=True)
        res = {False: [], True: []}
        for rep in range(3):
            for fused in (False, True):
                prog, loss, feed, _ = variants[fused]
                t0 = time.perf_counter()
                for _ in range(STEPS):
                    (l,) = exe.run(prog, feed=feed, fetch_list=[loss],
                                   return_numpy=False)
                float(np.asarray(l))
                dt = (time.perf_counter() - t0) / STEPS
                res[fused].append(dt)
                toks = batch * SEQLEN / dt
                print(f"bs={batch} rep{rep} fused={int(fused)}: "
                      f"{dt*1e3:6.1f} ms/step {toks/1e3:7.1f}k tok/s",
                      flush=True)
        mu = sorted(res[False])[1]
        mf = sorted(res[True])[1]
        print(f"bs={batch}: speedup {mu/mf:.3f}x "
              f"({batch*SEQLEN/mu/1e3:.1f}k -> {batch*SEQLEN/mf/1e3:.1f}k "
              f"tok/s)", flush=True)


if __name__ == "__main__":
    main()
