"""A/B: the book stacked_lstm_net (understand_sentiment, 3 layers)
through the single stacked_lstm op vs the per-layer fc+dynamic_lstm
build — the N-layer generalization of the r4 stacked_lstm2 lever.

Same-process interleaved (PERF.md methodology). Two regimes:
- hid 128 (the book's scale): below the fused-LSTM window, so the win
  is the single all-layers scan vs 3 scans + 2 fc op chains (the
  dispatch-floor lever);
- hid 512: in-window, per-layer fused kernels + batched inter-layer
  matmuls vs per-layer scan ops.
Run on TPU: python experiments/exp_stacked_book.py
"""
import os
import time

import numpy as np

STEPS = int(os.environ.get("STEPS", 60))
T = 128


def build(variant, hid, batch):
    """variant: "per_layer" (book multi-op build), "op" (stacked_lstm
    op, layer-by-layer default), "op_scan" (stacked_lstm op, the
    flag-gated all-layers single scan)."""
    import paddle_tpu as pt
    from paddle_tpu.core.lod import LoDArray
    from paddle_tpu.flags import FLAGS

    FLAGS.stacked_lstm_single_scan = variant == "op_scan"
    vocab = 30000
    prog, startup = pt.Program(), pt.Program()
    startup.random_seed = 7
    with pt.program_guard(prog, startup):
        ids = pt.layers.data("words", shape=[-1], dtype=np.int32,
                             lod_level=1, append_batch_size=False)
        label = pt.layers.data("label", shape=[1], dtype=np.int32)
        emb = pt.layers.embedding(ids, size=[vocab, 128])
        fc1 = pt.layers.fc(emb, size=hid * 4)
        if variant in ("op", "op_scan"):
            fc_seq, h_seq = pt.layers.stacked_lstm(
                fc1, size=hid * 4, stacked_num=3, max_len=T)
        else:
            fc_seq = fc1
            h_seq = pt.layers.dynamic_lstm(fc1, size=hid * 4, max_len=T)
            for _ in range(2):
                fc_seq = pt.layers.fc([fc_seq, h_seq], size=hid * 4)
                h_seq = pt.layers.dynamic_lstm(fc_seq, size=hid * 4,
                                               max_len=T)
        fc_last = pt.layers.sequence_pool(fc_seq, "max")
        h_last = pt.layers.sequence_pool(h_seq, "max")
        logits = pt.layers.fc([fc_last, h_last], size=2)
        loss = pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, label))
        pt.optimizer.Adam(learning_rate=2e-3).minimize(loss)
    prog.set_amp("bfloat16")
    rng = np.random.RandomState(0)
    seqs = [rng.randint(2, vocab, (T,)).astype(np.int32)
            for _ in range(batch)]
    feed = {"words": LoDArray.from_sequences(seqs, capacity=batch * T,
                                             max_seqs=batch),
            "label": rng.randint(0, 2, (batch, 1)).astype(np.int32)}
    return prog, startup, loss, feed


def main():
    import jax

    import paddle_tpu as pt

    from paddle_tpu.flags import FLAGS

    exe = pt.Executor()
    arms = ("per_layer", "op", "op_scan")
    for hid, batch in ((128, 128), (512, 128)):
        variants = {}
        for variant in arms:
            prog, startup, loss, feed = build(variant, hid, batch)
            feed = {k: jax.device_put(v) for k, v in feed.items()}
            for v in feed.values():
                for leaf in jax.tree.leaves(v):
                    np.asarray(leaf.ravel()[0])
            exe.run(startup)
            for _ in range(3):  # first run traces under the arm's flag
                (l,) = exe.run(prog, feed=feed, fetch_list=[loss])
            FLAGS.stacked_lstm_single_scan = False
            assert np.isfinite(l), f"variant={variant} loss {l}"
            variants[variant] = (prog, loss, feed)
        res = {v: [] for v in arms}
        for rep in range(3):
            for variant in arms:
                prog, loss, feed = variants[variant]
                t0 = time.perf_counter()
                for _ in range(STEPS):
                    (l,) = exe.run(prog, feed=feed, fetch_list=[loss],
                                   return_numpy=False)
                float(np.asarray(l))
                dt = (time.perf_counter() - t0) / STEPS
                res[variant].append(dt)
                print(f"hid={hid} rep{rep} {variant:>9}: "
                      f"{dt*1e3:6.1f} ms/step "
                      f"{batch*T/dt/1e3:7.1f}k tok/s", flush=True)
        base = sorted(res["per_layer"])[1]
        for variant in arms[1:]:
            m = sorted(res[variant])[1]
            print(f"hid={hid}: {variant} speedup {base/m:.3f}x "
                  f"({batch*T/base/1e3:.1f}k -> {batch*T/m/1e3:.1f}k "
                  f"tok/s)", flush=True)


if __name__ == "__main__":
    main()
