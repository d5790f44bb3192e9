#!/bin/bash
# Transformer MFU sweep (round 3, VERDICT item 1).
# Sequential — one process per chip at a time.
cd /root/repo
OUT=experiments/tfm_sweep.log
: > $OUT
run() {
  echo "=== $* ===" >> $OUT
  timeout 900 env "$@" BENCH_MODEL=transformer python bench.py 2>>$OUT | tail -1 >> $OUT
  echo >> $OUT
}
# r02 baseline repro
run BENCH_HIDDEN=2048 BENCH_DEPTH=12 BENCH_BATCH=4
# bigger batch via remat at same width
run BENCH_HIDDEN=2048 BENCH_DEPTH=12 BENCH_BATCH=8 BENCH_REMAT=dots
run BENCH_HIDDEN=2048 BENCH_DEPTH=12 BENCH_BATCH=16 BENCH_REMAT=full
# wider, fewer layers: best MXU shapes
run BENCH_HIDDEN=4096 BENCH_DEPTH=4 BENCH_BATCH=8 BENCH_REMAT=full
run BENCH_HIDDEN=3072 BENCH_DEPTH=6 BENCH_BATCH=8 BENCH_REMAT=full
echo DONE >> $OUT
