#!/bin/bash
# How a cell's spread was measured for PERF.md: two sets of <n> runs, the same seeds in both, one call.
# usage (from the root of a checkout, on the chip): bash benchmark/sets.sh <workload> <seconds> <n>
# Each run's stderr goes to chiprun_out/err_<workload>_<set>_<i>.log; its result line to stdout.
W=$1; S=$2; N=$3
for set in A B; do
  for i in $(seq 0 $((N-1))); do
    seed=$((2147483659 + i * 1000003))
    echo "RUN set=$set seed=$seed"
    python3 benchmark/run.py --workload $W --seed $seed --seconds $S --trace 0 2>chiprun_out/err_${W}_${set}_${i}.log | tail -1
    echo "RC=$?"
  done
done
