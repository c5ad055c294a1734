#!/bin/bash
# How a cell's spread was measured for PERF.md: two sets of <n> runs, the same seeds in both, one call.
# usage (from the root of a checkout, on the chip): bash benchmark/sets.sh <workload> <seconds> <n> [<first seed>]
# Each run's stderr goes to chiprun_out/err_<workload>_<set>_<i>.log; its result line to stdout and to
# chiprun_out/sets_<workload>.txt, from which the last lines give each set's median, quartiles and spread
# (benchmark/stats.py: between the quartiles over the median, as a check reads it, with and without the farthest run).
W=$1; S=$2; N=$3; FIRST=${4:-2147483659}
mkdir -p chiprun_out
OUT=chiprun_out/sets_${W}.txt
: > $OUT
for set in A B; do
  for i in $(seq 0 $((N-1))); do
    seed=$((FIRST + i * 1000003))
    echo "RUN set=$set seed=$seed" | tee -a $OUT
    python3 benchmark/run.py --workload $W --seed $seed --seconds $S --trace 0 2>chiprun_out/err_${W}_${set}_${i}.log | tail -1 | tee -a $OUT
    echo "RC=${PIPESTATUS[0]}"
  done
done
python3 benchmark/stats.py $OUT
