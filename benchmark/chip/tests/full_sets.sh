#!/bin/bash
# The two full sets of a cell, as the benchmark's contract asks for them
# when a bound is set: 2 x 6 runs at run_seconds, the same seeds in both
# sets, every run a new process; then three traced runs on further seeds.
#   chiprun -- bash benchmark/chip/tests/full_sets.sh <cell> <seconds>
cell=$1; seconds=$2; out=chiprun_out/sets_$cell.jsonl
mkdir -p chiprun_out
for set in 1 2; do
  for seed in 2147483659 19 4100000023 29 3000000031 37; do
    python3 benchmark/chip/run.py --workload $cell --seed $seed --seconds $seconds --trace 0 2> chiprun_out/last.err | tail -n 1 | sed "s/^{/{\"set\": $set, \"seed\": $seed, /" >> $out
    grep "^compared.*FAILS\|Error" chiprun_out/last.err | head -n 5
  done
done
for seed in 41 4200000043 47; do
  python3 benchmark/chip/run.py --workload $cell --seed $seed --seconds $seconds --trace 1 2> chiprun_out/last.err | tail -n 1 | sed "s/^{/{\"set\": 0, \"seed\": $seed, /" >> $out
  grep "^compared.*FAILS\|Error" chiprun_out/last.err | head -n 5
done
cut -c1-420 $out
