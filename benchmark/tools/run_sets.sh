#!/bin/bash
# Two sets of N runs of one cell, the same seeds in both sets, each run
# another seed; results under chiprun_out/sets/<cell>/set{1,2}/.  Run it
# through the chip tool:  chiprun -- bash benchmark/tools/run_sets.sh <cell> <seconds> [N] [first-seed]
# and then  python3 benchmark/tools/spread.py chiprun_out/sets/<cell>/set1 chiprun_out/sets/<cell>/set2
cell=$1; seconds=$2; n=${3:-6}; seed0=${4:-3000000000}
for set in 1 2; do
  out=chiprun_out/sets/$cell/set$set; mkdir -p "$out"
  for i in $(seq 1 "$n"); do
    python3 benchmark/run.py --workload "$cell" --seed $((seed0 + i)) --seconds "$seconds" --trace 0 \
      > "$out/run$i.txt" 2> "$out/run$i.err"
    echo "set$set run$i exit=$? $(tail -n 1 "$out/run$i.txt" | cut -c1-400)"
  done
done
python3 benchmark/tools/spread.py chiprun_out/sets/"$cell"/set1 chiprun_out/sets/"$cell"/set2
