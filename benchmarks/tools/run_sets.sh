#!/bin/bash
# Two sets of runs of one cell with the same seeds, then traced runs, in one
# call on the chip; each result line goes to chiprun_out/sets_<cell>.jsonl.
#   bash benchmarks/tools/run_sets.sh <cell> <seconds> "<seeds>" "<trace seeds>"
W=$1; S=$2; SEEDS=$3; TSEEDS=$4
mkdir -p chiprun_out
for SET in 1 2; do
  for SEED in $SEEDS; do
    python3 benchmarks/run.py --workload $W --seed $SEED --seconds $S --trace 0 > chiprun_out/_run.out 2> chiprun_out/_run.err
    echo "set $SET seed $SEED rc=$?"; grep -E "setup phases|reference|NOT OK" chiprun_out/_run.err | cut -c1-300
    echo "{\"set\": $SET, \"seed\": $SEED, \"line\": $(tail -n 1 chiprun_out/_run.out)}" >> chiprun_out/sets_$W.jsonl
  done
done
for SEED in $TSEEDS; do
  python3 benchmarks/run.py --workload $W --seed $SEED --seconds $S --trace 1 > chiprun_out/_run.out 2> chiprun_out/_run.err
  echo "trace seed $SEED rc=$?"; grep -E "reference|NOT OK" chiprun_out/_run.err | cut -c1-300
  echo "{\"seed\": $SEED, \"line\": $(tail -n 1 chiprun_out/_run.out)}" >> chiprun_out/traced_$W.jsonl
done
python3 benchmarks/tools/spread.py chiprun_out/sets_$W.jsonl
