#!/bin/sh
# One chip call, several runs: `sh benchmark/scripts/chip_try.sh <tag> <cell> <seconds> <trace> <seed>...`
# Each run's whole output goes to chiprun_out/<tag>/<cell>.<seed>.t<trace>.{out,err}; the last lines are echoed.
tag=$1; cell=$2; seconds=$3; trace=$4; shift 4
mkdir -p chiprun_out/$tag
for seed in "$@"; do
  f=chiprun_out/$tag/$cell.$seed.t$trace
  start=$(date +%s)
  python3 benchmark/run.py --workload $cell --seed $seed --seconds $seconds --trace $trace >$f.out 2>$f.err
  echo "rc=$? wall=$(( $(date +%s) - start ))s $f"
  grep -E '"line": "(setup|journal|judge|ticks|generator|compiles)"' $f.out | cut -c1-700
  tail -n 1 $f.out | cut -c1-2600
  tail -n 2 $f.err | cut -c1-300
done
