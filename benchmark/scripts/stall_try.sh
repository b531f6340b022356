#!/bin/sh
# One run with the host made to stand still: `sh benchmark/scripts/stall_try.sh <tag> <cell> <seconds> <seed> <after_s> <stall_s>`
# The run's process is stopped (SIGSTOP) <after_s> seconds after it starts, for <stall_s> seconds: what a machine that
# stands still does to it. Shows that `correct` holds and which shapes the stalled ticks dispatched.
tag=$1; cell=$2; seconds=$3; seed=$4; after=$5; stall=$6
mkdir -p chiprun_out/$tag
f=chiprun_out/$tag/$cell.$seed.stall
python3 benchmark/run.py --workload $cell --seed $seed --seconds $seconds --trace 0 >$f.out 2>$f.err &
pid=$!
sleep $after; kill -STOP $pid; sleep $stall; kill -CONT $pid
wait $pid
echo "rc=$? $f stopped at ${after}s for ${stall}s"
grep -E '"line": "(setup|ticks|generator|dispatched|compiles)"' $f.out | cut -c1-1500
tail -n 1 $f.out | cut -c1-2600
tail -n 3 $f.err | cut -c1-300
