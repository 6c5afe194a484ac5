#!/bin/bash
# Builder's helper, run on the chip: several runs of one cell in one call,
# each run's result line and the harness's own log lines kept.
#   benchmarks/tests/sweep.sh <out-dir> <cell> <seconds> <trace> <seed>... [-- extra args]
out=chiprun_out/$1; cell=$2; seconds=$3; trace=$4; shift 4
mkdir -p "$out"
seeds=()
while [ $# -gt 0 ] && [ "$1" != "--" ]; do seeds+=("$1"); shift; done
[ "$1" == "--" ] && shift
for seed in "${seeds[@]}"; do
  tag="$out/$cell.s$seed.t$trace"
  python3 benchmarks/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace "$trace" "$@" > "$tag.out" 2> "$tag.err"
  echo "rc=$? seed=$seed $*"
  grep -E "bench \+|^check " "$tag.err" | cut -c1-400
  tail -n 1 "$tag.out" | cut -c1-2500
done
