#!/bin/bash
# usage: set.sh <tag> <workload> <seconds> <trace> seeds...
tag=$1; wl=$2; secs=$3; tr=$4; shift 4
mkdir -p chiprun_out
for s in "$@"; do
  f=chiprun_out/${tag}_${wl}_$s
  python3 benchmark/run.py --workload $wl --seed $s --seconds $secs --trace $tr > $f.log 2> $f.err
  echo "rc=$? seed=$s $(grep -c FAIL $f.log) fails; $(tail -1 $f.log | cut -c1-400)"
  grep -E "^warm-up" $f.log | cut -c1-330
done
