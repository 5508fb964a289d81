#!/bin/sh
# Runs of one cell in one call, one result line each into chiprun_out/.
#   [EXTRA="--control int4"] sh benchmark/prove.sh <tag> <cell> <seconds> <trace> <seed> [<seed> ...]
# (with chiprun: chiprun --timeout 3000 -- sh benchmark/prove.sh ...)
tag=$1; cell=$2; seconds=$3; trace=$4; shift 4
mkdir -p chiprun_out
for seed in "$@"; do
  python3 -m benchmark.run --workload "$cell" --seed "$seed" --seconds "$seconds" --trace "$trace" $EXTRA \
    >> "chiprun_out/$tag.jsonl" 2> "chiprun_out/$tag.$seed.err"
  rc=$?
  echo "$tag seed $seed rc=$rc"
  grep -v '"level"' "chiprun_out/$tag.$seed.err" | grep "window opens\|requests:\|ttft\|check: \|FAILED\|memory\|warm-up\|flights" | cut -c1-260
  [ "$rc" = 0 ] || exit "$rc"
done
