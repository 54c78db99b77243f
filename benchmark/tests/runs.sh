#!/bin/bash
# Runs of one cell in one chip call, one result line per seed:
#   bash benchmark/tests/runs.sh <cell> <seconds> <trace 0|1> <tag> <seed>...
# Result lines go to chiprun_out/<tag>.jsonl, each run's standard error to
# chiprun_out/<tag>_<seed>.err. Two sets of one cell are two tags with the
# same seeds in one call.
cell=$1; secs=$2; trace=$3; tag=$4; shift 4
mkdir -p chiprun_out
for s in "$@"; do
  python3 benchmark/run.py --workload "$cell" --seed "$s" --seconds "$secs" \
    --trace "$trace" 2> "chiprun_out/${tag}_$s.err" | tail -1 \
    | tee -a "chiprun_out/${tag}.jsonl"
  echo "rc=${PIPESTATUS[0]} seed=$s $(grep -h 'window closed' "chiprun_out/${tag}_$s.err" | cut -c1-400)"
done
