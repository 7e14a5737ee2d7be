#!/bin/sh
# CPU rehearsal of every cell's control flow at the sizes under
# benchmark/rehearsal/ (tiny-test widths). No chip, no device metric: the
# result lines carry the names of the metrics computed under "rehearsal" and
# no values.
#   sh benchmark/rehearse.sh
set -e
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
cells=$(python3 -c 'import json; print(" ".join(w["name"] for w in json.load(open("BENCHMARK.json"))["workloads"]))')
for cell in $cells; do
  for trace in 0 1; do
    python3 benchmark/run.py --rehearse --workload "$cell" \
      --seed 2147483659 --seconds 4 --trace "$trace" --trace-seconds 1 \
      | tail -n 1
  done
done
