#!/bin/bash
# A first look at cells on the chip: short runs, cold then warm, and a
# traced run of each. Usage (from the repo root, on the chip):
#   bash benchmarks/tools/first_look.sh <seconds> <cell> [<cell> ...]
secs=$1; shift
for cell in "$@"; do
  python benchmarks/tools/sets.py --workload "$cell" --seeds 2147483659 2147483693 5 --seconds "$secs" --tag "look.$cell"
  python benchmarks/tools/sets.py --workload "$cell" --seeds 2147483659 --seconds "$secs" --trace 1 --tag "look.$cell.trace"
  grep -h '^{"\(train\|serve\|run\|trace\)"' "chiprun_out/bench/look.$cell.log" "chiprun_out/bench/look.$cell.trace.log" | cut -c1-1800
  tail -n 1 "chiprun_out/bench/look.$cell.trace.jsonl" | cut -c1-3000
done
