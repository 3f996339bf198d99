#!/bin/bash
# Run benchmark cells in one checkout, one after another, on the machine with
# the chip (through the builder's tool), and print what a reviewer needs of
# each run: its wall time, the window, every check that failed, and the
# result line.  The full output of each run goes to OUT.
#
#   tools/chip_cells.sh DIR OUT SECONDS CELL:SEED:TRACE [CELL:SEED:TRACE ...]
#
# DIR is the checkout to run in (the repo itself, or a copy of the parent or
# of `git archive $(git write-tree)` unpacked in a directory .gitignore
# lists), OUT a directory for the logs (under chiprun_out/ to get them back).
# To compare two commits put both in one call, parent, change, change, parent:
#
#   chiprun -- bash -c 'tools/chip_cells.sh _proof/parent chiprun_out/p 40 \
#       gpt2m.decode:11:0; tools/chip_cells.sh _proof/change chiprun_out/c 40 \
#       gpt2m.decode:11:0'
set -u
dir=$1; out=$(mkdir -p "$2" && cd "$2" && pwd); seconds=$3; shift 3
tag=$(basename "$(cd "$dir" && pwd)")
for run in "$@"; do
  IFS=: read -r cell seed trace <<< "$run"
  log=$out/${tag}_${cell}_${seed}_t${trace}.log
  s=$SECONDS
  ( cd "$dir" && python3 benchmark/run.py --workload "$cell" --seed "$seed" \
      --seconds "$seconds" --trace "$trace" > "$log" 2> "$log.err" )
  echo "== $tag $cell seed $seed trace $trace rc=$? wall=$((SECONDS - s))s"
  grep -h "logits_fn:" "$log.err" | tail -2
  grep -E '"obs": "(window|reference|check)"' "$log" | grep -v '"ok": true' \
    | cut -c1-420
  grep -E '^\{"correct"' "$log" | cut -c1-1500
done
