#!/bin/bash
# Two sets of N runs of one cell, the same seeds in both sets, then one
# traced run: what a bound is set from (see spread.py). On the chip:
#   bash benchmark/tools/sets.sh <cell> <seconds> [runs-per-set] [first-seed]
# Result lines go to chiprun_out/logs/sets_<cell>.jsonl, one per run,
# with "set", "seed" and "trace" added; each run's notes (counts beside
# the metrics) go to chiprun_out/logs/notes_<cell>.jsonl the same way.
cell=$1; seconds=$2; n=${3:-6}; first=${4:-2500000001}
mkdir -p chiprun_out/logs
out=chiprun_out/logs/sets_$cell.jsonl
notes=chiprun_out/logs/notes_$cell.jsonl
: > $out; : > $notes
one() { # set seed trace
  python3 benchmark/run.py --workload $cell --seed $2 --seconds $seconds --trace $3 \
    > chiprun_out/logs/last_$cell.out 2> chiprun_out/logs/last_$cell.err
  rc=$?
  line=$(tail -n 1 chiprun_out/logs/last_$cell.out)
  case "$line" in
    '{"correct"'*) echo "{\"set\": $1, \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"result\": $line}" >> $out ;;
    *) echo "{\"set\": $1, \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"result\": null}" >> $out
       cp chiprun_out/logs/last_$cell.out chiprun_out/logs/failed_${cell}_$1_$2.out
       cp chiprun_out/logs/last_$cell.err chiprun_out/logs/failed_${cell}_$1_$2.err ;;
  esac
  grep '^{"msg": "note"' chiprun_out/logs/last_$cell.out \
    | sed "s/^{/{\"set\": $1, \"seed\": $2, \"trace\": $3, /" >> $notes
  grep '^check' chiprun_out/logs/last_$cell.out | grep -v ' ok$'
}
for s in 1 2; do
  for i in $(seq 0 $((n - 1))); do
    one $s $((first + 104729 * i)) 0
    # a cell whose first run gives no result will give none: stop here
    if [ $s = 1 ] && [ $i = 0 ] && grep -q '"result": null' $out; then
      cat chiprun_out/logs/last_$cell.err | tail -n 30; exit 1
    fi
  done
done
one 0 $((first - 1)) 1
cp chiprun_out/logs/last_$cell.out chiprun_out/logs/traced_$cell.out
python3 benchmark/tools/spread.py $out
