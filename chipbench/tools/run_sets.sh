#!/bin/bash
# Sets of runs of one cell, the same seeds in every set, each run a new
# process:  bash chipbench/tools/run_sets.sh <cell> <seconds> <n_seeds> [first_seed]
# Appends one JSON line a run to chiprun_out/sets_<cell>_<seconds>s.jsonl.
#   RUN_SETS="A B"          the sets to make (default two)
#   RUN_SETS_DIR=<dir>      the checkout to run from (default: here)
#   RUN_SETS_DEADLINE_S=<s> start no run later than this after the start
#   RUN_SETS_TRACE=1        one --trace 1 run at the end, if the deadline allows
cell=$1; seconds=$2; n=$3; first=${4:-2200200001}
sets=${RUN_SETS:-A B}; deadline=${RUN_SETS_DEADLINE_S:-1000000}
here=$(pwd); mkdir -p "$here/chiprun_out"
out=$here/chiprun_out/sets_${cell}_${seconds}s.jsonl
last=$here/chiprun_out/last_run
cd "${RUN_SETS_DIR:-.}" || exit 1

one_run() {  # set seed trace
  python3 chipbench/run.py --workload "$cell" --seed "$2" --seconds "$seconds" --trace "$3" \
    > "$last.out" 2> "$last.err"
  rc=$?
  line=$(tail -n 1 "$last.out")
  case "$line" in "{"*) ;; *) line=null ;; esac
  grep "chipbench" "$last.err" | grep " s  " | tr '\n' ';' | cut -c1-400
  echo " t=${SECONDS}s set=$1 seed=$2 rc=$rc $(echo "$line" | cut -c1-330)"
  if [ "$rc" != 0 ]; then tail -n 5 "$last.err" | cut -c1-300; grep "check" "$last.out" | cut -c1-200; fi
}

for set in $sets; do
  for k in $(seq 0 $((n - 1))); do
    if [ "$SECONDS" -gt "$deadline" ]; then echo "deadline: stopping before set $set seed $k"; break 2; fi
    seed=$((first + 1009 * k))
    one_run "$set" "$seed" 0
    echo "{\"set\": \"$set\", \"seed\": $seed, \"rc\": $rc, \"line\": $line}" >> "$out"
  done
done
if [ "${RUN_SETS_TRACE:-0}" = 1 ] && [ "$SECONDS" -le "$deadline" ]; then
  one_run T $((first + 1009 * n)) 1
  grep "train:" "$last.out" | cut -c1-300
  echo "$line" > "$here/chiprun_out/trace_${cell}_${seconds}s.json"
  echo "$line" | cut -c330-3000
fi
python3 "$here/chipbench/tools/spread.py" "$out"
