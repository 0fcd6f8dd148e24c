#!/bin/bash
# Runs of one cell, one after another, on the machine this is started on
# (through the chip tool: chiprun -- bash benchmark/tests/chip_runs.sh ...).
#   chip_runs.sh <workload> <seconds> <trace 0|1> <seed> [<seed> ...]
# Each run's last stdout line goes to chiprun_out/<workload>.jsonl with its
# seed, trace flag, exit code and wall; its stderr tail and its log file go
# beside it.  Never stops at a failed run: the next seed still runs.
w=$1; secs=$2; trace=$3; shift 3
out=chiprun_out; mkdir -p $out
for seed in "$@"; do
  t0=$(date +%s%N)
  python3 benchmark/run.py --workload "$w" --seed "$seed" --seconds "$secs" \
      --trace "$trace" > $out/last.stdout 2> $out/last.stderr
  rc=$?
  t1=$(date +%s%N)
  wall=$(( (t1 - t0) / 1000000 ))e-3
  echo "{\"workload\": \"$w\", \"seed\": $seed, \"trace\": $trace, \"seconds\": $secs, \"rc\": $rc, \"wall_s\": $wall, \"result\": $(tail -n 1 $out/last.stdout | grep '^{' || echo null)}" >> $out/$w.jsonl
  tail -c 1500 $out/last.stderr > $out/$w.seed$seed.trace$trace.stderr
  cp store/bench/$w/seed$seed-trace$trace.log $out/ 2>/dev/null \
      && mv $out/seed$seed-trace$trace.log $out/$w.seed$seed.trace$trace.log
  echo "run $w seed $seed trace $trace rc $rc wall $wall: $(tail -n 1 $out/last.stdout | cut -c1-400)"
done
rm -f $out/last.stdout $out/last.stderr
