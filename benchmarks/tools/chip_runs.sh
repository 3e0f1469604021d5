#!/bin/bash
# Several runs of the benchmark in one chip call; each run's stdout and
# stderr go to chiprun_out/<tag>.<i>.{out,err}, a one-line digest to stdout.
#   benchmarks/tools/chip_runs.sh TAG WORKLOAD TRACE SECONDS SEED... [-- extra args]
# Stops at the first run that exits non-zero or is not correct (a fault
# found once is not paid for again); exit code 1 then.
tag=$1; workload=$2; trace=$3; seconds=$4; shift 4
seeds=(); while [ $# -gt 0 ] && [ "$1" != "--" ]; do seeds+=("$1"); shift; done
[ "$1" = "--" ] && shift
mkdir -p chiprun_out
i=0
for seed in "${seeds[@]}"; do
  i=$((i+1))
  t0=$(date +%s)
  python3 -m benchmarks.run --workload "$workload" --seed "$seed" --seconds "$seconds" \
    --trace "$trace" "$@" > "chiprun_out/$tag.$i.out" 2> "chiprun_out/$tag.$i.err"
  rc=$?
  echo "RUN $tag.$i seed=$seed rc=$rc wall=$(( $(date +%s) - t0 ))s"
  tail -n 1 "chiprun_out/$tag.$i.out" | cut -c1-700
  tail -n 1 "chiprun_out/$tag.$i.err" | cut -c1-600
  if [ $rc -ne 0 ] || ! tail -n 1 "chiprun_out/$tag.$i.out" | grep -q '"correct": true'; then
    echo "STOP after $tag.$i"; tail -c 3000 "chiprun_out/$tag.$i.err"; exit 1
  fi
done
