#!/usr/bin/env bash
# Prove one cell on the chip, in one command:
#
#   bash bench/prove.sh <cell> <seed base> [out dir] [runs per set]
#
# a cold run (it compiles; its set-up is recorded apart), two sets of N
# runs (default 6) on the same seeds (base+1 .. base+N), three traced runs
# (base+7 .. base+9), then bench/calibrate.py on N more seeds (program and
# float8 control readings for the limit of served_logit_gap).  Every
# result line goes to <out dir>/prove_<cell>.jsonl, tagged cold / A / B / T.
set -u
W=$1
B=$2
OUT_DIR=${3:-.bench_out}
N=${4:-6}
mkdir -p "$OUT_DIR"
OUT=$OUT_DIR/prove_$W.jsonl
SECONDS_RUN=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")

one() {  # tag seed trace [time limit]
  local t0 rc wall line
  t0=$(date +%s.%N)
  timeout "${4:-400}" python3 bench/run.py --workload "$W" --seed "$2" \
    --seconds "$SECONDS_RUN" --trace "$3" \
    > "$OUT_DIR/run_$2_$3.out" 2> "$OUT_DIR/run_$2_$3.err"
  rc=$?
  wall=$(python3 -c "import time; print(round(time.time() - $t0, 1))")
  line=$(tail -n 1 "$OUT_DIR/run_$2_$3.out")
  [ -z "$line" ] && line=null
  echo "{\"tag\": \"$1\", \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"wall\": $wall, \"res\": $line}" >> "$OUT"
  echo "$1 seed=$2 trace=$3 rc=$rc wall=$wall"
}

one cold "$B" 0 1200
if ! tail -n 1 "$OUT" | grep -q '"correct": '; then
  echo "the cold run printed no result; stopping"
  tail -n 40 "$OUT_DIR/run_${B}_0.err"
  exit 1
fi
for s in $(seq 1 "$N"); do one A $((B + s)) 0; done
for s in $(seq 1 "$N"); do one B $((B + s)) 0; done
for s in 7 8 9; do one T $((B + s)) 1; done
timeout 1200 python3 bench/calibrate.py --workload "$W" --seconds "$SECONDS_RUN" \
  --seeds $(seq $((B + 11)) $((B + 10 + N))) --out "$OUT_DIR/calibrate_$W.jsonl" \
  > /dev/null 2> "$OUT_DIR/calibrate_$W.err"
echo "calibrate rc=$?"
