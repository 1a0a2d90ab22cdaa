#!/bin/sh
# Runs of one cell on the chip, every run's output kept under chiprun_out/.
#   chip_try.sh <tag> <cell> <seconds> <trace 0|1> <seed>...
tag=$1; cell=$2; seconds=$3; traced=$4; shift 4
mkdir -p chiprun_out
for seed in "$@"; do
  base="chiprun_out/$tag.$cell.$seed.t$traced"
  echo "== $cell seed $seed trace $traced"
  python3 benchmarks/run.py --workload "$cell" --seed "$seed" \
    --seconds "$seconds" --trace "$traced" > "$base.out" 2> "$base.err"
  echo "rc=$?"
  grep -v '^{"check"' "$base.out" | tail -c 2500
  grep '^{"check"' "$base.out" | cut -c1-160
  grep -v "^WARNING\|^I0000\|^W0000" "$base.err" | tail -c 1200
done
