#!/bin/sh
# Runs of one cell from one checkout on the chip, as chip_try.sh makes them,
# with each run's wall seconds and, after a traced run, what the scope reader
# finds in its trace (tools/scopes_report.py), all kept under the calling
# directory's chiprun_out/.
#   chip_scopes.sh <tag> <checkout> <cell> <seconds> <trace 0|1> <seed>...
tag=$1; checkout=$2; cell=$3; seconds=$4; traced=$5; shift 5
out=$(pwd)/chiprun_out
mkdir -p "$out"
for seed in "$@"; do
  base="$out/$tag.$cell.$seed.t$traced"
  echo "== $tag $cell seed $seed trace $traced"
  start=$(date +%s%N)
  (cd "$checkout" && python3 benchmarks/run.py --workload "$cell" \
    --seed "$seed" --seconds "$seconds" --trace "$traced") \
    > "$base.out" 2> "$base.err"
  rc=$?
  end=$(date +%s%N)
  echo "rc=$rc wall_ms=$(( (end - start) / 1000000 ))" | tee "$base.wall"
  grep -v '^{"check"' "$base.out" | tail -c 1500
  grep '^{"check"' "$base.out" | cut -c1-160
  grep -v "^WARNING\|^I0000\|^W0000" "$base.err" | tail -c 600
  if [ "$traced" = 1 ]; then
    (cd "$checkout" && python3 benchmarks/tools/scopes_report.py "$cell") \
      > "$base.scopes" 2>> "$base.err"
    cut -c1-1200 "$base.scopes"
  fi
done
