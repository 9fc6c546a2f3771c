#!/usr/bin/env bash
# Measure one cell the way its bounds are set, in one call on the chip:
# one warm-up run (it compiles in a fresh checkout), two sets of 6 runs
# on the same 6 seeds, 3 traced runs and 2 more runs, all on seeds
# derived from BASE, each run its own process (bench/tools/sweep.py).
#
#   bash bench/tools/measure_cell.sh <cell> <base seed> <seconds> <out.jsonl>
set -u
CELL=$1
BASE=$2
SECONDS_=$3
OUT=$4
HERE=$(dirname "$0")
s() { echo $((BASE + $1)); }
SET="$(s 1),$(s 2),$(s 3),$(s 4),$(s 5),$(s 6)"
python3 "$HERE/sweep.py" --workload "$CELL" --seconds "$SECONDS_" --out "$OUT" --seeds "$(s 0)"
python3 "$HERE/sweep.py" --workload "$CELL" --seconds "$SECONDS_" --out "$OUT" --seeds "$SET"
python3 "$HERE/sweep.py" --workload "$CELL" --seconds "$SECONDS_" --out "$OUT" --seeds "$SET"
python3 "$HERE/sweep.py" --workload "$CELL" --seconds "$SECONDS_" --out "$OUT" --trace 1 --seeds "$(s 7),$(s 8),$(s 9)"
python3 "$HERE/sweep.py" --workload "$CELL" --seconds "$SECONDS_" --out "$OUT" --seeds "$(s 10),$(s 11)"
