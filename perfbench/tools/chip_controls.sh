# usage: bash perfbench/tools/chip_controls.sh <seconds> <workload>=<fault> ...
# Each fault on three seeds at the cell's own size; every run has to come
# out `correct: false` (sweep.py exits 0 only then).
set -x
mkdir -p chiprun_out
secs=$1; shift
for pair in "$@"; do
  w=${pair%%=*}; f=${pair##*=}
  python3 perfbench/sweep.py --workload $w --seeds 3 --seconds $secs --first-seed 2147200000 --fault $f --out chiprun_out/control_${w}_${f}.json
  echo control_rc=$?
done
