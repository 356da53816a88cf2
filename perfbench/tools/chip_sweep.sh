# usage: bash perfbench/tools/chip_sweep.sh <workload> <seeds> <seconds> <trace_last> <first_seed> [sets]
set -x
mkdir -p chiprun_out
python3 perfbench/sweep.py --workload $1 --seeds $2 --seconds $3 --trace-last $4 --first-seed $5 --sets ${6:-1} --out chiprun_out/sweep_$1_$3s.json
echo sweep_rc=$?
ls /dev/shm | grep -c tpu3fs; pgrep -fa tpu3fs.bin | head -3
