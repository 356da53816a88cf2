# usage: bash perfbench/tools/chip_full_sets.sh <first_seed> <workload> ...
# The two full sets of 6 runs, same seeds in both, at run_seconds, one cell
# after another in one call.
for w in "${@:2}"; do
  bash perfbench/tools/chip_sweep.sh $w 6 48 0 $1 2
done
