# usage: bash perfbench/tools/chip_from_archive.sh <command> [arguments]
# Runs the command from .bench_tree/, a copy of what git would commit, made
# before the chip call with
#   git add -A && rm -rf .bench_tree && mkdir .bench_tree &&
#   git archive $(git write-tree) | tar -x -C .bench_tree
# under a HOME and a TMPDIR of its own, as the driver's check does; what the
# command writes to chiprun_out/ lands in the repo's chiprun_out/.
set -e
top=$PWD
mkdir -p chiprun_out .bench_env/home .bench_env/tmp
ln -sfn $top/chiprun_out .bench_tree/chiprun_out
export HOME=$top/.bench_env/home TMPDIR=$top/.bench_env/tmp XDG_CACHE_HOME=$top/.bench_env/home/.cache
cd .bench_tree
test ! -e .git
"$@"
