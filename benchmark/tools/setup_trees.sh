#!/bin/bash
# Warm set-up of several trees of this repository, side by side, on the chip:
#
#   chiprun -- bash benchmark/tools/setup_trees.sh <cell> <seed> <tree> <tree> ...
#
# A tree is `change` (the checkout itself) or a directory inside it that holds
# another tree's BENCHMARK.json, benchmark/ and deepspeed_tpu/ (the parent:
# `git archive <commit> | tar -x -C .parent`, a directory .gitignore lists).
# Each argument is one run of 3 s in that tree, in the order given: name every
# tree once first (the run that compiles), then alternate. Prints each run's
# set-up parts and `setup_s`. Not part of a run. Why it exists: `setup_s` of a
# serve cell moves by ~0.4 s with the size of a Python frame that is on the
# stack while the warm-up traces and lowers (PERF.md section 6, PR 25): a PR
# that edits serve_job.py or run.py compares its warm set-up with this first.
cell=$1; seed=$2; shift 2
root=$(pwd); out=$root/chiprun_out/setup_trees.$cell; mkdir -p $out
n=0
for tree in "$@"; do
  n=$((n+1)); dir=$root; [ $tree != change ] && dir=$root/$tree
  log=$out/${n}_${tree#.}.log
  ( cd $dir && python3 benchmark/run.py --workload $cell --seed $seed --seconds 3 --trace 0 ) > $log 2>&1
  echo "== ${n}_$tree rc=$? $(grep 'set-up so far' $log | sed 's/.*set-up so far//') | $(tail -n 1 $log | grep -o '"setup_s": {"value": [0-9.]*')"
done
