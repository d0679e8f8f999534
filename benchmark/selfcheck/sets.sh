#!/bin/bash
# How a cell's two sets were measured (PERF.md, section 2), on the chip, from
# the root of a checkout:
#   bash benchmark/selfcheck/sets.sh <cell> <seconds> <tag> [runs=6] [traced=3] [out=chiprun_out]
# Two sets of <runs> runs on the same seeds (another seed for every run of a
# set), then <traced> traced runs on further seeds; every result line is kept
# under <out>/<tag>_*.out for benchmark/selfcheck/spreads.py.
# PR 23's calls, each one `chiprun --chips 1 -- bash <script>` (PERF.md):
#   A: python3 -m benchmark.run --workload glm_dense_1024.lambda_path --seed 3100000007 --seconds 10 --trace 1
#      python3 -m benchmark.selfcheck.readings --workload glm_dense_1024.lambda_path --seeds 12 --control-seeds 4 --raw chiprun_out/A_raw.jsonl
#   B: (in a `git archive` checkout) bash benchmark/selfcheck/sets.sh glm_dense_1024.lambda_path 40 glmB 6 3 ../chiprun_out
#   C: (likewise) benchmark.run --seconds 10 on seeds 2900000011 + 15485863 * {1, 2, 3}, the third with --trace 1
W=$1; S=$2; T=$3; N=${4:-6}; NT=${5:-3}; O=${6:-chiprun_out}
mkdir -p $O
for set in A B; do
  for i in $(seq 1 $N); do
    python3 -m benchmark.run --workload $W --seed $((2600000000 + 104729 * i)) --seconds $S --trace 0 > $O/${T}_${set}$i.out 2> $O/${T}_${set}$i.err; echo "rc=$? set=$set run=$i"
    tail -1 $O/${T}_${set}$i.out
  done
done
for i in $(seq 7 $((6 + NT))); do
  python3 -m benchmark.run --workload $W --seed $((2600000000 + 104729 * i)) --seconds $S --trace 1 > $O/${T}_T$i.out 2> $O/${T}_T$i.err; echo "rc=$? traced run=$i"
  tail -3 $O/${T}_T$i.out | cut -c1-3000
done
