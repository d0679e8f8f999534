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
# PR 27's calls for glm_dense_1024.lambda_path_dp4, each one
# `chiprun --chips 4 -- bash <script>` (PERF.md, sections 4 and 6):
#   A: python3 -m benchmark.run --workload glm_dense_1024.lambda_path_dp4 --seed 3100000007 --seconds 10 --trace 1
#      python3 -m benchmark.selfcheck.record_trace glm_dense_1024.lambda_path_dp4 glm_four_chip chiprun_out/trace ... (data/README.txt)
#      (in a `git archive` of the parent, given the new cell's entries) python3 -m benchmark.run --workload glm_dense_1024.lambda_path_dp4 --seed 5 --seconds 5 --trace 0
#      python3 -m benchmark.selfcheck.readings --workload glm_dense_1024.lambda_path_dp4 --seeds 12 --control-seeds 4 --raw chiprun_out/A_raw.jsonl
#   C: (in a `git archive` checkout) bash benchmark/selfcheck/sets.sh glm_dense_1024.lambda_path_dp4 40 dp4C 6 3 ../../chiprun_out
#   D2: (likewise) python3 -m benchmark.run --workload glm_dense_1024.lambda_path_dp4 --seconds 10 on seeds 2147483659 (--trace 1) and 4294967311 (--trace 0)
# and on one chip (`chiprun --chips 1`), parent and change in `git archive`
# checkouts, parent, change, change, parent on the same seeds:
#   B: python3 -m benchmark.run --workload glm_dense_1024.lambda_path --seed <2600000000 + 104729 * i> --seconds 40 --trace <0|1>
# PR 27, round 2 (the check refused fit_p95_s as too noisy: the seed drew the
# problem, and with it the solve's count of rejected trial points):
#   E (1 chip): on the refused tree (`git archive`) python3 -m benchmark.run --workload glm_dense_1024.lambda_path --seconds 40 --trace 0 on seed 3900000017 twice, 1234567891 and 4100000039 once;
#      python3 -m benchmark.selfcheck.seed_work --workload glm_dense_1024.lambda_path --seeds 8 --set problem_seed=null; the same --seeds 6 as committed; --seeds 8 with each row's sign and the order of the blocks drawn besides (that draw is deleted again);
#      benchmark.run --seconds 51 on the three seeds
#   F (1 chip, in a `git archive` checkout): python3 -m benchmark.selfcheck.readings --workload glm_dense_1024.lambda_path --seeds 3 --control-seeds 3 --first-seed 3300000023; bash benchmark/selfcheck/sets.sh glm_dense_1024.lambda_path 51 glmF 6 3 ../../chiprun_out
#   G1 (4 chips, likewise): readings --workload glm_dense_1024.lambda_path_dp4 --seeds 2 --control-seeds 1 --first-seed 3300000023
#   G2 (4 chips, likewise): bash benchmark/selfcheck/sets.sh glm_dense_1024.lambda_path_dp4 51 dp4G 3 1 ../../chiprun_out
#   H (1 chip, likewise, after the one-chip grad0_gap limit was set from call F): python3 -m benchmark.run --workload glm_dense_1024.lambda_path --seed 2147483659 --seconds 10 --trace 0, then --seed 4294967311 --trace 1
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
