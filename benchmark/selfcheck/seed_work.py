"""Does the seed change the work? In one process, for each of ``--seeds``
seeds: the data from the seed, a warm unit, a timed unit, and what the solves
counted: ``python3 -m benchmark.selfcheck.seed_work --workload <cell> --seeds 6
[--set key=value ...]`` (``--set problem_seed=null`` lets the seed draw the
whole problem, as before PR 27's second round). One JSON
line a seed: the unit's wall and every solve's evaluations and iterations.
The GLM family's cells only: it reads ``cell.last``."""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from benchmark import manifest


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=2_700_000_001)
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args(argv)

    entry, workload, config = manifest.cell(args.workload)
    for kv in args.set:
        k, v = kv.split("=", 1)
        workload[k] = json.loads(v)
    from photon_ml_tpu import compile_cache

    compile_cache.configure()
    import jax

    family = manifest.family(config)
    devices = jax.devices()[:int(entry["chips"])]
    for i in range(args.seeds):
        seed = args.first_seed + 15_485_863 * i
        cell = family.setup(seed, config, workload, devices)
        cell.unit()
        t = time.perf_counter()
        cell.unit()
        wall = time.perf_counter() - t
        print(json.dumps({
            "seed": seed, "unit_wall_s": wall,
            "evaluations": [int(r.evaluations) for r in cell.last],
            "iterations": [int(r.iterations) for r in cell.last],
            "values": [float(r.value) for r in cell.last]}), flush=True)
        del cell
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
