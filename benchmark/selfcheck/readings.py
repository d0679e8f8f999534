"""The readings that a cell's limits are set from, in one process:
``python3 -m benchmark.selfcheck.readings --workload <cell> --seeds 12
--control-seeds 4 [--raw FILE] [--set key=value ...]``.

For each seed: the data from the seed, one unit of the program through the
cell's own call, the reference's own outputs, and every number of the family's
comparison (the lower readings). For the first ``--control-seeds`` seeds also
the lower-precision control and each planted fault against the same reference
outputs (the upper readings). Every call is one of the family's contract
(``families/common.py``), so any family's cell can be read. One JSON line per reading on standard
output; ``--raw`` keeps every path's answers and histories, one JSON line
each, so that another number can be tried without the chip.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from benchmark import manifest


def _raw(outputs) -> list[dict]:
    return [{k: (v.tolist() if hasattr(v, "tolist") else v)
             for k, v in o.items()} for o in outputs]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--control-seeds", type=int, default=4)
    ap.add_argument("--raw", default=None)
    ap.add_argument("--set", action="append", default=[],
                    help="key=value over the workload file, for a CPU rehearsal")
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
    raw = open(args.raw, "w") if args.raw else None

    def say(seed, who, outputs, numbers, **more):
        print(json.dumps({"seed": seed, "who": who, **more, **{
            c.name: c.value for c in numbers}}), flush=True)
        if raw:
            raw.write(json.dumps({"seed": seed, "who": who,
                                  "outputs": _raw(outputs)}) + "\n")
            raw.flush()

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        cell = family.setup(seed, config, workload, devices)
        cell.unit()
        t1 = time.perf_counter()
        outputs = cell.outputs()
        cell.release()
        ref = family.reference_outputs(cell)
        t2 = time.perf_counter()
        say(seed, "reference", ref, [])
        say(seed, "program", outputs,
            family.compare_outputs(cell, outputs, ref),
            setup_and_unit_s=t1 - t0, reference_path_s=t2 - t1)
        if i < args.control_seeds:
            for who, stood in family.stand_ins(cell, family.FAULTS, ref):
                say(seed, who, stood,
                    family.compare_outputs(cell, stood, ref))
        del cell
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
