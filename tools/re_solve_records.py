#!/usr/bin/env python
"""The ``game.re.solve`` records of a traced benchmark run, one line a bucket
a step, after the run's own output::

    python3 tools/re_solve_records.py --workload game_glmix_user_song.cd_sweep \
        --seed 5 --seconds 12 --trace 1

The arguments are ``benchmark.run``'s. The program keeps a span's record
while a profiler runs, so ``--trace 1`` it has to be; what a record holds is
in OBSERVABILITY.md (``passes`` beside ``max_lane_evaluations`` since PR 29).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    from benchmark import run

    code = run.main(argv)
    from photon_ml_tpu.game.random_effect import SOLVE_SPAN
    from photon_ml_tpu.telemetry import tracing

    for r in tracing.recorded():
        if r["name"] == SOLVE_SPAN:
            print("record: " + json.dumps(
                {k: v for k, v in r.items()
                 if isinstance(v, (int, float, str, bool))}), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
