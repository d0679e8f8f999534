"""The spreads that the bounds were set from:
``python3 -m benchmark.selfcheck.spreads chiprun_out <tag>`` over the result
lines that ``sets.sh`` kept. A spread is the distance between the first and
the third quartile (``statistics.quantiles(n=4)``) as a share of the median."""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys


def spread(values: list[float]) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv) -> int:
    out_dir, tag = argv
    runs = {}
    for path in sorted(glob.glob(os.path.join(out_dir, f"{tag}_*.out"))):
        with open(path) as f:
            lines = f.read().strip().splitlines()
        runs[os.path.basename(path)[len(tag) + 1:-4]] = json.loads(lines[-1])
    for name, r in runs.items():
        print(name, "correct" if r["correct"] else "NOT CORRECT",
              r["attempted"], {m: v["value"] for m, v in r["metrics"].items()})
    sets = {s: [r for n, r in runs.items() if n.startswith(s)] for s in "AB"}
    for metric in sets["A"][0]["metrics"]:
        for s, rs in sets.items():
            values = [r["metrics"][metric]["value"] for r in rs]
            if len(values) >= 2:
                print(f"{metric} set {s}: median {statistics.median(values)} "
                      f"spread {spread(values):.5f} over {len(values)} runs")
    worst: dict[str, float] = {}
    for r in runs.values():
        for n, c in r["compared"].items():
            worst[n] = max(worst.get(n, 0.0), c["value"])
    print("largest compared over the runs:", worst)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
