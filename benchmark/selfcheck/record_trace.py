"""Record the small trace that ``test_trace.py`` holds the reduction to:
``python3 -m benchmark.selfcheck.record_trace <cell> <stem> <out_dir>
[key=value ...]`` on the chip. It runs the cell through ``benchmark.run`` with
the workload's sizes overridden, and the configuration's where a key starts
with ``config.`` (small and short, so that the file stays well under a
megabyte), keeps the run's ``.xplane.pb`` as ``<stem>.xplane.pb`` and writes
what the reduction read from it as ``<stem>.expected.json``."""

from __future__ import annotations

import json
import os
import sys

from benchmark import manifest, run, trace


def main(argv) -> int:
    name, stem, out_dir = argv[:3]
    over = {k: json.loads(v) for k, v in (kv.split("=", 1) for kv in argv[3:])}
    whole = manifest.cell

    def cell(n):
        entry, workload, config = whole(n)
        for k, v in over.items():
            if k.startswith("config."):
                config[k[len("config."):]] = v
            else:
                workload[k] = v
        return entry, workload, config

    manifest.cell = cell
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, stem + ".xplane.pb")
    code = run.main(["--workload", name, "--seed", "1", "--seconds", "1",
                     "--trace", "1", "--keep-trace", path])
    if code:
        return code
    chips = whole(name)[0]["chips"]
    got = trace.reduce(path, chips)
    expected = {
        "chips": chips, "window_s": got["window_s"], "busy_s": got["busy_s"],
        "per_chip": [{k: c[k] for k in ("busy_s", "collective_s", "modules_s")}
                     for c in got["per_chip"]],
        "top_op": got["device_ops"][0][0],
        "recorded_with": {"cell": name, **over},
    }
    with open(os.path.join(out_dir, stem + ".expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
        f.write("\n")
    print(os.path.getsize(path), "bytes", path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
