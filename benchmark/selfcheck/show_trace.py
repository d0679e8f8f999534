"""What a recorded trace holds, for a first look by hand:
``python3 -m benchmark.selfcheck.show_trace <file.xplane.pb> [chips]``."""

from __future__ import annotations

import json
import sys

import jax

from benchmark import trace


def main(argv) -> int:
    path = argv[0]
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("plane", plane.name)
        for line in plane.lines:
            events = list(line.events)
            names = sorted({e.name for e in events})
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{len(names)} names: {names[:12]}")
    reduced = trace.reduce(path, int(argv[1]) if len(argv) > 1 else 1)
    print(json.dumps(reduced, indent=1)[:6000])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
