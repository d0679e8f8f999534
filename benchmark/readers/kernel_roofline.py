"""The kernel alone against its roofline: least time for every evaluation the
window's solves made (the ``evaluations`` of the spans named
``params["window_span"]``; the required passes' operations and bytes, scaled by
evaluations over passes, so a trial point that a line search rejected counts
as work the kernel did) over the self time of the operations whose name
matches ``params["op"]`` (mean over the chips)."""

import re

from benchmark.readers.common import least_seconds
from benchmark.readers.program_records import named, window_records


def read(run, params):
    records = window_records(run, params)
    work = run["work"]
    if records is None or work["passes"] <= 0:
        return None
    evaluations = sum(int(s["evaluations"])
                      for s in named(records, params["window_span"]))
    scale = evaluations / work["passes"]
    rx = re.compile(params["op"])
    seconds = [sum(v for n, v in chip["ops_self_s"].items() if rx.search(n))
               for chip in run["trace"]["per_chip"]]
    kernel_s = sum(seconds) / len(seconds) if seconds else 0.0
    least = least_seconds(work["flops_per_chip"] * scale,
                          work["bytes_per_chip"] * scale, run["peaks"])
    if kernel_s <= 0 or least <= 0:
        return None
    return 100.0 * least / kernel_s
