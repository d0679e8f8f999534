"""A kernel against its roofline where the family states the kernel's own
work: least time for ``work[params["flops"]]`` operations and
``work[params["bytes"]]`` bytes over the self time of the operations whose
name matches ``params["op"]`` (mean over the chips). The family counts there
what the kernel was asked for and no more (for the entity kernel: every
evaluation of every lane over that lane's real rows), so the kernel's time
cannot be less and the share cannot pass 100."""

import re

from benchmark.readers.common import least_seconds


def read(run, params):
    work = run["work"]
    flops, bytes_ = work.get(params["flops"]), work.get(params["bytes"])
    if not flops or not bytes_:
        return None
    rx = re.compile(params["op"])
    seconds = [sum(v for n, v in chip["ops_self_s"].items() if rx.search(n))
               for chip in run["trace"]["per_chip"]]
    kernel_s = sum(seconds) / len(seconds) if seconds else 0.0
    if kernel_s <= 0:
        return None
    return 100.0 * least_seconds(flops, bytes_, run["peaks"]) / kernel_s
