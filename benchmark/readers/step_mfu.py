"""The whole step's share of the chip's peak: the least time a chip could
take for its part of the window's required work (the larger of operations over
peak FLOP/s and bytes over peak bytes/s), over the window's wall."""

from benchmark.readers.common import least_seconds


def read(run, params):
    work = run["work"]
    least = least_seconds(work["flops_per_chip"], work["bytes_per_chip"],
                          run["peaks"])
    if least <= 0 or run["window_s"] <= 0:
        return None
    return 100.0 * least / run["window_s"]
