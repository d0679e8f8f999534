"""Least time for the required work over the device time of the programs
whose name matches ``params["module"]`` (mean over the chips)."""

from benchmark.readers.common import least_seconds, matching_module_seconds


def read(run, params):
    work = run["work"]
    flops = work[params.get("flops", "flops_per_chip")]
    bytes_ = work[params.get("bytes", "bytes_per_chip")]
    seconds = matching_module_seconds(run, params["module"])
    device_s = sum(seconds) / len(seconds) if seconds else 0.0
    least = least_seconds(flops, bytes_, run["peaks"])
    if device_s <= 0 or least <= 0:
        return None
    return 100.0 * least / device_s
