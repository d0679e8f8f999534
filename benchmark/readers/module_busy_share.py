"""Share of a chip's busy time that the programs whose name matches
``params["module"]`` take (``XLA Modules`` line; mean over the chips)."""

from benchmark.readers.common import matching_module_seconds


def read(run, params):
    shares = [s / chip["busy_s"] for s, chip in zip(
        matching_module_seconds(run, params["module"]),
        run["trace"]["per_chip"]) if chip["busy_s"] > 0]
    if not shares or not any(shares):
        return None
    return 100.0 * sum(shares) / len(shares)
