"""Helpers shared by the readers. A reader is ``read(run, params)``: ``run``
holds what one traced run gathered (``trace``: the reduction of
``benchmark/trace.py``; ``counters``, ``work``: the family's; ``peaks``,
``chips``, ``window_s``, ``compiles_in_window``, ``memory_peak_bytes``). A
reader that finds nothing to read returns ``None`` and the
metric is left out of the line."""

from __future__ import annotations

import re

from benchmark.work import common as work


def matching_module_seconds(run: dict, pattern: str) -> list[float]:
    """Per chip, the device time of the programs whose name matches."""
    rx = re.compile(pattern)
    return [sum(v for n, v in chip["modules_s"].items() if rx.search(n))
            for chip in run["trace"]["per_chip"]]


def least_seconds(flops: float, bytes_: float, peaks: dict) -> float:
    return work.least_seconds(flops, bytes_, peaks)[0]
