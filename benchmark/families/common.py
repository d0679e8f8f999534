"""What a family module hands back to the harness: the contract.

A configuration's file names its ``family``; ``benchmark/families/<family>.py``
is that module. Nothing outside it knows what a family's data are: every call
below takes the cell (what ``setup`` returned) or hands one back.

``benchmark/run.py`` calls:

- ``setup(seed, config, workload, devices) -> cell``: data from the seed on
  ``devices`` (as many as the cell's ``chips``), the program's own builds;
- ``cell.unit()``: one unit of the timed path, ending in a barrier (the warm
  unit and the window's units alike); ``cell.rows_per_unit``;
- ``cell.reset_counts()``, ``cell.counters() -> dict``,
  ``cell.required_work() -> {"flops_per_chip", "bytes_per_chip", ...}``,
  ``cell.describe() -> dict``: what the readers see of the window;
- ``cell.release()``: drop the program's state, keep what the reference reads;
- ``cell.check() -> list[Comparison]``: the last unit's outputs against the
  plain reference, each number beside the limit in the workload's ``limits``.

The selfcheck (``benchmark/selfcheck/``, every test that runs over all cells)
and ``selfcheck/readings.py`` call besides:

- ``cell.outputs()``: the last unit's outputs, on the host;
- ``reference_outputs(cell)``: the reference's own outputs for the cell, in
  the same shape;
- ``compare_outputs(cell, outputs, ref=None) -> list[Comparison]``: any
  outputs against the reference's (``ref``: those of ``reference_outputs``,
  computed anew when not given);
- ``FAULTS``: the names of the faults the family can plant;
- ``stand_ins(cell, faults, ref)``: yields ``(name, outputs)`` of the
  lower-precision control (``control_...``) and of each of ``faults`` that the
  cell can have (``fault_<name>``), each the reference in the program's place.

A family's own tests (how its timed path is broken underneath, its readers)
pick their cells by the configuration's ``family``, never by a cell's name.
"""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Comparison:
    """One number compared with the plain reference, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def rel_gap(program: float, reference: float, scale: float | None = None) -> float:
    """Gap between the program's reading and the reference's, against the
    reference's (or ``scale``). A reading that is not finite is a gap of
    infinity, never a pass."""
    program, reference = float(program), float(reference)
    scale = abs(reference) if scale is None else abs(float(scale))
    if not (math.isfinite(program) and math.isfinite(reference)) or scale == 0:
        return math.inf
    return abs(program - reference) / scale
