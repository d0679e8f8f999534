"""What a family module hands back to the harness."""

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
