"""What every family's work model shares."""

from __future__ import annotations


def least_seconds(flops: float, bytes_: float, peaks: dict) -> tuple[float, str]:
    """Least time one chip could take for the work, and which bound held."""
    t_f = flops / peaks["flops_per_s"]
    t_b = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "bandwidth")
