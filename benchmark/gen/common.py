"""Seed handling shared by the generators."""

from __future__ import annotations

import jax


def key_from_seed(seed: int):
    """A PRNG key for any whole-number seed: the driver's seeds pass 2**31,
    more than a 32-bit signed key seed holds, so the high bits are folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)
