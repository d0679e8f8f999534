"""Where the persistent XLA compile cache lives — one rule for every entry
point (``python -m photon_ml_tpu``, ``bench.py``, ``chip_smoke.py``,
``tools/layout_crossover.py``).

The cache directory is part of every entry's key, so a directory that moves
between runs never hits. Two placements, both stable:

- ``JAX_COMPILATION_CACHE_DIR`` set: whoever launched the process placed the
  cache. JAX reads the variable itself; this module sets nothing.
- otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from typing import Optional

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def configure() -> Optional[str]:
    """Apply the rule above. Returns the directory this call set, or None
    when the environment variable owns the placement. Touches only
    ``jax.config`` — no backend is initialized."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
