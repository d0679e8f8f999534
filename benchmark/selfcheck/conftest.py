"""The selfcheck runs on the CPU, on four virtual devices, at tiny sizes:
``JAX_PLATFORMS=cpu python -m pytest benchmark/selfcheck -q`` from the root of
the repo. It checks forms, counts and agreement, never a time or a rate.

A cell's tiny sizes are data: ``tiny/<cell>.json`` holds the keys of the
cell's workload file that a test run replaces (sizes, ``trace_seconds``, and
``limits``, which are the test's own: the cell's limits come from chip runs at
the cell's sizes, PERF.md). Nothing here names a cell."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest

from benchmark import manifest


def tiny_file(name: str) -> str:
    return os.path.join(HERE, "tiny", name + ".json")


def tiny_cell(name: str, whole=manifest.cell) -> tuple[dict, dict, dict]:
    """``manifest.cell(name)`` (``whole``: as committed) with the workload at
    its tiny sizes. A cell without its file fails with the file's name."""
    entry, workload, config = whole(name)
    path = tiny_file(name)
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"cell {name!r} has no selfcheck sizes: add "
            f"{os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return entry, {**workload, **json.load(f)}, config


@pytest.fixture
def tiny_cells(monkeypatch):
    """Every cell at a size a test run can hold; the rest as committed."""
    monkeypatch.setattr(manifest, "cell", tiny_cell)
    return tiny_cell
