"""The selfcheck runs on the CPU, on four virtual devices, at tiny sizes:
``JAX_PLATFORMS=cpu python -m pytest benchmark/selfcheck -q`` from the root of
the repo. It checks forms, counts and agreement, never a time or a rate."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest

from benchmark import manifest

TINY = {
    "glm_dense_1024.lambda_path": dict(
        rows_per_chip=8000, row_chunk=2000, trace_seconds=1,
        limits={"grad0_gap": 2e-6, "step_loss_gap": 2e-5,
                "step_gnorm_gap": 2e-5, "solve1_loss_gap": 1e-4,
                "solve1_move_gap": 0.05, "later_loss_gap": 2e-3,
                "later_move_gap": 0.3, "report_loss_gap": 2e-5,
                "kkt_gap": 2e-6}),
}


@pytest.fixture
def tiny_cells(monkeypatch):
    """Every cell at a size a test run can hold; the rest as committed."""
    whole = manifest.cell

    def cell(name):
        entry, workload, config = whole(name)
        return entry, {**workload, **TINY[name]}, config

    monkeypatch.setattr(manifest, "cell", cell)
    return cell
