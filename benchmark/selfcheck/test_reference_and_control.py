"""The plain reference agrees with the program at a small size, and the
lower-precision control and the planted faults fail the same comparison.
Sizes and limits are the test's own (``tiny/<cell>.json``); the cells' limits
come from chip runs at the cells' sizes (PERF.md). Every call goes through the
family's contract (``families/common.py``)."""

import jax
import pytest

from benchmark import manifest
from benchmark.selfcheck.conftest import tiny_cell

CELLS = sorted(w["name"] for w in manifest.benchmark()["workloads"])


@pytest.fixture(scope="module")
def cells():
    """One set-up and one unit per cell, shared by the tests below."""
    built = {}

    def get(name):
        if name not in built:
            entry, workload, config = tiny_cell(name)
            if len(jax.devices()) < entry["chips"]:
                pytest.skip(f"needs {entry['chips']} (virtual) devices")
            family = manifest.family(config)
            cell = family.setup(11, config, workload,
                                jax.devices()[:entry["chips"]])
            cell.unit()
            outputs = cell.outputs()
            cell.release()
            built[name] = (cell, family, outputs)
        return built[name]

    return get


@pytest.mark.parametrize("name", CELLS)
def test_program_agrees_with_the_reference(cells, name):
    cell, family, outputs = cells(name)
    checked = cell.check()
    bad = [c for c in checked if not c.ok]
    assert not bad, bad
    # the contract's comparison of given outputs is the run's own
    assert family.compare_outputs(cell, outputs) == checked


@pytest.mark.parametrize("name", CELLS)
def test_control_and_faults_fail_the_comparison(cells, name):
    cell, family, _ = cells(name)
    ref = family.reference_outputs(cell)
    stood = []
    for who, outputs in family.stand_ins(cell, family.FAULTS, ref):
        stood.append(who)
        numbers = family.compare_outputs(cell, outputs, ref)
        assert any(not c.ok for c in numbers), (who, numbers)
        if who.startswith("fault_"):
            # a fault is gross: it reads ten times a limit or more
            assert any(c.value >= 10 * c.limit for c in numbers), numbers
    assert any(w.startswith("control_") for w in stood)
    assert any(w.startswith("fault_") for w in stood)
