"""The plain reference agrees with the program at a small size, and the
lower-precision control and the planted faults fail the same comparison.
Sizes and limits are the test's own (``conftest.TINY``); the cells' limits
come from chip runs at the cells' sizes (PERF.md)."""

import jax
import pytest

from benchmark import manifest

CELLS = sorted(w["name"] for w in manifest.benchmark()["workloads"])


def _numbers(comparisons):
    return {c.name: c for c in comparisons}


@pytest.fixture(scope="module")
def cells():
    """One set-up and one unit per cell, shared by the tests below."""
    from benchmark.selfcheck.conftest import TINY

    built = {}

    def get(name):
        if name not in built:
            entry, workload, config = manifest.cell(name)
            workload = {**workload, **TINY[name]}
            if len(jax.devices()) < entry["chips"]:
                pytest.skip(f"needs {entry['chips']} (virtual) devices")
            cell = manifest.family(config).setup(
                11, config, workload, jax.devices()[:entry["chips"]])
            cell.unit()
            cell.release()
            built[name] = (cell, manifest.family(config))
        return built[name]

    return get


@pytest.mark.parametrize("name", CELLS)
def test_program_agrees_with_the_reference(cells, name):
    cell, _ = cells(name)
    bad = [c for c in cell.check() if not c.ok]
    assert not bad, bad


@pytest.mark.parametrize("name", CELLS)
def test_control_and_faults_fail_the_comparison(cells, name):
    cell, family = cells(name)
    ref = family.solve_path(cell.x, cell.y, cell.config, cell.workload)
    for who, outputs in family.stand_ins(cell, family.FAULTS, ref):
        numbers = family.compare(outputs, cell.x, cell.y, cell.config,
                                 cell.workload, ref)
        assert any(not c.ok for c in numbers), (who, numbers)
        if who.startswith("fault_"):
            # a fault is gross: it reads ten times a limit or more
            assert any(c.value >= 10 * c.limit for c in numbers), numbers
