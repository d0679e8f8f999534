"""Finding the pieces of a cell by the names in ``BENCHMARK.json``.

Whatever belongs to one configuration, one traffic mix or one per-layer
metric is a file of its own: ``configs/<config>.json``,
``workloads/<cell>.json``, ``metrics/<metric>.json`` with its reader in
``readers/<reader>.py``, the family's driver, work model and plain reference
in ``families/``, ``work/`` and ``reference/``, the generator in ``gen/``. A
new cell, configuration, metric or reader is new files and new entries;
nothing that is there is edited.

Adding a cell (all new files, then the entries in ``BENCHMARK.json``):

- ``workloads/<cell>.json``: ``name``, ``config``, ``traffic``, ``chips`` and
  ``why`` as the manifest's entry has them, the traffic's parameters as the
  family's generator reads them, ``trace_seconds``, and ``limits``: one per
  number the family compares, set from chip readings
  (``selfcheck/readings.py``; PERF.md, section 4);
- ``selfcheck/tiny/<cell>.json``: the keys of that file which a CPU test run
  replaces (sizes, ``trace_seconds``, and ``limits`` at those sizes);
- the cell's name in the ``workloads`` list of every per-layer metric whose
  reader finds something to read in it;
- for a new traffic shape of a family that is there, at most a generator
  ``gen/<generator>.py`` (the workload file names it).

Adding a family (a configuration whose ``family`` no module serves) is
besides: ``configs/<config>.json``, ``families/<family>.py`` to the contract
in ``families/common.py``'s docstring, its plain reference under
``reference/`` (nothing of the program imported), its work model under
``work/``, its generator under ``gen/``, metric and reader files for its
layers, and tests of its own beside the selfcheck's. ``pytest
benchmark/selfcheck -q`` then runs every test that is parametrised over cells
on the new one, through the contract alone; no file that is there names a cell
or a family's data.
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _read(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return _read(ROOT, "BENCHMARK.json")


def cell(name: str) -> tuple[dict, dict, dict]:
    """``(manifest entry, workload file, configuration file)`` of a cell."""
    entries = {w["name"]: w for w in benchmark()["workloads"]}
    if name not in entries:
        raise KeyError(f"BENCHMARK.json lists no cell {name!r}")
    entry = entries[name]
    workload = _read(HERE, "workloads", name + ".json")
    for key in ("config", "traffic", "chips"):
        if workload[key] != entry[key]:
            raise ValueError(f"workloads/{name}.json and BENCHMARK.json "
                             f"differ on {key!r}")
    config = _read(HERE, "configs", entry["config"] + ".json")
    return entry, workload, config


def metrics_of(name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that the cell reports:
    those that list it, or list no cells."""
    return [m for m in benchmark()[kind]
            if "workloads" not in m or name in m["workloads"]]


def metric_file(name: str) -> dict:
    return _read(HERE, "metrics", name + ".json")


def family(config: dict):
    return importlib.import_module(f"benchmark.families.{config['family']}")


def reader(name: str):
    return importlib.import_module(f"benchmark.readers.{name}")


def peaks(device_kind: str) -> dict:
    table = _read(HERE, "peaks.json")
    if device_kind not in table:
        raise KeyError(f"peaks.json has no entry for device {device_kind!r}")
    return table[device_kind]
