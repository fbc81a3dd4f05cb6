"""The benchmark's own tests: ``JAX_PLATFORMS=cpu python -m pytest
cellbench/tests -q -p no:cacheprovider``.  They run the harness at the toy
size each configuration file gives under ``rehearsal``; nothing here is a
measurement."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def rehearsal_cell(name):
    """``(cell, cfg, traffic)`` of one cell at its rehearsal size."""
    import run

    _, cell, cfg, traffic = run.find_cell(name)
    run.rehearsal_size(cfg, traffic)
    return cell, cfg, traffic


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.fixture(scope="session")
def cell_names():
    return cells()
