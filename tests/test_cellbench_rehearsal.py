"""Every cell of ``BENCHMARK.json`` end to end at its toy size, on the CPU:
``cellbench/run.py --rehearse`` finds the cell's configuration, traffic,
kind, program, reference and work model by name, drives the window and
compares what the timed path produced with the plain reference.  Nothing
here is a measurement.  ``cellbench/tests`` holds the harness's own tests
(the reducer, the faults, the files-only toy); this file is the part of
them that tier-1 runs, so that the driver's tests guard every cell's files.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "cellbench")
TYPED = "mag240m-rgat.train-fused-typed"


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", cells())
def test_cell_rehearses_correct(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join("cellbench", "run.py"), "--workload",
         cell, "--seed", "2147483659", "--seconds", "1", "--trace", "0",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "metrics" not in line and "device" not in line
    for name, c in line["compared"].items():
        assert c["value"] <= c["limit"], (name, c)


CONTROL = """
import json, sys
sys.path[:0] = [{root!r}, {bench!r}]
import run
_, cell, cfg, traffic = run.find_cell({cell!r})
run.rehearsal_size(cfg, traffic)
out = run.run_cell(cell, cfg, traffic, seed=2**31 + 77, seconds=0.5, trace=0,
                   control=True)
compared, correct = run.compare(out["numbers"], run.limits_of(cfg, cell))
print(json.dumps({{"correct": correct, "compared": compared,
                  "failed": out["facts"]["failed"]}}))
"""


def test_typed_cells_bf16_control_is_not_correct():
    """The program's own lower-precision path (``RGNN(dtype=bfloat16)``:
    the products' results in bfloat16), through ``run.run_cell(...,
    control=True)``, has to fail the cell's limits.  In a process of its
    own: ``run_cell`` points JAX's compile cache at the benchmark's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c",
         CONTROL.format(root=ROOT, bench=BENCH, cell=TYPED)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, line
    assert line["failed"] == 0      # wrong, not broken
