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
# the cells whose model carries state and has a bfloat16 path of its own
STATEFUL = ["mag240m-rgat.train-fused-typed",
            "mag240m-gat.train-fused-stateful"]


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


@pytest.mark.parametrize("cell", STATEFUL)
def test_typed_cells_bf16_control_is_not_correct(cell):
    """The program's own lower-precision path (``RGNN(dtype=bfloat16)``,
    ``GNN(dtype=bfloat16)``: the products' results in bfloat16), through
    ``run.run_cell(..., control=True)``, has to fail the cell's limits.  In
    a process of its own: ``run_cell`` points JAX's compile cache at the
    benchmark's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c",
         CONTROL.format(root=ROOT, bench=BENCH, cell=cell)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, line
    assert line["failed"] == 0      # wrong, not broken


HOST = "papers100m-sage-host.train-dist"
EXCHANGE = """
import json, sys
sys.path[:0] = [{root!r}, {bench!r}]
import run
_, cell, cfg, traffic = run.find_cell({cell!r})
run.rehearsal_size(cfg, traffic)
out = run.run_cell(cell, cfg, traffic, seed=2**31 + 77, seconds=0.5, trace=0)
limits, stated = run.limits_of(cfg, cell), cfg["precision"]["matmul"]
alone = out["numbers_fn"](out["replayed"], stated, fault="rank0_alone")
facts = out["facts"]
work = run.load_named("work", cfg["work"])
print(json.dumps({{
    "sound": run.compare(out["numbers"], limits)[1],
    "alone": run.compare(alone, limits)[1], "alone_numbers": alone,
    "facts": {{k: v for k, v in facts.items() if k.startswith("exchange")}},
    "bytes": work.exchange_bytes(facts, cfg),
    "bytes_uncounted": work.exchange_bytes({{}}, cfg)}}))
"""


def test_host_cell_counts_its_exchange_and_sees_it_left_out():
    """The four-rank cell at its toy size: the reference with the ranks'
    average left out (rank 0's loss and gradient alone) in the program's
    place fails the cell's limits where the sound run passes; the live
    slots the kind hands the exchange's readers add up by layer, and the
    work model's bytes are made of them (nothing counted, no floor)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c",
         EXCHANGE.format(root=ROOT, bench=BENCH, cell=HOST)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["sound"] is True and line["alone"] is False, line
    f = line["facts"]
    assert f["exchange_drops"] == 0
    # slots are what was shipped: buckets of an owner's share, so more
    # than one in ``ranks`` is live (the parent's whole-frontier buckets
    # held ``live <= slots // 4``)
    assert (sum(f["exchange_live_hops"]) + f["exchange_live_rows"]
            == f["exchange_live_slots"] <= f["exchange_slots"])
    assert f["exchange_live_slots"] > f["exchange_slots"] // 4
    # 3 checked steps, 4 ranks, fanout [15, 10, 5], 16-wide bfloat16 rows
    asked = sum(n * (4 + 4 * k) for n, k in zip(f["exchange_live_hops"],
                                                (15, 10, 5)))
    asked += f["exchange_live_rows"] * (4 + 32)
    assert line["bytes"] == pytest.approx(2 * 0.75 * asked / 12)
    assert line["bytes_uncounted"] is None
