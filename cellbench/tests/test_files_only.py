"""The seam is enough: a copy of ``BENCHMARK.json`` + ``cellbench/`` takes a
deployment that no file of it knows of - another program over four devices,
another kind, another reference and work model, a reader of a ``qt.`` host
span, a traffic mix - as NEW FILES ALONE (``toy/``, laid out as
``cellbench/`` is) plus one more entry each in the copy's
``BENCHMARK.json``, and rehearses it ``correct`` beside the cell that is
there, with every file the copy had still byte for byte what it was.

The toy is a table in four row blocks looked up through a ``psum``, held
to a host ``take``; it is sized in seconds on the CPU, under
``--xla_force_host_platform_device_count=4``."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT, cells

TOY = os.path.join(HERE, "toy")
TOY_CELL = "toy-rows.lookup-steady"


def hashes(top):
    out = {}
    for folder, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", ".trace")]
        for f in files:
            path = os.path.join(folder, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``(directory, hashes of what it held before the toy came)``."""
    top = str(tmp_path_factory.mktemp("files_only"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), top)
    shutil.copytree(BENCH, os.path.join(top, "cellbench"),
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    before = hashes(top)
    for folder in sorted(os.listdir(TOY)):
        src = os.path.join(TOY, folder)
        if not os.path.isdir(src):
            continue
        for f in sorted(os.listdir(src)):
            dst = os.path.join(top, "cellbench", folder, f)
            assert not os.path.exists(dst), dst
            shutil.copy(os.path.join(src, f), dst)
    with open(os.path.join(TOY, "benchmark_entries.json")) as f:
        more = json.load(f)
    with open(os.path.join(top, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for key, entries in more.items():
        bench[key] = bench[key] + entries
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return top, before


def in_copy(top, *argv, timeout=600, devices=4):
    """A Python process in the copy, on four virtual devices; the system
    under test is found where it is, the benchmark in the copy alone."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run([sys.executable, *argv], cwd=top, env=env,
                          capture_output=True, text=True, timeout=timeout)


def rehearse(top, cell):
    p = in_copy(top, os.path.join("cellbench", "run.py"), "--workload", cell,
                "--seed", "2147483777", "--seconds", "1", "--trace", "0",
                "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_toy_cell_rehearses_correct_on_four_devices(copy):
    line, err = rehearse(copy[0], TOY_CELL)
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] > 0
    assert line["compared"] == {"wrong_rows": {"value": 0.0, "limit": 0}}
    assert "the cell takes 4" in err and "'devices': 4" in err


def test_toy_cell_refuses_fewer_devices_than_its_chips(copy):
    p = in_copy(copy[0], os.path.join("cellbench", "run.py"), "--workload",
                TOY_CELL, "--seed", "1", "--seconds", "1", "--trace", "0",
                "--rehearse", devices=2)
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "needs 4 chips, JAX found 2" in p.stderr


@pytest.mark.parametrize("cell", cells())
def test_cell_that_was_there_rehearses_correct_in_the_copy(copy, cell):
    line, _ = rehearse(copy[0], cell)
    assert line["correct"] is True and line["failed"] == 0, line


DRIVE = """
import json, sys
sys.path.insert(0, "cellbench")
import run
_, cell, cfg, traffic = run.find_cell(sys.argv[1])
run.rehearsal_size(cfg, traffic)
out = run.run_cell(cell, cfg, traffic, 2147483777, 1.0, 0, fault=sys.argv[2])
print(json.dumps(run.compare(out["numbers"], run.limits_of(cfg, cell))))
"""


def test_toy_without_its_exchange_is_not_correct(copy):
    p = in_copy(copy[0], "-c", DRIVE, TOY_CELL, "no_exchange")
    assert p.returncode == 0, p.stderr[-3000:]
    compared, correct = json.loads(p.stdout.strip().splitlines()[-1])
    assert correct is False and compared["wrong_rows"]["value"] > 0


READ = """
import json, sys
sys.path.insert(0, "cellbench")
import run
bench, cell, cfg, traffic = run.find_cell(sys.argv[1])
span = {"count": 4, "seconds": 0.002, "idle_overlap_s": 0.001}
for spans in ({"qt.toy.lookup": span}, {"cb.dispatch": span}):
    ctx = {"facts": {"kind": "lookup"}, "trace": {"host_spans": spans}}
    print(json.dumps(run.read_layer_metrics(bench, cell, ctx)))
"""


def test_toy_reader_reads_the_programs_host_span(copy):
    p = in_copy(copy[0], "-c", READ, TOY_CELL)
    assert p.returncode == 0, p.stderr[-3000:]
    found, nothing = map(json.loads, p.stdout.strip().splitlines()[-2:])
    assert found == {"lookup_host_ms.toy": {"value": 0.5, "unit": "ms"}}
    assert nothing == {}        # nothing to read: left out, never 0


def test_no_file_the_copy_had_was_changed(copy):
    top, before = copy
    after = hashes(top)
    changed = [f for f in before
               if f != "BENCHMARK.json" and after.get(f) != before[f]]
    assert not changed, changed
    new = sorted(set(after) - set(before))
    assert len(new) == 7 and all(f.startswith("cellbench") for f in new), new
    # BENCHMARK.json only grew: what it had is first, and as it was
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        was = json.load(f)
    with open(os.path.join(top, "BENCHMARK.json")) as f:
        now = json.load(f)
    for key, value in was.items():
        kept = now[key][:len(value)] if isinstance(value, list) else now[key]
        assert kept == value, key
