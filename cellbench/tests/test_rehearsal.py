"""``run.py`` end to end at a toy size, for every cell of BENCHMARK.json:
the command line, the data files found by name, the window, the comparison
with the reference."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT, cells


def run_py(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("cellbench", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("cell", cells())
def test_rehearsal_is_correct_and_prints_no_metric(cell):
    p = run_py("--workload", cell, "--seed", "2147483659", "--seconds", "2",
               "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, line
    assert "metrics" not in line and "device" not in line
    assert line["attempted"] > 0 and line["failed"] == 0
    # every number compared stands beside its limit, on stderr as well
    for name, c in line["compared"].items():
        assert c["value"] <= c["limit"], (name, c)
        assert f"compared {name}:" in p.stderr


def test_refuses_to_measure_without_a_tpu():
    p = run_py("--workload", cells()[0], "--seed", "1", "--seconds", "1",
               "--trace", "0")
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_same_seed_same_inputs():
    import run

    _, _, cfg, traffic = run.find_cell(cells()[0])
    cfg.update(cfg["rehearsal"])
    a, b = run.make_data(cfg, 2**31 + 5), run.make_data(cfg, 2**31 + 5)
    c = run.make_data(cfg, 2**31 + 6)
    assert all((a[k] == b[k]).all() for k in ("indptr", "indices", "labels"))
    assert (a["features"] == b["features"]).all()
    assert not (a["indices"] == c["indices"]).all()
    # every seed: the same shapes, so only a checkout's first run compiles
    assert a["indices"].shape == c["indices"].shape == (cfg["edges"],)
    assert a["indptr"][-1] == c["indptr"][-1] == cfg["edges"]
