"""chip_smoke.py off the chip: it refuses to run, and its phases —
imported, at a tiny size, on the suite's virtual CPU devices — walk the
same entry points and checks the chip run does.  This is the rehearsal
the on-chip-measurement guide asks for before a chip call; it proves
control flow and the comparisons, and says nothing about the chip.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# tiny rows and batch (the rehearsal cut); the phases themselves are the
# chip run's
TRAIN = chip_smoke.Shape(3000, 60_000, 100, 47, 32, (5, 4, 3), batch=64)
SERVE = chip_smoke.Shape(2000, 80_000, 602, 41, 16, (6, 4))
FOUR = chip_smoke.Shape(4000, 48_000, 100, 47, 32, (5, 4, 3), batch=16)


@pytest.fixture
def watch():
    return chip_smoke.CompileWatch()


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_refuses_a_cpu_backend(args):
    """Non-zero exit, nothing built, never an ``"ok": true``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout == "" and "'cpu'" in p.stderr


def test_train_phase_rehearsal(watch, capsys):
    chip_smoke.train_phase(TRAIN, seed=0, steps=3, on_chip=False,
                           watch=watch)
    out = capsys.readouterr().out
    assert "second step built nothing" in out
    assert "bit-equal to the host table" in out


def test_serve_phase_rehearsal(watch, capsys):
    chip_smoke.serve_phase(SERVE, seed=0, n_requests=12, on_chip=False,
                           watch=watch)
    assert "did not move" in capsys.readouterr().out


def test_four_device_phases_rehearsal(watch, capsys):
    """Four of the suite's eight virtual devices: the placement check of
    ``--chips 4`` (every sharded structure split four ways) and both
    comparisons."""
    devices = jax.devices()[:4]
    chip_smoke.dist_phase(FOUR, 0, devices, watch)
    chip_smoke.mesh_phase(FOUR, 0, devices, watch)
    out = capsys.readouterr().out
    assert "bit-equal to the host table" in out
    assert "bit-identical to GraphSageSampler -> Feature" in out


def test_split_check_fails_when_one_device_holds_the_lot():
    d0, d1 = jax.devices()[:2]
    chip_smoke.check_split("even", [(d0, 100), (d1, 100)], 2)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_split("one device", [(d0, 100), (d0, 100)], 2)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_split("lopsided", [(d0, 190), (d1, 10)], 2)


def test_neighbour_check_catches_a_non_neighbour():
    indptr = np.array([0, 2, 3, 3])
    indices = np.array([1, 2, 0], np.int32)
    src = np.array([0, 0, 1])
    assert chip_smoke.neighbours_valid(indptr, indices, src,
                                       np.array([1, 2, 0]))
    assert not chip_smoke.neighbours_valid(indptr, indices, src,
                                           np.array([1, 0, 0]))
    assert not chip_smoke.neighbours_valid(indptr, indices,
                                           np.array([2]), np.array([0]))


def test_pallas_watch_refuses_interpret_mode_on_chip(monkeypatch):
    w = chip_smoke.PallasWatch()
    try:
        w.calls.append(("kernel", True))
        w.check(on_chip=False)
        with pytest.raises(chip_smoke.SmokeFailure):
            w.check(on_chip=True)
    finally:
        w.close()
