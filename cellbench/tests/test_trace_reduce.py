"""The reduction from a trace to busy time, per-operation time and launch
counts, on one small trace recorded on a v5e (``data/small_step.xplane.pb``:
three launches of one small program under the benchmark's own spans,
recorded by ``record_trace.py``) and on hand-made planes."""

import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_step.xplane.pb")


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40)]
    assert tr.union_seconds(iv) == 30
    assert tr.gaps(iv, 0, 50) == [(20, 30), (40, 50)]
    assert tr.gaps([], 0, 5) == [(0, 5)]
    assert tr.module_family("jit_step(123456)") == "jit_step"


def planes(n_dev=1):
    dev = [("XLA Ops", [("%a", 100.0, 50.0), ("%b", 150.0, 50.0),
                        ("%a", 300.0, 100.0)]),
           ("XLA Modules", [("jit_step(1)", 100.0, 100.0),
                            ("jit_step(1)", 300.0, 100.0),
                            ("jit_tiny(2)", 250.0, 1.0)])]
    host = [("python3", [("cb.window_start", 0.0, 1.0),
                         ("cb.dispatch", 0.0, 90.0),
                         ("cb.wait_result", 190.0, 120.0),
                         ("cb.window_end", 500.0, 1.0),
                         ("PjitFunction(step)", 10.0, 5.0)])]
    return ([(f"/device:TPU:{i}", dev) for i in range(n_dev)]
            + [("/host:CPU", host), ("#Chip0 Misc", [])])


@pytest.mark.parametrize("n_dev", [1, 4])
def test_hand_made_planes(n_dev):
    red = tr.reduce_planes(planes(n_dev))
    assert red["devices"] == n_dev
    assert red["window_s"] == pytest.approx(500e-9)
    assert red["busy_s"] == pytest.approx(200e-9)     # mean over devices
    assert red["ops"]["%a"] == pytest.approx(150e-9)
    assert red["modules"]["jit_step"]["launches"] == 2 * n_dev
    assert red["modules"]["jit_tiny"]["launches"] == n_dev
    gaps = dict()
    for what, s in red["idle_gaps"]:
        gaps[what] = gaps.get(what, 0.0) + s
    assert gaps["dispatch"] == pytest.approx(100e-9)
    assert gaps["wait_result"] == pytest.approx(100e-9)
    assert sum(gaps.values()) == pytest.approx(300e-9)
    b = tr.breakdown(red)
    assert b["device_ops"][0][0] == "%a" and len(b["idle_gaps"]) <= 10


def test_no_device_plane_gives_nothing():
    assert tr.reduce_planes([("/host:CPU", [])]) is None


def test_recorded_v5e_trace():
    red = tr.reduce_planes(tr.read_xplane(DATA))
    assert red["devices"] == 1
    fam = [f for f in red["modules"] if "small_step" in f]
    # the device's clock runs a few hundred microseconds ahead of the
    # host's in this trace (the first launch starts 0.1 ms BEFORE the
    # host's window_start mark), so the window cuts the first launch off
    assert len(fam) == 1 and red["modules"][fam[0]]["launches"] in (2, 3)
    assert 0 < red["busy_s"] < red["window_s"]
    # three short launches with a 2 ms sleep after each: mostly idle, and
    # the idle time is the benchmark's own spans'
    assert red["busy_s"] / red["window_s"] < 0.5
    names = {what for what, _ in red["idle_gaps"]}
    assert "generate" in names
    assert all(s > 0 for _, s in red["ops"].items())
    whole = tr.reduce_planes(tr.read_xplane(DATA), window=(0.0, 1e12))
    assert whole["modules"][fam[0]]["launches"] == 3
