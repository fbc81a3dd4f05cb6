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


HOST = [("python3", [("cb.window_start", 0.0, 1.0),
                     ("cb.dispatch", 0.0, 90.0),
                     ("cb.wait_result", 190.0, 120.0),
                     ("cb.window_end", 500.0, 1.0),
                     ("PjitFunction(step)", 10.0, 5.0)])]


def planes(n_dev=1, host=HOST):
    dev = [("XLA Ops", [("%a", 100.0, 50.0), ("%b", 150.0, 50.0),
                        ("%a", 300.0, 100.0)]),
           ("XLA Modules", [("jit_step(1)", 100.0, 100.0),
                            ("jit_step(1)", 300.0, 100.0),
                            ("jit_tiny(2)", 250.0, 1.0)])]
    return ([(f"/device:TPU:{i}", dev) for i in range(n_dev)]
            + [("/host:CPU", host), ("#Chip0 Misc", [])])


@pytest.mark.parametrize("n_dev", [1, 4])
def test_hand_made_planes(n_dev):
    red = tr.reduce_planes(planes(n_dev))
    assert red["devices"] == n_dev
    assert red["window_s"] == pytest.approx(500e-9)
    assert red["busy_s"] == pytest.approx(200e-9)     # mean over devices
    assert red["busy_s_per_device"] == {
        i: pytest.approx(200e-9) for i in range(n_dev)}
    assert red["idle_gaps_device"] == 0
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


def test_four_devices_each_with_its_own_busy_time():
    """Devices 1 to 4 (no device 0: the gaps are of the lowest-numbered),
    device n busy for n x 50 ns; the mean is what it was, each device's
    own time stands beside it."""
    devs = [(f"/device:TPU:{n}", [("XLA Ops", [("%a", 100.0, 50.0 * n)])])
            for n in (3, 1, 4, 2)]
    red = tr.reduce_planes(devs + [("/host:CPU", HOST)])
    assert red["devices"] == 4
    assert red["busy_s_per_device"] == {
        n: pytest.approx(50e-9 * n) for n in (1, 2, 3, 4)}
    assert red["busy_s"] == pytest.approx(125e-9)
    assert red["ops"]["%a"] == pytest.approx(125e-9)
    assert red["idle_gaps_device"] == 1
    # device 1 ran from 100 to 150 of a window of 500: 450 idle
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(450e-9)


def test_program_host_spans_are_kept_and_cb_spans_decide_the_window():
    host = [("python3", HOST[0][1] + [
        # before the window, across its start, inside a gap, across busy
        # time and a gap, and past the window's end
        ("qt.feature.cold_fetch", -50.0, 20.0),
        ("qt.feature.cold_fetch", -10.0, 30.0),
        ("qt.feature.cold_fetch", 210.0, 40.0),
        ("qt.sampler.sample", 180.0, 40.0),
        ("qt.sampler.sample", 480.0, 100.0),
        ("qt.window_start", 50.0, 1.0),
        ("other.span", 210.0, 40.0)])]
    red = tr.reduce_planes(planes(1, host))
    # the window is the cb. marks', whatever the program calls its spans
    assert red["window_s"] == pytest.approx(500e-9)
    spans = red["host_spans"]
    assert "other.span" not in spans and "PjitFunction(step)" not in spans
    fetch = spans["qt.feature.cold_fetch"]
    assert fetch["count"] == 2          # the one before the window is cut
    assert fetch["seconds"] == pytest.approx(60e-9)
    assert fetch["idle_overlap_s"] == pytest.approx(60e-9)
    sample = spans["qt.sampler.sample"]
    assert sample["count"] == 2
    assert sample["seconds"] == pytest.approx(60e-9)    # 40 + 20 of 100
    assert sample["idle_overlap_s"] == pytest.approx(40e-9)  # 20 + 20
    assert spans["cb.dispatch"] == {
        "count": 1, "seconds": pytest.approx(90e-9),
        "idle_overlap_s": pytest.approx(90e-9)}
    # the gaps are still named by the benchmark's own spans alone: the
    # last, 400 to 500, which only ``qt.sampler.sample`` reaches into,
    # stays unattributed
    assert {what for what, _ in red["idle_gaps"]} == {
        "dispatch", "wait_result", "unattributed"}
    assert red == tr.reduce_planes(planes(1, host), window=(0.0, 500.0))


def test_overlap_with():
    inside = tr.overlap_with([(0, 100), (200, 300)])
    assert inside(0, 300) == 200 and inside(50, 250) == 100
    assert inside(100, 200) == 0 and inside(-10, 10) == 10
    assert inside(290, 400) == 10 and tr.overlap_with([])(0, 5) == 0


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
    # the numbers this trace reduced to before the reducer learnt of
    # several devices and of the program's host spans (PR 28), held
    assert red["window_s"] == pytest.approx(0.010012739, rel=1e-12)
    assert red["busy_s"] == pytest.approx(1.1201e-05, rel=1e-12)
    assert red["busy_s_per_device"] == {0: red["busy_s"]}
    assert red["modules"] == {"jit_small_step": {
        "launches": 2, "seconds": pytest.approx(1.1239e-05, rel=1e-12)}}
    assert len(red["ops"]) == 10
    assert sum(red["ops"].values()) == pytest.approx(1.1201e-05, rel=1e-12)
    assert [(w, round(s * 1e9)) for w, s in red["idle_gaps"][:4]] == [
        ("generate", 3456207), ("generate", 3391719), ("generate", 3153587),
        ("generate", 2)]
    assert len(red["idle_gaps"]) == 19 and red["idle_gaps_device"] == 0
    assert {k: (v["count"], round(v["seconds"] * 1e9))
            for k, v in red["host_spans"].items()} == {
        "cb.window_start": (1, 2260), "cb.dispatch": (3, 830050),
        "cb.wait_result": (3, 1639089), "cb.generate": (3, 7458960)}
