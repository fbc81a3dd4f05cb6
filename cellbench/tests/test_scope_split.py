"""The six per-layer metrics that split the traced step by the program's
``qt.*`` scopes, on a hand-made reduced trace and a planted scope table:
HLO-line keys as the recorded v5e trace spells them, values in seconds."""

import json
import os

import pytest

import run
import scope_split

CELL = "papers100m-sage.train-fused"
STEP = "jit(qt_fused_train_step)/"

# instruction (an HLO line up to its opcode) -> op_name, as
# ``quiver_tpu.telemetry.device_scopes()`` gives it for one program
TABLE = {
    "%fusion.8 = s32[901120,128]{1,0:T(8,128)S(1)}":
        STEP + "qt.sampler.hop3/jit(sample_neighbors)/jit(_take)/gather",
    "%fusion.7 = s32[163840,128]{1,0:T(8,128)}":
        STEP + "qt.sampler.hop2/jit(sample_neighbors)/jit(_take)/gather",
    "%iota.3 = s32[1024]{0:T(1024)}": STEP + "qt.sampler/iota",
    "%fusion.9 = bf16[1081344,128]{1,0:T(8,128)(2,1)}":
        STEP + "qt.feature.gather/jit(_take)/gather",
    "%fusion.10 = f32[901120,128]{1,0:T(8,128)}":
        STEP + "jvp(qt.model)/GraphSAGE/conv0/jit(_take)/gather",
    "%fusion.15 = (f32[180224,256]{1,0:T(8,128)}, f32[256]{0:T(256)})":
        STEP + "transpose(jvp(qt.model))/GraphSAGE/conv1/scatter-add",
    "%fusion.40 = f32[256,172]{0,1:T(8,128)}":
        STEP + "qt.optimizer/add",
    "%tables_0_.1 = s32[27765120]{0:T(1024)}": "tables[0]",
}


def line(key, rest):
    return f"{key} {rest}"


# 10 traced steps; seconds over the whole traced window
OPS = {
    line("%fusion.8 = s32[901120,128]{1,0:T(8,128)S(1)}",
         "fusion(s32[3155637,128]{1,0:T(8,128)} %bitcast.1, s32[901120]"
         "{0:T(1024)} %x), kind=kCustom, calls=%fused_computation.8"): 0.120,
    line("%fusion.7 = s32[163840,128]{1,0:T(8,128)}",
         "fusion(s32[163840]{0:T(1024)} %y), kind=kCustom, "
         "calls=%fused_computation.7"): 0.020,
    line("%iota.3 = s32[1024]{0:T(1024)}", "iota(), iota_dimension=0"): 0.001,
    line("%fusion.9 = bf16[1081344,128]{1,0:T(8,128)(2,1)}",
         "fusion(bf16[27764989,128]{1,0:T(8,128)(2,1)} %t), kind=kCustom, "
         "calls=%fused_computation.9"): 0.150,
    line("%fusion.10 = f32[901120,128]{1,0:T(8,128)}",
         "fusion(f32[1081344,128]{1,0:T(8,128)} %z), kind=kCustom, "
         "calls=%fused_computation.10"): 0.122,
    # a tuple shape: the trace prints the printer's index comments
    line("%fusion.15 = (f32[180224,256]{1,0:T(8,128)}, /*index=1*/f32[256]"
         "{0:T(256)})", "fusion(f32[180224,256]{1,0:T(8,128)} %g), "
         "kind=kOutput, calls=%fused_computation.15"): 0.033,
    line("%fusion.40 = f32[256,172]{0,1:T(8,128)}",
         "fusion(f32[256,172]{0,1:T(8,128)} %p), kind=kLoop, "
         "calls=%fused_computation.40"): 0.004,
    # not in the table: a copy, and a helper program's fusion whose NAME is
    # the step's but whose shape is not
    line("%copy-done.2 = s32[2048]{0:T(1024)S(1)}",
         "copy-done((s32[2048]{0:T(1024)S(1)}, s32[2048]{0:T(1024)}, u32[]"
         "{:S(2)}) %copy-start.2)"): 0.030,
    line("%fusion.9 = f32[1024]{0:T(1024)}",
         "fusion(f32[1024]{0:T(1024)} %q), kind=kLoop, "
         "calls=%fused_computation.2"): 0.020,
}
EXPECTED = {                        # ms per traced step
    "sampler_device_ms.train": 14.1,
    "feature_gather_device_ms.train": 15.0,
    "model_forward_device_ms.train": 12.2,
    "model_backward_device_ms.train": 3.3,
    "optimizer_device_ms.train": 0.4,
    "scope_attributed_pct.train": 90.0,     # 0.450 of 0.500 s
}


def ctx(ops=OPS, kind="train", trace=True, steps=10):
    return {"facts": {"kind": kind, "traced_steps": steps, "batch": 1024,
                      "traced_s": 1.0, "window_compiles": 0},
            "trace": {"ops": ops, "busy_s": 0.5, "window_s": 1.0}
            if trace else None}


@pytest.fixture
def planted(monkeypatch):
    """The helper reading ``TABLE`` where it would ask the program."""
    def plant(table):
        monkeypatch.setattr(scope_split, "_memo", {})
        monkeypatch.setattr(scope_split, "scope_table", lambda: table)
    plant(TABLE)
    return plant


def new_metrics():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return bench, [m for m in bench["per_layer"] if m["name"] in EXPECTED]


def read_all(c):
    bench, _ = new_metrics()
    _, cell, _, _ = run.find_cell(CELL)
    # the four older readers want more of a ctx than the split does
    bench = dict(bench, per_layer=new_metrics()[1])
    return {k: v["value"]
            for k, v in run.read_layer_metrics(bench, cell, c).items()}


def test_the_six_values_forward_and_backward_apart(planted, capsys):
    got = read_all(ctx())
    assert set(got) == set(EXPECTED)
    for name, value in EXPECTED.items():
        assert got[name] == pytest.approx(value), name
    # the five split exactly the attributed share of all operation time
    five = sum(v for k, v in got.items() if k.endswith("_device_ms.train"))
    assert five == pytest.approx(0.9 * 1e3 * sum(OPS.values()) / 10)
    err = capsys.readouterr().err
    # one table for a person, hops apart, logged once for six readers
    assert err.count("scope_split:") == 1
    assert "qt.sampler.hop3 forward" in err
    assert "qt.sampler.hop2 forward" in err
    assert "qt.model backward" in err and "(no qt. scope)" in err
    assert "longest operations" in err


def test_unknown_instructions_count_against_the_share_only(planted):
    ops = dict(OPS)
    ops["%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)"] = 0.5
    got = read_all(ctx(ops))
    assert got["scope_attributed_pct.train"] == pytest.approx(45.0)
    for name in EXPECTED:
        if name != "scope_attributed_pct.train":
            assert got[name] == pytest.approx(EXPECTED[name]), name


@pytest.mark.parametrize("table", [{}, {"%a = f32[2]{0}": "jit(f)/add"}])
def test_a_table_without_scopes_gives_zeros_not_an_exception(planted, table):
    planted(table)
    got = read_all(ctx())
    assert set(got) == set(EXPECTED)
    assert all(v == 0.0 for v in got.values()), got
    planted(TABLE)
    assert read_all(ctx({}))["scope_attributed_pct.train"] == 0.0


@pytest.mark.parametrize("c", [ctx(trace=False), ctx(kind="serve"),
                               ctx(steps=None)],
                         ids=["no-trace", "not-train", "no-steps"])
def test_nothing_to_read_is_none_like_the_older_readers(planted, c):
    assert read_all(c) == {}


def test_a_program_without_the_table_reports_nothing(monkeypatch):
    """The parent commit of the PR that brought the table: the readers
    return None and the line leaves the metrics out."""
    import builtins

    real = builtins.__import__

    def no_table(name, *a, **kw):
        if name == "quiver_tpu.telemetry":
            raise ImportError("cannot import name 'device_scopes'")
        return real(name, *a, **kw)

    monkeypatch.setattr(scope_split, "_memo", {})
    monkeypatch.setattr(builtins, "__import__", no_table)
    assert scope_split.scope_table() is None
    assert read_all(ctx()) == {}


def test_helper_reads_the_programs_own_table(monkeypatch):
    """Unplanted: the table is ``quiver_tpu.telemetry.device_scopes()``
    merged over programs, and its keys are cut as the helper cuts a trace
    event's name."""
    from quiver_tpu import telemetry

    monkeypatch.setattr(
        telemetry, "device_scopes",
        lambda: {"jit_a": {"%x = f32[2]{0}": "jit(a)/qt.model/add"},
                 "jit_b": {"%y = f32[2]{0}": "jit(b)/qt.sampler.hop1/mul"}})
    assert scope_split.scope_table() == {
        "%x = f32[2]{0}": "jit(a)/qt.model/add",
        "%y = f32[2]{0}": "jit(b)/qt.sampler.hop1/mul"}
    from quiver_tpu.telemetry.device_scopes import instruction_key

    for event in OPS:
        assert scope_split.instruction_of(event) == instruction_key(event)


def test_recorded_trace_names_cut_to_name_and_shape():
    import trace_reduce as tr

    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "small_step.xplane.pb")
    red = tr.reduce_planes(tr.read_xplane(data), window=(0.0, 1e12))
    keys = {scope_split.instruction_of(name) for name in red["ops"]}
    assert None not in keys and len(keys) == len(red["ops"])
    assert "%fusion.5 = s32[2048]{0:T(1024)S(1)}" in keys
    assert ("%copy-start = (f32[4096,128]{1,0:T(8,128)S(1)}, "
            "f32[4096,128]{1,0:T(8,128)}, u32[]{:S(2)})") in keys


def test_benchmark_json_loads_and_every_reader_is_there():
    bench, cell, cfg, traffic = run.find_cell(CELL)
    _, added = new_metrics()
    assert [m["name"] for m in added] == list(EXPECTED)
    for m in bench["per_layer"]:
        path = os.path.join(run.HERE, "metrics", m["name"] + ".py")
        assert os.path.isfile(path), path
    for m in added:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_seeds_per_s"
        assert m["source"] == "device_trace"
