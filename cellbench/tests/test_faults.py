"""The harness has to SEE a broken timed path.  Each test drives a whole
run at the toy size with one fault planted underneath (in the cell's
``programs/<name>.py``, behind the harness) and sees ``correct`` come out
false under the cell's own limits; the sound run beside it comes out
true.  Which faults a cell can have its kind says (``FAULTS`` of
``kinds/<kind>.py``).  Those a training cell can have: a step that returns its state
unchanged; half of the batch left out, the mean taken over the rest.  (No
cell spans chips, so there is no exchange to leave out; no cell serves, so
there is no answer to alter.)

The control: the reference computed one precision below the one the
configuration states (bfloat16 results for products summed in float32),
put in the program's place, has to fail as well."""

import pytest

from conftest import cells, rehearsal_cell

CELLS = cells()


def faults():
    """Every cell beside each fault that its kind names."""
    import run

    return [(name, fault) for name in CELLS
            for fault in run.load_named(
                "kinds", run.find_cell(name)[3]["kind"]).FAULTS]


def drive(name, fault=None, seconds=1.0):
    import run

    cell, cfg, traffic = rehearsal_cell(name)
    out = run.run_cell(cell, cfg, traffic, seed=2**31 + 77, seconds=seconds,
                       trace=0, fault=fault)
    limits = run.limits_of(cfg, cell)
    return out, limits, cfg


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_is_not(cell):
    import run

    out, limits, cfg = drive(cell)
    compared, correct = run.compare(out["numbers"], limits)
    assert correct, compared
    # against the precision the cell states at its own size, the narrower
    # gap: on the chip the reference runs at that one
    stated = run.find_cell(cell)[2]["precision"]["matmul"]
    control = out["numbers_fn"](out["replayed"], stated,
                                stand_in=cfg["precision"]["control"])
    compared, correct = run.compare(control, limits)
    assert not correct, compared


@pytest.mark.parametrize("cell,fault", faults())
def test_broken_timed_path_is_not_correct(cell, fault):
    import run

    out, limits, _ = drive(cell, fault=fault)
    compared, correct = run.compare(out["numbers"], limits)
    assert not correct, compared


def test_missing_number_is_not_correct():
    import run

    compared, correct = run.compare({"loss_gap": 0.0}, {"loss_gap": 1e-3,
                                                        "grad_gap": 1e-3})
    assert not correct
    assert not run.compare({}, {})[1]
    assert not run.compare({"loss_gap": float("nan")}, {"loss_gap": 1.0})[1]
