"""One lookup by name for everything a cell's data names: a program, a
kind, a reference, a work model, a per-layer reader (and the traffic
file).  A name with no file exits non-zero and says which names exist."""

import json
import os

import pytest

import run
from conftest import BENCH, cells

FOLDERS = {"programs": "sage_fused", "kinds": "train", "references": "sage",
           "work": "sage", "metrics": "train_step_mfu"}


@pytest.mark.parametrize("folder", sorted(FOLDERS))
def test_a_name_with_no_file_exits_naming_what_exists(folder):
    with pytest.raises(SystemExit) as e:
        run.load_named(folder, "no-such-name")
    assert e.value.code not in (None, 0)
    assert f"cellbench/{folder}/no-such-name.py" in str(e.value.code)
    assert repr(FOLDERS[folder]) in str(e.value.code)


def test_a_traffic_mix_with_no_file_exits_naming_what_exists():
    with pytest.raises(SystemExit) as e:
        run.find_file("traffic", "no-such-mix", ".json")
    assert "'train-fused'" in str(e.value.code)
    with pytest.raises(SystemExit) as e:
        run.find_cell("no-such.cell")
    assert repr(cells()[0]) in str(e.value.code)


@pytest.mark.parametrize("folder", sorted(FOLDERS))
def test_a_file_is_loaded_once_and_exposes_what_is_asked_of_it(folder):
    mod = run.load_named(folder, FOLDERS[folder])
    assert mod is run.load_named(folder, FOLDERS[folder])
    want = {"programs": ["Program"], "kinds": ["run", "FAULTS"],
            "references": ["make_data"], "metrics": ["read"],
            "work": ["step_flops", "step_bytes", "least_step_seconds"]}
    assert all(hasattr(mod, name) for name in want[folder])


@pytest.mark.parametrize("cell", cells())
def test_every_cell_names_files_that_exist(cell):
    bench, c, cfg, traffic = run.find_cell(cell)
    assert set(run.parts_of(cfg, traffic)) == {"program", "reference",
                                               "work", "kind"}
    for m in bench["per_layer"]:
        if cell in m.get("workloads", [cell]):
            run.find_file("metrics", m["name"], ".py")


def test_harness_names_no_program_kind_reference_or_work_model():
    """``run.py`` and ``cells.py`` find them by the cell's data alone."""
    for name in ("run.py", "cells.py"):
        with open(os.path.join(BENCH, name)) as f:
            text = f.read().lower()
        for word in ("sage", "kinds =", "import program", "import reference",
                     "import workmodel"):
            assert word not in text, (name, word)


def test_work_model_counts_what_it_counted():
    """The work model behind its generic names reads as ``workmodel.py``
    did for the cell's shapes (FLOPs and least bytes of one B=1024
    [15,10,5] step, forward and backward)."""
    import peaks

    _, _, cfg, _ = run.find_cell("papers100m-sage.train-fused")
    work = run.load_named("work", cfg["work"])
    peak = peaks.peaks("TPU v5 lite")
    assert work.step_flops(1024, cfg, backward=True) == 60877701120
    assert work.step_bytes(1024, cfg, peak, backward=True) == 1127508688
    least, bound = work.least_step_seconds(1024, cfg, peak, backward=True)
    assert bound == "bytes" and least == pytest.approx(1127508688 / 819e9)
    with pytest.raises(KeyError):
        peaks.peaks("no such device")
