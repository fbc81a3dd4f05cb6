"""Resolution precedence for gather_mode / sample_rng.

Explicit kwarg > env (QUIVER_TPU_*) / tuned file > backend default.
Backend default on CPU (the test backend): gather_mode="xla",
sample_rng="key".  The accelerator branch can't execute here
(chip_smoke.py prints what it resolves there), so its default is stated
with the backend's name patched; the precedence logic it shares is
what's under test.

All env mutation goes through ``monkeypatch`` so it is restored even on
assertion failure — the round-3 hand-rolled save/restore leaked
``QUIVER_TPU_SAMPLE_RNG=hash`` into the rest of the pytest session and
flipped 94 unrelated tests onto the accelerator RNG path.
"""

import pytest

import quiver_tpu.config as qconfig
from quiver_tpu.config import resolve_gather_mode, resolve_sample_rng


@pytest.fixture(autouse=True)
def _clean_config(monkeypatch):
    """Reset the config singleton, scrub env overrides, and disable the
    tuned-file loader around each test (a locally-written
    .quiver_tpu_tuned.json must not leak into backend-default asserts).

    monkeypatch records and restores everything it touches — including
    deleting vars a test adds via ``monkeypatch.setenv`` — so nothing
    this module does survives past its own tests."""
    monkeypatch.delenv("QUIVER_TPU_GATHER_MODE", raising=False)
    monkeypatch.delenv("QUIVER_TPU_SAMPLE_RNG", raising=False)
    monkeypatch.delenv("QUIVER_TPU_DEDUP", raising=False)
    monkeypatch.setattr(qconfig, "_load_tuned", lambda cfg, path=None: None)
    qconfig._config = None
    yield
    qconfig._config = None


def test_explicit_wins():
    assert resolve_gather_mode("pallas") == "pallas"
    assert resolve_sample_rng("hash") == "hash"


def test_backend_default_cpu():
    assert resolve_gather_mode("auto") == "xla"
    assert resolve_sample_rng("auto") == "key"


def test_backend_default_accelerator(monkeypatch):
    """On a TPU ``auto`` is the window fetch at the chip's block width
    (PERF.md, PR 31), spelt in the ``blocked:U`` grammar, with the hash
    uniforms; the environment still overrides it."""
    import jax

    from quiver_tpu.ops.blockgather import DEFAULT_U, parse_blocked

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mode = resolve_gather_mode("auto")
    assert mode == "blocked:2" and parse_blocked(mode) == DEFAULT_U
    assert resolve_sample_rng("auto", mode) == "hash"
    monkeypatch.setenv("QUIVER_TPU_GATHER_MODE", "lanes")
    qconfig._config = None
    assert resolve_gather_mode("auto") == "lanes"


def test_env_overrides_auto(monkeypatch):
    monkeypatch.setenv("QUIVER_TPU_GATHER_MODE", "lanes")
    monkeypatch.setenv("QUIVER_TPU_SAMPLE_RNG", "hash")
    qconfig._config = None
    assert resolve_gather_mode("auto") == "lanes"
    assert resolve_sample_rng("auto") == "hash"


def test_explicit_beats_env(monkeypatch):
    monkeypatch.setenv("QUIVER_TPU_GATHER_MODE", "lanes")
    monkeypatch.setenv("QUIVER_TPU_SAMPLE_RNG", "hash")
    qconfig._config = None
    assert resolve_gather_mode("xla") == "xla"
    assert resolve_sample_rng("key") == "key"


def test_invalid_values_raise():
    with pytest.raises(ValueError):
        resolve_gather_mode("fast")
    with pytest.raises(ValueError):
        resolve_sample_rng("Hash")


def test_invalid_env_raises_not_silently_defaults(monkeypatch):
    monkeypatch.setenv("QUIVER_TPU_SAMPLE_RNG", "keyed")
    qconfig._config = None
    with pytest.raises(ValueError):
        resolve_sample_rng("auto")


# captured at import time, before the autouse fixture stubs the attribute
_ORIG_LOAD_TUNED = qconfig._load_tuned


def test_malformed_tuned_blocked_is_ignored(tmp_path):
    """A tuned file carrying 'blocked:0' / 'blockedx' must be skipped like
    any other invalid tuned value, not crash resolve_gather_mode later."""
    import json

    import jax

    backend = jax.default_backend()
    p = tmp_path / ".quiver_tpu_tuned.json"
    for bad in ("blocked:0", "blocked:-2", "blockedx", "blocked:"):
        p.write_text(json.dumps({"backend": backend, "gather_mode": bad}))
        cfg = qconfig.Config()
        _ORIG_LOAD_TUNED(cfg, path=str(p))
        assert cfg.gather_mode == "auto", bad
    # a WELL-FORMED blocked value is accepted
    p.write_text(json.dumps(
        {"backend": backend, "gather_mode": "blocked:3"}))
    cfg = qconfig.Config()
    _ORIG_LOAD_TUNED(cfg, path=str(p))
    assert cfg.gather_mode == "blocked:3"


def test_sampler_resolves_at_init():
    import numpy as np

    from quiver_tpu import CSRTopo, GraphSageSampler
    from quiver_tpu.utils.synthetic import synthetic_csr

    indptr, indices = synthetic_csr(500, 4000, 0)
    topo = CSRTopo(indptr=indptr, indices=indices)
    s = GraphSageSampler(topo, [3], gather_mode="auto", sample_rng="auto")
    assert s.gather_mode == "xla" and s.sample_rng == "key"
    b = s.sample(np.arange(8, dtype=np.int32))
    assert int(b.num_nodes) >= 8


def test_auto_rng_resolves_hash_under_pwindow(monkeypatch):
    """gather_mode='pwindow' only supports the in-kernel counter-hash;
    'auto' must resolve to 'hash' under it even on CPU (where auto
    otherwise resolves to 'key')."""
    from quiver_tpu.config import resolve_sample_rng

    assert resolve_sample_rng("auto", "pwindow") == "hash"
    assert resolve_sample_rng("auto", "pwindow:2") == "hash"
    # explicit choice is surfaced, not overridden (the op raises)
    assert resolve_sample_rng("key", "pwindow") == "key"
    # other modes keep the backend default (cpu -> key in this suite)
    assert resolve_sample_rng("auto", "lanes") == "key"


def test_env_pinned_key_rng_warns_under_pwindow(monkeypatch):
    """gather_mode='pwindow' forces 'hash'; when the displaced 'key' pin
    came from env/tuned (not an explicit kwarg) the override must be
    surfaced as a warning, not silent."""
    import warnings

    monkeypatch.setenv("QUIVER_TPU_SAMPLE_RNG", "key")
    qconfig._config = None
    with pytest.warns(UserWarning, match="overridden to 'hash'"):
        assert resolve_sample_rng("auto", "pwindow:2") == "hash"
    # no pin -> no warning (the override changes nothing the user chose)
    monkeypatch.delenv("QUIVER_TPU_SAMPLE_RNG")
    qconfig._config = None
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert resolve_sample_rng("auto", "pwindow:2") == "hash"


def test_pwindow_rejects_unsupported_backend(monkeypatch, small_graph):
    """An unsupported backend must fail with a clear ValueError before
    Mosaic lowering is attempted (ops/sample.py pwindow branch)."""
    import jax

    from quiver_tpu.ops.fastgather import pad_table_128
    from quiver_tpu.ops.sample import sample_neighbors
    from quiver_tpu.utils.rng import make_key

    indptr, indices = small_graph.to_device()
    indices = pad_table_128(indices)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(ValueError, match="pwindow.*needs backend"):
        # unique k so the jit cache can't serve a pre-gate trace
        sample_neighbors(indptr, indices,
                         jax.numpy.arange(13, dtype=jax.numpy.int32),
                         7, make_key(0), gather_mode="pwindow:2",
                         sample_rng="hash")


def test_auto_gather_degrades_pwindow_for_explicit_key_rng(monkeypatch):
    """A tuned/env 'pwindow' pick must not crash a user who explicitly
    chose sample_rng='key': auto resolution degrades to the equivalent
    XLA blocked mode.  An explicit pwindow+key still raises at the op."""
    from quiver_tpu import config as qc

    monkeypatch.setenv("QUIVER_TPU_GATHER_MODE", "pwindow:3")
    monkeypatch.setattr(qc, "_config", None)
    assert qc.resolve_gather_mode("auto", "key") == "blocked:3"
    assert qc.resolve_gather_mode("auto", "hash") == "pwindow:3"
    assert qc.resolve_gather_mode("auto", "auto") == "pwindow:3"
    # explicit kwarg is never rewritten
    assert qc.resolve_gather_mode("pwindow:3", "key") == "pwindow:3"
    monkeypatch.setattr(qc, "_config", None)


def test_dedup_resolution(monkeypatch, tmp_path):
    """'auto' dedup follows env > tuned file (the on-chip e2e A/B's
    winner) > 'none'; explicit values pass through; bad values raise."""
    from quiver_tpu import config as qc

    monkeypatch.setattr(qc, "_config", None)
    monkeypatch.delenv("QUIVER_TPU_DEDUP", raising=False)
    assert qc.resolve_dedup("auto") == "none"
    assert qc.resolve_dedup("hop") == "hop"
    with pytest.raises(ValueError, match="dedup"):
        qc.resolve_dedup("both")
    monkeypatch.setenv("QUIVER_TPU_DEDUP", "hop")
    monkeypatch.setattr(qc, "_config", None)
    assert qc.resolve_dedup("auto") == "hop"
    # tuned-file overlay (same backend) flips the default — the suite
    # fixture no-ops qc._load_tuned, so call the saved original against
    # a scratch tuned file
    monkeypatch.delenv("QUIVER_TPU_DEDUP", raising=False)
    import jax, json
    tuned = tmp_path / "tuned.json"
    tuned.write_text(json.dumps(
        {"backend": jax.default_backend(), "dedup": "hop"}))
    cfg = qc.Config()
    _ORIG_LOAD_TUNED(cfg, str(tuned))
    monkeypatch.setattr(qc, "_config", cfg)
    assert qc.resolve_dedup("auto") == "hop"
    monkeypatch.setattr(qc, "_config", None)


def test_persist_dedup_winner_gate(tmp_path, monkeypatch):
    """bench.persist_dedup_winner: only live accelerator A/B pairs are
    persisted; CPU or replayed sections never flip the default."""
    import bench

    tuned = str(tmp_path / "tuned.json")
    live = {"e2e": {"ms_per_step": 100.0, "gather_mode": "lanes"},
            "e2e_dedup_hop": {"ms_per_step": 80.0, "gather_mode": "lanes"}}
    replay = {"e2e": {"ms_per_step": 100.0, "source": "cached:tpu",
                      "gather_mode": "lanes"},
              "e2e_dedup_hop": {"ms_per_step": 80.0,
                                "gather_mode": "lanes"}}
    assert bench.persist_dedup_winner(live, "cpu", tuned) is None
    assert bench.persist_dedup_winner(replay, "tpu", tuned) is None
    assert bench.persist_dedup_winner(live, "tpu", tuned) == "hop"
    import json
    assert bench.read_tuned("tpu", tuned)["dedup"] == "hop"
    live["e2e_dedup_hop"]["ms_per_step"] = 150.0
    assert bench.persist_dedup_winner(live, "tpu", tuned) == "none"
    # merge semantics: a later gather-probe write must keep the dedup key
    bench.merge_tuned({"gather_mode": "pwindow:3", "modes_version": 99},
                      "tpu", tuned)
    t = bench.read_tuned("tpu", tuned)
    assert t["dedup"] == "none" and t["gather_mode"] == "pwindow:3"
    # a CPU write must NOT erase the TPU entry (per-backend v2 format)
    bench.merge_tuned({"gather_mode": "lanes"}, "cpu", tuned)
    assert bench.read_tuned("cpu", tuned)["gather_mode"] == "lanes"
    assert bench.read_tuned("tpu", tuned)["dedup"] == "none"
    # a cross-mode A/B pair is refused
    mixed = {"e2e": {"ms_per_step": 100.0, "gather_mode": "pwindow:3"},
             "e2e_dedup_hop": {"ms_per_step": 80.0,
                               "gather_mode": "lanes"}}
    assert bench.persist_dedup_winner(mixed, "tpu", tuned) is None
    # legacy-format caches WITHOUT the gather_mode stamp are refused too:
    # None == None must not pass as "same mode" (missing on either side
    # or both means the pair's comparability is unknown)
    legacy = {"e2e": {"ms_per_step": 100.0},
              "e2e_dedup_hop": {"ms_per_step": 80.0}}
    assert bench.persist_dedup_winner(legacy, "tpu", tuned) is None
    half = {"e2e": {"ms_per_step": 100.0, "gather_mode": "lanes"},
            "e2e_dedup_hop": {"ms_per_step": 80.0}}
    assert bench.persist_dedup_winner(half, "tpu", tuned) is None


def test_uva_auto_dedup_survives_tuned_hop(monkeypatch, small_graph):
    """A tuned/env dedup='hop' must not crash UVA samplers constructed
    with the default dedup (UVA rides the positional pipeline only)."""
    import numpy as np

    from quiver_tpu import GraphSageSampler

    monkeypatch.setenv("QUIVER_TPU_DEDUP", "hop")
    qconfig._config = None
    s = GraphSageSampler(small_graph, [3], mode="UVA",
                         uva_budget=small_graph.edge_count * 2)
    assert s.dedup == "none"
    s.sample(np.arange(8, dtype=np.int32))
    # an explicit hop still surfaces the incompatibility
    with pytest.raises(AssertionError, match="positional"):
        GraphSageSampler(small_graph, [3], mode="UVA", dedup="hop",
                         uva_budget=small_graph.edge_count * 2)
