"""Resolution of gather_mode / sample_rng / dedup.

Two sources: an explicit kwarg wins, else the backend's default.  The
backend is an argument of the resolvers, so what a TPU resolves to is
asked here with no chip attached (``chip_smoke.py`` prints what the
samplers resolved on one).  No environment variable and no file in the
checkout reaches the samplers.
"""

import pytest

from quiver_tpu.config import (resolve_dedup, resolve_gather_mode,
                               resolve_sample_rng)


def test_explicit_wins():
    for backend in (None, "cpu", "tpu"):
        assert resolve_gather_mode("blocked", backend) == "blocked"
        assert resolve_gather_mode("xla", backend) == "xla"
        assert resolve_sample_rng("hash", backend) == "hash"
        assert resolve_sample_rng("key", backend) == "key"
    assert resolve_dedup("hop") == "hop"


@pytest.mark.parametrize("backend,want", [
    ("cpu", ("xla", "key", "none")),
    ("tpu", ("blocked", "hash", "none")),
    ("gpu", ("blocked", "hash", "none")),
])
def test_backend_defaults(backend, want):
    """``auto`` on an accelerator is the window fetch with the hash
    uniforms (ledger, PR 31), on the CPU ``jnp.take`` with key-based
    ones; ``backend=None`` reads the backend JAX runs on (the CPU here)."""
    got = (resolve_gather_mode("auto", backend=backend),
           resolve_sample_rng("auto", backend=backend),
           resolve_dedup("auto"))
    assert got == want
    if backend == "cpu":
        assert (resolve_gather_mode("auto"),
                resolve_sample_rng("auto")) == want[:2]


def test_invalid_values_raise():
    with pytest.raises(ValueError):
        resolve_gather_mode("fast")
    with pytest.raises(ValueError):
        resolve_sample_rng("Hash")
    with pytest.raises(ValueError, match="dedup"):
        resolve_dedup("both")


@pytest.mark.parametrize("mode", ["lanes", "lanes_fused", "pallas",
                                  "pwindow", "pwindow:2", "blocked:3"])
def test_removed_modes_are_refused(mode, small_graph):
    """The paths the chip turned down, and the ``:U`` suffix, are refused
    by name at the resolver, at every constructor and at the op."""
    import jax

    from quiver_tpu import GraphSageSampler
    from quiver_tpu.ops.sample import sample_neighbors

    with pytest.raises(ValueError, match=r"auto \| xla \| blocked"):
        resolve_gather_mode(mode)
    with pytest.raises(ValueError, match=r"auto \| xla \| blocked"):
        GraphSageSampler(small_graph, [3], gather_mode=mode)
    indptr, indices = small_graph.to_device()
    with pytest.raises(ValueError, match=r"xla \| blocked"):
        sample_neighbors(indptr, indices,
                         jax.numpy.arange(8, dtype=jax.numpy.int32), 3,
                         jax.random.PRNGKey(0), gather_mode=mode)


@pytest.mark.parametrize("source", ["env", "tuned_file"])
def test_nothing_but_the_kwarg_and_the_backend_reaches_the_sampler(
        source, monkeypatch, tmp_path):
    """A ``QUIVER_TPU_*`` variable or a ``.quiver_tpu_tuned.json`` beside
    the package (the two overlays the parent obeyed) changes nothing: a
    fresh interpreter resolves the backend's defaults."""
    import json
    import os
    import shutil
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = repo
    if source == "env":
        env.update(QUIVER_TPU_GATHER_MODE="blocked",
                   QUIVER_TPU_SAMPLE_RNG="hash", QUIVER_TPU_DEDUP="hop")
    else:
        # the parent looked for the file in the directory that holds the
        # package: give a copy of the package one
        root = str(tmp_path)
        shutil.copytree(
            os.path.join(repo, "quiver_tpu"), tmp_path / "quiver_tpu",
            ignore=shutil.ignore_patterns("__pycache__", "*.so", "cpp"))
        (tmp_path / ".quiver_tpu_tuned.json").write_text(json.dumps(
            {"backends": {"cpu": {"gather_mode": "blocked",
                                  "sample_rng": "hash", "dedup": "hop"}}}))
    env["PYTHONPATH"] = root
    code = ("import numpy as np\n"
            "from quiver_tpu import CSRTopo, GraphSageSampler\n"
            "rng = np.random.default_rng(0)\n"
            "t = CSRTopo(edge_index=rng.integers(0, 50, (2, 400)))\n"
            "s = GraphSageSampler(t, [3])\n"
            "print(s.gather_mode, s.sample_rng, s.dedup)\n")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split() == ["xla", "key", "none"]


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


def test_uva_rides_the_positional_pipeline_only(small_graph):
    """A UVA sampler built with the default dedup samples; an explicit
    ``hop`` surfaces the incompatibility."""
    import numpy as np

    from quiver_tpu import GraphSageSampler

    s = GraphSageSampler(small_graph, [3], mode="UVA",
                         uva_budget=small_graph.edge_count * 2)
    assert s.dedup == "none"
    s.sample(np.arange(8, dtype=np.int32))
    with pytest.raises(AssertionError, match="positional"):
        GraphSageSampler(small_graph, [3], mode="UVA", dedup="hop",
                         uva_budget=small_graph.edge_count * 2)
