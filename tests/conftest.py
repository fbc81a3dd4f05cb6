"""Test config: force CPU backend with 8 virtual devices BEFORE jax import.

This gives every test a simulated 8-chip mesh (the multi-host coverage the
reference never had — SURVEY.md §4's lesson), and keeps the suite runnable
anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax
import numpy as np
import pytest

# retrace_guard hooks (@pytest.mark.retrace_budget).  Re-exported here —
# NOT listed via `-p` in pytest.ini — so the import happens after the
# JAX_PLATFORMS / XLA_FLAGS staging above (the plugin pulls in
# quiver_tpu, which imports jax).
from quiver_tpu.analysis.retrace_guard import *  # noqa: F401,F403


# ---------------------------------------------------------------------------
# Lock-witness sanitizer harness (`make sanitize` sets QUIVER_SANITIZE=1;
# quiver_tpu/__init__.py installed the witness before jax even imported).
# Seed the canonical acquisition order once from the static analyzer, then
# drain after every test and fail the owner on any recorded violation.
_SANITIZING = os.environ.get("QUIVER_SANITIZE") == "1"

if _SANITIZING:
    from quiver_tpu.analysis import witness as _witness

    @pytest.fixture(scope="session", autouse=True)
    def _witness_seed():
        from quiver_tpu.analysis.concurrency import canonical_lock_edges
        from quiver_tpu.analysis.core import load_contexts

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ctxs = load_contexts([os.path.join(root, "quiver_tpu")])
        _witness.seed_order(canonical_lock_edges(ctxs))
        yield

    @pytest.fixture(autouse=True)
    def _witness_drain(request):
        from quiver_tpu.analysis import transfer_witness as _transfer

        _witness.drain()  # don't blame this test for prior leftovers
        _transfer.drain()
        yield
        vs = [("lock-witness", v) for v in _witness.drain()]
        vs += [("transfer-witness", v) for v in _transfer.drain()]
        if vs:
            lines = [f"  [{src}:{v.kind}] {v.message} (thread {v.thread})"
                     for src, v in vs]
            pytest.fail(
                "sanitizer recorded %d violation(s):\n%s"
                % (len(vs), "\n".join(lines)), pytrace=False)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# ---------------------------------------------------------------------------
# Multi-device / multi-process helpers for the mesh tier (docs/SHARDING.md).
# The suite itself already runs on 8 virtual CPU devices (above); tests
# that need a SEPARATE process with its own device count (shard-group
# members, device-count isolation) spawn one through this helper.
def run_devices_subprocess(code, n_devices=8, env=None, timeout=120):
    """Run ``code`` in a fresh python with ``n_devices`` virtual CPU
    devices; returns the CompletedProcess (caller asserts on
    returncode/stdout).  The child re-stages JAX_PLATFORMS/XLA_FLAGS
    before its first jax import, exactly like this conftest."""
    import subprocess
    import sys

    child_env = dict(os.environ)
    child_env["JAX_PLATFORMS"] = "cpu"
    child_env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={int(n_devices)}")
    if env:
        child_env.update(env)
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, env=child_env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture
def devices_subprocess():
    """Fixture form of :func:`run_devices_subprocess` for mesh tests."""
    return run_devices_subprocess


def make_random_csr(n_nodes=200, avg_deg=8, seed=0, power_law=False):
    """Random graph fixture (parity: gen_random_graph,
    tests/cpp/test_quiver.cu:17-85)."""
    rng = np.random.default_rng(seed)
    if power_law:
        deg = np.minimum(
            rng.zipf(1.6, n_nodes) + 1, n_nodes - 1
        ).astype(np.int64)
    else:
        deg = rng.poisson(avg_deg, n_nodes).astype(np.int64)
    src = np.repeat(np.arange(n_nodes), deg)
    dst = rng.integers(0, n_nodes, size=src.shape[0])
    # drop parallel edges so "k distinct positions" == "k distinct ids"
    # in the property tests (samplers pick positions, as the reference does)
    pairs = np.unique(np.stack([src, dst], axis=1), axis=0)
    return pairs[:, 0], pairs[:, 1]


@pytest.fixture
def small_graph():
    from quiver_tpu import CSRTopo

    src, dst = make_random_csr(n_nodes=200, avg_deg=8, seed=1)
    return CSRTopo(edge_index=np.stack([src, dst]))


@pytest.fixture
def power_graph():
    from quiver_tpu import CSRTopo

    src, dst = make_random_csr(n_nodes=500, avg_deg=8, seed=2,
                               power_law=True)
    return CSRTopo(edge_index=np.stack([src, dst]))


# ---------------------------------------------------------------------------
# What a traced train step holds under the model's scope (test_pipeline.py,
# test_dist.py)
def model_primitives(jaxpr, inside=False):
    """Names of the primitives traced under the ``qt.model`` scope
    (forward, and backward as ``transpose(jvp(qt.model))``), through every
    nested jaxpr."""
    names = []
    for eqn in jaxpr.eqns:
        here = inside or "qt.model" in str(eqn.source_info.name_stack)
        if here:
            names.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names += model_primitives(sub, here)
    return names


def onehot_loss(logits, labels, mask):
    # the default loss picks each label's logit with a gather of its own;
    # this one has none, so any gather under qt.model is a conv's
    ls = -(jax.nn.one_hot(labels, logits.shape[-1])
           * jax.nn.log_softmax(logits)).sum(-1)
    return (ls * mask).sum() / jax.numpy.maximum(mask.sum(), 1.0)
