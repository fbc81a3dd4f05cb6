"""The typed fused step of ``igbh-rgat.train-fused-relfanout`` compiled
whole, at the cell's REAL shapes, by the TPU's own compiler for a
described v5e (nothing runs: ``tests/test_aot_compile.py``'s docstring).
A chip's share of IGBH-full (2,138,130 float16 rows of 1,024, 46,668,860
edges, eleven relations' windows), 32 seeds, [15, 10, 5] per relation,
MLPerf's R-GAT 3 x 512: four seconds to lower, about a minute to compile.

Kept in a file of its own, beside ``tests/test_aot_compile.py``, which is
the longest file of the suite: a worker that describes the topology holds
libtpu until it exits, so this file's fixture describes its own.
"""

import hashlib
import json
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from quiver_tpu.config import resolve_gather_mode, resolve_sample_rng

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E_HBM = int(15.75 * 2 ** 30)                  # what a v5e chip reports


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to ask
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single-device sharding on the described chip, the persistent
    compile cache off while this file runs (a described compile cannot be
    read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _pad128(n):
    return -(-n // 128) * 128


@pytest.fixture(scope="module")
def step(one_chip):
    """``(lowered, compiled)`` of the cell's ``jit_qt_fused_train_step``:
    what the program builds (``cellbench/programs/igbh_rgat_fused.py``),
    from the configuration's own numbers, the tables as arguments."""
    import optax

    from quiver_tpu.models import TypedRGNN
    from quiver_tpu.parallel import TrainState
    from quiver_tpu.pipeline import _fused_train_impl
    from quiver_tpu.sampler import Schema, run_pipeline

    sys.path.insert(0, os.path.join(ROOT, "cellbench"))
    import run

    igbh = run.load_named("references", "igbh_rgat")
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "igbh-rgat.json")) as f:
        cfg = json.load(f)
    off = igbh.type_offsets(cfg)
    nodes = off[-1]
    edges = sum(igbh.relation_edges(cfg, r)
                for r in range(len(igbh.RELATIONS)))
    schema = Schema(off, tuple((s, t) for _, s, t in igbh.RELATIONS))
    windows = _pad128(2 * sum(off[t + 1] - off[t]
                              for _, _, t in igbh.RELATIONS))
    sizes, B = tuple(cfg["fanout"]), cfg["batch"]
    gm = resolve_gather_mode("auto", backend="tpu")
    srng = resolve_sample_rng("auto", backend="tpu")

    def S(shape, dtype=jnp.int32, sharding=one_chip):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    graph = (S((_pad128(nodes + 1),)), S((_pad128(edges),)))
    _, _, _, blocks, _, _ = jax.eval_shape(
        lambda ip, ix, w, s, k: run_pipeline(
            "none", ip, ix, s, k, sizes, (None,) * 3, gather_mode=gm,
            sample_rng=srng, schema=schema, windows=w),
        *graph, S((windows,)), S((B,)), jax.random.key(0))
    frontier = 26_151 * B
    model = TypedRGNN(hidden=cfg["hidden"], out_dim=cfg["classes"],
                      num_relations=len(igbh.RELATIONS), heads=cfg["heads"],
                      dropout=cfg["dropout"])
    x = jax.ShapeDtypeStruct((frontier, cfg["feature_dim"]), jnp.float16)
    params = jax.eval_shape(model.init, jax.random.key(1), x, blocks)
    tx = optax.adam(cfg["lr"])
    state = jax.eval_shape(lambda p: TrainState.create(p, tx), params)
    sampler = types.SimpleNamespace(
        sizes=sizes, gather_mode=gm, sample_rng=srng, dedup="none",
        frontier_caps=(None,) * 3, schema=schema)
    feature = types.SimpleNamespace(cache_count=nodes, node_count=nodes)

    def apply_fn(p, x, blocks, train=False, rngs=None):
        return model.apply(p, x, blocks, train=train, rngs=rngs)

    impl = _fused_train_impl(sampler, feature, apply_fn, None)
    tables = (*graph, (S((nodes, cfg["feature_dim"]), jnp.float16), None),
              S((windows,)))
    lowered = jax.jit(impl, donate_argnums=(1,)).lower(
        tables, _on(one_chip, state), S((B,)), S((B,)), S((B,), jnp.bool_),
        _on(one_chip, jax.eval_shape(lambda: jax.random.key(0))))
    return lowered, lowered.compile()


def _on(sharding, tree):
    """Shapes only (a described device holds no array), each on the chip."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


# sha256 of the lowered text of the cell's step.  It stood at
# 18d59754f26cff0e421e0ad1f1e0a84b48552a71c52373502e7ccd524ee55c2d on the
# tree that adds the cell (5a9238d).  Re-recorded on the tree that MEANS
# to change the program: each relation block's weights' gradient is
# a multiply-reduce per head's slice of lanes (``layers._weighted_sum``),
# no longer a flat product re-laid to ``[n, k, 512]`` and multiplied with
# ``head_of``.  A PR that MEANS to change the program records the new
# text's hash here
IGBH_STEP_RECORDED = ("261d067e909eb0e3ecbde23eb38f8ddf906af623b3ad6c931034"
                      "56a57a6eee29")


def test_igbh_step_lowers_as_recorded(step):
    """``igbh-rgat.train-fused-relfanout``'s program, to the letter: the
    same compile-cache entry, the same numbers for the cell."""
    text = step[0].as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == IGBH_STEP_RECORDED


def test_igbh_step_fits_a_chip(step):
    """By the compiler's own estimate the step fits a chip beside its
    tables: 4.78 GB of arguments (rows, CSR, windows, state) and 5.35 GB of
    temporaries when recorded.  The frontier's 836,832 positions would not
    at the reference's 1,024 seeds (configuration, ``reduced_why``)."""
    m = step[1].memory_analysis()
    assert m.argument_size_in_bytes > 4_600_000_000          # the tables
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes) < V5E_HBM, m


def test_igbh_step_projects_each_relation_by_a_dense_product(step):
    """Each relation block is projected by one dense product of its slice:
    no grouped kernel (``ragged-dot``), no sort and no row permute in the
    model; no instruction of a convolution gathers or scatters; both parts
    of the convolution carry their scope in both passes; the hop draws
    under its three scopes."""
    from quiver_tpu.telemetry.device_scopes import parse_hlo_scopes

    text = step[1].as_text()
    assert "ragged-dot" not in text
    module, table = parse_hlo_scopes(text)
    assert module == "jit_qt_fused_train_step"
    convs = {op for op in table.values()
             if "qt.model" in op and "TypedRGNN/conv" in op}
    assert any(op.endswith("dot_general") and "qt.model.project" in op
               for op in convs)
    # (``jnp`` indexing names a head's static slice ``gather``: the opcode
    # says what the compiler made of it)
    moved = [line[:160] for line in text.splitlines()
             if re.search(r"\} (gather|scatter|sort)\(", line)
             and "TypedRGNN/conv" in line]
    assert not moved, moved
    for scope in ("qt.model.project", "qt.model.attention"):
        for backward in (False, True):
            assert [op for op in convs if scope in op
                    and ("transpose(" in op) == backward], (scope, backward)
    for hop in (1, 2, 3):
        assert any(f"qt.sampler.hop{hop}" in op for op in table.values())
