"""Real compiles for a described TPU v5e, asked of the chip's own compiler.

Interpret-mode tests prove a kernel's semantics and skip Mosaic; an
export to MLIR stops before it too.  These cases run the whole TPU
compiler (XLA + Mosaic) against a ``v5e:2x2`` topology that is described,
not attached, at the widths the repo benchmarks: ogbn-products (D=100,
B=1024, fanout [15,10,5], 124M-entry ``indices``) and Reddit (D=602,
[25,10]).  Nothing runs, so they say nothing about results or times —
only that the chip's compiler accepts the program and, where a kernel was
asked for, that the kernel is in it.

Keep every such case in THIS file: the worker that describes the topology
holds libtpu until it exits, so a second file on another worker would skip.
The topology is described inside a fixture, never at import.
"""

import functools
import re

import jax
import jax.numpy as jnp

import pytest
from jax.sharding import SingleDeviceSharding

from quiver_tpu.config import (resolve_dedup, resolve_gather_mode,
                               resolve_sample_rng)

PRODUCTS_NODES, PRODUCTS_EDGES = 2_449_029, 123_718_280
PRODUCTS_DIM, PRODUCTS_CLASSES = 100, 47
FANOUT, BATCH = (15, 10, 5), 1024
REDDIT_NODES, REDDIT_EDGES = 232_965, 114_615_892
REDDIT_DIM, REDDIT_CLASSES, REDDIT_FANOUT = 602, 41, (25, 10)

# frontier a hop samples FROM and its fanout, dedup="none" (the frontier
# grows by (1 + k) per hop): B=1024 [15,10,5], whose last hop is the
# SAGE cell's dear shape, and [25,15]'s last hop, the typed cell's
HOPS = [(1024, 15), (16_384, 10), (180_224, 5), (26_624, 15)]
WIDTHS = [100, 128, 602]


def _pad128(n):
    return -(-n // 128) * 128


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
    """A single-device sharding on the described chip, with the
    persistent compile cache off while this file runs: a described
    compile is written to the cache but cannot be read back without a
    chip, so the next run would warn and compile again anyway."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    """Shapes only (a described device holds no array), each on the chip."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _s(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(f, *args, **kw):
    return jax.jit(f, **kw).lower(*args).compile()


def _graph(sharding, nodes, edges):
    # CSRTopo.to_device pads both tables to a multiple of 128
    return (_s(sharding, (_pad128(nodes + 1),)),
            _s(sharding, (_pad128(edges),)))


def _key(sharding):
    return _on(sharding, jax.eval_shape(lambda: jax.random.key(0)))


# --------------------------------------------------------------- sampling
# what the library resolves to on a TPU, asked of the resolvers by the
# backend's name: code that resolves in this process with no name given
# resolves for the CPU it runs on
TPU_GATHER_MODE = resolve_gather_mode("auto", backend="tpu")
TPU_SAMPLE_RNG = resolve_sample_rng("auto", backend="tpu")


@pytest.mark.parametrize("B,k", HOPS)
def test_default_tpu_hop_compiles(one_chip, B, k):
    """What ``config.resolve_*`` pick on a TPU: the window fetch (two
    rows of ``indices`` per target, the per-draw path in the other branch
    of a ``conditional``) + hash RNG."""
    from quiver_tpu.ops.sample import sample_neighbors

    indptr, indices = _graph(one_chip, PRODUCTS_NODES, PRODUCTS_EDGES)
    c = _compile(
        lambda ip, ix, s, kk, m: sample_neighbors(
            ip, ix, s, k, kk, seed_mask=m, gather_mode=TPU_GATHER_MODE,
            sample_rng=TPU_SAMPLE_RNG),
        indptr, indices, _s(one_chip, (B,)), _key(one_chip),
        _s(one_chip, (B,), jnp.bool_))
    text = c.as_text()
    assert "tpu_custom_call" not in text   # pure XLA by design
    assert " conditional(" in text         # k > U: the hop is routed
    # the window branch asks for a row a target, twice; the other for k
    assert f"s32[{B},128]" in text and f"s32[{B * k},128]" in text


def _padded(one_chip, n, dtype=jnp.int32):
    return _s(one_chip, (_pad128(n),), dtype)


def test_overlay_hop_compiles(one_chip):
    """The streaming hop (base CSR + tombstones + delta CSR, time window
    on) on the accelerator path, at the SAGE cell's second hop: every
    read goes per element (row gather + lane select), pure XLA."""
    from quiver_tpu.ops.sample import sample_neighbors_overlay

    B, k, delta = 16_384, 10, 65_536
    indptr, indices = _graph(one_chip, PRODUCTS_NODES, PRODUCTS_EDGES)
    edges = _padded(one_chip, PRODUCTS_EDGES)
    scalar = _s(one_chip, ())
    c = _compile(
        lambda ip, ix, tomb, dip, dix, s, kk, m, bts, dts, lo, hi:
        sample_neighbors_overlay(
            ip, ix, tomb, dip, dix, s, k, kk, seed_mask=m, base_ts=bts,
            d_ts=dts, window_lo=lo, window_hi=hi, windowed=True,
            gather_mode=TPU_GATHER_MODE, sample_rng=TPU_SAMPLE_RNG),
        indptr, indices, edges, indptr, _padded(one_chip, delta),
        _s(one_chip, (B,)), _key(one_chip), _s(one_chip, (B,), jnp.bool_),
        edges, _padded(one_chip, delta), scalar, scalar)
    text = c.as_text()
    assert "tpu_custom_call" not in text
    assert f"s32[{B * k},128]" in text     # a 512-B row per draw


def test_weighted_hop_compiles(one_chip):
    """The weight-proportional hop on the accelerator path, same shape:
    the CDF inversion is one pass over each target's two-row block of
    ``cum_weights`` and the draws come out of its block of ``indices``,
    the per-draw search in the other branch of each ``conditional``."""
    from quiver_tpu.ops.sample import sample_neighbors_weighted

    B, k = 16_384, 10
    indptr, indices = _graph(one_chip, PRODUCTS_NODES, PRODUCTS_EDGES)
    c = _compile(
        lambda ip, ix, cw, s, kk, m: sample_neighbors_weighted(
            ip, ix, cw, s, k, kk, seed_mask=m,
            gather_mode=TPU_GATHER_MODE, sample_rng=TPU_SAMPLE_RNG),
        indptr, indices, _padded(one_chip, PRODUCTS_EDGES, jnp.float32),
        _s(one_chip, (B,)), _key(one_chip), _s(one_chip, (B,), jnp.bool_))
    text = c.as_text()
    assert "tpu_custom_call" not in text
    assert text.count(" conditional(") >= 2
    assert f"f32[{B},128]" in text and f"s32[{B},128]" in text


# ---------------------------------------------------------------- features
# The two feature-row kernels compile only inside a narrow envelope.  The
# library refuses the rest by name on a TPU (``KernelConstraintError``,
# ops/pallas/__init__.py); the strict xfails below switch that check off
# and keep the chip compiler's own words on record — when a repair makes
# one of them compile, its XPASS fails the suite and the check can go
# (PR 33 did so for the row gather's 1.08M-row frontier: its ids come per
# grid program as SMEM blocks where they were prefetched whole).
_LANES = ("Mosaic failed to compile TPU kernel: Slice shape along "
          "dimension N must be aligned to tiling (128), but is 100 / 602")
_SUBLANE = ("Mosaic failed to compile TPU kernel: Slice shape along "
            "dimension 0 must be aligned to tiling (8), but is 1")
_HALF_ROWS = ("Mosaic failed to compile TPU kernel: Slice shape along "
              "dimension 0 must be aligned to tiling (8), but is 1: a "
              "one-row DMA out of bf16[N,128] (through ref.bitcast(int32) "
              "in the kernel: ... tiling (4))")
_SMEM = ("RESOURCE_EXHAUSTED: Allocation (size=4325376) would exceed "
         "memory (size=1048576) ... space=smem ... prefetched SMEM "
         "operand 0")


@pytest.fixture
def unchecked(monkeypatch):
    """Let a refused shape through to the compiler."""
    from quiver_tpu.ops.pallas import gather_kernel, page_gather_kernel

    monkeypatch.setattr(gather_kernel, "check_word_rows", lambda *a: None)
    monkeypatch.setattr(page_gather_kernel, "check_lane_width",
                        lambda *a: None)
    monkeypatch.setattr(page_gather_kernel, "check_scalar_prefetch",
                        lambda *a: None)


def _row_gather(one_chip, d, m, dtype=jnp.float32):
    """The masked row gather: a row fetched for a live slot only."""
    from quiver_tpu.ops.pallas.gather_kernel import gather_rows

    return _compile(gather_rows,
                    _s(one_chip, (PRODUCTS_NODES, d), dtype),
                    _s(one_chip, (m,)), _s(one_chip, (m,), jnp.bool_))


def _row_gather_16_bit(one_chip, d, m):
    return _row_gather(one_chip, d, m, jnp.bfloat16)


def _row_gather_words(one_chip, d, m):
    return _row_gather(one_chip, d, m, jnp.int32)


def _page_gather(one_chip, d, m):
    from quiver_tpu.ops.paged import _plan_geometry, default_page_rows
    from quiver_tpu.ops.pallas.page_gather_kernel import page_gather

    rows = default_page_rows(d * 4)
    block, ppb = _plan_geometry(rows, d, 4)
    m = -(-m // block) * block
    nb = m // block
    return _compile(
        functools.partial(page_gather, page_rows=rows, block=block,
                          ppb=ppb),
        _s(one_chip, (PRODUCTS_NODES // rows + 1, rows, d), jnp.float32),
        _s(one_chip, (nb * ppb,)), _s(one_chip, (nb,)),
        _s(one_chip, (m,)), _s(one_chip, (m,)))


@pytest.mark.parametrize("kernel,m", [
    (_row_gather, 65_536), (_page_gather, 65_536),
    # the SAGE cell's frontier and twice it: refused (``_SMEM``) while
    # the row gather's ids were prefetched whole, through PR 32
    (_row_gather, 1_081_344), (_row_gather, 2_162_688)])
def test_feature_row_kernels_compile_at_128_lanes(one_chip, kernel, m):
    """D=128 with an index plan that fits SMEM: the envelope.  The row
    gather's plan is a block of ids per grid program, whatever ``m``."""
    assert "tpu_custom_call" in kernel(one_chip, 128, m).as_text()


@pytest.mark.parametrize("kernel,d,m", [
    pytest.param(_row_gather, 100, 65_536,
                 marks=pytest.mark.xfail(strict=True, reason=_LANES)),
    pytest.param(_row_gather, 602, 65_536,
                 marks=pytest.mark.xfail(strict=True, reason=_SUBLANE)),
    pytest.param(_row_gather_16_bit, 128, 65_536,
                 marks=pytest.mark.xfail(strict=True, reason=_HALF_ROWS)),
    pytest.param(_row_gather_words, 768, 65_536,
                 marks=pytest.mark.xfail(strict=True, reason=_SUBLANE)),
    pytest.param(_page_gather, 100, 65_536,
                 marks=pytest.mark.xfail(strict=True, reason=_LANES)),
    pytest.param(_page_gather, 602, 65_536,
                 marks=pytest.mark.xfail(strict=True, reason=_LANES)),
    pytest.param(_page_gather, 128, 1_081_344,
                 marks=pytest.mark.xfail(strict=True, reason=_SMEM)),
])
def test_feature_row_kernels_refused_by_mosaic(one_chip, unchecked, kernel,
                                               d, m):
    """Products (D=100) and Reddit (D=602) widths; a one-row DMA out of
    a 16-bit table or out of a row wider than 128 words (the typed cell's
    768); the page gather's products-sized frontier (1.08M rows) at any
    width."""
    kernel(one_chip, d, m)


@pytest.mark.parametrize("kernel,d,m", [
    (_row_gather, 100, 32_768), (_row_gather, 602, 32_768),
    (_row_gather_16_bit, 128, 32_768), (_row_gather_words, 768, 32_768),
    (_page_gather, 100, 32_768),
    (_page_gather, 602, 32_768), (_page_gather, 128, 2_162_688)])
def test_feature_row_kernels_refuse_by_name(one_chip, kernel, d, m):
    # sizes differ from the xfails above: those traced the same jitted
    # wrappers with the check off, and jit caches a trace by shape
    from quiver_tpu.ops.pallas import KernelConstraintError

    with pytest.raises(KernelConstraintError):
        kernel(one_chip, d, m)


@pytest.mark.parametrize("nodes,d,m", [
    (PRODUCTS_NODES, PRODUCTS_DIM, 1_081_344),
    (REDDIT_NODES, REDDIT_DIM, 128 * 26 * 11)])
def test_feature_hot_gather_compiles(one_chip, nodes, d, m):
    """``Feature.lookup_device`` on a fully HBM-resident table."""
    from quiver_tpu.feature import _lookup_tables

    _compile(_lookup_tables,
             (_s(one_chip, (nodes, d), jnp.float32), _s(one_chip, (nodes,))),
             _s(one_chip, (m,)))


SAGE_CELL_NODES, SAGE_CELL_FRONTIER = 27_764_989, 1_081_344


@pytest.mark.parametrize("stored", ["words", "plain"])
def test_masked_lookup_compiles_at_the_sage_cell(one_chip, stored):
    """The SAGE cell's frontier out of its table, widened as its model
    does.  ``plain``: what a fused program runs, ``_lookup_tables`` handed
    the sampler's mask: XLA's gather with no out-of-range pass.
    ``words``: the table as ``int32[13,882,495,128]`` word rows through
    the masked DMA kernel and the half-pick (no caller in the library:
    the chip turned it down, PERF.md PR 33)."""
    from quiver_tpu.feature import _lookup_tables
    from quiver_tpu.ops.pallas.gather_kernel import (gather_rows,
                                                     pick_word_rows)

    n, m = SAGE_CELL_NODES, SAGE_CELL_FRONTIER
    if stored == "words":
        table = _s(one_chip, ((n + 1) // 2, 128))

        def lookup(t, i, mk):
            return pick_word_rows(gather_rows(t, i >> 1, mk), i,
                                  jnp.bfloat16)
    else:
        table = _s(one_chip, (n, 128), jnp.bfloat16)

        def lookup(t, i, mk):
            return _lookup_tables((t, None), i, mk)

    text = _compile(lambda t, i, mk: lookup(t, i, mk).astype(jnp.float32),
                    table, _s(one_chip, (m,)),
                    _s(one_chip, (m,), jnp.bool_)).as_text()
    entry = text[text.index("\nENTRY "):]
    if stored == "words":
        assert "tpu_custom_call" in entry
        assert f"s32[{m // 2048},1,2048]" in entry  # a block of ids each
        assert f"bf16[{m},128]" not in entry        # the pick is fused
    else:
        assert "tpu_custom_call" not in text
        assert "broadcast_select" not in entry      # no out-of-range pass


# ------------------------------------------------------------ whole steps
def _sage(hidden, classes, layers):
    from quiver_tpu.models import GraphSAGE

    model = GraphSAGE(hidden=hidden, out_dim=classes, num_layers=layers)

    def apply_fn(p, x, blocks, train=False, rngs=None):
        return model.apply(p, x, blocks, train=train, rngs=rngs)

    return model, apply_fn


def _sampled_shapes(nodes, edges, dim, B, sizes):
    """Abstract ``(x, blocks)`` of one sampled batch, dedup='none'."""
    from quiver_tpu.sampler import run_pipeline

    indptr = jax.ShapeDtypeStruct((_pad128(nodes + 1),), jnp.int32)
    indices = jax.ShapeDtypeStruct((_pad128(edges),), jnp.int32)
    n_id, _, _, blocks, _, _ = jax.eval_shape(
        lambda ip, ix, s, k: run_pipeline(
            "none", ip, ix, s, k, tuple(sizes), (None,) * len(sizes),
            gather_mode=TPU_GATHER_MODE, sample_rng=TPU_SAMPLE_RNG),
        indptr, indices, jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.random.key(0))
    return jax.ShapeDtypeStruct((n_id.shape[0], dim), jnp.float32), blocks


def _train_state(model, x, blocks):
    import optax

    from quiver_tpu.parallel import TrainState

    tx = optax.adam(3e-3)
    params = jax.eval_shape(model.init, jax.random.key(1), x, blocks)
    return tx, jax.eval_shape(lambda p: TrainState.create(p, tx), params)


def test_sage_train_step_compiles(one_chip):
    """``parallel.make_train_step``: 3-layer hidden-256 SAGE forward +
    backward + adam on one sampled products batch."""
    from quiver_tpu.parallel import make_train_step

    model, apply_fn = _sage(256, PRODUCTS_CLASSES, 3)
    x, blocks = _sampled_shapes(PRODUCTS_NODES, PRODUCTS_EDGES,
                                PRODUCTS_DIM, BATCH, FANOUT)
    tx, state = _train_state(model, x, blocks)
    step = make_train_step(apply_fn, tx)
    args = _on(one_chip, (state, x, blocks,
                          jax.ShapeDtypeStruct((BATCH,), jnp.int32),
                          jax.ShapeDtypeStruct((BATCH,), jnp.bool_)))
    c = step.lower(*args, _key(one_chip)).compile()
    assert c.memory_analysis().temp_size_in_bytes < 12 << 30


def _tpu_sampler(sizes):
    """What a fused program reads of a ``GraphSageSampler`` built on a
    TPU (one built here would resolve for the CPU it runs on)."""
    import types

    return types.SimpleNamespace(
        sizes=sizes, gather_mode=TPU_GATHER_MODE,
        sample_rng=TPU_SAMPLE_RNG, dedup=resolve_dedup("auto"),
        frontier_caps=(None,) * len(sizes))


def _small_fused_sage_step(one_chip):
    """The fused SAGE step at a small graph, batch and fanout (seconds to
    compile), lowered for the described chip."""
    import types

    from quiver_tpu.pipeline import _fused_train_impl

    nodes, edges, dim, B, sizes = 20_000, 200_000, 128, 64, (5, 4, 3)
    model, apply_fn = _sage(64, 16, 3)
    x, blocks = _sampled_shapes(nodes, edges, dim, B, sizes)
    tx, state = _train_state(model, x, blocks)
    feature = types.SimpleNamespace(cache_count=nodes, node_count=nodes)
    impl = _fused_train_impl(
        _tpu_sampler(sizes), feature,
        lambda p, x, blocks, **kw: apply_fn(p, x.astype(jnp.float32),
                                            blocks, **kw), None)
    tables = (*_graph(one_chip, nodes, edges),
              (_s(one_chip, (nodes, dim), jnp.bfloat16), None))
    return jax.jit(impl, donate_argnums=(1,)).lower(
        tables, _on(one_chip, state), _s(one_chip, (B,)),
        _s(one_chip, (B,)), _s(one_chip, (B,), jnp.bool_),
        _key(one_chip))


# sha256 of ``_small_fused_sage_step(...).as_text()``.  It stood at
# f33332031cfac31e037a8c56a00d8e0102b244201ab4318ff8b5c5924ea6342a from
# commit e4950b4 (PR 28), before ``TrainState`` had a slot for model state
# and the fused step a frontier to hand over, through d90ff1a (PR 30), and
# at 13b12aa3dff04b751e6615a9002ac9aff735aec8affd487987d33e7af1ee1f6a from
# PR 31's tree (the hops fetch a two-row window per target) through
# da26c51 (PR 32).  Re-recorded on PR 33's tree, which MEANS to change the
# step: its lookup is handed the sampler's mask, so the row gather
# promises its ids in bounds (``jnp.take``'s out-of-range pass is gone)
# and a dead slot asks for a row of its own, not for row 0
SAGE_STEP_BEFORE_MODEL_STATE = "30f914a0c0e56c4babbc4a22242217f8c29cfcf171d53437f1ac569e1b78643b"


def test_fused_sage_step_lowers_as_before_model_state(one_chip):
    """A model that asks for neither frontier nor state (GraphSAGE) gets
    the program it always got: ``TrainState.model_state`` is a pytree with
    no leaf and ``call_model`` calls such an ``apply_fn`` as ever, so the
    lowered text (arguments, instructions, donation) is to the letter the
    one recorded - the same compile-cache entry, the same numbers for
    ``papers100m-sage.train-fused``.  A PR that MEANS to change the SAGE
    step's program records the new text's hash here, as PR 31 did."""
    import hashlib

    text = _small_fused_sage_step(one_chip).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        SAGE_STEP_BEFORE_MODEL_STATE


def test_fused_train_step_scopes_reach_the_tpu_text(one_chip):
    """The fused step at a small graph, batch and fanout (seconds to
    compile), parsed by the scope table's own parser: the module carries
    its stable name and every ``fusion`` / ``custom-call`` of the entry
    computation sits under a ``qt.`` scope, but for the listed few."""
    import re

    from quiver_tpu.telemetry.device_scopes import (instruction_key,
                                                    parse_hlo_scopes)

    c = _small_fused_sage_step(one_chip).compile()
    text = c.as_text()
    module, table = parse_hlo_scopes(text)
    assert module == "jit_qt_fused_train_step"

    # outside every scope, and why:
    own_key_split = re.compile(       # ``ks, kd = jax.random.split(key)``
        r"^jit\(qt_fused_train_step\)/(jit\(_threefry_split\)/|squeeze$)")
    index_clamp = "gather"   # added by the compiler before a gather; keeps
    #                          only the primitive's name
    unnamed = []             # a dot rewritten into a convolution loses all
    scopes = set()
    entry = text[text.index("\nENTRY "):]
    for line in entry.splitlines():
        if not re.search(r"[})] (fusion|custom-call)\(", line):
            continue
        op = table.get(instruction_key(line))
        if op is None:
            unnamed.append(line[:120])
        elif not (own_key_split.match(op) or op == index_clamp):
            m = re.search(r"qt(\.\w+)+", op)
            assert m, f"outside every qt. scope: {op}\n{line[:200]}"
            scopes.add((m.group(0), "transpose(" in op))
    assert len(unnamed) <= 2, unnamed
    for scope in ("qt.sampler.hop1", "qt.sampler.hop2", "qt.sampler.hop3",
                  "qt.feature.gather", "qt.model", "qt.optimizer"):
        assert (scope, False) in scopes, (scope, sorted(scopes))
    assert ("qt.model", True) in scopes
    # positional blocks: the convs read their sources as a slice, so no
    # instruction of the flax module is a gather or a scatter (a block
    # that lost its marker brings ``GraphSAGE/conv<i>/jit(_take)/gather``
    # and its ``scatter-add`` back); the loss keeps its own label lookup
    convs = {op for op in table.values() if "qt.model" in op
             and "/GraphSAGE/conv" in op}
    assert convs
    assert not {op for op in convs
                if re.search(r"/(gather|scatter(-add)?)$", op)}


MAG_SHARE = (1_902_369, 1_912_236, 401)     # papers, authors, institutions
MAG_EDGES, MAG_DIM, MAG_CLASSES = 54_023_314, 768, 153
MAG_RELATION_OF = ((0, 2, -1), (1, -1, 3), (-1, 4, -1))


def _stateful_fused_step(one_chip, model, nodes, edges, B):
    """A model that carries state and asks for the frontier (``RGNN``,
    ``GNN``: both through ``rgnn_apply_fn``) in the fused step over a
    MAG240M share's tables (768-d float16 rows, fanout [25, 15]), lowered
    for the described chip."""
    import optax

    from quiver_tpu.models import rgnn_apply_fn
    from quiver_tpu.parallel import TrainState
    from quiver_tpu.pipeline import _fused_train_impl
    from quiver_tpu.sampler import run_pipeline
    import types

    sizes = (25, 15)
    indptr, indices = _graph(None, nodes, edges)
    n_id, n_mask, _, blocks, _, _ = jax.eval_shape(
        lambda ip, ix, s, k: run_pipeline(
            "none", ip, ix, s, k, sizes, (None,) * 2,
            gather_mode=TPU_GATHER_MODE, sample_rng=TPU_SAMPLE_RNG),
        indptr, indices, _s(None, (B,)), jax.random.key(0))
    x = _s(None, (n_id.shape[0], MAG_DIM), jnp.float32)
    v = jax.eval_shape(model.init, jax.random.key(1), x, blocks, n_id,
                       n_mask)
    tx = optax.adam(1e-3)
    state = jax.eval_shape(
        lambda p, ms: TrainState.create(p, tx, ms),
        {"params": v["params"]}, {"batch_stats": v["batch_stats"]})
    feature = types.SimpleNamespace(cache_count=nodes, node_count=nodes)
    impl = _fused_train_impl(_tpu_sampler(sizes), feature,
                             rgnn_apply_fn(model), None)
    tables = (*_graph(one_chip, nodes, edges),
              (_s(one_chip, (nodes, MAG_DIM), jnp.float16), None))
    return jax.jit(impl, donate_argnums=(1,)).lower(
        tables, _on(one_chip, state), _s(one_chip, (B,)),
        _s(one_chip, (B,)), _s(one_chip, (B,), jnp.bool_),
        _key(one_chip))


def _typed_fused_step(one_chip):
    """The published R-GAT (``models.RGNN``) through the fused step, at
    the MAG240M share's tables and published widths (768 float16 rows, 2 x
    1024, 4 heads, 5 relations, fanout [25, 15]) with 64 seeds where the
    cell has 1,024 (a quarter of a minute to compile where the cell's
    program takes three and a half), lowered for the described chip."""
    import numpy as np

    from quiver_tpu.models import RGNN

    offsets = tuple(int(v) for v in np.cumsum((0,) + MAG_SHARE))
    model = RGNN(hidden=1024, out_dim=MAG_CLASSES, num_relations=5,
                 type_offsets=offsets, relation_of=MAG_RELATION_OF)
    return _stateful_fused_step(one_chip, model, offsets[-1], MAG_EDGES, 64)


# sha256 of ``_typed_fused_step(...).as_text()``.  It stood at
# cb5ebd4e3bad9fdf3dcc0818faa6a2c8cefb0f19922bbc4beb81b0be33a7a043 from
# ab18fb6 (PR 31) through da26c51 (PR 32).  Re-recorded on PR 33's tree,
# which MEANS to change the typed step's program: its lookup is handed
# the sampler's mask, as the SAGE step's above
TYPED_STEP_RECORDED = "45d1a2cd8c96e8d41c8ca0b3ae21f5486168ad480427d6832adebb030e8f7d74"


def test_fused_typed_step_lowers_as_recorded(one_chip):
    """``mag240m-rgat.train-fused-typed``'s program, to the letter: the
    same compile-cache entry, the same numbers for the cell."""
    import hashlib

    text = _typed_fused_step(one_chip).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == TYPED_STEP_RECORDED


def test_typed_fused_step_groups_its_projections(one_chip):
    """The chip's compiler turns ``lax.ragged_dot`` into grouped kernels
    (``ragged-dot-*`` custom calls) for the forward product and for both
    gradients, so the program's FLOPs are one product per source, not one
    per relation; the scope table gives each kernel back the
    ``qt.model.project`` scope the compiler's renaming took, in the pass
    it ran in."""
    from quiver_tpu.telemetry.device_scopes import parse_hlo_scopes

    c = _typed_fused_step(one_chip).compile()
    _, table = parse_hlo_scopes(c.as_text())
    kernels = {k: op for k, op in table.items()
               if k.startswith("%ragged-dot-none")}
    # conv0: forward, dW (the features are not trained); conv1: forward,
    # dW and dx
    assert len(kernels) == 5, sorted(kernels)
    assert all("qt.model.project" in op for op in kernels.values()), kernels
    assert sum("transpose(" in op for op in kernels.values()) == 3, kernels
    # the float16 table stays float16, row-major, and is gathered as it is
    assert "f16[3815006,768]{1,0" in c.as_text()


GAT_PAPERS, GAT_EDGES = 3_804_740, 81_109_308   # mag240m-gat: a 32nd


def _gat_fused_step(one_chip):
    """The published GAT (``models.GNN``) through the fused step at
    ``mag240m-gat.train-fused-stateful``'s REAL shapes: the citation
    share's tables, 768 float16 rows, 2 x 1024, 4 heads, fanout [25, 15],
    1,024 seeds (a second to lower, under a minute to compile: one dense
    product where the typed step sorts and groups)."""
    from quiver_tpu.models import GNN

    return _stateful_fused_step(one_chip, GNN(hidden=1024,
                                              out_dim=MAG_CLASSES),
                                GAT_PAPERS, GAT_EDGES, BATCH)


# sha256 of ``_gat_fused_step(...).as_text()``, recorded on PR 36's tree,
# which adds the program.  A PR that MEANS to change it (ROADMAP S12: the
# attention's passes) records the new text's hash here
GAT_STEP_RECORDED = "4bd8cabc8b89275f40288e6b307c28486c028264a1643a60c2ee4806fa641b14"


def test_fused_gat_step_lowers_as_recorded(one_chip):
    """``mag240m-gat.train-fused-stateful``'s program, to the letter: the
    same compile-cache entry, the same numbers for the cell."""
    import hashlib

    text = _gat_fused_step(one_chip).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == GAT_STEP_RECORDED


def test_fused_gat_step_compiles_at_the_cells_real_shapes(one_chip):
    """The chip's compiler takes the cell's program at its real shapes:
    it fits beside the tables; the first layer's projection is ONE
    ``[425984,768] x [768,1024]`` product whose ``[26624,16,1024]`` view
    (a target's 15 neighbours and its self-loop, slot by slot) is free:
    nothing copies, concatenates, pads or re-lays the PROJECTION, forward
    or backward (what is laid out by slot is the narrower float16 input);
    and both parts of the convolution carry their scope in both passes."""
    from quiver_tpu.telemetry.device_scopes import parse_hlo_scopes

    c = _gat_fused_step(one_chip).compile()
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes > 6_100_000_000      # the tables
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.generated_code_size_in_bytes) < 15 << 30
    text = c.as_text()
    entry = text[text.index("\nENTRY "):]
    assert "f16[3804740,768]{1,0" in text       # gathered as it is stored
    projection = re.compile(
        r" = (?:f32|bf16)\[(?:425984,1024|26624,16,1024|26624,16,4,256)\]")
    made_by = [(re.search(r"\} ([\w\-]+)\(", line).group(1),
                (re.search(r'op_name="([^"]*)"', line) or [None, ""])[1]
                .rsplit("/", 1)[-1])
               for line in entry.splitlines() if projection.search(line)]
    assert ("fusion", "dot_general") in made_by, made_by
    moved = [m for m in made_by if m[0] in ("copy", "concatenate", "pad",
                                            "transpose", "gather")
             or m[1] in ("concatenate", "pad", "copy", "transpose")]
    assert not moved, moved
    _, table = parse_hlo_scopes(text)
    for scope in ("qt.model.project", "qt.model.attention"):
        for backward in (False, True):
            assert [op for op in table.values() if "GNN/conv0/" + scope
                    in op and ("transpose(" in op) == backward], (
                        scope, backward)


@pytest.mark.parametrize("bucket", [8, 128, 2048])
def test_serving_bucket_forward_compiles(one_chip, bucket):
    """One ``InferenceServer`` bucket at Reddit widths: sample [25,10] +
    602-d gather + 2-layer SAGE forward in one program."""
    from quiver_tpu.feature import _lookup_tables
    from quiver_tpu.sampler import run_pipeline

    model, apply_fn = _sage(128, REDDIT_CLASSES, 2)
    x, blocks = _sampled_shapes(REDDIT_NODES, REDDIT_EDGES, REDDIT_DIM,
                                bucket, REDDIT_FANOUT)
    params = jax.eval_shape(model.init, jax.random.key(1), x, blocks)

    def forward(tables, params, seeds, key):
        indptr, indices, feat_tables = tables
        n_id, _, _, blocks, _, _ = run_pipeline(
            "none", indptr, indices, seeds, key, REDDIT_FANOUT,
            (None, None), gather_mode=TPU_GATHER_MODE,
            sample_rng=TPU_SAMPLE_RNG)
        return apply_fn(params, _lookup_tables(feat_tables, n_id), blocks)

    tables = (*_graph(one_chip, REDDIT_NODES, REDDIT_EDGES),
              (_s(one_chip, (REDDIT_NODES, REDDIT_DIM), jnp.float32), None))
    c = _compile(forward, tables, _on(one_chip, params),
                 _s(one_chip, (bucket,)), _key(one_chip))
    # graph + features (1 GB) are arguments, not constants in the program
    m = c.memory_analysis()
    assert m.argument_size_in_bytes > 1_000_000_000
    assert m.generated_code_size_in_bytes < 64 << 20


# ------------------------------------------ the whole papers100M host (PR 34)
# The three programs of the cell ``papers100m-sage-host.train-dist`` at its
# real shapes over the described 2x2: a quarter of 111,059,956 rows of 128
# bfloat16 and of 1,615,685,872 int32 edges a chip (edge-balanced ranges
# are uneven and differ from seed to seed: the cell's program states one
# shard length for all of them, ``programs/sage_dist.one_shape``),
# 1,024 seeds a rank, [15,10,5], exact caps.
HOST_NODES, HOST_EDGES, HOST_DIM, HOST_CLASSES = (111_059_956, 1_615_685_872,
                                                  128, 172)
HOST_RANKS = 4
HOST_ROWS = 29_360_128     # one_shape of the 27.77 M rows a chip read there
HOST_SHARD_EDGES = 436_207_616      # one_shape of a quarter of the edges
HOST_FRONTIER = BATCH * 16 * 11 * 6             # 1,081,344 slots a rank
V5E_HBM = int(15.75 * 2 ** 30)                  # what a v5e chip reports


@pytest.fixture(scope="module")
def host_programs(topo, one_chip):
    """``{name: compiled}`` of ``jit_qt_dist_sample``, ``jit_qt_dist_lookup``
    and ``jit_qt_dp_train_step``, abstract arguments sharded over a mesh of
    the four described chips; the step is compiled over the sample
    program's own output tree of ``blocks``, its ``layout`` marker with
    it."""
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from quiver_tpu.dist.feature import lookup_program
    from quiver_tpu.dist.sampler import sample_program
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.parallel import TrainState, make_train_step

    mesh = Mesh(np.array(topo.devices), ("data",))
    n = HOST_RANKS
    assert mesh.size == n

    def S(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    out = {}
    sample = sample_program(mesh, "data", FANOUT, 1.0, TPU_GATHER_MODE,
                            TPU_SAMPLE_RNG)
    sample_args = (
        S((n, _pad128(HOST_ROWS + 1)), jnp.int32, "data", None),
        S((n, HOST_SHARD_EDGES), jnp.int32, "data", None),
        S((n + 1,), jnp.int32), S((n, BATCH), jnp.int32, "data", None),
        S((n, BATCH), jnp.bool_, "data", None), S((), jnp.int32))
    out["jit_qt_dist_sample"] = sample.lower(*sample_args).compile()
    out["jit_qt_dist_lookup"] = lookup_program(
        mesh, "data", None, True).lower(
        S((n, HOST_ROWS, HOST_DIM), jnp.bfloat16, "data", None, None),
        {"row_starts": S((n + 1,), jnp.int32)},
        S((n, HOST_FRONTIER), jnp.int32, "data", None),
        S((n, HOST_FRONTIER), jnp.bool_, "data", None)).compile()

    model = GraphSAGE(hidden=256, out_dim=HOST_CLASSES, num_layers=3,
                      dropout=0.5)

    def apply_fn(p, x, blocks, train=False, rngs=None):
        return model.apply(p, x.astype(jnp.float32), blocks, train=train,
                           rngs=rngs)

    tm = jax.tree_util.tree_map
    # what the step is handed is what the sampler returns: its tree, its
    # marker, a rank on every leaf's leading axis
    n_id, _, _, blocks = jax.eval_shape(sample, *sample_args)[:4]
    assert n_id.shape == (n, HOST_FRONTIER)
    assert [b.mask.shape for b in blocks] == [
        (n, t, k) for t, k in zip((BATCH * 16 * 11, BATCH * 16, BATCH),
                                  FANOUT[::-1])]      # outermost first
    one = tm(lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), blocks)
    tx = optax.adam(3e-3)
    params = jax.eval_shape(
        model.init, jax.random.key(1),
        jax.ShapeDtypeStruct((HOST_FRONTIER, HOST_DIM), jnp.float32), one)
    state = jax.eval_shape(lambda p: TrainState.create(p, tx), params)
    out["jit_qt_dp_train_step"] = make_train_step(
        apply_fn, tx, mesh=mesh).jitted.lower(
        tm(lambda s: S(s.shape, s.dtype), state),
        S((n, HOST_FRONTIER, HOST_DIM), jnp.bfloat16, "data"),
        tm(lambda s: S(s.shape, s.dtype, "data"), blocks),
        S((n, BATCH), jnp.int32, "data"), S((n, BATCH), jnp.bool_, "data"),
        S((2,), jnp.uint32), None).compile()
    return out


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("name", ["jit_qt_dist_sample", "jit_qt_dist_lookup",
                                  "jit_qt_dp_train_step"])
def test_host_cell_program_fits_a_chip_at_the_real_shapes(host_programs,
                                                          name):
    """By the compiler's own estimate, a device's share of each program
    (its tables among the arguments) is under the chip's memory, its
    tables are arguments (no constant of a table's size in the code), and
    the sharded two exchange through ``all-to-all``."""
    c = host_programs[name]
    m = c.memory_analysis()
    assert _device_bytes(c) < V5E_HBM, m
    assert m.generated_code_size_in_bytes < 128 << 20
    text = c.as_text()
    if name == "jit_qt_dp_train_step":
        assert "all-reduce" in text and "all-to-all" not in text
    else:
        # the exact exchange's rounds: both ``all-to-all``s of a hop (of
        # the lookup) inside a ``while`` whose trip count the counts give
        assert re.search(r'all-to-all\(.*op_name="[^"]*/while/body/', text)
        assert m.argument_size_in_bytes > 1_500_000_000
        # buckets of an owner's share: 0.79 GB (sample) and 0.58 GB
        # (lookup) of temporaries where whole-frontier buckets took 3.48
        # and 2.22 (PERF.md, PR 34 and 35)
        assert m.temp_size_in_bytes < 1 << 30, m


def test_host_cell_programs_fit_beside_each_other(host_programs):
    """The step's three programs launched back to back, two steps in
    flight: both tables once, and every program's outputs and temporaries
    at once (the runtime may hold them all), under the chip's memory."""
    m = {k: c.memory_analysis() for k, c in host_programs.items()}
    tables = (m["jit_qt_dist_sample"].argument_size_in_bytes
              + m["jit_qt_dist_lookup"].argument_size_in_bytes)
    rest = sum(x.temp_size_in_bytes + x.output_size_in_bytes
               for x in m.values())
    assert tables + rest < V5E_HBM, (tables, rest)


def test_host_cell_step_convs_slice_the_samplers_blocks(host_programs):
    """``jit_qt_dp_train_step`` over the blocks ``jit_qt_dist_sample``
    returns, at the cell's real shapes: they say that they are positional,
    so no instruction of a conv is a gather or a scatter.  With the marker
    stripped (the program before PR 37) ``conv<i>/jit(_take)/gather`` of
    every conv and ``scatter-add`` of conv1 and conv2 are there (12.2 and
    3.3 ms a step on the chip, PERF.md section 5), the compiler takes 118 s
    over the step where it now takes 6, and a chip's temporaries are
    1,204,893,184 B where they now are 803,188,224 (AOT here, PR 37)."""
    from quiver_tpu.telemetry.device_scopes import parse_hlo_scopes

    c = host_programs["jit_qt_dp_train_step"]
    module, table = parse_hlo_scopes(c.as_text())
    assert module == "jit_qt_dp_train_step"
    convs = {op for op in table.values()
             if "qt.model" in op and "GraphSAGE)/conv" in op}
    assert any("/conv0/lin_nbr/dot_general" in op for op in convs)
    assert not {op for op in convs
                if re.search(r"/(gather|scatter(-add)?)$", op)}
    assert c.memory_analysis().temp_size_in_bytes < 900_000_000
