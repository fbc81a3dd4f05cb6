"""The fused programs' row gather and the sampler's mask, on the CPU: the
hand-over in ``pipeline.py`` and what ``feature._lookup_tables`` makes of
it; the masked Pallas kernel under the interpreter and its word-row
storage (``ops/pallas/gather_kernel.py``: timed on the chip and turned
down, PERF.md PR 33).  What the chip's compiler makes of either is
``tests/test_aot_compile.py``'s."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# appended, not put first: ``cellbench/tests`` must not shadow ``tests``
# for the files collected after this one
if os.path.join(ROOT, "cellbench") not in sys.path:
    sys.path.append(os.path.join(ROOT, "cellbench"))

from quiver_tpu import (CSRTopo, Feature, GraphSageSampler, feature as fmod,  # noqa: E402
                        make_key, pipeline, telemetry)
from quiver_tpu.feature import _lookup_tables  # noqa: E402
from quiver_tpu.models import RGNN, GraphSAGE, rgnn_apply_fn  # noqa: E402
from quiver_tpu.ops.pallas.gather_kernel import (gather_rows,  # noqa: E402
                                                 pack_word_rows,
                                                 pick_word_rows)
from quiver_tpu.parallel import TrainState  # noqa: E402

rgat = importlib.import_module("references.rgat")
tm = jax.tree_util.tree_map


def raw(x):
    """An array's bits, whatever float type it holds."""
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def rows16(n, dtype, seed=0):
    """``[n, 128]`` finite 16-bit floats, every bit pattern likely."""
    bits = np.random.default_rng(seed).integers(0, 0x7C00, (n, 128),
                                                dtype=np.uint16)
    return bits.view(dtype)


# ------------------------------------------------------------- the kernel
def _mask(kind, m, rng):
    return {"random": rng.random(m) < 0.4,
            "prefix": np.arange(m) < m // 3,
            "all_dead": np.zeros(m, bool),
            "all_live": np.ones(m, bool)}[kind]


@pytest.mark.parametrize("kind,n,m,ids", [
    ("random", 1001, 512, "any"), ("random", 1000, 700, "any"),
    ("prefix", 1001, 300, "any"), ("all_dead", 1001, 256, "any"),
    ("all_live", 1001, 256, "any"), ("random", 1001, 256, "odd"),
    ("random", 1001, 256, "even"), ("random", 7, 1, "any"),
    ("all_live", 1001, 129, "last")])
def test_masked_kernel_equals_where_of_take(kind, n, m, ids):
    """Bit for bit, dead rows ZERO (never unwritten): random, prefix,
    all-dead and all-live masks, odd and even ids, an odd table, ``m``
    that is no multiple of the block."""
    rng = np.random.default_rng(m)
    table = jnp.asarray(rng.integers(-2**31, 2**31, (n, 128),
                                     dtype=np.int64).astype(np.int32))
    idx = rng.integers(0, n, m)
    if ids == "odd":
        idx = np.minimum(idx | 1, n - 2)        # n is odd in that case
    elif ids == "even":
        idx = idx & ~1
    if ids == "last":
        idx[:] = n - 1
    idx = jnp.asarray(idx, jnp.int32)
    mask = jnp.asarray(_mask(kind, m, rng))
    got = gather_rows(table, idx, mask, block=128, window=8, unroll=4,
                      interpret=True)
    want = jnp.where(mask[:, None], table[idx], 0)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_kernel_without_a_mask_is_a_plain_row_gather(rng):
    table = jnp.asarray(rng.normal(size=(300, 128)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 300, 200), jnp.int32)
    got = gather_rows(table, idx, block=128, interpret=True)
    assert np.array_equal(raw(got), raw(table[idx]))



# ---------------------------------------------------------- the word rows
@pytest.mark.parametrize("dtype", [ml_dtypes.bfloat16, np.float16])
@pytest.mark.parametrize("n", [1001, 1000])
def test_pack_then_gather_round_trips_every_row(dtype, n):
    """Packed in chunks on the device, then every row read back through
    the kernel and the half-pick, in both orders."""
    host = rows16(n, dtype)
    words = pack_word_rows(host, chunk_rows=256)
    assert words.shape == (-(-n // 2), 128) and words.dtype == jnp.int32
    for ids in (jnp.arange(n, dtype=jnp.int32),
                jnp.arange(n - 1, -1, -1, dtype=jnp.int32)):
        got = pick_word_rows(
            gather_rows(words, ids >> 1, block=256, window=8, unroll=4,
                        interpret=True), ids, host.dtype)
        assert got.dtype == np.dtype(dtype)
        assert np.array_equal(raw(got), raw(host[np.asarray(ids)]))


def test_a_dead_word_row_picks_to_a_zero_row():
    host = rows16(64, ml_dtypes.bfloat16)
    ids = jnp.asarray([3, 8, 63, 0], jnp.int32)
    mask = jnp.asarray([True, False, True, False])
    got = pick_word_rows(
        gather_rows(pack_word_rows(host), ids >> 1, mask, block=128,
                    interpret=True), ids, host.dtype)
    want = np.where(np.asarray(mask)[:, None], raw(host[np.asarray(ids)]), 0)
    assert np.array_equal(raw(got), want)


# -------------------------------------------------- the lookup and its mask
def test_no_mask_keeps_takes_out_of_range_fill():
    """A caller's own ids: a negative one counts from the end, one out of
    range reads NaN, as ``jnp.take`` has it."""
    plain = jnp.asarray(rows16(301, ml_dtypes.bfloat16))
    ids = jnp.asarray([0, 300, 301, 1000, -1, -301, -302, 2**31 - 1],
                      jnp.int32)
    got = np.asarray(_lookup_tables((plain, None), ids)).astype(np.float32)
    want = np.asarray(jnp.take(plain, ids, axis=0)).astype(np.float32)
    assert np.isnan(want[2]).all() and not np.isnan(want[4]).any()
    assert np.array_equal(got, want, equal_nan=True)


@pytest.mark.parametrize("n,m", [(300, 64), (40, 64)])
def test_masked_lookup_reads_live_rows_exactly_and_dead_ones_anywhere(n, m):
    """A live slot reads the table's row, bit for bit.  A dead one reads
    SOME row of the table (a row of its own, 16 on from its neighbour's,
    wrapped at the table's end), never row 0 again and again."""
    host = rows16(n, np.float16)
    rng = np.random.default_rng(n)
    mask = rng.random(m) < 0.4
    ids = jnp.asarray(np.where(mask, rng.integers(0, n, m), 0), jnp.int32)
    got = raw(jax.jit(_lookup_tables)((jnp.asarray(host), None), ids,
                                      jnp.asarray(mask)))
    assert np.array_equal(got[mask], raw(host[np.asarray(ids)])[mask])
    dead = np.flatnonzero(~mask)
    assert np.array_equal(got[dead], raw(host[dead * 16 % n]))


def test_masked_lookup_goes_through_the_cache_order():
    host = rows16(50, ml_dtypes.bfloat16)
    order = np.random.default_rng(0).permutation(50).astype(np.int32)
    ids = jnp.asarray([7, 0, 49, 0], jnp.int32)
    mask = jnp.asarray([True, False, True, True])
    got = raw(_lookup_tables((jnp.asarray(host), jnp.asarray(order)), ids,
                             mask))
    want = raw(host[order[np.asarray(ids)]])
    assert np.array_equal(got[[0, 2, 3]], want[[0, 2, 3]])


# ------------------------------------------------------------ the counters
def test_an_eager_masked_lookup_counts_its_slots_and_the_live_ones():
    telemetry.reset()
    host = rows16(300, ml_dtypes.bfloat16)
    f = Feature(device_cache_size=300, cache_unit="rows",
                dtype=jnp.bfloat16).from_cpu_tensor(host)
    ids = jnp.asarray([1, 0, 2, 0, 0], jnp.int32)
    mask = jnp.asarray([True, False, True, False, False])
    got = f.lookup_device(ids, mask)
    assert np.array_equal(raw(got)[[0, 2]], raw(host[[1, 2]]))
    jax.jit(f.lookup_device)(ids, mask)     # traced: nothing to read
    f.lookup_device(ids)                    # a caller's own ids
    c = telemetry.snapshot()["counters"]
    assert c["feature_gather_slots_total"] == 5
    assert c["feature_gather_live_slots_total"] == 2


# ------------------------------------------- the hand-over in pipeline.py
def _sage_step():
    rng = np.random.default_rng(3)
    n = 400
    deg = rng.integers(0, 9, n)                 # degree 0: dead targets
    indptr = np.concatenate([[0], np.cumsum(deg)])
    topo = CSRTopo(indptr=indptr,
                   indices=rng.integers(0, n, indptr[-1]).astype(np.int32))
    host = rows16(n, ml_dtypes.bfloat16, seed=4)
    feature = Feature(device_cache_size=n, cache_unit="rows",
                      dtype=jnp.bfloat16).from_cpu_tensor(host)
    sampler = GraphSageSampler(topo, [4, 3])
    model = GraphSAGE(hidden=16, out_dim=5, num_layers=2, dropout=0.5)
    seeds = jnp.asarray(rng.integers(0, n, 16), jnp.int32)
    bt = sampler.sample(np.asarray(seeds), key=make_key(0))
    assert not np.asarray(bt.n_id_mask).all()
    params = model.init(jax.random.key(0),
                        feature[bt.n_id].astype(jnp.float32), bt.layers)
    tx = optax.adam(1e-2)

    def apply_fn(p, x, blocks, train=False, rngs=None):
        return model.apply(p, x.astype(jnp.float32), blocks, train=train,
                           rngs=rngs)

    step = pipeline.make_fused_train_step(sampler, feature, apply_fn, tx)
    labels = jnp.asarray(rng.integers(0, 5, 16), jnp.int32)
    return step, TrainState.create(params, tx), seeds, labels


def _rgat_step():
    cfg = dict(papers=600, authors=500, institutions=20, edges_cites=3000,
               edges_writes=1200, edges_affiliated_with=300,
               feature_dim=24, classes=7, hidden=16, heads=4,
               num_layers=2, num_relations=5, fanout=[4, 3], batch=16,
               dropout=0.5, lr=1e-3)
    data = rgat.make_data(cfg, 2**31 + 9)
    off = rgat.type_offsets(cfg)
    topo = CSRTopo(indptr=data["indptr"], indices=data["indices"])
    feature = Feature(device_cache_size=off[-1], cache_unit="rows",
                      dtype=jnp.float16).from_cpu_tensor(data["features"])
    sampler = GraphSageSampler(topo, cfg["fanout"])
    model = RGNN(hidden=cfg["hidden"], out_dim=cfg["classes"],
                 num_relations=5, type_offsets=off,
                 relation_of=rgat.RELATION_OF, heads=cfg["heads"],
                 dropout=cfg["dropout"])
    tx = optax.adam(cfg["lr"])
    state = TrainState.create(tm(jnp.asarray, data["params"]), tx,
                              tm(jnp.asarray, data["model_state"]))
    step = pipeline.make_fused_train_step(sampler, feature,
                                          rgnn_apply_fn(model), tx)
    seeds = jnp.asarray((np.arange(16) * 7) % cfg["papers"], jnp.int32)
    return step, state, seeds, jnp.asarray(data["labels"][np.asarray(seeds)])


@pytest.mark.parametrize("build", [_sage_step, _rgat_step])
def test_fused_step_is_the_same_with_and_without_the_mask(build,
                                                          monkeypatch):
    """Loss, the gradient Adam was handed, the parameters after it and
    BatchNorm's running averages, bit for bit: a dead slot's row was row
    0 times a zero mask and is another row of the table times a zero
    mask."""
    seen = []

    def run():
        step, state, seeds, labels = build()
        state, loss = step(state, seeds, labels,
                           jnp.ones(seeds.shape, bool), make_key(5))
        return jax.tree_util.tree_leaves(
            (loss, state.params, state.opt_state, state.model_state))

    def spy(tables, idx, mask=None):
        seen.append(mask is not None)
        return fmod._lookup_tables(tables, idx, mask)

    monkeypatch.setattr(pipeline, "_lookup_tables", spy)
    with_mask = run()
    assert seen == [True]               # the step hands its mask over
    monkeypatch.setattr(pipeline, "_lookup_tables",
                        lambda tables, idx, mask=None: fmod._lookup_tables(
                            tables, idx))
    without = run()
    assert len(with_mask) == len(without) > 4
    for a, b in zip(with_mask, without):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("make", ["eval", "scan"])
def test_eval_and_scan_epoch_hand_the_mask_over_too(make, monkeypatch):
    seen = []

    def spy(tables, idx, mask=None):
        seen.append(mask is not None)
        return fmod._lookup_tables(tables, idx, mask)

    monkeypatch.setattr(pipeline, "_lookup_tables", spy)
    rng = np.random.default_rng(1)
    n = 200
    deg = rng.integers(0, 6, n)
    indptr = np.concatenate([[0], np.cumsum(deg)])
    topo = CSRTopo(indptr=indptr,
                   indices=rng.integers(0, n, indptr[-1]).astype(np.int32))
    feature = Feature(device_cache_size=n, cache_unit="rows").from_cpu_tensor(
        rng.normal(size=(n, 8)).astype(np.float32))
    sampler = GraphSageSampler(topo, [3, 2])
    model = GraphSAGE(hidden=8, out_dim=3, num_layers=2, dropout=0.0)
    seeds = jnp.asarray(rng.integers(0, n, 8), jnp.int32)
    bt = sampler.sample(np.asarray(seeds), key=make_key(0))
    params = model.init(jax.random.key(0), feature[bt.n_id], bt.layers)

    def apply_fn(p, x, blocks, train=False, rngs=None):
        return model.apply(p, x, blocks, train=train, rngs=rngs)

    if make == "eval":
        out = pipeline.make_fused_eval_fn(sampler, feature, apply_fn)(
            params, seeds, make_key(1))
    else:
        tx = optax.adam(1e-2)
        _, out = pipeline.make_scan_epoch(sampler, feature, apply_fn, tx)(
            TrainState.create(params, tx), seeds[None], seeds[None] % 3,
            make_key(1))
    assert seen == [True] and np.isfinite(np.asarray(out)).all()
