"""Distributed feature exchange over an 8-device virtual mesh — the
simulated multi-host coverage the reference lacked (SURVEY.md §4)."""

import numpy as np
import pytest

import jax

from quiver_tpu.dist import DistFeature, PartitionInfo, TpuComm
from quiver_tpu.utils.mesh import make_mesh


NHOSTS = 8


@pytest.fixture(scope="module")
def mesh():
    assert jax.device_count() == NHOSTS
    return make_mesh(("data",))


def test_allreduce(mesh):
    comm = TpuComm(mesh, "data")
    x = np.arange(NHOSTS * 4, dtype=np.float32).reshape(NHOSTS, 4)
    out = np.asarray(comm.allreduce(x))
    np.testing.assert_allclose(out, x.sum(axis=0))


def test_all_to_all(mesh):
    comm = TpuComm(mesh, "data")
    # x[i, j] = payload i sends to j
    x = np.arange(NHOSTS * NHOSTS, dtype=np.int32).reshape(NHOSTS, NHOSTS, 1)
    out = np.asarray(comm.all_to_all(x))
    np.testing.assert_array_equal(out[:, :, 0], x[:, :, 0].T)


def test_partition_info_dispatch():
    n = 100
    g2h = np.arange(n) % 4
    info = PartitionInfo(host=1, hosts=4, global2host=g2h)
    ids = np.array([0, 1, 2, 3, 4, 5, 6, 7])
    out_ids, out_pos = info.dispatch(ids)
    for h in range(4):
        assert (g2h[out_ids[h]] == h).all()
    got = np.concatenate(out_ids)
    assert sorted(got.tolist()) == sorted(ids.tolist())


def test_dist_feature_exchange(mesh, rng):
    n, d = 256, 8
    full = rng.normal(size=(n, d)).astype(np.float32)
    g2h = rng.integers(0, NHOSTS, n).astype(np.int32)
    info = PartitionInfo(host=0, hosts=NHOSTS, global2host=g2h)
    df = DistFeature.from_global_feature(full, mesh, info)
    B = 32
    ids = rng.integers(0, n, (NHOSTS, B)).astype(np.int32)
    out = np.asarray(df.lookup(ids))
    for h in range(NHOSTS):
        np.testing.assert_allclose(out[h], full[ids[h]], rtol=1e-6)


def test_dist_feature_with_replication(mesh, rng):
    n, d = 128, 4
    full = rng.normal(size=(n, d)).astype(np.float32)
    g2h = rng.integers(0, NHOSTS, n).astype(np.int32)
    rep = np.array([0, 5, 17, 99])
    info = PartitionInfo(host=0, hosts=NHOSTS, global2host=g2h,
                         replicate=rep)
    df = DistFeature.from_global_feature(full, mesh, info)
    ids = np.tile(rep[None], (NHOSTS, 8)).astype(np.int32)
    out = np.asarray(df.lookup(ids))
    for h in range(NHOSTS):
        np.testing.assert_allclose(out[h], full[ids[h]], rtol=1e-6)


def test_dist_feature_skewed_load(mesh, rng):
    """All requests target one owner — worst-case bucket pressure."""
    n, d = 64, 4
    full = rng.normal(size=(n, d)).astype(np.float32)
    g2h = np.zeros(n, dtype=np.int32)  # everything owned by host 0
    info = PartitionInfo(host=0, hosts=NHOSTS, global2host=g2h)
    df = DistFeature.from_global_feature(full, mesh, info)
    B = 16
    ids = rng.integers(0, n, (NHOSTS, B)).astype(np.int32)
    out = np.asarray(df.lookup(ids))
    for h in range(NHOSTS):
        np.testing.assert_allclose(out[h], full[ids[h]], rtol=1e-6)


def test_dist_feature_parity_getitem(mesh, rng):
    n, d = 64, 4
    full = rng.normal(size=(n, d)).astype(np.float32)
    g2h = rng.integers(0, NHOSTS, n).astype(np.int32)
    info = PartitionInfo(host=2, hosts=NHOSTS, global2host=g2h)
    df = DistFeature.from_global_feature(full, mesh, info)
    ids = rng.integers(0, n, 16)
    out = np.asarray(df[ids])
    np.testing.assert_allclose(out, full[ids], rtol=1e-6)


def test_partition_to_distfeature_roundtrip(mesh, tmp_path, rng):
    """quiver_partition_feature book -> PartitionInfo -> DistFeature lookup
    equals the original features (tooling + runtime coherence)."""
    from quiver_tpu import quiver_partition_feature

    n, d = 160, 4
    feature = rng.normal(size=(n, d)).astype(np.float32)
    probs = [rng.uniform(0, 1, n) for _ in range(NHOSTS)]
    _, _, book = quiver_partition_feature(feature, probs, str(tmp_path))
    info = PartitionInfo.from_partition_book(book)
    assert info.hosts == NHOSTS
    df = DistFeature.from_global_feature(feature, mesh, info)
    ids = rng.integers(0, n, (NHOSTS, 16)).astype(np.int32)
    out = np.asarray(df.lookup(ids))
    for h in range(NHOSTS):
        np.testing.assert_allclose(out[h], feature[ids[h]], rtol=1e-6)


def test_hybrid_mesh_degenerate():
    from quiver_tpu.dist import make_hybrid_mesh

    mesh = make_hybrid_mesh()
    assert mesh.axis_names == ("dcn", "ici")
    assert int(np.prod(list(mesh.shape.values()))) == NHOSTS


def test_ring_feature_lookup(mesh, rng):
    from quiver_tpu.dist import RingFeature

    n, d = 100, 8  # NOT a multiple of 8 devices -> exercises padding
    full = rng.normal(size=(n, d)).astype(np.float32)
    rf = RingFeature(full, mesh)
    ids = rng.integers(0, n, (NHOSTS, 24)).astype(np.int32)
    out = np.asarray(rf.lookup(ids))
    for h in range(NHOSTS):
        np.testing.assert_allclose(out[h], full[ids[h]], rtol=1e-6)


# ---------------------------------------------------------------------------
# A partition by contiguous row ranges: DistFeature.from_row_ranges
# (PERF.md, PR 34: the whole papers100M host)
import re  # noqa: E402

import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from quiver_tpu import telemetry  # noqa: E402
from quiver_tpu.dist.exchange import TILE, bucket_len, shard_len  # noqa: E402
from quiver_tpu.dist.feature import lookup_program  # noqa: E402

EVEN = [0, 32, 64, 96, 128, 160, 192, 224, 256]
UNEVEN = [0, 1, 1, 40, 41, 130, 200, 255, 256]     # an empty range too


def _boundary_ids(starts, n_rows, B, rng):
    """Every range's first and last row in every rank's batch, the rest
    random; some slots masked out, some ids outside the table."""
    edge = np.unique(np.clip(np.concatenate(
        [np.asarray(starts) - 1, np.asarray(starts)]), 0, n_rows - 1))
    ids = rng.integers(0, n_rows, (NHOSTS, B)).astype(np.int32)
    ids[:, :len(edge)] = edge
    valid = rng.random((NHOSTS, B)) < 0.7
    valid[:, :len(edge)] = True
    return ids, valid


@pytest.mark.parametrize("starts", [EVEN, UNEVEN], ids=["even", "uneven"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_row_range_lookup_is_the_tables_rows_bit_for_bit(mesh, rng, starts,
                                                         dtype):
    n, d, B = 256, 8, 48
    full = rng.normal(size=(n, d)).astype(np.float32)
    df = DistFeature.from_row_ranges(full, mesh, starts,
                                     dtype=jnp.dtype(dtype))
    stored = np.asarray(jnp.asarray(full).astype(dtype))
    ids, valid = _boundary_ids(starts, n, B, rng)
    out = np.asarray(df.lookup(ids, valid))
    want = np.where(valid[..., None], stored[ids], 0)
    assert out.dtype == stored.dtype
    assert np.array_equal(out.view(np.uint8), want.view(np.uint8))
    assert int(df.overflow_stats().sum()) == 0
    # no mask: every slot valid, the device array as it comes
    out = np.asarray(df.lookup(jnp.asarray(ids)))
    assert np.array_equal(out.view(np.uint8), stored[ids].view(np.uint8))


def test_row_range_lookup_reads_nothing_for_ids_outside_the_table(mesh, rng):
    n, d = 256, 4
    full = rng.normal(size=(n, d)).astype(np.float32)
    df = DistFeature.from_row_ranges(full, mesh, UNEVEN)
    ids = np.tile(np.array([0, -1, n, n + 7, 255, -300], np.int32),
                  (NHOSTS, 1))
    out = np.asarray(df.lookup(ids))
    inside = (ids >= 0) & (ids < n)
    assert np.array_equal(out, np.where(inside[..., None],
                                        full[np.clip(ids, 0, n - 1)], 0))
    slots, live = df.exchange_stats()
    assert live == int(inside.sum()) and slots == NHOSTS * NHOSTS * 6


def test_row_range_shards_are_slices_of_the_host_table(mesh, rng):
    full = rng.normal(size=(256, 4)).astype(np.float32)
    df = DistFeature.from_row_ranges(full, mesh, UNEVEN)
    assert df._host_source is None and df.info is None
    shards = np.asarray(df.shards)
    assert shards.shape == (NHOSTS, shard_len(max(np.diff(UNEVEN))), 4)
    for p in range(NHOSTS):
        lo, hi = UNEVEN[p], UNEVEN[p + 1]
        assert np.array_equal(shards[p, :hi - lo], full[lo:hi])
    with pytest.raises(ValueError, match="contiguous ranges"):
        DistFeature.from_row_ranges(full, mesh, [0, 10, 256])


def _literal_lengths(text):
    """Element counts of the literal constants of a lowered program: those
    written out element by element or as a hex blob; a splat is one
    element however wide it is broadcast."""
    out = []
    for m in re.finditer(
            r'constant dense<(\[|"0x)[^>]*> : tensor<([0-9x]*)x?[a-z]',
            text):
        dims = [int(x) for x in m.group(2).split("x") if x]
        out.append(int(np.prod(dims)) if dims else 1)
    return out


@pytest.mark.parametrize("form", ["ranges", "global2host"])
def test_lookup_program_holds_no_node_length_constant(mesh, rng, form):
    """The tables are the program's ARGUMENTS: its lowered text holds no
    literal longer than the range starts, whatever the node count."""
    n, d, B = 50_000, 8, 64
    sh = lambda *spec: NamedSharding(mesh, P(*spec))
    S = jax.ShapeDtypeStruct
    if form == "ranges":
        tables = {"row_starts": S((NHOSTS + 1,), jnp.int32, sharding=sh())}
    else:
        tables = {k: S((NHOSTS,) if k == "owned_counts" else (n,),
                       jnp.bool_ if k == "rep_mask" else jnp.int32,
                       sharding=sh())
                  for k in ("g2l", "g2h", "rep_mask", "rep_rank",
                            "owned_counts")}
    text = lookup_program(mesh, "data", None, form == "ranges").lower(
        S((NHOSTS, n // NHOSTS + 1, d), jnp.float32,
          sharding=sh("data", None, None)), tables,
        S((NHOSTS, B), jnp.int32, sharding=sh("data", None)),
        S((NHOSTS, B), jnp.bool_, sharding=sh("data", None))).as_text()
    assert "all_to_all" in text
    assert max(_literal_lengths(text), default=0) <= NHOSTS + 1


def test_global2host_form_keeps_its_answers_with_maps_as_arguments(mesh,
                                                                  rng):
    n, d, B = 300, 8, 40
    full = rng.normal(size=(n, d)).astype(np.float32)
    g2h = rng.integers(0, NHOSTS, n).astype(np.int32)
    info = PartitionInfo(host=0, hosts=NHOSTS, global2host=g2h,
                         replicate=np.arange(0, n, 17))
    df = DistFeature.from_global_feature(full, mesh, info)
    assert set(df.tables) == {"g2l", "g2h", "rep_mask", "rep_rank",
                              "owned_counts"}
    assert all(len(t.sharding.device_set) == NHOSTS
               for t in df.tables.values())
    ids = rng.integers(0, n, (NHOSTS, B)).astype(np.int32)
    valid = rng.random((NHOSTS, B)) < 0.5
    out = np.asarray(df.lookup(ids, valid))
    assert np.array_equal(out, np.where(valid[..., None], full[ids], 0))


def test_exchange_counters_count_slots_and_live_slots(mesh, rng):
    n, d, B = 256, 4, 2048
    full = rng.normal(size=(n, d)).astype(np.float32)
    df = DistFeature.from_row_ranges(full, mesh, EVEN)
    assert df.exchange_stats() is None

    def counted(name):
        return telemetry.counter(name, layer="feature").value

    names = ("dist_exchange_slots_total", "dist_exchange_live_slots_total",
             "dist_exchange_rounds_total")
    before = [counted(name) for name in names]
    valid = rng.random((NHOSTS, B)) < 0.25
    df.lookup(rng.integers(0, n, (NHOSTS, B)).astype(np.int32), valid)
    slots, live = df.exchange_stats()
    # slots are what was shipped: a quarter of the batch live and spread
    # over the owners goes in ONE round of buckets sized for an owner's
    # share, where the parent shipped buckets as long as the batch
    bucket = bucket_len(B, NHOSTS)
    assert bucket == 384 and (np.asarray(df.last_rounds) == 1).all()
    assert (slots, live) == (NHOSTS * NHOSTS * bucket, int(valid.sum()))
    assert df.exchange_stats() == (slots, live)     # counted once a call
    assert [counted(name) - b for name, b in zip(names, before)] == [
        slots, live, 1]
    assert int(df.overflow_stats().sum()) == 0


@pytest.mark.parametrize("form", ["one_round", "one_owner", "cap"])
def test_exchange_counts_the_request_slots_it_unpacks(mesh, rng, form):
    """``dist_exchange_unpacked_slots_total``: every round unpacks the whole
    frontier, so F x rounds summed over the ranks, once a call; the live
    slots are among them (``1 - live / unpacked`` is the dead share)."""
    n, d, B = 256, 4, 2048
    full = rng.normal(size=(n, d)).astype(np.float32)
    cap = 128 if form == "cap" else None
    df = DistFeature.from_row_ranges(full, mesh, EVEN, request_cap=cap)

    def counted():
        return telemetry.counter("dist_exchange_unpacked_slots_total",
                                 layer="feature").value

    before = counted()
    if form == "one_round":     # the counters test's quarter of a batch
        ids = rng.integers(0, n, (NHOSTS, B)).astype(np.int32)
        valid = rng.random((NHOSTS, B)) < 0.25
    else:                       # every id in rank 0's rows
        ids = rng.integers(0, EVEN[1], (NHOSTS, B)).astype(np.int32)
        valid = np.ones((NHOSTS, B), bool)
    df.lookup(ids, valid)
    slots, live = df.exchange_stats()
    rounds = np.asarray(df.last_rounds)
    assert (rounds == rounds[0]).all()
    assert int(rounds[0]) == {"one_round": 1, "cap": 1,
                              "one_owner": -(-B // bucket_len(B, NHOSTS))}[
        form]
    assert counted() - before == NHOSTS * B * int(rounds[0])
    assert live <= counted() - before
    df.exchange_stats()
    assert counted() - before == NHOSTS * B * int(rounds[0])   # once a call


# ---------------------------------------------------------------------------
# The exact exchange ships buckets sized for an owner's share, in as many
# rounds as its own counts ask for (PERF.md, PR 35)


def _stored(full, dtype):
    return np.asarray(jnp.asarray(full).astype(dtype))


def _sharded(form, mesh, full, dtype, g2h=None, request_cap=None):
    """The table in either partition form, stored as ``dtype``."""
    if form == "ranges":
        starts = np.linspace(0, len(full), NHOSTS + 1).astype(np.int64)
        return DistFeature.from_row_ranges(
            full, mesh, starts, dtype=jnp.dtype(dtype),
            request_cap=request_cap)
    if g2h is None:
        g2h = (np.arange(len(full)) * NHOSTS // len(full)).astype(np.int32)
    info = PartitionInfo(hosts=NHOSTS, global2host=g2h,
                         replicate=np.arange(5, len(full), 97))
    return DistFeature.from_global_feature(
        _stored(full, dtype), mesh, info, request_cap=request_cap)


def test_a_bucket_is_an_owners_share_or_the_whole_frontier():
    # the cell's four exchanges: a quarter and eight sigmas of room
    assert [bucket_len(F, 4) for F in (1024, 16384, 180224, 1081344)] == [
        384, 4608, 46848, 274560]
    for F, n in ((1024, 4), (16384, 4), (1081344, 4), (2048, 8), (10**7, 64)):
        b = bucket_len(F, n)
        assert b % 128 == 0 and F / n < b < 1.6 * F / n + 128
    # a frontier too small to divide, or a single owner: one round of F
    assert [bucket_len(F, n) for F, n in ((16, 8), (32, 8), (6, 8), (256, 2),
                                          (1024, 1), (10**6, 1))] == [
        16, 32, 6, 256, 1024, 10**6]


@pytest.mark.parametrize("form", ["ranges", "global2host"])
def test_every_id_on_one_owner_is_n_rounds_and_every_row(mesh, rng, form):
    # a batch whose owner's share (4,096) dwarfs the slack, so that one
    # owner's whole batch is exactly ``n`` buckets
    n, d, B = 512, 4, 32768
    full = rng.normal(size=(n, d)).astype(np.float32)
    df = _sharded(form, mesh, full, "float32",
                  g2h=np.zeros(n, np.int32))     # host 0 owns every row
    ids = rng.integers(0, n // NHOSTS, (NHOSTS, B)).astype(np.int32)
    if form == "global2host":       # but the replicated, served at home
        ids[np.isin(ids, df.info.rep_ids)] += 1
    out = np.asarray(df.lookup(ids))
    assert np.array_equal(out.view(np.uint8), full[ids].view(np.uint8))
    rounds = np.asarray(df.last_rounds)
    assert (rounds == NHOSTS).all() and -(-B // bucket_len(B, NHOSTS)) == NHOSTS
    assert (df.overflow_stats() == 0).all()
    assert df.exchange_stats() == (
        NHOSTS * NHOSTS * NHOSTS * bucket_len(B, NHOSTS), NHOSTS * B)


def test_ids_spread_evenly_take_one_round(mesh, rng):
    n, d, B = 256, 4, 2048
    full = rng.normal(size=(n, d)).astype(np.float32)
    df = DistFeature.from_row_ranges(full, mesh, EVEN)
    # every rank asks every owner for B / n rows: all live, one round
    ids = rng.permuted(np.tile(np.arange(n, dtype=np.int32),
                               (NHOSTS, B // n)), axis=1)
    out = np.asarray(df.lookup(ids))
    assert np.array_equal(out, full[ids])
    assert (np.asarray(df.last_rounds) == 1).all()
    assert df.exchange_stats() == (
        NHOSTS * NHOSTS * bucket_len(B, NHOSTS), NHOSTS * B)
    # one id more than a bucket holds on one owner: a second round, on
    # every rank, and still every row
    bucket = bucket_len(B, NHOSTS)
    ids[3, :bucket + 1] = 7
    out = np.asarray(df.lookup(ids))
    assert np.array_equal(out, full[ids])
    assert (np.asarray(df.last_rounds) == 2).all()
    assert int(df.overflow_stats().sum()) == 0


@pytest.mark.parametrize("form", ["ranges", "global2host"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_lookup_in_rounds_equals_the_one_wide_round_bit_for_bit(mesh, rng,
                                                               form, dtype):
    """The default (buckets of an owner's share, in rounds) against a
    caller's ``request_cap=B`` (ONE round of buckets as long as the batch,
    which nothing overflows either): the same rows to the last bit, on a
    batch skewed enough to take several rounds."""
    n, d, B = 512, 8, 2048
    full = rng.normal(size=(n, d)).astype(np.float32)
    ids = rng.integers(0, n, (NHOSTS, B)).astype(np.int32)
    ids[:, ::2] = rng.integers(0, n // NHOSTS, (NHOSTS, B // 2))
    valid = rng.random((NHOSTS, B)) < 0.8
    rounds_df = _sharded(form, mesh, full, dtype)
    wide_df = _sharded(form, mesh, full, dtype, request_cap=B)
    got = np.asarray(rounds_df.lookup(ids, valid))
    wide = np.asarray(wide_df.lookup(ids, valid))
    assert got.dtype == _stored(full, dtype).dtype == wide.dtype
    assert np.array_equal(got.view(np.uint8), wide.view(np.uint8))
    assert np.array_equal(got.view(np.uint8), np.where(
        valid[..., None], _stored(full, dtype)[ids], 0).view(np.uint8))
    assert (np.asarray(rounds_df.last_rounds) > 1).all()
    assert (np.asarray(wide_df.last_rounds) == 1).all()
    assert wide_df.exchange_stats() == (NHOSTS * NHOSTS * B, int(valid.sum()))
    assert int(rounds_df.overflow_stats().sum()
               + wide_df.overflow_stats().sum()) == 0


@pytest.mark.parametrize("form", ["ranges", "global2host"])
def test_a_batch_with_no_valid_id_ships_nothing(mesh, rng, form):
    n, d, B = 512, 4, 2048
    full = rng.normal(size=(n, d)).astype(np.float32)
    df = _sharded(form, mesh, full, "float32")
    ids = rng.integers(0, n, (NHOSTS, B)).astype(np.int32)
    out = np.asarray(df.lookup(ids, np.zeros((NHOSTS, B), bool)))
    assert out.shape == (NHOSTS, B, d) and not out.any()
    assert (np.asarray(df.last_rounds) == 0).all()
    assert df.exchange_stats() == (0, 0)
    assert (df.overflow_stats() == 0).all()
    # and the next batch is served as ever
    assert np.array_equal(np.asarray(df.lookup(ids)), full[ids])


def test_a_callers_cap_is_one_round_that_drops_and_counts(mesh, rng):
    n, d, B, cap = 512, 4, 2048, 128
    full = rng.normal(size=(n, d)).astype(np.float32)
    df = _sharded("ranges", mesh, full, "float32", request_cap=cap)
    ids = rng.integers(0, n // NHOSTS, (NHOSTS, B)).astype(np.int32)
    out = np.asarray(df.lookup(ids))
    assert (np.asarray(df.last_rounds) == 1).all()
    assert (df.overflow_stats() == B - cap).all()
    # the first ``cap`` requests of a bucket are served, the rest zero
    assert np.array_equal(out[:, :cap], full[ids[:, :cap]])
    assert not out[:, cap:].any()
    assert df.exchange_stats() == (NHOSTS * NHOSTS * cap, NHOSTS * cap)


# ---------------------------------------------------------------------------
# The unpacking: a slot that sent nothing in a round reads a row of its own
# and the ``where`` after it keeps zeros there (PERF.md, PR 39).  Tested on
# ``exchange`` itself, over four of the eight devices, against a plain
# reference: the owner's row where a request was sent, zeros elsewhere.
import functools  # noqa: E402

from jax import shard_map  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from quiver_tpu.dist.exchange import exchange  # noqa: E402

UNPACK_RANKS, UNPACK_IDS, UNPACK_F = 4, 4096, 8192
UNPACK_ROWS = {"lookup": ((8,), jnp.bfloat16), "hop": ((5,), jnp.int32)}


def _unpack_row(layer, table, ids):
    """What the owner of ``ids`` answers: a lookup's table rows, a hop's
    ``k`` draws (never 0, as a hop ships them)."""
    if layer == "lookup":
        return table[ids]
    k = UNPACK_ROWS["hop"][0][0]
    return ids[..., None] * k + np.arange(k, dtype=np.int32) + 1


@functools.lru_cache(maxsize=None)
def _unpack_program(layer, cap):
    """``(table, ids, valid) -> (answers, dropped, rounds)`` of ``exchange``
    per rank; rank ``p`` owns ids ``[p N/n, (p+1) N/n)`` and answers any
    other id with rubbish, so a request sent to the wrong owner shows."""
    n, N = UNPACK_RANKS, UNPACK_IDS
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
    shape, dtype = UNPACK_ROWS[layer]

    def body(table, ids, valid):
        ids, valid = ids[0], valid[0]
        me = jax.lax.axis_index("data")

        def serve(rids, rvalid, r):
            own = ((rids * n // N) == me) & rvalid
            rubbish = jnp.full((rids.shape[0],) + shape, 3, dtype)
            return jnp.where(own[:, None], _unpack_row(
                layer, table, jnp.clip(rids, 0, N - 1)).astype(dtype),
                rubbish)

        got, counts = exchange(f"qt.test.{layer}", "data", n, cap, ids,
                               ids * n // N, valid, serve,
                               jax.ShapeDtypeStruct(shape, dtype))
        return got[None], counts.dropped[None], counts.rounds[None]

    return jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(), P("data", None), P("data", None)),
        out_specs=(P("data"), P("data"), P("data"))))


def _unpack_reference(layer, cap, table, ids, valid):
    """Per request: sent where valid and, under a cap, among the first
    ``cap`` valid requests to its owner; the owner's row where sent, zeros
    elsewhere.  Also the dropped counts and the rounds the exact exchange
    takes: its fullest bucket over one's length, the same on every rank."""
    n, N = UNPACK_RANKS, UNPACK_IDS
    owner = ids * n // N
    sent = np.zeros_like(valid)
    fullest = 0
    for p in range(n):
        to_p = valid & (owner == p)
        rank = np.cumsum(to_p, axis=1) - 1
        sent |= to_p & (cap is None or rank < cap)
        fullest = max(fullest, int(to_p.sum(axis=1).max()))
    shape, dtype = UNPACK_ROWS[layer]
    rows = np.asarray(_unpack_row(layer, table, ids)).astype(dtype)
    want = np.where(sent.reshape(sent.shape + (1,) * len(shape)), rows, 0)
    bucket = bucket_len(ids.shape[1], n, cap)
    rounds = 1 if cap is not None else -(-fullest // bucket)
    return want.astype(dtype), (valid & ~sent).sum(axis=1), rounds


@pytest.mark.parametrize("layer", ["lookup", "hop"])
@pytest.mark.parametrize("form", ["one_round", "one_owner", "cap_drops"])
@pytest.mark.parametrize("frontier", ["dead_interleaved", "all_dead",
                                      "all_live"])
def test_unpacked_answers_are_the_owners_rows_or_zeros_bit_for_bit(
        layer, form, frontier):
    """Every round form (the exact exchange in one round, every id on one
    owner in ``n``, a caller's cap that drops) over frontiers whose dead
    slots sit among the live ones, or that are all dead or all live, for
    a lookup's bf16 rows and a hop's int32 draws: the answers are the
    reference's to the last bit, so a dead slot's own row never leaks."""
    n, N, F = UNPACK_RANKS, UNPACK_IDS, UNPACK_F
    rng = np.random.default_rng(39)
    table = rng.normal(size=(N,) + UNPACK_ROWS["lookup"][0]).astype(
        np.float32)
    table = np.asarray(jnp.asarray(table, jnp.bfloat16))
    hi = N // n if form == "one_owner" else N       # rank 0 owns them all
    ids = rng.integers(0, hi, (n, F)).astype(np.int32)
    valid = {"dead_interleaved": rng.random((n, F)) < 0.435,
             "all_dead": np.zeros((n, F), bool),
             "all_live": np.ones((n, F), bool)}[frontier]
    cap = F // (4 * n) if form == "cap_drops" else None
    got, dropped, rounds = (np.asarray(a) for a in _unpack_program(
        layer, cap)(jnp.asarray(table), ids, valid))
    want, want_dropped, want_rounds = _unpack_reference(layer, cap, table,
                                                        ids, valid)
    assert got.dtype == want.dtype
    assert got.tobytes() == want.tobytes()
    assert np.array_equal(dropped, want_dropped)
    assert (rounds == want_rounds).all()
    if form == "cap_drops" and frontier != "all_dead":
        assert dropped.sum() > 0
    if form == "one_owner" and frontier == "all_live":
        assert want_rounds == n


def test_row_ranges_with_the_overlay_kept(mesh, rng):
    n, d, B = 256, 4, 32
    full = rng.normal(size=(n, d)).astype(np.float32)
    df = DistFeature.from_row_ranges(full, mesh, UNEVEN, overlay=True)
    df.enable_cold_cache(rows=64, admit_threshold=1)
    ids = rng.integers(0, n, (NHOSTS, B)).astype(np.int32)
    for _ in range(3):      # admitted on the first pass, served after
        assert np.array_equal(np.asarray(df.lookup(ids)), full[ids])


def test_a_shard_is_as_long_as_its_largest_range_or_as_the_caller_says(mesh,
                                                                       rng):
    """The library pads a shard to the chip's tile and no further (an
    unaligned ``[1, E]`` shard is re-laid out whole in every step: 7.8 ms
    on the chip, PR 34); a caller who wants graphs of nearly one size at
    one shape states the length, and one too short is refused."""
    for v in (1, 15, 1023, 1024, 1025, 27_774_070, 403_921_468):
        assert v <= shard_len(v) < v + TILE and shard_len(v) % TILE == 0
        assert shard_len(shard_len(v)) == shard_len(v)
    assert shard_len(27_774_070, 29_360_128) == 29_360_128 == shard_len(
        27_768_776, 29_360_128)
    assert shard_len(10, 1500) == 2 * TILE
    with pytest.raises(ValueError, match="cannot hold"):
        shard_len(27_774_070, 27_000_000)
    full = rng.normal(size=(256, 4)).astype(np.float32)
    df = DistFeature.from_row_ranges(full, mesh, UNEVEN, shard_rows=3 * TILE)
    assert df.shards.shape == (NHOSTS, 3 * TILE, 4)
    ids = rng.integers(0, 256, (NHOSTS, 16)).astype(np.int32)
    assert np.array_equal(np.asarray(df.lookup(ids)), full[ids])
    with pytest.raises(ValueError, match="cannot hold"):
        DistFeature.from_row_ranges(full, mesh, UNEVEN, shard_rows=16)


# ---------------------------------------------------------------------------
# The data-parallel step over the sharded sampler's blocks: they say that
# they are positional, so the convs slice (PERF.md, PR 37)
import optax  # noqa: E402

from quiver_tpu.dist.sampler import DistGraphSampler  # noqa: E402
from quiver_tpu.models import GAT, GraphSAGE  # noqa: E402
from quiver_tpu.parallel import (TrainState, make_train_step,  # noqa: E402
                                 replicate)
from quiver_tpu.sampler import POSITIONAL  # noqa: E402
from tests.conftest import (make_random_csr, model_primitives,  # noqa: E402
                            onehot_loss)

DP_B, DP_CLASSES = 16, 5


@pytest.fixture(scope="module")
def sharded_batches(mesh):
    """Three batches ``(xs, blocks, labels)`` of the sharded sampler and
    feature store over one partition, a rank on every leading axis."""
    from quiver_tpu import CSRTopo

    topo = CSRTopo(edge_index=np.stack(make_random_csr(400, 6, seed=37)))
    rng = np.random.default_rng(37)
    feat = rng.normal(size=(topo.node_count, 12)).astype(np.float32)
    sampler = DistGraphSampler(topo, mesh, sizes=[4, 3])
    store = DistFeature.from_row_ranges(feat, mesh, sampler.row_starts_host)
    batches = []
    for i in range(3):
        seeds = rng.integers(0, topo.node_count, (NHOSTS, DP_B))
        n_id, n_mask, _, blocks = sampler.sample(seeds, key=i)
        batches.append((store.lookup(n_id, n_mask), blocks,
                        jnp.asarray(seeds % DP_CLASSES, jnp.int32)))
    # holes to read through: targets of degree 0 and dead frontier slots
    m = np.asarray(batches[0][1][0].mask)
    assert not m.all(axis=2).all() and not m.any(axis=2).all()
    return batches


def _dp_model(name):
    if name == "sage":
        return GraphSAGE(hidden=16, out_dim=DP_CLASSES, num_layers=2,
                         dropout=0.5)
    return GAT(hidden=16, out_dim=DP_CLASSES, num_layers=2, heads=2,
               dropout=0.5)


def _dp_step(mesh, model, batch, **kw):
    """``(step, fresh state)``: the state is donated, so one a call, and
    replicated as the step returns it (another sharding is another
    trace)."""
    def apply_fn(p, x, blocks, train=False, rngs=None):
        return model.apply(p, x, blocks, train=train, rngs=rngs)

    xs, blocks, _ = batch
    tx = optax.adam(1e-2)
    params = model.init(jax.random.PRNGKey(1), xs[0],
                        jax.tree.map(lambda l: l[0], blocks))
    return (make_train_step(apply_fn, tx, mesh=mesh, **kw),
            lambda: replicate(mesh, TrainState.create(
                jax.tree.map(jnp.copy, params), tx)))


def _stripped(blocks):
    return tuple(b._replace(layout=None) for b in blocks)


@pytest.mark.parametrize("name", ["sage", "gat"])
def test_dp_step_over_positional_blocks_equals_the_gathered_one(
        mesh, sharded_batches, name):
    """The marker changes how a conv finds its sources, not what it finds:
    loss exactly, the first gradient (Adam's ``mu`` over 0.1) and the
    parameters after the step to float32 rounding."""
    xs, blocks, labels = batch = sharded_batches[0]
    assert all(b.layout is POSITIONAL for b in blocks)
    step, fresh = _dp_step(mesh, _dp_model(name), batch)
    ones, key = jnp.ones((NHOSTS, DP_B), bool), jax.random.PRNGKey(7)
    sliced, loss_s = step(fresh(), xs, blocks, labels, ones, key)
    gathered, loss_g = step(fresh(), xs, _stripped(blocks), labels, ones, key)
    assert float(loss_s) == float(loss_g) and np.isfinite(float(loss_s))
    for got, want in ((sliced.opt_state[0].mu, gathered.opt_state[0].mu),
                      (sliced.params, gathered.params)):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            scale = float(np.abs(np.asarray(b)).max())
            assert scale > 0
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6 * scale)


@pytest.mark.parametrize("marked", [True, False],
                         ids=["positional", "stripped"])
def test_dp_step_model_gathers_follow_the_block_layout(mesh, sharded_batches,
                                                       marked):
    """``qt_dp_train_step``'s value_and_grad over the sampler's blocks holds
    no gather and no scatter under ``qt.model``; with the marker stripped
    both are back (``jnp.take`` and its scatter-add)."""
    xs, blocks, labels = batch = sharded_batches[0]
    step, fresh = _dp_step(mesh, _dp_model("sage"), batch,
                           loss_fn=onehot_loss)
    jaxpr = jax.make_jaxpr(step.jitted)(
        fresh(), xs, blocks if marked else _stripped(blocks), labels,
        jnp.ones((NHOSTS, DP_B), bool), jax.random.PRNGKey(1), None)
    names = model_primitives(jaxpr.jaxpr)
    assert "dot_general" in names  # the scope was found
    found = {n for n in names if "gather" in n or "scatter" in n}
    if marked:
        assert not found, found
    else:
        assert "gather" in found and "scatter-add" in found, found


def test_dp_step_traces_once(mesh, sharded_batches):
    """The marker is structure: it crosses the step's ``jit``, its
    ``in_shardings`` and the replica ``vmap`` as a Python value, and a
    second and a third batch trace nothing."""
    model, traces = _dp_model("sage"), []

    class Spy:
        def apply(self, p, x, blocks, **kw):
            traces.append(tuple(b.layout for b in blocks))
            assert x.ndim == 2 and blocks[0].mask.ndim == 2   # one replica
            return model.apply(p, x, blocks, **kw)

        init = model.init

    step, fresh = _dp_step(mesh, Spy(), sharded_batches[0])
    state, ones = fresh(), jnp.ones((NHOSTS, DP_B), bool)
    for i, (xs, blocks, labels) in enumerate(sharded_batches):
        state, loss = step(state, xs, blocks, labels, ones,
                           jax.random.PRNGKey(i))
        if i == 0:
            first = len(traces)
    assert first >= 1 and traces == [(POSITIONAL, POSITIONAL)] * first
    assert np.isfinite(float(loss))
