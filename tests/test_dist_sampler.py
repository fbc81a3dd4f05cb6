"""Row-sharded distributed sampling over the 8-device virtual mesh."""

import numpy as np
import jax
import pytest

from quiver_tpu.dist.sampler import DistGraphSampler, shard_csr_by_rows
from quiver_tpu.utils.mesh import make_mesh


def test_shard_csr_by_rows(small_graph):
    row_starts, lips, lids = shard_csr_by_rows(small_graph, 4)
    assert row_starts[0] == 0 and row_starts[-1] == small_graph.node_count
    # every edge lands in exactly one shard, contiguous rebuild matches
    rebuilt = np.concatenate(lids)
    np.testing.assert_array_equal(rebuilt, small_graph.indices)
    for s in range(4):
        lo, hi = row_starts[s], row_starts[s + 1]
        np.testing.assert_array_equal(
            lips[s],
            small_graph.indptr[lo: hi + 1] - small_graph.indptr[lo],
        )



def _assert_shard_edges_real(small_graph, seeds, n_id, blk, k):
    """Shared ground-truth check: every masked neighbor of every seed on
    every shard is a real edge of the graph."""
    n_id = np.asarray(n_id)
    local = np.asarray(blk.nbr_local)
    m = np.asarray(blk.mask)
    D, B = seeds.shape
    for d in range(D):
        for b in range(B):
            tgt = seeds[d, b]
            row = set(small_graph.indices[
                small_graph.indptr[tgt]: small_graph.indptr[tgt + 1]
            ].tolist())
            for j in range(local.shape[-1]):
                if m[d, b, j]:
                    assert n_id[d, local[d, b, j]] in row


def test_dist_sampler_edges_real(small_graph):
    mesh = make_mesh(("data",))
    s = DistGraphSampler(small_graph, mesh, sizes=[4, 3])
    rng = np.random.default_rng(0)
    B = 16
    seeds = rng.integers(0, small_graph.node_count, (8, B))
    n_id, n_mask, num, blocks = s.sample(seeds, key=7)
    n_id = np.asarray(n_id)
    n_mask = np.asarray(n_mask)
    assert n_id.shape[0] == 8
    # seeds occupy the frontier prefix per shard
    np.testing.assert_array_equal(n_id[:, :B], seeds)
    # spot-check sampled edges against ground truth on each shard
    blk = blocks[-1]  # innermost hop: targets = seeds
    for d in range(8):
        assert int(np.asarray(blk.num_targets)[d]) == B
        local = np.asarray(blk.nbr_local)[d]
        m = np.asarray(blk.mask)[d]
        for b in range(B):
            tgt = seeds[d, b]
            deg = small_graph.indptr[tgt + 1] - small_graph.indptr[tgt]
            got = m[b].sum()
            assert got == min(deg, 4) or deg > 4  # cap overflow only
    _assert_shard_edges_real(small_graph, seeds, n_id, blk, 4)


def test_dist_sampler_counts_match_single(small_graph):
    """Per-seed neighbor counts equal min(deg, k) when caps are exact."""
    mesh = make_mesh(("data",))
    s = DistGraphSampler(small_graph, mesh, sizes=[5],
                         request_cap_frac=1.0)
    B = 8
    seeds = np.tile(np.arange(B)[None], (8, 1))
    n_id, n_mask, num, blocks = s.sample(seeds, key=3)
    deg = small_graph.degree
    counts = np.asarray(blocks[0].mask).sum(axis=2)
    for d in range(8):
        np.testing.assert_array_equal(
            counts[d], np.minimum(deg[:B], 5)
        )


def test_dist_sampler_cap_overflow_drops(small_graph):
    """With a tiny request cap, overflowed seeds sample zero neighbors
    (documented degradation, never corruption)."""
    mesh = make_mesh(("data",))
    s = DistGraphSampler(small_graph, mesh, sizes=[4],
                         request_cap_frac=0.01)
    # all seeds in one shard's row range -> guaranteed bucket pressure
    seeds = np.zeros((8, 32), dtype=np.int64)
    n_id, n_mask, num, blocks = s.sample(seeds, key=1)
    m = np.asarray(blocks[0].mask)
    counts = m.sum(axis=2)
    deg0 = int(small_graph.degree[0])
    # every served seed got min(deg, 4); the rest got zero
    assert set(np.unique(counts)) <= {0, min(deg0, 4)}
    # frontier entries for dropped seeds are masked invalid
    nm = np.asarray(n_mask)
    assert nm.shape[1] == 32 + 32 * 4


def test_dist_sampler_hash_rng_executes(small_graph):
    """sample_rng='hash' (the TPU ship default) through the row-sharded
    dist sampler's shard_map pipeline: deterministic per key, edges real."""
    mesh = make_mesh(("data",))
    s = DistGraphSampler(small_graph, mesh, sizes=[4, 3],
                         sample_rng="hash")
    assert s.sample_rng == "hash"
    seeds = np.random.default_rng(1).integers(
        0, small_graph.node_count, (8, 8))
    n_id_a, mask_a, _, blocks = s.sample(seeds, key=11)
    n_id_b, mask_b, _, _ = s.sample(seeds, key=11)
    np.testing.assert_array_equal(np.asarray(n_id_a), np.asarray(n_id_b))
    np.testing.assert_array_equal(np.asarray(mask_a), np.asarray(mask_b))
    _assert_shard_edges_real(small_graph, seeds, n_id_a, blocks[-1], 4)


# ---------------------------------------------------------------------------
# >2^31-edge regime (VERDICT r4 weak #2): the papers100M claim rests on the
# row-split plan never letting a shard's local edge count overflow int32.
# Planning works from indptr alone, so the test builds a synthetic indptr
# from degrees without materializing an edge array.


def _big_indptr(n_nodes=1024, deg=4_300_000):
    indptr = np.arange(n_nodes + 1, dtype=np.int64) * deg
    assert indptr[-1] > 2**31  # ~4.4B edges
    return indptr


def test_plan_row_shards_raises_on_int32_overflow():
    from quiver_tpu.dist.sampler import plan_row_shards

    indptr = _big_indptr()
    with pytest.raises(ValueError, match="shard"):
        plan_row_shards(indptr, 2)  # ~2.2B edges/shard > 2^31


def test_plan_row_shards_big_graph_offsets():
    from quiver_tpu.dist.sampler import plan_row_shards

    indptr = _big_indptr()
    row_starts = plan_row_shards(indptr, 4)
    assert row_starts[0] == 0 and row_starts[-1] == len(indptr) - 1
    assert np.all(np.diff(row_starts) > 0)
    for s in range(4):
        lo, hi = row_starts[s], row_starts[s + 1]
        local_edges = int(indptr[hi] - indptr[lo])
        assert local_edges < 2**31
        # rebased local offsets stay int32-representable end to end
        local = indptr[lo: hi + 1] - indptr[lo]
        assert local[-1] == local_edges and local[-1] < 2**31


def test_dist_sampler_padded_indptr_is_monotone(small_graph):
    """Padded per-shard indptr rows must repeat the final offset, not
    read zero (zero padding makes padded rows look negative-degree —
    masked today, but a trap; mirror uva.py's edge-value padding)."""
    mesh = make_mesh(("data",))
    s = DistGraphSampler(small_graph, mesh, sizes=[3])
    ip = np.asarray(s.indptr_sh)
    for row in ip:
        assert np.all(np.diff(row.astype(np.int64)) >= 0)


# ---------------------------------------------------------------------------
# The sharded step the cell ``papers100m-sage-host.train-dist`` times
# (PERF.md, PR 34): draws against the WHOLE graph, the exchange's counters,
# its scopes, and the three-program step against the plain DDP reference.
import os  # noqa: E402
import sys  # noqa: E402

import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# appended, not put first: ``cellbench/tests`` must not come to stand for
# this directory's ``tests`` package in files collected after this one
for p in (ROOT, os.path.join(ROOT, "cellbench")):
    if p not in sys.path:
        sys.path.append(p)

from quiver_tpu.dist.exchange import bucket_len  # noqa: E402

RANKS = 4
CELL = "papers100m-sage-host.train-dist"


@pytest.fixture(scope="module")
def host_cell():
    """``(cfg, data, reference, program)`` of the cell at its rehearsal
    size over four of the eight virtual devices, float32 rows."""
    import run

    _, cell, cfg, traffic = run.find_cell(CELL)
    run.rehearsal_size(cfg, traffic)
    cfg["feature_dtype"] = "float32"
    parts = run.parts_of(cfg, traffic)
    data = parts["reference"].make_data(cfg, 2 ** 31 + 34)
    prog = parts["program"].Program(cfg, data, jax.devices()[:RANKS])
    return cfg, data, parts["reference"], prog


def _host_seeds(cfg, data, rng):
    return rng.choice(cfg["nodes"], RANKS * cfg["batch"],
                      replace=False).astype(np.int32)


def test_every_ranks_draw_is_held_to_the_whole_graph(host_cell, rng):
    cfg, data, ref, prog = host_cell
    seeds = _host_seeds(cfg, data, rng)
    n_id, n_mask, layers = prog.replay_sample(seeds, 1234)
    assert n_id.shape[0] == RANKS
    bad, edges = ref.check_sample(data["indptr"], data["indices"],
                                  cfg["fanout"], seeds, n_id, n_mask, layers)
    assert edges > 0 and not any(bad.values()), bad
    # three quarters of what a rank asks for is another rank's
    starts = prog.sampler.row_starts_host
    owner = np.searchsorted(starts, n_id, side="right") - 1
    remote = (owner != np.arange(RANKS)[:, None]) & n_mask
    assert 0.6 < remote.sum() / n_mask.sum() < 0.9
    rows = prog.replay_rows(n_id, n_mask).copy()
    assert ref.check_rows(data["features"], n_id, n_mask, rows) == 0
    rows[1, 0, 0] += 1.0    # the check sees one wrong row
    assert ref.check_rows(data["features"], n_id, n_mask, rows) == 1


def test_exchange_drops_nothing_at_default_caps_and_counts_its_slots(
        host_cell, rng):
    cfg, data, _, prog = host_cell
    s = prog.sampler
    seeds = _host_seeds(cfg, data, rng)
    n_id, n_mask, _ = prog.replay_sample(seeds, 77)
    prog.replay_rows(n_id, n_mask)
    assert prog.exchange_drops() == 0
    assert s.overflow_stats().shape == (RANKS, len(cfg["fanout"]))
    caps = s.hop_caps(cfg["batch"])
    # an owner's share of each hop's frontier (16, 256, 2,816 slots) and
    # its room, where the parent shipped the whole frontier; 16 slots are
    # too few to divide
    assert caps == [bucket_len(F, RANKS) for F in (16, 256, 2816)] == [
        16, 128, 1024]
    rounds = np.asarray(s.last_rounds)
    assert rounds.shape == (RANKS, len(caps)) and (rounds == 1).all()
    slots, live = s.exchange_stats()
    assert slots == RANKS * RANKS * sum(caps)       # what was shipped
    # every live target of every hop was asked of its owner, once
    assert live == sum(int(n_mask[:, :F].sum()) for F in (16, 256, 2816))
    f_slots, f_live = prog.feature.exchange_stats()
    assert (np.asarray(prog.feature.last_rounds) == 1).all()
    assert f_slots == RANKS * RANKS * bucket_len(n_id.shape[1], RANKS)
    assert f_live == int(n_mask.sum())
    assert prog.exchange_slots() == (slots + f_slots, live + f_live)


def test_the_three_programs_carry_their_names_and_scopes(host_cell,
                                                         monkeypatch):
    import re

    from quiver_tpu import telemetry

    # the module, not the function the package re-exports under its name
    ds = sys.modules["quiver_tpu.telemetry.device_scopes"]
    cfg, data, _, prog = host_cell
    monkeypatch.setattr(ds, "_programs", {})
    monkeypatch.setattr(ds, "_tables", {})
    prog.sampler._fn.clear()    # programs register at their first call
    prog.feature._fn.clear()
    state, step = prog.fused_train_step()
    B = RANKS * cfg["batch"]
    seeds = jnp.arange(B, dtype=jnp.int32)
    state, loss = step(state, seeds, jnp.asarray(data["labels"][:B]),
                       jnp.ones((B,), bool), prog.make_key(5))
    assert np.isfinite(float(loss))
    tables = telemetry.device_scopes()
    assert set(tables) == {"jit_qt_dist_sample", "jit_qt_dist_lookup",
                           "jit_qt_dp_train_step"}
    names = {k: set(v.values()) for k, v in tables.items()}
    last = lambda n: (re.findall(r"qt(?:\.[A-Za-z0-9_]+)+", n)
                      or [None])[-1]
    first = lambda n: (re.findall(r"qt(?:\.[A-Za-z0-9_]+)+", n)
                       or [None])[0]
    sample = names["jit_qt_dist_sample"]
    for hop in (1, 2, 3):
        scope = ds.sampler_hop(hop)
        assert any(first(n) == scope and last(n) == ds.EXCHANGE
                   for n in sample), f"no exchange under hop {hop}"
        assert any(last(n) == scope for n in sample), \
            f"no local draw under hop {hop}"
    lookup = names["jit_qt_dist_lookup"]
    assert any(first(n) == ds.FEATURE_GATHER and last(n) == ds.EXCHANGE
               for n in lookup)
    assert any(last(n) == ds.FEATURE_GATHER for n in lookup)
    assert all(first(n) in (None, ds.FEATURE_GATHER, ds.FLOW)
               for n in lookup)
    # the collectives themselves sit under the exchange
    for prog_names in (sample, lookup):
        assert any("all_to_all" in n and last(n) == ds.EXCHANGE
                   for n in prog_names)
    train = names["jit_qt_dp_train_step"]
    assert any(ds.MODEL in n and "transpose(" not in n for n in train)
    assert any(ds.MODEL in n and "transpose(" in n for n in train)
    assert any(ds.OPTIMIZER in n for n in train)
    assert not any(ds.EXCHANGE in n for n in train)


def test_three_program_step_follows_the_ddp_reference(host_cell, rng):
    """Loss, every leaf of the first gradient and the parameters after
    three Adam steps, at ``highest``: the reference computes each rank's
    loss and gradient apart and averages them."""
    cfg, data, ref, prog = host_cell
    host = dict(cfg, batch=RANKS * cfg["batch"])
    B = host["batch"]
    state, step = prog.fused_train_step()
    batches, losses, first = [], [], None
    for i in range(3):
        seeds = _host_seeds(cfg, data, rng)
        key = jax.random.fold_in(prog.make_key(34), i)
        labels = data["labels"][seeds]
        state, loss = step(state, jnp.asarray(seeds), jnp.asarray(labels),
                           jnp.ones((B,), bool), key)
        losses.append(float(loss))
        if first is None:
            first = prog.first_gradient(state)
        ks, kd = prog.step_keys(key)
        n_id, n_mask, layers = prog.replay_sample(seeds, ks)
        batches.append({"rows": data["features"][n_id], "layers": layers,
                        "labels": labels, "drop_key": kd})
    want_losses, want_first, want_params = ref.train_follow(
        data["params"], batches, host, "highest")
    np.testing.assert_allclose(losses, want_losses, rtol=2e-6)
    leaves = jax.tree_util.tree_leaves_with_path
    for (path, got), (_, want) in zip(leaves(first), leaves(want_first)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))
    for (path, got), (_, want) in zip(leaves(state.params),
                                      leaves(want_params)):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                                   atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))
    # and a dropped rank is seen: the first half of the host's batch alone
    half_losses, _, _ = ref.train_follow(data["params"], batches, host,
                                         "highest", fault="half_batch")
    assert abs(half_losses[0] - want_losses[0]) > 1e-3


def test_sampler_shards_are_put_from_slices_of_the_host_csr(small_graph):
    mesh = make_mesh(("data",))
    s = DistGraphSampler(small_graph, mesh, [3])
    starts, lips, lids = shard_csr_by_rows(small_graph, 8)
    assert np.array_equal(s.row_starts_host, starts)
    ip, ix = np.asarray(s.indptr_sh), np.asarray(s.indices_sh)
    for p in range(8):
        assert np.array_equal(ip[p, :len(lips[p])], lips[p])
        assert (ip[p, len(lips[p]):] == lips[p][-1]).all()
        assert np.array_equal(ix[p, :len(lids[p])], lids[p])


# ---------------------------------------------------------------------------
# The exact exchange in rounds under the hops (PERF.md, PR 35)


def _four_rank_sampler(graph, sizes, **kw):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:RANKS]), ("data",))
    return DistGraphSampler(graph, mesh, sizes=sizes, **kw)


def _spread_seeds(s, B, rng):
    """``[RANKS, B]`` seeds, a quarter of every rank's in each owner's
    rows."""
    starts = s.row_starts_host
    return rng.permuted(np.concatenate(
        [rng.integers(starts[p], starts[p + 1], (RANKS, B // RANKS))
         for p in range(RANKS)], axis=1), axis=1)


def _assert_draws(graph, s, seeds, n_id, n_mask, blocks, sizes):
    """Every target of every hop draws ``min(degree, k)`` neighbours of
    its own, masks are prefixes, nothing was dropped."""
    n_id, n_mask = np.asarray(n_id), np.asarray(n_mask)
    deg = np.asarray(graph.degree)
    F = seeds.shape[1]
    for blk, k in zip(blocks[::-1], sizes):     # innermost first
        local, m = np.asarray(blk.nbr_local), np.asarray(blk.mask)
        targets, live = n_id[:, :F], n_mask[:, :F]
        want = np.where(live, np.minimum(deg[targets], k), 0)
        assert np.array_equal(m.sum(axis=2), want)
        assert (m[..., :-1] >= m[..., 1:]).all()       # prefixes
        for r in range(RANKS):
            for t in np.flatnonzero(live[r])[::max(F // 256, 1)]:
                row = graph.indices[graph.indptr[targets[r, t]]:
                                    graph.indptr[targets[r, t] + 1]]
                assert np.isin(n_id[r, local[r, t][m[r, t]]], row).all()
        F *= 1 + k
    assert (s.overflow_stats() == 0).all()


@pytest.mark.parametrize("sample_rng", ["key", "hash"])
def test_every_seed_on_one_owner_is_n_rounds_and_every_draw(small_graph,
                                                            sample_rng):
    sizes, B = [3, 2], 4096
    s = _four_rank_sampler(small_graph, sizes, sample_rng=sample_rng)
    lo, hi = (int(v) for v in s.row_starts_host[2:4])   # rank 2's rows
    seeds = np.random.default_rng(5).integers(lo, hi, (RANKS, B))
    n_id, n_mask, _, blocks = s.sample(seeds, key=9)
    rounds = np.asarray(s.last_rounds)
    # hop 1: 4,096 requests a rank into rank 2's bucket of 1,280
    assert s.hop_caps(B) == [1280, 4608]
    assert (rounds[:, 0] == RANKS).all() and (rounds[0] == rounds).all()
    _assert_draws(small_graph, s, seeds, n_id, n_mask, blocks, sizes)
    slots, live = s.exchange_stats()
    assert slots == RANKS * RANKS * int((rounds[0] * [1280, 4608]).sum())
    assert live == int(np.asarray(n_mask)[:, :B * 4].sum()) + RANKS * B


def test_seeds_spread_evenly_take_one_round(small_graph):
    sizes, B = [3, 2], 4096
    s = _four_rank_sampler(small_graph, sizes)
    # 1,024 of a rank's seeds into each owner's bucket of 1,280.  Hop 2's
    # frontier goes where the neighbours live (ranges balanced by edges are
    # not balanced by rows): its rounds are the counts', and its slots what
    # those rounds shipped
    seeds = _spread_seeds(s, B, np.random.default_rng(6))
    n_id, n_mask, _, blocks = s.sample(seeds, key=10)
    rounds = np.asarray(s.last_rounds)
    assert (rounds[:, 0] == 1).all() and (rounds[0] == rounds).all()
    _assert_draws(small_graph, s, seeds, n_id, n_mask, blocks, sizes)
    assert s.exchange_stats()[0] == RANKS * RANKS * (
        1280 + int(rounds[0, 1]) * 4608)


def test_a_fraction_under_one_is_one_round_that_drops_and_counts(small_graph):
    """A caller's cap stays ONE round: at a fraction under 1.0 the same
    skewed seeds overflow and are counted, where the default drops none."""
    sizes, B = [3], 4096
    s = _four_rank_sampler(small_graph, sizes, request_cap_frac=0.5)
    lo, hi = (int(v) for v in s.row_starts_host[:2])
    seeds = np.random.default_rng(7).integers(lo, hi, (RANKS, B))
    s.sample(seeds, key=11)
    cap = s.hop_caps(B)[0]
    assert cap == 1024 and (np.asarray(s.last_rounds) == 1).all()
    assert (s.overflow_stats()[:, 0] == B - cap).all()
    assert s.exchange_stats() == (RANKS * RANKS * cap, RANKS * cap)


@pytest.mark.parametrize("traffic,frac", [("spread", 1.0),
                                           ("one-owner", 1.0),
                                           ("one-owner", 0.5)])
def test_hops_count_the_request_slots_they_unpack(small_graph, traffic, frac):
    """``dist_exchange_unpacked_slots_total{layer="sampler"}``: each hop
    unpacks its whole frontier (``hop_frontiers``: B, B (1 + k_1), ...)
    once a round, summed over the ranks; its live slots are among them."""
    from quiver_tpu import telemetry

    sizes, B = [3, 2], 4096
    s = _four_rank_sampler(small_graph, sizes, request_cap_frac=frac)
    assert s.hop_frontiers(B) == [B, B * 4]

    def counted():
        return telemetry.counter("dist_exchange_unpacked_slots_total",
                                 layer="sampler").value

    rng = np.random.default_rng(39)
    seeds = (_spread_seeds(s, B, rng) if traffic == "spread" else
             rng.integers(*s.row_starts_host[1:3], (RANKS, B)))
    before = counted()
    s.sample(seeds, key=13)
    _, live = s.exchange_stats()
    rounds = np.asarray(s.last_rounds)
    assert (rounds == rounds[0]).all()
    if traffic == "one-owner":
        assert rounds[0, 0] == (RANKS if frac == 1.0 else 1)
    unpacked = RANKS * int((rounds[0] * s.hop_frontiers(B)).sum())
    assert counted() - before == unpacked and live <= unpacked


# ---------------------------------------------------------------------------
# The blocks say that they are positional, and the promise holds through the
# exchange (PERF.md, PR 37)


@pytest.mark.parametrize("traffic,sample_rng,frac,hop1_rounds", [
    ("one-owner", "key", 1.0, RANKS), ("one-owner", "hash", 1.0, RANKS),
    ("spread", "key", 1.0, 1), ("spread", "hash", 1.0, 1),
    ("one-owner", "hash", 0.5, 1)])
def test_blocks_are_positional_whatever_the_exchange_did(
        small_graph, traffic, sample_rng, frac, hop1_rounds):
    """``LayerBlock.layout``'s promise for every rank and hop: a frontier of
    ``T (1 + k)``, ``nbr_local[b, j] == T + b*k + j`` wherever ``mask``, the
    drawn ids at those very positions - in one round or in ``n``, and where
    a caller's cap dropped requests (their slots are ``mask == False``)."""
    from quiver_tpu.sampler import POSITIONAL

    sizes, B = [3, 2], 4096
    s = _four_rank_sampler(small_graph, sizes, sample_rng=sample_rng,
                           request_cap_frac=frac)
    rng = np.random.default_rng(37)
    if traffic == "spread":
        seeds = _spread_seeds(s, B, rng)
    else:       # every seed in rank 1's rows
        seeds = rng.integers(*s.row_starts_host[1:3], (RANKS, B))
    n_id, n_mask, _, blocks = s.sample(seeds, key=12)
    assert (np.asarray(s.last_rounds)[:, 0] == hop1_rounds).all()
    n_id, n_mask = np.asarray(n_id), np.asarray(n_mask)
    T = B
    for blk, k in zip(blocks[::-1], sizes):     # innermost first
        assert blk.layout is POSITIONAL
        local, m = np.asarray(blk.nbr_local), np.asarray(blk.mask)
        assert m.shape == (RANKS, T, k)
        pos = T + np.arange(T)[:, None] * k + np.arange(k)[None, :]
        assert np.array_equal(local, np.where(m, pos, 0))
        # what sits at those positions is what the owners drew, and the
        # frontier's own mask is the block's
        assert np.array_equal(n_mask[:, T:T * (1 + k)], m.reshape(RANKS, -1))
        deg = np.asarray(small_graph.degree)[n_id[:, :T]]
        served = m.any(axis=2)
        assert np.array_equal(
            m.sum(axis=2), np.where(served, np.minimum(deg, k), 0))
        assert not (served & ~n_mask[:, :T]).any()    # a dead slot asks none
        T *= 1 + k
    assert n_id.shape == (RANKS, T)
    dropped = s.overflow_stats()
    if frac < 1.0:
        # hop 1: all 4,096 requests of a rank into one bucket of 1,024
        assert (dropped[:, 0] == B - s.hop_caps(B)[0]).all()
        assert (np.asarray(blocks[-1].mask).any(axis=2).sum(axis=1)
                <= s.hop_caps(B)[0]).all()
    else:
        assert (dropped == 0).all()
    # a rank's blocks, as dist/e2e.py and the example take them
    one = jax.tree_util.tree_map(lambda l: l[0], blocks)
    assert all(b.layout is POSITIONAL and b.mask.ndim == 2 for b in one)
    assert len(jax.tree_util.tree_leaves(one)) == 3 * len(sizes)
