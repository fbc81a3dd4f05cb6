"""The typed hop: ``GraphSageSampler(schema=...)`` draws a fanout PER
RELATION into the typed tree of ``Schema.tree``, held here to a numpy
reference on a small six-type, eleven-relation CSR in the shape of IGBH
(``cellbench/references/igbh_rgat.typed_csr``, which imports nothing of
quiver_tpu).  Every live draw is a source of its relation in its target's
row, each target draws ``min(its degree under the relation, k)`` distinct
ones, masks are prefixes and the draws sit where the tree says; relations
of degree 0, below k and above k all occur.  The one-relation schema
samples bit for bit as the untyped positional pipeline does.  The
relation counters hold what the masks hold."""

import importlib
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "cellbench")):
    if p not in sys.path:
        sys.path.insert(0, p)

from quiver_tpu import CSRTopo, GraphSageSampler, telemetry  # noqa: E402
from quiver_tpu.sampler import LayerBlock, Schema  # noqa: E402

igbh = importlib.import_module("references.igbh_rgat")

CFG = dict(papers=600, authors=500, institutes=12, fields=40, journals=9,
           conferences=5, edges_cites=4000, edges_written_by=1500,
           edges_affiliated_to=300, edges_topic=2000, edges_published=240,
           edges_venue=120)
FANOUT = (4, 3, 2)
B = 6


@pytest.fixture(scope="module")
def graph():
    indptr, indices = igbh.typed_csr(CFG, 2**31 + 11)
    offsets = igbh.type_offsets(CFG)
    schema = Schema(offsets, tuple((s, t) for _, s, t in igbh.RELATIONS),
                    names=tuple(n for n, _, _ in igbh.RELATIONS))
    return indptr, indices, offsets, schema


def windows_np(indptr, indices, offsets, r):
    """``[n_t, 2]`` start and end of relation r's part of each row of its
    target type, by a scan of each row (the reference)."""
    _, s, t = igbh.RELATIONS[r]
    out = []
    for v in range(offsets[t], offsets[t + 1]):
        row = range(indptr[v], indptr[v + 1])
        mine = [e for e in row if offsets[s] <= indices[e] < offsets[s + 1]]
        out.append((mine[0], mine[-1] + 1) if mine else (indptr[v],) * 2)
    return np.asarray(out)


def test_schema_reads_every_relations_window_off_the_csr(graph):
    indptr, indices, offsets, schema = graph
    table = schema.windows(indptr, indices)
    assert table.dtype == np.int32 and len(table) % 128 == 0
    for r, base in enumerate(schema.window_base()):
        want = windows_np(indptr, indices, offsets, r)
        got = table[base:base + 2 * len(want)].reshape(-1, 2)
        np.testing.assert_array_equal(got[want[:, 1] > want[:, 0]],
                                      want[want[:, 1] > want[:, 0]])
        np.testing.assert_array_equal(got[:, 1] - got[:, 0],
                                      want[:, 1] - want[:, 0])


def test_schema_refuses_rows_not_grouped_by_type(graph):
    indptr, indices, offsets, schema = graph
    ends = igbh.node_types(indices[indptr[:-1][np.diff(indptr) > 0]],
                           offsets), igbh.node_types(
        indices[indptr[1:][np.diff(indptr) > 0] - 1], offsets)
    # a row whose first and last sources differ in type, swapped
    row = np.flatnonzero(np.diff(indptr) > 0)[np.flatnonzero(
        ends[0] != ends[1])[0]]
    bad = indices.copy()
    a, b = indptr[row], indptr[row + 1] - 1
    bad[a], bad[b] = bad[b], bad[a]
    with pytest.raises(ValueError, match="grouped"):
        schema.windows(indptr, bad)


def test_tree_is_the_references(graph):
    """The sampler's static tree and the one the reference works out alone
    agree: relation, targets and first source of every block."""
    _, _, _, schema = graph
    ours = schema.tree(B, FANOUT)
    theirs, _ = igbh.tree(B, FANOUT)
    for hop, runs in zip(ours, theirs):
        assert [b.relation for b in hop] == [r for r, _, _ in runs]
        assert [b.sources for b in hop] == [s for _, _, s in runs]
        for blk, (_, pos, _) in zip(hop, runs):
            want = np.concatenate([np.arange(a, a + n)
                                   for a, n in blk.targets])
            np.testing.assert_array_equal(want, pos)


def sample(graph, gather_mode, sample_rng, seed=0, **kw):
    indptr, indices, offsets, schema = graph
    s = GraphSageSampler(CSRTopo(indptr=indptr, indices=indices),
                         list(FANOUT), schema=schema, gather_mode=gather_mode,
                         sample_rng=sample_rng, **kw)
    seeds = np.random.default_rng(seed).choice(offsets[1], B, replace=False)
    return s, seeds, s.sample(seeds, key=jax.random.PRNGKey(seed))


@pytest.mark.parametrize("gather_mode,sample_rng",
                         [("xla", "key"), ("blocked", "hash")])
def test_typed_hop_against_numpy(graph, gather_mode, sample_rng):
    indptr, indices, offsets, schema = graph
    _, seeds, batch = sample(graph, gather_mode, sample_rng,
                             return_eid=True)
    n_id, n_mask = np.asarray(batch.n_id), np.asarray(batch.n_id_mask)
    np.testing.assert_array_equal(n_id[:B], seeds)
    hops, ptype = igbh.tree(B, FANOUT)
    assert len(n_id) == len(ptype)
    seen = {"zero": 0, "below": 0, "above": 0}
    for k, runs, blocks in zip(FANOUT, hops, batch.layers[::-1]):
        assert isinstance(blocks, tuple) and len(blocks) == len(runs)
        for (r, pos, first), blk in zip(runs, blocks):
            assert isinstance(blk, LayerBlock)
            assert blk.layout.relation == r and blk.layout.sources == first
            mask, nbr = np.asarray(blk.mask), np.asarray(blk.nbr_local)
            eid = np.asarray(blk.eid)
            assert mask.shape == (len(pos), k)
            _, s, t = igbh.RELATIONS[r]
            win = windows_np(indptr, indices, offsets, r)
            for b, p in enumerate(pos):
                if not n_mask[p]:
                    assert not mask[b].any()
                    continue
                lo, hi = win[n_id[p] - offsets[t]]
                deg = hi - lo
                seen["zero" if deg == 0 else "below" if deg < k
                     else "above"] += 1
                want = min(deg, k)
                np.testing.assert_array_equal(mask[b], np.arange(k) < want)
                e = eid[b, :want]
                assert ((e >= lo) & (e < hi)).all()
                assert len(set(e.tolist())) == want         # distinct
                slots = first + b * k + np.arange(want)
                np.testing.assert_array_equal(nbr[b, :want], slots)
                np.testing.assert_array_equal(n_id[slots], indices[e])
                assert n_mask[slots].all() and not n_mask[
                    first + b * k + np.arange(want, k)].any()
                assert (ptype[slots] == s).all()
    assert all(seen.values()), seen


@pytest.mark.parametrize("gather_mode", ["xla", "blocked"])
def test_one_relation_schema_is_the_untyped_pipeline_bit_for_bit(
        gather_mode):
    """The untyped graph as a schema of one type and one relation whose
    window is the whole row: the same frontier, masks and blocks as the
    positional pipeline, bit for bit (the blocks one per hop in a tuple),
    and the place the untyped block's ``POSITIONAL`` reads off its shape is
    the one the typed block carries."""
    from quiver_tpu.utils.synthetic import synthetic_csr

    indptr, indices = synthetic_csr(900, 9000, seed=5)
    topo = CSRTopo(indptr=indptr, indices=indices)
    one = Schema((0, topo.node_count), ((0, 0),))
    seeds = np.arange(0, 64, 3)
    out = []
    for schema in (None, one):
        s = GraphSageSampler(topo, [5, 4, 3], schema=schema,
                             gather_mode=gather_mode, sample_rng="hash")
        out.append(s.sample(seeds, key=jax.random.PRNGKey(9)))
    plain, typed = out
    np.testing.assert_array_equal(plain.n_id, typed.n_id)
    np.testing.assert_array_equal(plain.n_id_mask, typed.n_id_mask)
    for a, (b,) in zip(plain.layers, typed.layers):
        np.testing.assert_array_equal(a.nbr_local, b.nbr_local)
        np.testing.assert_array_equal(a.mask, b.mask)
        t = a.mask.shape[0]
        assert a.layout.sources is None and b.layout.sources == t
        assert a.layout.place(t) == b.layout.place(t) == (((0, t),), t)
    np.testing.assert_array_equal(plain.relation_live, typed.relation_live)


def test_relation_counters_hold_the_masks(graph):
    """Live draws never pass the slots, and their sum over the relations
    is the sum of the masks; the counters take each call in once."""
    telemetry.reset()
    s, _, batch = sample(graph, "xla", "key", seed=3)
    stats = s.relation_stats()
    assert s.relation_stats() == stats          # no second count
    masks = sum(int(np.asarray(b.mask).sum()) for hop in batch.layers
                for b in hop)
    assert sum(x["live"] for x in stats) == masks
    slots = igbh_slots()
    for x in stats:
        assert 0 <= x["live"] <= x["slots"] == slots[x["relation"]]
    snap = telemetry.snapshot()["counters"]
    for x in stats:
        key = f'{{relation={x["relation"]}}}'
        assert snap["sampler_relation_slots_total" + key] == x["slots"]
        assert snap["sampler_relation_live_slots_total" + key] == x["live"]
    assert s.relation_stats(batch) == stats


def igbh_slots():
    hops, _ = igbh.tree(B, FANOUT)
    out = {}
    for k, runs in zip(FANOUT, hops):
        for r, pos, _ in runs:
            name = igbh.RELATIONS[r][0]
            out[name] = out.get(name, 0) + k * len(pos)
    return out


@pytest.mark.parametrize("kw", [dict(mode="CPU"), dict(dedup="hop"),
                                dict(edge_weights="ones")])
def test_typed_draw_refuses_what_it_does_not_do(graph, kw):
    indptr, indices, _, schema = graph
    if kw.get("edge_weights") == "ones":
        kw = dict(edge_weights=np.ones(len(indices), np.float32))
    with pytest.raises(ValueError):
        GraphSageSampler(CSRTopo(indptr=indptr, indices=indices),
                         list(FANOUT), schema=schema, **kw)
