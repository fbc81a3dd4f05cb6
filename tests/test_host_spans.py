"""The host's side of the step path: the spans the library enters at its
step-path boundaries (``telemetry/device_scopes.py`` lists their names
beside the device scopes'), the longest call ``SpanTracer`` keeps beside
the mean, and the five per-layer readers of ``cellbench/metrics/`` that turn
the spans of a traced run into metrics.

CPU, toy sizes: what is checked is names, nesting and bookkeeping, never a
time.  The sharded calls run on four of the suite's virtual devices, as
``papers100m-sage-host.train-dist`` runs on four chips.
"""

import ast
import hashlib
import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from quiver_tpu import (CSRTopo, DistFeature, DistGraphSampler, Feature,
                        GraphSageSampler, telemetry)
from quiver_tpu.models import GraphSAGE
from quiver_tpu.parallel import TrainState, make_train_step, replicate
from quiver_tpu.pipeline import (make_fused_eval_fn, make_fused_train_step,
                                 make_scan_epoch)
from quiver_tpu.telemetry import noop
from quiver_tpu.telemetry.device_scopes import (
    HOST_LOOKUP, HOST_SAMPLE, HOST_STEP_EPOCH, HOST_STEP_EVAL,
    HOST_STEP_TRAIN, LAUNCH, PLACE)
from quiver_tpu.utils.synthetic import community_graph
from tests.conftest import make_random_csr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "cellbench")
RANKS, B, CLASSES = 4, 16, 5


@pytest.fixture
def traced():
    """Telemetry on, the tracer keeping every span as an event (name,
    start, duration, thread, depth); what the process had is put back."""
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    telemetry.reset()
    tracer = telemetry.get_tracer()
    kept = tracer.tracing
    tracer.set_tracing(True)
    yield tracer
    tracer.set_tracing(kept)
    telemetry.set_enabled(was)
    telemetry.reset()


def _inside(child, parent):
    """``child`` ran on ``parent``'s thread, within its interval, one level
    down."""
    return (child["tid"] == parent["tid"]
            and child["depth"] == parent["depth"] + 1
            and child["ts_us"] >= parent["ts_us"]
            and child["ts_us"] + child["dur_us"]
            <= parent["ts_us"] + parent["dur_us"])


def _by_name(tracer):
    out = {}
    for e in tracer.events():
        out.setdefault(e["name"], []).append(e)
    return out


# ------------------------------------------------------- the sharded path
@pytest.fixture(scope="module")
def sharded():
    """Sampler, feature store and data-parallel step over four devices,
    each called once already (so that no test below times a first call's
    tracing) and a fresh replicated state per call."""
    mesh = Mesh(np.array(jax.devices()[:RANKS]), ("data",))
    topo = CSRTopo(edge_index=np.stack(make_random_csr(400, 6, seed=41)))
    rng = np.random.default_rng(41)
    feat = rng.normal(size=(topo.node_count, 12)).astype(np.float32)
    sampler = DistGraphSampler(topo, mesh, sizes=[4, 3])
    store = DistFeature.from_row_ranges(feat, mesh, sampler.row_starts_host)
    seeds = rng.integers(0, topo.node_count, (RANKS, B))
    n_id, n_mask, _, blocks = sampler.sample(seeds, key=0)
    xs = store.lookup(n_id, n_mask)
    model = GraphSAGE(hidden=16, out_dim=CLASSES, num_layers=2, dropout=0.5)

    def apply_fn(p, x, blocks, train=False, rngs=None):
        return model.apply(p, x, blocks, train=train, rngs=rngs)

    tx = optax.adam(1e-2)
    params = model.init(jax.random.PRNGKey(1), xs[0],
                        jax.tree.map(lambda l: l[0], blocks))
    step = make_train_step(apply_fn, tx, mesh=mesh)

    def state():    # donated by the step: one a call
        return replicate(mesh, TrainState.create(
            jax.tree.map(jnp.copy, params), tx))

    def train(n_id, n_mask, blocks, key):
        return step(state(), store.lookup(n_id, n_mask), blocks,
                    jnp.asarray(seeds % CLASSES, jnp.int32),
                    jnp.ones((RANKS, B), bool), jax.random.PRNGKey(key))

    train(n_id, n_mask, blocks, 0)
    return sampler, store, seeds, train


SHARDED_CALLS = {
    "sample": (HOST_SAMPLE, lambda s: s[0].sample(s[2], key=3)),
    "lookup": (HOST_LOOKUP, lambda s: s[1].lookup(
        *s[0].sample(s[2], key=4)[:2])),
}


@pytest.mark.parametrize("call", sorted(SHARDED_CALLS))
def test_a_sharded_call_is_one_span_with_place_and_launch_inside(
        traced, sharded, call):
    """``DistGraphSampler.sample`` / ``DistFeature.lookup``: the whole call
    is one span; putting the arguments onto the mesh and the launch of the
    program are one part each, inside it, on its thread, in that order."""
    name, run = SHARDED_CALLS[call]
    run(sharded)
    if call == "lookup":        # the sample that fed it is not the subject
        assert len(_by_name(traced)[HOST_SAMPLE]) == 1
    run(sharded)
    events = _by_name(traced)
    whole, place, launch = (events[n] for n in (name, name + PLACE,
                                                name + LAUNCH))
    assert len(whole) == len(place) == len(launch) == 2   # once a call
    for w, p, l in zip(whole, place, launch):
        assert _inside(p, w) and _inside(l, w)
        assert p["ts_us"] + p["dur_us"] <= l["ts_us"]
        assert w["tid"] == threading.get_ident() and w["depth"] == 0


def test_a_sharded_step_is_three_top_level_spans_in_order(traced, sharded):
    """sample -> lookup -> the data-parallel step, as the four-chip cell
    calls them: three spans at depth 0 on the caller's thread, none inside
    another, ``step.train`` around the jitted call alone."""
    sampler, store, seeds, train = sharded
    n_id, n_mask, _, blocks = sampler.sample(seeds, key=5)
    state, loss = train(n_id, n_mask, blocks, 5)
    assert np.isfinite(float(loss))
    top = [e for e in sorted(traced.events(), key=lambda e: e["ts_us"])
           if e["depth"] == 0]
    assert [e["name"] for e in top] == [HOST_SAMPLE, HOST_LOOKUP,
                                        HOST_STEP_TRAIN]
    for a, b in zip(top, top[1:]):
        assert a["ts_us"] + a["dur_us"] <= b["ts_us"]
    assert not [e for e in traced.events()
                if e["name"].startswith(HOST_STEP_TRAIN + ".")]
    summary = traced.summary()
    assert summary[HOST_STEP_TRAIN]["count"] == 1
    assert summary[HOST_SAMPLE + LAUNCH]["count"] == 1


# --------------------------------------------------- the fused programs
@pytest.fixture(scope="module")
def fused():
    topo, feat, comm = community_graph(300, 4, seed=5)
    feature = Feature(device_cache_size="1G").from_cpu_tensor(feat)
    sampler = GraphSageSampler(topo, [4, 3])
    model = GraphSAGE(hidden=16, out_dim=4, num_layers=2, dropout=0.5)
    seeds = jnp.arange(B, dtype=jnp.int32)
    b0 = sampler.sample(np.asarray(seeds))
    params = model.init(jax.random.PRNGKey(0), feature[b0.n_id], b0.layers)
    labels = jnp.asarray(np.asarray(comm)[:B])

    def apply_fn(p, x, blocks, train=False, rngs=None):
        return model.apply(p, x, blocks, train=train, rngs=rngs)

    tx = optax.adam(1e-2)

    def state():
        return TrainState.create(jax.tree.map(jnp.copy, params), tx)

    step = make_fused_train_step(sampler, feature, apply_fn, tx)
    epoch = make_scan_epoch(sampler, feature, apply_fn, tx)
    ev = make_fused_eval_fn(sampler, feature, apply_fn)
    key = jax.random.PRNGKey(2)
    return {
        HOST_STEP_TRAIN: lambda: step(state(), seeds, labels,
                                      jnp.ones((B,), bool), key),
        HOST_STEP_EPOCH: lambda: epoch(state(), jnp.stack([seeds, seeds]),
                                       jnp.stack([labels, labels]), key),
        HOST_STEP_EVAL: lambda: ev(params, seeds, key),
    }


@pytest.mark.parametrize("name", [HOST_STEP_TRAIN, HOST_STEP_EPOCH,
                                  HOST_STEP_EVAL])
def test_a_fused_program_is_one_span_a_call(traced, fused, name):
    """The three wrappers of ``pipeline.py`` are alike: each call of the
    jitted program is one span of its own name, at depth 0, and enters no
    other span of the step path."""
    fused[name]()
    telemetry.reset()           # the first call traced and registered
    for _ in range(3):
        jax.block_until_ready(fused[name]())
    events = traced.events()
    assert [e["name"] for e in events] == [name] * 3
    assert {e["depth"] for e in events} == {0}
    assert traced.summary()[name]["count"] == 3


def test_with_telemetry_off_the_step_path_gets_the_noop_span(
        traced, sharded, fused, monkeypatch):
    """``QUIVER_TELEMETRY=off``: every span of the step path is the shared
    do-nothing object; the live tracer is never asked for one."""
    asked = []
    live = telemetry._tracer
    monkeypatch.setattr(live, "span", lambda name, block=None: asked.append(
        name) or noop.SPAN)
    telemetry.set_enabled(False)
    assert telemetry.span(HOST_STEP_TRAIN) is noop.SPAN
    sampler, store, seeds, train = sharded
    n_id, n_mask, _, blocks = sampler.sample(seeds, key=6)
    train(n_id, n_mask, blocks, 6)
    fused[HOST_STEP_TRAIN]()
    assert asked == [] and live.summary() == {}
    telemetry.set_enabled(True)
    sampler.sample(seeds, key=7)
    assert asked == [HOST_SAMPLE, HOST_SAMPLE + PLACE, HOST_SAMPLE + LAUNCH]


STEP_PATH = ["quiver_tpu/dist/sampler.py", "quiver_tpu/dist/feature.py",
             "quiver_tpu/parallel/train.py", "quiver_tpu/pipeline.py",
             "quiver_tpu/sampler.py", "quiver_tpu/feature.py"]


@pytest.mark.parametrize("path", STEP_PATH)
def test_no_span_of_the_step_path_blocks_on_the_device(path):
    """A span of the step path times how long the CALLER's thread is held:
    it is handed a name and nothing else (``block=`` would wait for the
    device inside the interval, and serialise what it measures), and the
    name is a constant of ``telemetry/device_scopes.py``."""
    with open(os.path.join(ROOT, path)) as f:
        tree = ast.parse(f.read())
    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute) and n.func.attr == "span"
             and getattr(n.func.value, "id", None) == "telemetry"]
    assert calls, path
    for c in calls:
        assert len(c.args) == 1 and not c.keywords, ast.dump(c)
        names = {n.id for n in ast.walk(c.args[0])
                 if isinstance(n, ast.Name)}
        assert names and all(n.startswith("HOST_") or n in ("PLACE",
                                                            "LAUNCH")
                             for n in names), ast.dump(c)


# ------------------------------------------------- the longest single call
def test_summary_keeps_the_longest_call_and_reset_clears_it(traced):
    """``max_ms`` beside ``count`` and ``mean_ms``: what an untraced run has
    of a step that stalled once."""
    for dt in (0.002, 0.040, 0.001):
        traced._close("stall", 10.0, 10.0 + dt, 0)
    traced._close("steady", 1.0, 1.5, 0)
    s = traced.summary()
    assert s["stall"]["count"] == 3
    assert s["stall"]["max_ms"] == pytest.approx(40.0)
    assert s["stall"]["mean_ms"] == pytest.approx(43.0 / 3)
    assert s["steady"]["max_ms"] == s["steady"]["mean_ms"] == \
        pytest.approx(500.0)
    with telemetry.span("real"):
        pass
    real = traced.summary()["real"]
    assert 0.0 <= real["mean_ms"] == pytest.approx(real["max_ms"])
    traced.reset()
    assert traced.summary() == {}
    traced._close("stall", 0.0, 0.003, 0)
    assert traced.summary()["stall"]["max_ms"] == pytest.approx(3.0)
    assert "max_ms" not in noop.TRACER.summary().get("stall", {})


# --------------------------------------------------- the per-layer readers
@pytest.fixture(scope="module")
def load_reader():
    """``cellbench/run.py``'s own loader, so that a reader is found as the
    benchmark finds it: by the metric's name."""
    sys.path[:0] = [p for p in (ROOT, BENCH) if p not in sys.path]
    import run

    return lambda name: run.load_named("metrics", name)


def span(count, seconds, idle):
    return {"count": count, "seconds": seconds, "idle_overlap_s": idle}


# 10 traced steps in a window of 2 s; seconds over the whole window
STEPS, WINDOW_S = 10, 2.0
SPANS = {
    "qt.sampler.sample": span(10, 0.050, 0.004),
    "qt.sampler.sample.place": span(10, 0.030, 0.003),
    "qt.sampler.sample.launch": span(10, 0.015, 0.001),
    "qt.feature.lookup": span(10, 0.020, 0.002),
    "qt.feature.lookup.place": span(10, 0.012, 0.002),
    "qt.feature.lookup.launch": span(10, 0.006, 0.0),
    "qt.step.train": span(10, 0.300, 0.010),
    "cb.dispatch": span(10, 0.500, 0.030),
    "cb.wait_result": span(8, 0.900, 0.020),
}
# metric -> (its value on SPANS, the spans it needs at least one of)
READERS = {
    "sampler_host_ms.train": (5.0, ["qt.sampler.sample"]),
    "feature_host_ms.train": (2.0, ["qt.feature.lookup"]),
    "step_launch_host_ms.train": (30.0, ["qt.step.train"]),
    "library_host_pct.train": (
        100.0 * (0.050 + 0.020 + 0.300) / WINDOW_S,
        ["qt.sampler.sample", "qt.feature.lookup", "qt.step.train"]),
    "device_idle_in_library_pct.train": (
        100.0 * (0.004 + 0.002 + 0.010) / WINDOW_S,
        ["qt.sampler.sample", "qt.feature.lookup", "qt.step.train"]),
}


def ctx_of(spans, kind="train", steps=STEPS):
    return {"facts": {"kind": kind, "traced_steps": steps},
            "trace": None if spans is None else {
                "window_s": WINDOW_S, "busy_s": 1.9, "idle_gaps_device": 0,
                "host_spans": spans}}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_takes_its_value_from_the_spans(load_reader, metric, capsys):
    """Seconds a traced step (``facts["traced_steps"]``, not the span's own
    count), shares of the window; a part is inside its parent and is added
    to no sum."""
    want, needs = READERS[metric]
    read = load_reader(metric).read
    assert read(ctx_of(SPANS)) == pytest.approx(want)
    # twice as many calls in the same seconds: the same value a step
    twice = {n: dict(sp, count=2 * sp["count"]) for n, sp in SPANS.items()}
    assert read(ctx_of(twice)) == pytest.approx(want)
    # the parts taken away: nothing moves, they were never counted
    whole = {n: sp for n, sp in SPANS.items()
             if not n.endswith((".place", ".launch"))}
    assert read(ctx_of(whole)) == pytest.approx(want)
    # one top-level span alone, as on a one-chip cell
    alone = {n: SPANS[n] for n in ("qt.step.train", "cb.dispatch")}
    got = read(ctx_of(alone))
    if "qt.step.train" in needs:
        field = ("idle_overlap_s" if metric.startswith("device_idle")
                 else "seconds")
        scale = 1e3 / STEPS if metric.endswith("_ms.train") \
            else 100.0 / WINDOW_S
        assert got == pytest.approx(SPANS["qt.step.train"][field] * scale)
    else:
        assert got is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_none_never_zero_where_its_span_is_missing(load_reader,
                                                             metric):
    """A parent commit has no such span: the reader answers None and the
    result line leaves the metric out.  So does a run with no trace, no
    traced step, another kind, or a span that was never entered."""
    want, needs = READERS[metric]
    read = load_reader(metric).read
    harness_only = {n: sp for n, sp in SPANS.items() if n.startswith("cb.")}
    without = {n: sp for n, sp in SPANS.items() if n not in needs}
    never = dict(without, **{n: span(0, 0.0, 0.0) for n in needs})
    for ctx in (ctx_of(None), ctx_of({}), ctx_of(harness_only),
                ctx_of(without), ctx_of(never), ctx_of(SPANS, steps=None),
                ctx_of(SPANS, steps=0), ctx_of(SPANS, kind="lookup")):
        assert read(ctx) is None
    no_window = ctx_of(SPANS)
    no_window["trace"]["window_s"] = 0.0
    if metric.endswith("_pct.train"):
        assert read(no_window) is None


def test_readers_log_every_span_beside_its_parts_once(load_reader, capsys):
    """``.place`` and ``.launch`` have no metric of their own: they are read
    in the log, under their parent, with what of ``cb.dispatch`` is left to
    the harness."""
    sys.modules.pop("host_spans", None)     # a fresh memo of "logged"
    for metric in sorted(READERS):
        sys.modules.pop("cb_metrics_" + metric.replace(".", "_"), None)
        load_reader(metric).read(ctx_of(SPANS))
    err = capsys.readouterr().err
    assert err.count("host_spans:") == 1
    lines = [l.rstrip() for l in err.splitlines() if " ms " in l]
    order = [l.split()[-1] for l in lines if l.split()[-1].startswith("qt.")]
    assert order == ["qt.feature.lookup", "qt.feature.lookup.launch",
                     "qt.feature.lookup.place", "qt.sampler.sample",
                     "qt.sampler.sample.launch", "qt.sampler.sample.place",
                     "qt.step.train"]
    assert "top-level spans together 37.000 ms a step" in err
    assert "cb.dispatch 50.000 ms a step, so 13.000 are the harness's" in err


# ------------------------------------------------------- the benchmark file
# sha256 of what ``BENCHMARK.json`` held at commit b4cbdf5 (PR 37), each list
# as ``json.dumps(..., sort_keys=True)``: a PR that is not a ``benchmark``
# PR may append to the lists, and append new cells to an entry's
# ``workloads``, and do nothing else.  A ``benchmark`` PR that means to
# edit what is there records the new hashes here.
ACCEPTED = {     # key -> (entries it had, their hash)
    "configs": (4, "4e5d6c6438a8bc191370f70d4c1546e5ee0122f2006276a669e971d21d1e3f84"),
    "workloads": (4, "c148ec71d89e0298f243fba94b0de81306c0a89a0d784649b2b161654448b51f"),
    "end_to_end": (2, "2f027a3ee26804757fe2479e87bbb1671b6c52434972239c5adfe51b461caa2e"),
    "per_layer": (17, "266cb38a75e267478d9235451759b6e5a6519dc68564719667f8f124d4f1ab13"),
}
ACCEPTED_TOP = "399008cfa1c2ddbe2052ef70bec34abff12a7ea8cd45cb84137e4859b58ef165"
NEW_IN_PR_38 = ["sampler_host_ms.train", "feature_host_ms.train",
                "step_launch_host_ms.train", "library_host_pct.train",
                "device_idle_in_library_pct.train"]


def _sha(value):
    return hashlib.sha256(
        json.dumps(value, sort_keys=True).encode()).hexdigest()


def test_benchmark_json_only_grew():
    """What the accepted benchmark had comes first and as it was, but for
    new cells appended to an entry's ``workloads`` after the cells it
    listed; every entry after it names files that exist and cells that
    exist, and every new cell reports the end-to-end metrics that list
    cells."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    top = {k: bench[k] for k in ("command", "paths", "run_seconds")}
    assert set(bench) == set(top) | set(ACCEPTED)
    assert _sha(top) == ACCEPTED_TOP
    was = {w["name"] for w in bench["workloads"][:ACCEPTED["workloads"][0]]}
    for key, (n, sha) in ACCEPTED.items():
        kept = []
        for entry in bench[key][:n]:
            entry = dict(entry)
            if "workloads" in entry:
                old = [w for w in entry["workloads"] if w in was]
                assert entry["workloads"][:len(old)] == old, entry["name"]
                entry["workloads"] = old
            kept.append(entry)
        assert _sha(kept) == sha, key
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"][ACCEPTED["configs"][0]:]:
        assert os.path.isfile(os.path.join(ROOT, c["file"])), c["name"]
    for w in bench["workloads"][ACCEPTED["workloads"][0]:]:
        assert w["config"] in configs and os.path.isfile(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json")), w["name"]
        assert all(w["name"] in m["workloads"] for m in bench["end_to_end"]
                   if "workloads" in m), w["name"]
    moved = {m["name"] for m in bench["end_to_end"]}
    added = bench["per_layer"][ACCEPTED["per_layer"][0]:]
    assert [m["name"] for m in added][:len(NEW_IN_PR_38)] == NEW_IN_PR_38
    for m in added:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
        assert m["workloads"] and set(m["workloads"]) <= cells, m["name"]
        assert m["moves"] in moved and m["better"] in ("lower", "higher")
    for m in added[:len(NEW_IN_PR_38)]:
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["moves"] == "train_seeds_per_s"
