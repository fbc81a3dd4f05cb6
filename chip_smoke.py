"""The quickest proof that quiver_tpu still starts on the chip.

``python chip_smoke.py`` drives the main path once on ONE TPU chip, through
the entry points a user calls, at published widths, on data made from
``--seed``:

  * train — ogbn-products shape (2,449,029 nodes, ~123.7M edges, 100-d
    float32 features all in HBM, 47 classes, 3-layer hidden-256 GraphSAGE,
    fanout [15,10,5], B=1024): ``CSRTopo`` -> ``GraphSageSampler`` (no mode
    kwargs, so ``config.resolve_*`` pick what they pick on a TPU) ->
    ``Feature`` -> ``SeedLoader`` -> ``parallel.make_train_step``, then
    ``pipeline.make_fused_train_step``;
  * serve — Reddit shape (232,965 nodes, ~114.6M edges, 602-d, 41 classes,
    2-layer hidden-128 GraphSAGE, fanout [25,10]): ``RequestBatcher(mode=
    "Device")`` -> ``InferenceServer.warmup()``, no ``cpu_sampler`` wired.

``python chip_smoke.py --chips 4`` runs, and runs only, what exists across
chips — ``dist/`` training over a 4-device mesh and ``mesh/`` sharded
sample -> gather — each compared with host truth or the one-device path.

Every phase checks its output against the host CSR / the host table / a
direct call on the same key, outside any timing.  The times it prints are
smoke timings of a handful of calls, not results.  It refuses to run
without a TPU: it exits non-zero before building anything and never
prints a result line.  The phases take their sizes as arguments so that
``tests/test_chip_smoke.py`` can rehearse them tiny on the CPU.

The last line of standard output is the one JSON object the driver reads.
"""

import argparse
import contextlib
import gc
import json
import queue
import sys
import time
from dataclasses import dataclass
from typing import Tuple

import numpy as np


@dataclass(frozen=True)
class Shape:
    """One deployment shape: graph scale, widths, model, traffic."""

    nodes: int
    edges: int
    dim: int
    classes: int
    hidden: int
    fanout: Tuple[int, ...]
    batch: int = 1024


# bench.py:46-50 / examples/ogbn_products_sage.py / examples/serving_reddit.py
PRODUCTS = Shape(2_449_029, 123_718_280, 100, 47, 256, (15, 10, 5))
REDDIT = Shape(232_965, 114_615_892, 602, 41, 128, (25, 10))
# __graft_entry__.dryrun_multichip's rows at products widths; batch is
# per device (4 x 256 = the products batch)
FOUR_CHIP = Shape(1_000_000, 12_000_000, 100, 47, 256, (15, 10, 5), 256)


def log(*a):
    print(*a, flush=True)


class SmokeFailure(AssertionError):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------ observers
class CompileWatch:
    """Backend compiles and persistent-cache traffic, from JAX's own
    monitoring events — what ``analysis.retrace_guard`` listens to."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **kw):
        if event.endswith("/cache_hits"):
            self.cache_hits += 1
        elif event.endswith("/cache_misses"):
            self.cache_misses += 1

    @contextlib.contextmanager
    def program(self, name):
        """Log what the first call of one program cost."""
        n, s, t0 = self.compiles, self.compile_s, time.perf_counter()
        yield
        log(f"  compile {name}: {self.compiles - n} programs, "
            f"{self.compile_s - s:.1f} s in the compiler, "
            f"{time.perf_counter() - t0:.1f} s first call")


class PallasWatch:
    """Records ``interpret=`` of every ``pallas_call`` traced while
    installed: nothing else says which of Mosaic and the interpreter a
    call site picked."""

    def __init__(self):
        from jax.experimental import pallas as pl

        self.calls = []
        self._pl, self._orig = pl, pl.pallas_call

        def recording(kernel, *a, **kw):
            self.calls.append((getattr(kernel, "__qualname__", str(kernel)),
                               bool(kw.get("interpret", False))))
            return self._orig(kernel, *a, **kw)

        pl.pallas_call = recording

    def close(self):
        self._pl.pallas_call = self._orig

    def check(self, on_chip):
        log(f"  pallas calls traced: {len(self.calls)} "
            f"{sorted(set(self.calls))}")
        if not on_chip:
            return
        check(not any(interp for _, interp in self.calls),
              f"a Pallas call ran in interpret mode on the chip: "
              f"{self.calls}")


def peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# --------------------------------------------------------- host truth
def neighbours_valid(indptr, indices, src, nbr):
    """``nbr[i]`` is a neighbour of ``src[i]`` in the host CSR, for every
    ``i`` — a scan of each row with the unresolved pairs only."""
    start = indptr[src]
    deg = indptr[src + 1] - start
    found = np.zeros(len(src), bool)
    active = np.flatnonzero(deg > 0)
    t = 0
    while active.size:
        hit = indices[start[active] + t] == nbr[active]
        found[active[hit]] = True
        t += 1
        active = active[~hit]
        active = active[deg[active] > t]
    return bool(found.all())


def check_sampled(topo, sizes, n_id, n_mask, layers, what):
    """Every sampled neighbour is a neighbour of its target in the host
    CSR, and every target drew ``min(deg, k)`` of them."""
    n_id, n_mask = np.asarray(n_id), np.asarray(n_mask)
    indptr, indices = topo.indptr, topo.indices
    deg = indptr[1:] - indptr[:-1]
    check(len(layers) == len(sizes), f"{what}: {len(layers)} layers")
    edges = 0
    for k, blk in zip(sizes, layers[::-1]):       # layers: outermost first
        mask = np.asarray(blk.mask)
        nbr_local = np.asarray(blk.nbr_local)
        t = mask.shape[0]
        check(mask.shape == (t, k), f"{what}: block shape {mask.shape}")
        want = np.where(n_mask[:t], np.minimum(deg[n_id[:t]], k), 0)
        check(np.array_equal(mask.sum(axis=1), want),
              f"{what}: fanout {k} masks disagree with min(deg, k)")
        check(np.array_equal(mask, np.arange(k)[None, :] < want[:, None]),
              f"{what}: fanout {k} masks are not prefixes")
        tgt, col = np.nonzero(mask)
        check(neighbours_valid(indptr, indices, n_id[tgt],
                               n_id[nbr_local[tgt, col]]),
              f"{what}: fanout {k} drew a non-neighbour")
        edges += len(tgt)
    return edges


# ---------------------------------------------------------------- train
def train_phase(shape, seed, steps, on_chip, watch):
    import jax
    import jax.numpy as jnp
    import optax

    from quiver_tpu import (CSRTopo, Feature, GraphSageSampler, SeedLoader,
                            make_key)
    from quiver_tpu.analysis.retrace_guard import count_jit_builds
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.parallel import TrainState, make_train_step
    from quiver_tpu.pipeline import make_fused_train_step
    from quiver_tpu.utils.synthetic import synthetic_csr

    log(f"train phase: {shape}")
    pallas = PallasWatch()
    rng = np.random.default_rng(seed)
    B = shape.batch

    t0 = time.perf_counter()
    indptr, indices = synthetic_csr(shape.nodes, shape.edges, seed)
    topo = CSRTopo(indptr=indptr, indices=indices)
    feat = rng.standard_normal((shape.nodes, shape.dim), dtype=np.float32)
    labels = rng.integers(0, shape.classes, shape.nodes).astype(np.int32)
    t1 = time.perf_counter()
    sampler = GraphSageSampler(topo, list(shape.fanout))
    feature = Feature(device_cache_size=shape.nodes,
                      cache_unit="rows").from_cpu_tensor(feat)
    jax.block_until_ready((topo.to_device(), feature.hot))
    log(f"  graph + features: {t1 - t0:.1f} s to make "
        f"(N={topo.node_count:,} E={topo.edge_count:,} D={shape.dim}), "
        f"{time.perf_counter() - t1:.1f} s to upload")
    log(f"  resolved gather_mode={sampler.gather_mode} "
        f"sample_rng={sampler.sample_rng} dedup={sampler.dedup}")
    check(feature.cache_count == shape.nodes, "features not all in HBM")

    # -- one batch against host truth, outside any timing
    seeds = rng.integers(0, shape.nodes, B)
    with watch.program(f"sampler B={B}"):
        batch = sampler.sample(seeds, key=make_key(seed))
        jax.block_until_ready(batch.n_id)
    edges = check_sampled(topo, shape.fanout, batch.n_id, batch.n_id_mask,
                          batch.layers, "train sampler")
    check(np.array_equal(np.asarray(batch.n_id)[:B], seeds),
          "frontier does not start with the seeds")
    with watch.program(f"feature gather {batch.n_id.shape[0]:,} rows"):
        x = feature[batch.n_id]
        jax.block_until_ready(x)
    check(np.array_equal(np.asarray(x), feat[np.asarray(batch.n_id)]),
          "gathered rows differ from the host table")
    log(f"  checked: {edges:,} sampled edges are host-CSR neighbours, "
        f"masks = min(deg, k), {x.shape[0]:,} x {x.shape[1]} gathered "
        f"rows bit-equal to the host table")

    model = GraphSAGE(hidden=shape.hidden, out_dim=shape.classes,
                      num_layers=len(shape.fanout))

    def apply_fn(p, x, blocks, train=False, rngs=None):
        return model.apply(p, x, blocks, train=train, rngs=rngs)

    b0 = sampler.sample(seeds[:8], key=make_key(seed))
    params = jax.jit(model.init)(make_key(1), feature[b0.n_id], b0.layers)
    tx = optax.adam(3e-3)
    train_idx = rng.choice(shape.nodes, steps * B, replace=False)

    def run(name, one_step):
        """``steps`` calls; the second may build no new executable."""
        losses, ms = [], []
        for i in range(steps):
            guard = (count_jit_builds() if i == 1
                     else contextlib.nullcontext())
            compiles, t = watch.compiles, time.perf_counter()
            with guard as built, (watch.program(name) if i == 0
                                  else contextlib.nullcontext()):
                losses.append(float(one_step(i)))   # waits for the device
            ms.append((time.perf_counter() - t) * 1e3)
            if built is not None:
                check(built.builds == 0 and watch.compiles == compiles,
                      f"{name}: second step built {built.describe()}, "
                      f"{watch.compiles - compiles} backend compiles")
        check(all(np.isfinite(l) for l in losses), f"{name}: loss {losses}")
        log(f"  {name}: losses {[round(l, 4) for l in losses]}, smoke "
            f"timing ms/step after the first "
            f"{[round(m, 1) for m in ms[1:]]}; second step built nothing")

    # -- sample, then feature[n_id], then step (SeedLoader does the first
    #    two, one batch ahead, on its prefetch thread)
    step = make_train_step(apply_fn, tx)
    state = [TrainState.create(jax.tree_util.tree_map(jnp.copy, params), tx)]
    loader = iter(SeedLoader(train_idx, sampler, feature, labels,
                             batch_size=B, shuffle=False, seed=seed))

    def unfused(i):
        bt, x, lab, mask = next(loader)
        state[0], loss = step(state[0], x, bt.layers, lab, mask,
                              make_key(100 + i))
        return loss

    run("train step (SeedLoader + make_train_step)", unfused)

    # -- the same in one program
    fused = make_fused_train_step(sampler, feature, apply_fn, tx)
    fstate = [TrainState.create(params, tx)]
    ones = jnp.ones((B,), bool)

    def fused_step(i):
        s = train_idx[i * B:(i + 1) * B]
        fstate[0], loss = fused(fstate[0], jnp.asarray(s, jnp.int32),
                                jnp.asarray(labels[s]), ones,
                                make_key(200 + i))
        return loss

    run("fused train step (make_fused_train_step)", fused_step)
    pallas.check(on_chip)
    pallas.close()
    log(f"  peak_bytes_in_use: {peak_bytes(jax.devices()[0])}")


# ---------------------------------------------------------------- serve
def serve_phase(shape, seed, n_requests, on_chip, watch):
    import jax

    from quiver_tpu import (CSRTopo, Feature, GraphSageSampler,
                            InferenceServer, RequestBatcher, make_key,
                            telemetry)
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.serving import ServingRequest
    from quiver_tpu.utils.synthetic import synthetic_csr

    log(f"serve phase: {shape}")
    telemetry.set_enabled(True)     # the counters below must be live
    pallas = PallasWatch()
    rng = np.random.default_rng(seed + 1)

    t0 = time.perf_counter()
    indptr, indices = synthetic_csr(shape.nodes, shape.edges, seed + 1)
    topo = CSRTopo(indptr=indptr, indices=indices)
    feat = rng.standard_normal((shape.nodes, shape.dim), dtype=np.float32)
    t1 = time.perf_counter()
    sampler = GraphSageSampler(topo, list(shape.fanout))
    feature = Feature(device_cache_size=shape.nodes,
                      cache_unit="rows").from_cpu_tensor(feat)
    jax.block_until_ready((topo.to_device(), feature.hot))
    log(f"  graph + features: {t1 - t0:.1f} s to make "
        f"(N={topo.node_count:,} E={topo.edge_count:,} D={shape.dim}), "
        f"{time.perf_counter() - t1:.1f} s to upload")
    log(f"  resolved gather_mode={sampler.gather_mode} "
        f"sample_rng={sampler.sample_rng} dedup={sampler.dedup}")

    model = GraphSAGE(hidden=shape.hidden, out_dim=shape.classes,
                      num_layers=len(shape.fanout), dropout=0.0)
    b0 = sampler.sample(np.arange(8), key=make_key(seed))
    params = jax.jit(model.init)(make_key(1), feature[b0.n_id], b0.layers)
    apply_fn = jax.jit(lambda p, x, blocks: model.apply(p, x, blocks))

    def counter_total(name):
        return sum(v for key, v in telemetry.snapshot()["counters"].items()
                   if telemetry.parse_metric_key(key)[0] == name)

    before = {n: counter_total(n) for n in
              ("serving_failover_total", "serving_shed_total")}
    results, stream = queue.Queue(), queue.Queue()
    batcher = RequestBatcher([stream], mode="Device",
                             result_queue=results).start()
    server = InferenceServer(sampler, feature, apply_fn, params,
                             batcher.device_batched_queue,
                             result_queue=results)       # no cpu_sampler
    check(server._fused, "the fused device lane was not selected")
    try:
        with watch.program(f"warmup, buckets {server.BUCKETS}"):
            server.warmup()
        server.start()

        def submit(seq, ids):
            stream.put(ServingRequest(ids=ids, client=0, seq=seq))

        def collect(n):
            got = {}
            for _ in range(n):
                req, out = results.get(timeout=300)
                check(not isinstance(out, BaseException),
                      f"request {req.seq} answered with {out!r}")
                check(out.shape == (len(req.ids), shape.classes)
                      and np.isfinite(out).all(),
                      f"request {req.seq}: logits {out.shape}")
                got[req.seq] = (req, out)
            return got

        # -- one request at a time, so that a device pass is one request
        #    and its key is known: the server draws it from numpy's
        #    global stream, which is seeded here just before
        worst, ms = 0.0, []
        for seq, n in enumerate((1, 20, 100, 128)):
            ids = rng.integers(0, shape.nodes, n)
            np.random.seed(seed + seq)
            t = time.perf_counter()
            submit(seq, ids)
            _, out = collect(1)[seq]
            ms.append((time.perf_counter() - t) * 1e3)
            key = make_key(np.random.RandomState(seed + seq).randint(
                0, 2**31 - 1))
            padded = server._pad_ids(ids)
            bt = sampler.sample(padded, key=key)
            ref = np.asarray(apply_fn(params, feature[bt.n_id],
                                      bt.layers))[:n]
            worst = max(worst, float(np.abs(out - ref).max()))
            # one fused program against three: the same float32 sums in
            # another order, so a tolerance from the dtype, not equality
            check(np.allclose(out, ref, rtol=1e-4, atol=1e-4),
                  f"request of {n} seeds: logits differ from the direct "
                  f"call by {np.abs(out - ref).max()}")
        log(f"  checked: 4 requests equal sampler.sample -> feature[...] "
            f"-> model.apply on the same padded seeds and key (max abs "
            f"diff {worst:.2e}); smoke timing ms/request "
            f"{[round(m, 1) for m in ms]}")

        # -- then a burst: small and large requests in one queue
        t = time.perf_counter()
        for seq in range(4, n_requests):
            submit(seq, rng.integers(0, shape.nodes, rng.integers(1, 129)))
        collect(n_requests - 4)
        log(f"  {n_requests - 4} more requests of 1-128 seeds answered, "
            f"none with an error, in "
            f"{(time.perf_counter() - t) * 1e3:.0f} ms (smoke timing)")
    finally:
        leaked = batcher.stop() or []
        leaked += server.stop() or []
    check(not leaked, f"serving threads still running: {leaked}")
    for name, was in before.items():
        check(counter_total(name) == was,
              f"{name} moved: {counter_total(name) - was}")
    log("  serving_failover_total and serving_shed_total did not move")
    pallas.check(on_chip)
    pallas.close()
    log(f"  peak_bytes_in_use: {peak_bytes(jax.devices()[0])}")


# ----------------------------------------------------------- four chips
def check_split(what, holdings, n):
    """``holdings``: (device, bytes) of each piece of one sharded
    structure.  Fails unless ``n`` devices each hold a fair share."""
    log(f"  {what}: " + ", ".join(f"{d}={b:,} B" for d, b in holdings))
    total = sum(b for _, b in holdings)
    per_device = {}
    for d, b in holdings:
        per_device[str(d)] = per_device.get(str(d), 0) + b
    check(len(per_device) == n and
          max(per_device.values()) <= 1.5 * total / n,
          f"{what} is not split {n} ways: {per_device}")


def array_holdings(arr):
    return [(s.device, s.data.nbytes) for s in arr.addressable_shards]


def check_memory(devices):
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        log("  memory_stats: not reported by this backend")
        return
    used = [s["bytes_in_use"] for s in stats]
    log("  bytes_in_use: " + ", ".join(
        f"{d}={u:,}" for d, u in zip(devices, used)))
    check(max(used) < 0.5 * sum(used),
          f"one device holds most of what is on the chips: {used}")


def dist_phase(shape, seed, devices, watch):
    """``dist/``: row-sharded DistGraphSampler + all-to-all DistFeature +
    psum'd gradients, against host truth."""
    import jax

    from quiver_tpu.dist.e2e import run_dist_training

    n = len(devices)
    log(f"dist phase on {n} devices: {shape}")
    with watch.program("dist training, 3 steps"):
        out = run_dist_training(
            n_devices=n, n_nodes=shape.nodes,
            avg_deg=shape.edges // shape.nodes, feat_dim=shape.dim,
            batch_per_dev=shape.batch, sizes=list(shape.fanout), steps=3,
            classes=shape.classes, hidden=shape.hidden, seed=seed)
    sampler, feature = out["sampler"], out["feature"]
    topo, feat = out["topo"], out["feat"]
    log(f"  DistGraphSampler ran gather_mode={sampler.gather_mode} "
        f"sample_rng={sampler.sample_rng}; losses "
        f"{[round(l, 4) for l in out['losses']]}")
    check(all(np.isfinite(l) for l in out["losses"]), "dist loss")
    check(out["sampler_overflow"].sum() == 0 and
          out["feature_overflow"] == 0, "dist overflow counters moved")

    check_split("DistGraphSampler.indices_sh",
                array_holdings(sampler.indices_sh), n)
    check_split("DistGraphSampler.indptr_sh",
                array_holdings(sampler.indptr_sh), n)
    check_split("DistFeature.shards", array_holdings(feature.shards), n)
    check_memory(devices)

    rng = np.random.default_rng(seed + 2)
    seeds = rng.integers(0, shape.nodes, (n, shape.batch))
    n_id, n_mask, _, blocks = sampler.sample(seeds, key=seed + 99)
    check(int(np.asarray(sampler.last_overflow).sum()) == 0,
          "sampler overflow")
    edges = 0
    for d in range(n):
        layers = jax.tree_util.tree_map(lambda a: a[d], blocks)
        edges += check_sampled(topo, shape.fanout, n_id[d], n_mask[d],
                               layers, f"dist sampler shard {d}")
    rows = feature.lookup(np.asarray(n_id))
    check(not feature.last_degraded and
          int(np.asarray(feature.last_overflow).sum()) == 0,
          "DistFeature degraded or overflowed")
    check(np.array_equal(np.asarray(rows), feat[np.asarray(n_id)]),
          "DistFeature rows differ from the host table")
    log(f"  checked: {edges:,} sampled edges are host-CSR neighbours, "
        f"{rows.shape} DistFeature rows bit-equal to the host table, "
        f"zero overflow")


def mesh_phase(shape, seed, devices, watch):
    """``mesh/`` with ``mesh_shards`` = the device count: MeshSampler ->
    MeshFeature, bit-identical to GraphSageSampler -> Feature."""
    from quiver_tpu import config

    n = len(devices)
    log(f"mesh phase, mesh_shards={n}: {shape}")
    was = config.get_config().mesh_shards
    config.update(mesh_shards=n)
    try:
        _mesh_phase(shape, seed, devices, watch)
    finally:
        config.update(mesh_shards=was)


def _mesh_phase(shape, seed, devices, watch):
    import jax

    from quiver_tpu import CSRTopo, Feature, GraphSageSampler, make_key
    from quiver_tpu.mesh import MeshFeature, MeshSampler
    from quiver_tpu.utils.synthetic import synthetic_csr

    n = len(devices)
    rng = np.random.default_rng(seed + 3)
    indptr, indices = synthetic_csr(shape.nodes, shape.edges, seed + 3)
    table = rng.standard_normal((shape.nodes, shape.dim), dtype=np.float32)
    B, k = shape.batch * n, shape.fanout[0]
    seeds = rng.integers(0, shape.nodes, B)
    key = make_key(seed)
    hop_key = jax.random.split(key, 1)[0]    # what sample() gives hop 0

    ms = MeshSampler(indptr, indices)
    mf = MeshFeature(table)
    with watch.program(f"mesh sample B={B} k={k} + gather"):
        got = ms.sample(seeds, k, hop_key)
        mask = np.asarray(got.mask)
        flat = np.where(mask, np.asarray(got.nbrs), 0).reshape(-1)
        got_rows = np.asarray(mf[np.concatenate([seeds, flat])])
    check_split("MeshSampler CSR",
                [(p["device"], p["bytes"])
                 for p in ms.stats()["placement"]], n)
    check_split("MeshFeature frame pools",
                [(s["device"], s["bytes"])
                 for s in mf.stats()["shards"]], n)
    check_split("MeshFeature sharded view", array_holdings(mf._frames_g), n)
    check_memory(devices)

    # the one-device path on the same key (it resolves its own RNG; the
    # mesh sampler's gather is XLA by signature, so that one is named)
    one = GraphSageSampler(CSRTopo(indptr=indptr, indices=indices), [k],
                           gather_mode=ms.gather_mode,
                           sample_rng=ms.sample_rng)
    batch = one.sample(seeds, key=key)
    check(np.array_equal(mask, np.asarray(batch.layers[0].mask)),
          "mesh masks differ from the one-device sampler")
    check(np.array_equal(np.concatenate([seeds, flat]),
                         np.asarray(batch.n_id)),
          "mesh neighbours differ from the one-device sampler")
    ref = Feature(device_cache_size=shape.nodes,
                  cache_unit="rows").from_cpu_tensor(table)
    check(np.array_equal(got_rows, np.asarray(ref[batch.n_id])),
          "MeshFeature rows differ from the one-device Feature")
    check(mf.stats()["fallbacks"] == 0, "MeshFeature fell back to the host")
    log(f"  checked: MeshSampler (gather_mode={ms.gather_mode} "
        f"sample_rng={ms.sample_rng}) -> MeshFeature bit-identical to "
        f"GraphSageSampler -> Feature on the same key: {int(mask.sum()):,}"
        f" neighbours, {got_rows.shape} rows")


# ----------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + serve on one chip; 4: the dist/ and "
                         "mesh/ paths across four, and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--requests", type=int, default=40)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r} ({len(devices)} devices); nothing "
              f"was built and there is no result", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} on {len(devices)} "
              f"devices", file=sys.stderr)
        return 2

    from quiver_tpu.cpp.native import native_available
    from quiver_tpu.utils import compile_cache

    watch = CompileWatch()
    log(f"device: {devices[0].platform} {devices[0].device_kind} x "
        f"{len(devices)}; jax {jax.__version__}; compile cache at "
        f"{compile_cache.enable()}")
    native = native_available()
    log(f"native_available(): {native}")
    check(native, "the native host sampler did not build (see the "
                  "compiler's stderr above)")

    t0 = time.perf_counter()
    if args.chips == 1:
        train_phase(PRODUCTS, args.seed, args.steps, True, watch)
        gc.collect()     # the products tables leave HBM before Reddit's
        serve_phase(REDDIT, args.seed, args.requests, True, watch)
    else:
        dist_phase(FOUR_CHIP, args.seed, devices[:4], watch)
        gc.collect()
        mesh_phase(FOUR_CHIP, args.seed, devices[:4], watch)
    log(f"all phases passed in {time.perf_counter() - t0:.0f} s: "
        f"{watch.compiles} backend compiles, {watch.compile_s:.0f} s in "
        f"the compiler; persistent cache hits {watch.cache_hits}, "
        f"misses {watch.cache_misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
