"""The program ``sage_dist`` (a configuration names it under
``"program"``): GraphSAGE trained over a graph and a feature table that are
divided by rows over the cell's devices, built as a user of
``quiver_tpu/dist/`` builds it (``dist/e2e.py`` is the library's own copy
of this recipe) with no mode or cap kwargs, so the library picks what it
picks on this device and the exchange's caps are its exact defaults:

  mesh over the devices the cell was given -> ``CSRTopo`` ->
  ``DistGraphSampler`` (row ranges balanced by edges) ->
  ``DistFeature.from_row_ranges`` (the sampler's ranges: one partition
  for both tables) -> ``GraphSAGE`` -> ``make_train_step(mesh=)``.

The one thing this file adds to that recipe is the benchmark's own: every
seed makes another graph of the same size, whose edge-balanced ranges
differ by a few thousand rows, and the library gives a shard the length of
its largest range (rounded up to the tile), so every seed would meet two
programs of a new shape (120 s of compiling, 58 MB more in a compile cache
of 192 MiB).  ``one_shape`` states a length that all of them fit, and the
sampler and the feature store are told it (``shard_rows``,
``shard_edges``): a deployment has one graph and leaves both out.

A step is three launches and no host read: ``sample`` (every rank its own
seeds; frontier ids routed to the rank that owns the row) -> ``lookup`` on
the device array the sampler returned, with its mask -> the data-parallel
train step (gradients averaged over the ranks).

What the harness reads back of a training program (the first gradient,
``make_key``) is ``sage_fused``'s.
"""

import numpy as np

from run import load_named

from quiver_tpu.dist.feature import DistFeature

if not hasattr(DistFeature, "from_row_ranges"):
    # a checkout from before the constructor: say so and leave at once,
    # before any data is made (``run.py`` loads this file first of all)
    raise SystemExit(
        "cellbench: the program sage_dist needs DistFeature.from_row_ranges "
        "(a row-range partition whose tables are the program's arguments); "
        "this checkout's quiver_tpu has none, so the cell cannot run here")

sage_fused = load_named("programs", "sage_fused")
planted = load_named("programs", "rgat_fused").planted


def one_shape(v):
    """``v`` rounded up to a multiple of a power of two between a sixteenth
    and an eighth of it: 27,774,070 and 27,768,776 rows both give
    29,360,128."""
    grain = 1 << max(int(v).bit_length() - 4, 0)
    return -(-int(v) // grain) * grain


class Program:
    """Graph, features and model of one configuration over a mesh."""

    first_gradient = staticmethod(sage_fused.Program.first_gradient)
    make_key = sage_fused.Program.make_key

    def __init__(self, cfg, data, devices, control=False, fault=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh

        from quiver_tpu import CSRTopo, DistFeature, DistGraphSampler
        from quiver_tpu.dist.sampler import plan_row_shards
        from quiver_tpu.models import GraphSAGE
        from quiver_tpu.parallel import replicate

        self.cfg = cfg
        self.fault = fault      # as sage_fused: no run of the benchmark
        self.ranks = cfg["ranks"]
        if len(devices) != self.ranks:
            raise RuntimeError(f"{self.ranks} ranks need as many devices, "
                               f"the cell was given {len(devices)}")
        self.mesh = Mesh(np.array(devices), ("data",))
        self.topo = CSRTopo(indptr=data["indptr"], indices=data["indices"])
        starts = plan_row_shards(data["indptr"], self.ranks)
        rows = one_shape(int(np.diff(starts).max()) + 1)
        edges = one_shape(int(np.diff(data["indptr"][starts]).max()))
        self.sampler = DistGraphSampler(self.topo, self.mesh,
                                        list(cfg["fanout"]),
                                        shard_rows=rows, shard_edges=edges)
        dtype = (jnp.bfloat16 if cfg["feature_dtype"] == "bfloat16"
                 else None)
        self.feature = DistFeature.from_row_ranges(
            data["features"], self.mesh, self.sampler.row_starts_host,
            dtype=dtype, shard_rows=self.sampler.shard_rows)
        # ``control``: the program's own lower-precision path (products in
        # bfloat16), as in sage_fused
        self.model = GraphSAGE(
            hidden=cfg["hidden"], out_dim=cfg["classes"],
            num_layers=cfg["num_layers"], dropout=cfg["dropout"],
            dtype=jnp.bfloat16 if control else None)
        self.params = replicate(self.mesh, data["params"])
        self._keys = jax.jit(self.step_keys)
        jax.block_until_ready((self.sampler.indptr_sh,
                               self.sampler.indices_sh, self.feature.shards,
                               self.params))

    def resolved(self):
        s = self.sampler
        return {"gather_mode": s.gather_mode, "sample_rng": s.sample_rng,
                "ranks": self.ranks,
                "row_starts": s.row_starts_host.tolist(),
                "request_cap_frac": s.request_cap_frac,
                "request_cap": self.feature.request_cap}

    # ---------------------------------------------------------- training
    def fused_train_step(self):
        """``(state, step)``: ``step(state, seeds, labels, mask, key)``
        over the host's whole batch (``ranks`` x the configuration's
        batch, cut into the ranks' in order), and the replicated state it
        starts from.  Rows stored narrower than float32 are widened before
        the model sees them, as the configuration states."""
        import jax
        import jax.numpy as jnp
        import optax

        from quiver_tpu.parallel import (TrainState, make_train_step,
                                         replicate)

        model, ranks = self.model, self.ranks

        def apply_fn(p, x, blocks, train=False, rngs=None):
            return model.apply(p, x.astype(jnp.float32), blocks,
                               train=train, rngs=rngs)

        tx = optax.adam(self.cfg["lr"])
        train = make_train_step(apply_fn, tx, mesh=self.mesh)
        state = replicate(self.mesh, TrainState.create(
            jax.tree_util.tree_map(jnp.copy, self.params), tx))

        def step(state, seeds, labels, mask, key):
            ks, kd = self._keys(key)
            n_id, n_mask, _, blocks = self.sampler.sample(
                seeds.reshape(ranks, -1), key=ks)
            x = self.feature.lookup(n_id, n_mask)
            return train(state, x, blocks, labels.reshape(ranks, -1),
                         mask.reshape(ranks, -1), kd)

        return state, planted(step, self.fault)

    @staticmethod
    def step_keys(key):
        """What a step makes of its key: the sampler's seed (the sharded
        sampler takes a scalar and derives each rank's and hop's keys from
        it) and the train step's key, which ``make_train_step`` splits
        into the ranks' dropout keys."""
        import jax
        import jax.numpy as jnp

        k, kd = jax.random.split(key)
        return jax.random.randint(k, (), 0, 2 ** 31 - 1, jnp.int32), kd

    def replay_sample(self, seeds, sample_seed):
        """The draws of one step, read back through the program's sampler
        on the step's own seed, as host arrays stacked over the ranks:
        ``(n_id [R, P], n_mask [R, P], [(nbr_local [R, T, k], mask), ...])``,
        outermost first."""
        n_id, n_mask, _, blocks = self.sampler.sample(
            np.asarray(seeds).reshape(self.ranks, -1), key=sample_seed)
        return (np.asarray(n_id), np.asarray(n_mask),
                [(np.asarray(b.nbr_local), np.asarray(b.mask))
                 for b in blocks])

    def replay_rows(self, n_id, n_mask):
        """The rows the feature store answers with, as it stores them."""
        return np.asarray(self.feature.lookup(n_id, n_mask))

    # ------------------------------------------------------ the exchange
    def exchange_drops(self):
        """Requests the most recent ``sample`` and ``lookup`` dropped."""
        return int(self.sampler.overflow_stats().sum()
                   + self.feature.overflow_stats().sum())

    def exchange_slots(self):
        """``(slots shipped, slots that held a request)`` of the most
        recent ``sample`` and ``lookup``, sampler and feature together."""
        pairs = (self.sampler.exchange_stats(), self.feature.exchange_stats())
        return tuple(int(sum(p[i] for p in pairs)) for i in (0, 1))

    def exchange_live(self):
        """The most recent step's requests by layer, summed over the ranks
        (whoever owns the row): the targets each hop sent, outward, and
        the frontier rows asked of the feature store."""
        return {"hops": np.asarray(self.sampler.last_live).sum(axis=0)
                .tolist(), "rows": self.feature.exchange_stats()[1]}

    def free(self):
        """Drop the device tables, so that the reference has the chips."""
        import gc

        for name in ("sampler", "feature", "topo", "params", "model"):
            self.__dict__.pop(name, None)
        gc.collect()
