"""The program ``igbh_rgat_fused`` (a configuration names it under
``"program"``): the R-GAT of MLPerf Training's GNN benchmark over a typed
graph drawn per relation, built as a user of quiver_tpu builds it and as
``programs/sage_fused.py`` builds GraphSAGE: ``CSRTopo`` ->
``GraphSageSampler(schema=...)`` (the ONE sampler, told the node types and
relations; no mode kwargs) -> ``Feature`` all in HBM, float16 rows ->
``models.TypedRGNN`` -> ``pipeline.make_fused_train_step``, on one device.

The library's typed hop and model are imported here, at the top, so that
a tree without them refuses the cell when its files are found, before any
data is made.  What the harness reads back of a training program (the
first gradient, the step's keys, ``free``) is ``sage_fused``'s and the
planted faults are ``rgat_fused``'s; the replay reads the sampler's
per-relation counters too, and the model's first logits
(:meth:`Program.first_logits`).
"""

import numpy as np

from quiver_tpu.models import TypedRGNN
from quiver_tpu.sampler import Schema
from run import load_named

sage_fused = load_named("programs", "sage_fused")
rgat_fused = load_named("programs", "rgat_fused")
igbh = load_named("references", "igbh_rgat")    # the schema, the table


def schema(cfg):
    """IGBH's types and relations as the sampler is told them."""
    return Schema(type_offsets=igbh.type_offsets(cfg),
                  relations=tuple((s, t) for _, s, t in igbh.RELATIONS),
                  seed_type=0,
                  names=tuple(name for name, _, _ in igbh.RELATIONS))


class Program(sage_fused.Program):
    """Graph, features and model of one configuration on the device."""

    def __init__(self, cfg, data, devices, control=False, fault=None):
        import jax
        import jax.numpy as jnp

        from quiver_tpu import CSRTopo, Feature, GraphSageSampler

        self.cfg = cfg
        self.fault = fault      # as sage_fused: no run of the benchmark
        nodes = igbh.type_offsets(cfg)[-1]
        self.topo = CSRTopo(indptr=data["indptr"], indices=data["indices"])
        self.sampler = GraphSageSampler(self.topo, list(cfg["fanout"]),
                                        schema=schema(cfg))
        self.feature = Feature(device_cache_size=nodes, cache_unit="rows",
                               dtype=jnp.dtype(cfg["feature_dtype"])
                               ).from_cpu_tensor(data["features"])
        if self.feature.cache_count != nodes:
            raise RuntimeError("features are not all in HBM")
        # ``control``: the model's own bfloat16 path for its products
        self.model = TypedRGNN(
            hidden=cfg["hidden"], out_dim=cfg["classes"],
            num_relations=len(igbh.RELATIONS), heads=cfg["heads"],
            dropout=cfg["dropout"],
            dtype=jnp.bfloat16 if control else None)
        self.params = jax.tree_util.tree_map(jnp.asarray, data["params"])
        jax.block_until_ready((self.topo.to_device(),
                               self.sampler.windows_device(),
                               self.feature.hot, self.params))

    def fused_train_step(self):
        """``(state, step)``: the fused program and the state it starts
        from.  The rows reach the model as stored (float16): each
        relation's product widens its own."""
        import jax
        import jax.numpy as jnp
        import optax

        from quiver_tpu.parallel import TrainState
        from quiver_tpu.pipeline import make_fused_train_step

        model = self.model

        def apply_fn(p, x, blocks, train=False, rngs=None):
            return model.apply(p, x, blocks, train=train, rngs=rngs)

        tx = optax.adam(self.cfg["lr"])
        step = make_fused_train_step(self.sampler, self.feature, apply_fn,
                                     tx)
        state = TrainState.create(
            jax.tree_util.tree_map(jnp.copy, self.params), tx)
        return state, rgat_fused.planted(step, self.fault)

    def first_logits(self, seeds, sample_key):
        """The model's logits at the parameters it starts from, with no
        dropout, over the draw a pass makes on ``sample_key`` and the
        table's rows as stored: host numpy.  The model and precision are
        the step's; what a lower precision moves shows here element by
        element, where the gradient's sums average it away."""
        import jax
        import jax.numpy as jnp

        model = self.model
        bt = self.sampler.sample(jnp.asarray(seeds, jnp.int32),
                                 key=sample_key)
        x = self.feature.lookup_device(bt.n_id, bt.n_id_mask)

        @jax.jit
        def igbh_first_logits(params, x, blocks):
            return model.apply(params, x, blocks, train=False)

        return np.asarray(igbh_first_logits(self.params, x, bt.layers))

    def replay_sample(self, seeds, sample_key):
        """The draw of one pass, read back through the program's sampler
        on the pass's own key, as host arrays: ``(n_id, n_mask, layers)``,
        ``layers`` outermost first, each hop the ``(nbr_local, mask)`` of
        its relation blocks in relation order.  The sampler's relation
        counters take this draw in."""
        import jax.numpy as jnp

        bt = self.sampler.sample(jnp.asarray(seeds, jnp.int32),
                                 key=sample_key)
        self.sampler.relation_stats()
        return (np.asarray(bt.n_id), np.asarray(bt.n_id_mask),
                [[(np.asarray(b.nbr_local), np.asarray(b.mask)) for b in hop]
                 for hop in bt.layers])

    @staticmethod
    def relation_counts():
        """``(slots, live)``: the sampler's relation counters so far, over
        every relation (``sampler_relation_slots_total``,
        ``sampler_relation_live_slots_total``)."""
        from quiver_tpu import telemetry

        snap = telemetry.snapshot().get("counters", {})
        return tuple(
            sum(v for k, v in snap.items() if k.split("{")[0] == name)
            for name in ("sampler_relation_slots_total",
                         "sampler_relation_live_slots_total"))
