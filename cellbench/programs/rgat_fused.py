"""The program ``rgat_fused`` (a configuration names it under
``"program"``): the published R-GAT over a typed graph, built as a user of
quiver_tpu builds it and as ``programs/sage_fused.py`` builds GraphSAGE:
``CSRTopo`` -> ``GraphSageSampler`` (no mode kwargs: the ONE sampler, which
knows nothing of types) -> ``Feature`` all in HBM, float16 rows ->
``models.RGNN`` -> ``pipeline.make_fused_train_step``, on one device.

What the harness reads back of a training program (the first gradient,
the step's keys, the sampler's draw, ``free``) is ``sage_fused``'s.
"""

from run import load_named

sage_fused = load_named("programs", "sage_fused")
rgat = load_named("references", "rgat")     # the schema: type ranges, table


def planted(step, fault):
    """``step`` with one of the kind's faults underneath (``kinds/train.
    FAULTS``, as ``sage_fused`` plants them); ``step`` itself for None."""
    import jax
    import jax.numpy as jnp

    if fault == "stale_state":
        def stale(state, seeds, labels, mask, key):
            keep = jax.tree_util.tree_map(jnp.copy, state)
            _, loss = step(state, seeds, labels, mask, key)
            return keep, loss
        return stale
    if fault == "half_batch":
        def half(state, seeds, labels, mask, key):
            first = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
            return step(state, seeds, labels, first, key)
        return half
    return step


class Program(sage_fused.Program):
    """Graph, features and model of one configuration on the device."""

    def __init__(self, cfg, data, devices, control=False, fault=None):
        import jax
        import jax.numpy as jnp

        from quiver_tpu import CSRTopo, Feature, GraphSageSampler
        from quiver_tpu.models import RGNN

        self.cfg = cfg
        self.fault = fault      # as sage_fused: no run of the benchmark
        offsets = rgat.type_offsets(cfg)
        nodes = offsets[-1]
        self.topo = CSRTopo(indptr=data["indptr"], indices=data["indices"])
        self.sampler = GraphSageSampler(self.topo, list(cfg["fanout"]))
        self.feature = Feature(device_cache_size=nodes, cache_unit="rows",
                               dtype=jnp.dtype(cfg["feature_dtype"])
                               ).from_cpu_tensor(data["features"])
        if self.feature.cache_count != nodes:
            raise RuntimeError("features are not all in HBM")
        # ``control``: the model's own bfloat16 path for its products
        self.model = RGNN(
            hidden=cfg["hidden"], out_dim=cfg["classes"],
            num_relations=cfg["num_relations"], type_offsets=offsets,
            relation_of=rgat.RELATION_OF, num_layers=cfg["num_layers"],
            heads=cfg["heads"], dropout=cfg["dropout"],
            dtype=jnp.bfloat16 if control else None)
        tm = jax.tree_util.tree_map
        self.params = tm(jnp.asarray, data["params"])
        self.model_state = tm(jnp.asarray, data["model_state"])
        jax.block_until_ready((self.topo.to_device(), self.feature.hot,
                               self.params, self.model_state))

    def fused_train_step(self):
        """``(state, step)``: the fused program and the state it starts
        from, BatchNorm's running averages in ``state.model_state``."""
        import jax
        import jax.numpy as jnp
        import optax

        from quiver_tpu.models import rgnn_apply_fn
        from quiver_tpu.parallel import TrainState
        from quiver_tpu.pipeline import make_fused_train_step

        tx = optax.adam(self.cfg["lr"])
        step = make_fused_train_step(self.sampler, self.feature,
                                     rgnn_apply_fn(self.model), tx)
        copy = jax.tree_util.tree_map
        state = TrainState.create(copy(jnp.copy, self.params), tx,
                                  copy(jnp.copy, self.model_state))
        return state, planted(step, self.fault)

    def free(self):
        self.__dict__.pop("model_state", None)
        super().free()
