"""The program ``gat_fused`` (a configuration names it under
``"program"``): the published GAT over a citation graph, built as a user of
quiver_tpu builds it and as ``programs/rgat_fused.py`` builds R-GAT:
``CSRTopo`` -> ``GraphSageSampler`` (no mode kwargs: the ONE sampler) ->
``Feature`` all in HBM, float16 rows -> ``models.GNN`` ->
``pipeline.make_fused_train_step``, on one device.

The fused step with its state (BatchNorm's running averages in
``TrainState.model_state``, through ``models.rgnn_apply_fn``) and the
planted faults are ``rgat_fused``'s, and what the harness reads back of a
training program (the first gradient, the step's keys, the sampler's draw,
``free``) is ``sage_fused``'s.
"""

from run import load_named

rgat_fused = load_named("programs", "rgat_fused")


class Program(rgat_fused.Program):
    """Graph, features and model of one configuration on the device."""

    def __init__(self, cfg, data, devices, control=False, fault=None):
        import jax
        import jax.numpy as jnp

        from quiver_tpu import CSRTopo, Feature, GraphSageSampler
        from quiver_tpu.models import GNN

        self.cfg = cfg
        self.fault = fault      # as sage_fused: no run of the benchmark
        nodes = cfg["papers"]
        self.topo = CSRTopo(indptr=data["indptr"], indices=data["indices"])
        self.sampler = GraphSageSampler(self.topo, list(cfg["fanout"]))
        self.feature = Feature(device_cache_size=nodes, cache_unit="rows",
                               dtype=jnp.dtype(cfg["feature_dtype"])
                               ).from_cpu_tensor(data["features"])
        if self.feature.cache_count != nodes:
            raise RuntimeError("features are not all in HBM")
        # ``control``: the model's own bfloat16 path for its products
        self.model = GNN(
            hidden=cfg["hidden"], out_dim=cfg["classes"],
            num_layers=cfg["num_layers"], heads=cfg["heads"],
            dropout=cfg["dropout"],
            dtype=jnp.bfloat16 if control else None)
        tm = jax.tree_util.tree_map
        self.params = tm(jnp.asarray, data["params"])
        self.model_state = tm(jnp.asarray, data["model_state"])
        jax.block_until_ready((self.topo.to_device(), self.feature.hot,
                               self.params, self.model_state))
