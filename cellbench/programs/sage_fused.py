"""The program ``sage_fused`` (a configuration names it under
``"program"``).  ``programs/`` is where the benchmark touches the system
under test.

Builds, from a configuration and the seeded host data, what a user of
quiver_tpu builds (copied from ``chip_smoke.train_phase``): ``CSRTopo`` ->
``GraphSageSampler`` (no mode kwargs, so the library picks what it picks
on this device) -> ``Feature`` all in HBM ->
``pipeline.make_fused_train_step``, all on one device: the first of those
the cell was given.  Everything else under ``cellbench/`` imports nothing
of ``quiver_tpu`` (but ``scope_split.py``, a table of names, and
``compile_cache.py``, a path).
"""

import numpy as np


class Program:
    """Graph, features and model of one configuration on the device."""

    def __init__(self, cfg, data, devices, control=False, fault=None):
        import jax
        import jax.numpy as jnp

        from quiver_tpu import CSRTopo, Feature, GraphSageSampler
        from quiver_tpu.models import GraphSAGE

        # a one-device program: ``devices[0]`` is JAX's default device,
        # where the library puts what it is not told to put elsewhere
        self.cfg = cfg
        # ``fault``: break the timed path underneath the harness, for
        # cellbench/tests/test_faults.py: "stale_state", "half_batch".
        # No run of the benchmark sets it.
        self.fault = fault
        self.topo = CSRTopo(indptr=data["indptr"], indices=data["indices"])
        self.sampler = GraphSageSampler(self.topo, list(cfg["fanout"]))
        dtype = (jnp.bfloat16 if cfg["feature_dtype"] == "bfloat16"
                 else None)
        self.feature = Feature(device_cache_size=cfg["nodes"],
                               cache_unit="rows", dtype=dtype
                               ).from_cpu_tensor(data["features"])
        if self.feature.cache_count != cfg["nodes"]:
            raise RuntimeError("features are not all in HBM")
        # ``control``: the program's own lower-precision path, the one a
        # later PR would be tempted by (products in bfloat16)
        self.model = GraphSAGE(
            hidden=cfg["hidden"], out_dim=cfg["classes"],
            num_layers=cfg["num_layers"], dropout=cfg["dropout"],
            dtype=jnp.bfloat16 if control else None)
        self.params = jax.tree_util.tree_map(jnp.asarray, data["params"])
        jax.block_until_ready((self.topo.to_device(), self.feature.hot,
                               self.params))

    def resolved(self):
        s = self.sampler
        return {"gather_mode": s.gather_mode, "sample_rng": s.sample_rng,
                "dedup": s.dedup}

    # ---------------------------------------------------------- training
    def fused_train_step(self):
        """``(state, step)``: the fused program and the state it starts
        from.  Rows stored narrower than float32 are widened before the
        model sees them, as the configuration states."""
        import jax
        import jax.numpy as jnp
        import optax

        from quiver_tpu.parallel import TrainState
        from quiver_tpu.pipeline import make_fused_train_step

        model = self.model

        def apply_fn(p, x, blocks, train=False, rngs=None):
            return model.apply(p, x.astype(jnp.float32), blocks,
                               train=train, rngs=rngs)

        tx = optax.adam(self.cfg["lr"])
        step = make_fused_train_step(self.sampler, self.feature, apply_fn,
                                     tx)
        state = TrainState.create(
            jax.tree_util.tree_map(jnp.copy, self.params), tx)
        if self.fault == "stale_state":
            whole = step

            def step(state, seeds, labels, mask, key):
                keep = jax.tree_util.tree_map(jnp.copy, state)
                _, loss = whole(state, seeds, labels, mask, key)
                return keep, loss
        elif self.fault == "half_batch":
            whole = step

            def step(state, seeds, labels, mask, key):
                half = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
                return whole(state, seeds, labels, half, key)
        return state, step

    @staticmethod
    def first_gradient(state, b1=0.9):
        """The gradient Adam was handed at step 1, from its first moment
        after that step: ``mu = (1 - b1) g``."""
        import jax

        mu = state.opt_state[0].mu
        return jax.tree_util.tree_map(
            lambda a: np.asarray(a) / (1.0 - b1), mu)

    @staticmethod
    def step_keys(key):
        """What the fused step makes of its key: the sampler's and the
        dropout's (``pipeline._fused_train_impl``: ``jax.random.split``)."""
        import jax

        ks, kd = jax.random.split(key)
        return ks, kd

    def make_key(self, n):
        from quiver_tpu import make_key

        return make_key(int(n))

    def replay_sample(self, seeds, sample_key):
        """The draw of one pass, read back through the program's sampler
        on the pass's own key, as host arrays: ``(n_id, n_mask, [(nbr_local,
        mask), ...])``, outermost first."""
        import jax.numpy as jnp

        bt = self.sampler.sample(jnp.asarray(seeds, jnp.int32),
                                 key=sample_key)
        return (np.asarray(bt.n_id), np.asarray(bt.n_id_mask),
                [(np.asarray(b.nbr_local), np.asarray(b.mask))
                 for b in bt.layers])

    def free(self):
        """Drop the device tables, so that the reference has the chip."""
        import gc

        for name in ("sampler", "feature", "topo", "params", "model"):
            self.__dict__.pop(name, None)
        gc.collect()
