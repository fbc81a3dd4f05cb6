"""The toy's program: a table in row blocks, one a device, and a lookup in
which each device answers for the rows it holds and a ``psum`` puts the
answers together.  Its host span is the system under test's
(``quiver_tpu.telemetry.span`` -> ``qt.toy.lookup``)."""

import numpy as np


class Program:
    def __init__(self, cfg, data, devices, control=False, fault=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        n = len(devices)
        per = cfg["rows"] // n
        if per * n != cfg["rows"]:
            raise ValueError(f"{cfg['rows']} rows do not divide over {n}")
        mesh = Mesh(np.array(devices), ("x",))
        self.table = jax.device_put(data["table"],
                                    NamedSharding(mesh, P("x", None)))

        def part(table, ids):
            local = ids - jax.lax.axis_index("x") * per
            mine = (local >= 0) & (local < per)
            got = jnp.where(mine[:, None], jnp.take(
                table, jnp.clip(local, 0, per - 1), axis=0), 0)
            # ``fault``: the exchange between the devices left out
            return got if fault == "no_exchange" else jax.lax.psum(got, "x")

        self._lookup = jax.jit(jax.shard_map(
            part, mesh=mesh, in_specs=(P("x", None), P()), out_specs=P(),
            check_vma=fault is None))
        self._resolved = {"devices": n, "rows_per_device": per}

    def resolved(self):
        return self._resolved

    def lookup(self, ids):
        import jax.numpy as jnp

        from quiver_tpu import telemetry

        with telemetry.span("toy.lookup"):
            return self._lookup(self.table, jnp.asarray(ids))

    def free(self):
        self.__dict__.pop("table", None)
