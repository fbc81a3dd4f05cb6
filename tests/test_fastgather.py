"""Fast gather paths: lane-select element gather + the feature store's
Pallas row gather (interpret mode on CPU)."""

import numpy as np
import jax.numpy as jnp

from quiver_tpu.ops.fastgather import element_gather, prepare_table


def test_element_gather_matches_take(rng):
    table = jnp.asarray(rng.integers(0, 1000, 1000, dtype=np.int32))
    t2d = prepare_table(table)
    idx = jnp.asarray(rng.integers(0, 1000, 513, dtype=np.int32))
    out = element_gather(t2d, idx)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(table)[np.asarray(idx)])


def test_element_gather_2d_idx(rng):
    table = jnp.asarray(rng.normal(size=300).astype(np.float32))
    t2d = prepare_table(table)
    idx = jnp.asarray(rng.integers(0, 300, (7, 9), dtype=np.int32))
    out = element_gather(t2d, idx)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(table)[np.asarray(idx)], rtol=1e-7
    )


def test_pallas_gather_rows_interpret(rng):
    from quiver_tpu.ops.pallas.gather_kernel import gather_rows

    table = jnp.asarray(rng.normal(size=(500, 32)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 500, 512, dtype=np.int32))
    out = gather_rows(table, idx, block=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(table)[np.asarray(idx)], rtol=1e-7
    )
