"""Records the small trace that test_trace_reduce.py reads: three launches
of one small program on the chip, under the benchmark's own spans.  Run on
the chip, once: ``python3 cellbench/tests/record_trace.py <out_dir>``."""

import glob
import os
import shutil
import sys
import time


def main(out_dir):
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def small_step(table, idx, w):
        return jnp.tanh(jnp.take(table, idx, axis=0) @ w).sum()

    table = jnp.ones((4096, 128), jnp.float32)
    idx = jnp.arange(2048, dtype=jnp.int32) * 2
    w = jnp.ones((128, 128), jnp.float32)
    small_step(table, idx, w).block_until_ready()
    tmp = os.path.join(out_dir, "_tmp")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("cb.window_start"):
        pass
    for _ in range(3):
        with jax.profiler.TraceAnnotation("cb.dispatch"):
            out = small_step(table, idx, w)
        with jax.profiler.TraceAnnotation("cb.wait_result"):
            out.block_until_ready()
        with jax.profiler.TraceAnnotation("cb.generate"):
            time.sleep(0.002)
    with jax.profiler.TraceAnnotation("cb.window_end"):
        pass
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(path, os.path.join(out_dir, "small_step.xplane.pb"))
    shutil.rmtree(tmp)
    print(os.path.getsize(os.path.join(out_dir, "small_step.xplane.pb")))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
