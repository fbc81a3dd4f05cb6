"""What every kind of cell uses to drive its window: the benchmark's own
spans, the tracer of the window's first seconds, and the last act of
set-up.  The kinds themselves are files, ``kinds/<kind>.py``, found by the
``kind`` of the traffic file.
"""

import gc
import threading
import time


def span(name):
    """A span of the benchmark's own, on the profiler's clock; costs a few
    hundred nanoseconds when no trace is being taken."""
    import jax

    return jax.profiler.TraceAnnotation("cb." + name)


def settle():
    """The last act of set-up.  A process that has just compiled carries
    millions of tracing objects; the first full collection that meets them
    holds the interpreter lock for a second or more, and did so inside the
    window of cold runs (PERF.md, PR 25).  Collect now, and keep what is
    left out of later collections until the window has closed."""
    gc.collect()
    gc.freeze()


class Tracer:
    """Traces the first ``seconds`` of the window when asked to.  The
    window's two ends are marked by spans of their own; stopping (which
    collects and writes the trace, for seconds) runs on a helper thread, so
    that the loop that feeds the program is not held up by it."""

    def __init__(self, on, trace_dir, seconds):
        self.on, self.dir, self.seconds = bool(on), trace_dir, seconds
        self.t0 = self.t1 = None
        self._stopper = None

    def start(self):
        if not self.on:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with span("window_start"):
            self.t0 = time.perf_counter()

    def due(self):
        return (self.on and self.t1 is None and self.t0 is not None
                and time.perf_counter() - self.t0 >= self.seconds)

    def stop(self):
        if not self.on or self.t1 is not None or self.t0 is None:
            return
        import jax

        with span("window_end"):
            self.t1 = time.perf_counter()
        self._stopper = threading.Thread(target=jax.profiler.stop_trace,
                                         name="cb-trace-stop")
        self._stopper.start()

    def join(self):
        if self._stopper is not None:
            self._stopper.join()
