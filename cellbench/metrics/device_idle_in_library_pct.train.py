"""The part of ``device_idle_pct.train`` during which the caller's thread
was inside the library: the seconds of the top-level spans
(``qt.sampler.sample``, ``qt.feature.lookup``, ``qt.step.train``) in which
the device whose gaps are named ran nothing, over the traced window.  The
rest of the idle share is the harness's feed, its wait, or gaps no span
covers (cellbench/host_spans.py).  None on a program without the spans."""

import host_spans


def read(ctx):
    return host_spans.top_level_pct(ctx, "idle_overlap_s")
