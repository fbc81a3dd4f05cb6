"""The share of the traced window in which the caller's thread is inside
the library: seconds under the top-level spans (``qt.sampler.sample``,
``qt.feature.lookup``, ``qt.step.train``; their parts not added again) over
the window.  Near 100 the host sets the pace, whatever the device does
(cellbench/host_spans.py).  None on a program without the spans."""

import host_spans


def read(ctx):
    return host_spans.top_level_pct(ctx, "seconds")
