"""The traced window's HOST time by the program's own spans.

``quiver_tpu.telemetry.span(name)`` enters a ``jax.profiler.
TraceAnnotation("qt." + name)``, so a span of the program is an event of
the trace's ``/host:CPU`` plane on the device trace's clock, and the
reduced trace carries it by name (``ctx["trace"]["host_spans"]``: name ->
``count``, ``seconds`` and ``idle_overlap_s`` inside the window, the last
being the seconds of it in which the lowest-numbered device ran nothing;
``trace_reduce.reduce_planes``).  A span times how long the CALLER's thread
is held inside the library, never the device: the program it launched runs
on after the call has returned.

The library's step path has three spans at the top, none inside another:
``qt.sampler.sample`` and ``qt.feature.lookup`` (the sharded sampler and
feature store: three launches a step) and ``qt.step.train`` (the jitted
train step, fused or data-parallel).  A part is named after its parent
(``qt.sampler.sample.place``, ``.launch``) and runs inside its interval on
its thread: it is logged beside it and added to no sum.  What of a
``cb.dispatch`` the three do not cover is the harness's: its feed queue and
the program file's eager glue.

On a program without the spans (a parent commit of the PR that brought
them) every reader over this file finds nothing to read and returns None.
"""

import sys

PREFIX = "qt."
TOP_LEVEL = ("qt.sampler.sample", "qt.feature.lookup", "qt.step.train")
DISPATCH = "cb.dispatch"

_logged = []


def spans(ctx):
    """The program's host spans of a traced train cell, name -> what the
    reduced trace holds of it; None where there is none (no trace, no
    step, not a train cell, a program without spans).  The first call that
    finds any logs the table a person reads."""
    f, t = ctx["facts"], ctx["trace"]
    if f["kind"] != "train" or t is None or not f.get("traced_steps"):
        return None
    found = {name: sp for name, sp in (t.get("host_spans") or {}).items()
             if name.startswith(PREFIX) and sp["count"]}
    if not found:
        return None
    if not _logged:
        _logged.append(True)
        _log(found, t, f["traced_steps"])
    return found


def span_ms(ctx, name):
    """Host milliseconds a traced step inside the span ``name``."""
    found = spans(ctx)
    if not found or name not in found:
        return None
    return 1e3 * found[name]["seconds"] / ctx["facts"]["traced_steps"]


def top_level_pct(ctx, field):
    """``field`` (``seconds`` or ``idle_overlap_s``) of the top-level spans
    that are there, together, as a share of the traced window in percent.
    Parts are inside their parents and are not added again."""
    found = spans(ctx)
    if not found or ctx["trace"]["window_s"] <= 0:
        return None
    total = _top_level(found, field)
    return None if total is None else (
        100.0 * total / ctx["trace"]["window_s"])


def _top_level(found, field):
    """``field`` summed over the top-level spans among ``found``; None
    where none of them is."""
    top = [found[name][field] for name in TOP_LEVEL if name in found]
    return sum(top) if top else None


def _log(found, trace, steps):
    def say(name, sp, indent=""):
        print(f"  {1e3 * sp['seconds'] / steps:9.3f} ms  "
              f"{1e3 * sp['idle_overlap_s'] / steps:9.3f} ms idle  "
              f"x {sp['count'] / steps:.2f}  {indent}{name}",
              file=sys.stderr, flush=True)

    print(f"host_spans: {len(found)} qt. spans over {steps} traced steps "
          f"(a step: ms on the caller's thread, ms of it with device "
          f"{trace.get('idle_gaps_device')} idle, calls)", file=sys.stderr)
    for name in sorted(found):
        if any(name.startswith(p + ".") for p in found):
            continue                # a part: under its parent below
        say(name, found[name])
        for part in sorted(found):
            if part.startswith(name + "."):
                say(part, found[part], "  ")
    seconds = _top_level(found, "seconds")
    if seconds is None:
        return
    idle = _top_level(found, "idle_overlap_s")
    print(f"  top-level spans together {1e3 * seconds / steps:.3f} ms a "
          f"step, {1e3 * idle / steps:.3f} of it idle; window "
          f"{trace['window_s']:.3f} s", file=sys.stderr, flush=True)
    dispatch = trace["host_spans"].get(DISPATCH)
    if dispatch:
        print(f"  {DISPATCH} {1e3 * dispatch['seconds'] / steps:.3f} ms a "
              f"step, so {1e3 * (dispatch['seconds'] - seconds) / steps:.3f} "
              f"are the harness's feed and glue", file=sys.stderr, flush=True)
