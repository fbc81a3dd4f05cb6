"""From a ``jax.profiler`` trace (``.xplane.pb``) to the few things the
per-layer metrics read: when each device was busy, how long each operation
and each program ran there, how often a program was launched, and what the
benchmark's own threads were doing in the longest idle gaps.

Read with ``jax.profiler.ProfileData`` and nothing else.  On a TPU the
device planes are named ``/device:TPU:<n>``; the line ``XLA Ops`` holds one
event per operation that ran on the core, and ``XLA Modules`` one event per
launch of a compiled program (named ``jit_<function>(<fingerprint>)``).
Host threads are lines of the plane ``/host:CPU``; the benchmark's own
spans (``jax.profiler.TraceAnnotation`` with names that start ``cb.``) and
the program's (``quiver_tpu.telemetry.span``: names that start ``qt.``)
are events there, on the same clock as the device's.  The ``cb.`` spans
alone decide the window's ends and name the idle gaps; both kinds are
summed by name under ``host_spans``, for a per-layer reader to turn a cold
fetch or a server's device thread into a metric.
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "cb."
HOST_PREFIXES = (SPAN_PREFIX, "qt.")   # the host events that are kept
ATTRIBUTED_GAPS = 200      # the longest idle gaps are named, the rest summed


def newest_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union_seconds(intervals):
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """The idle stretches of ``[lo, hi]`` that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def overlap_with(stretches):
    """``f(s, e)``: how much of ``[s, e]`` lies inside ``stretches``,
    which are disjoint and in order of time (as ``gaps`` gives them)."""
    starts = [s for s, _ in stretches]
    before = [0.0]                  # before[k]: length of the first k
    for s, e in stretches:
        before.append(before[-1] + (e - s))

    def upto(x):
        i = bisect.bisect_right(starts, x)
        if i == 0:
            return 0.0
        s, e = stretches[i - 1]
        return before[i - 1] + min(x, e) - s

    return lambda s, e: upto(e) - upto(s)


def module_family(name):
    """``jit_step(1234567)`` -> ``jit_step``: one program under whatever
    fingerprint this build gave it."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce_planes(planes, window=None):
    """``planes``: an iterable of ``(plane_name, [(line_name, [(event_name,
    start_ns, duration_ns), ...]), ...])``.  ``window``: ``(lo_ns, hi_ns)``
    to cut to; by default from the first device event to the last.

    Returns a dict: ``devices`` (count), ``window_s``, ``busy_s`` (mean over
    devices of the union of operation intervals), ``busy_s_per_device``
    (device number -> that union), ``ops`` (name -> seconds, mean over
    devices), ``modules`` (family -> {"launches", "seconds"}, summed over
    devices), ``idle_gaps`` ([(what, seconds)], longest first, of the
    device ``idle_gaps_device``, the lowest-numbered, named by the ``cb.``
    span that covers most of each), ``host_spans`` (name of a ``cb.`` or
    ``qt.`` host span -> {"count", "seconds", "idle_overlap_s"} inside the
    window, the last being the seconds in which that device ran nothing).
    """
    dev_ops, dev_mods, host = {}, {}, []
    for pname, lines in planes:
        m = DEVICE_PLANE.match(pname)
        for lname, events in lines:
            if m and lname == OPS_LINE:
                dev_ops.setdefault(int(m.group(1)), []).extend(events)
            elif m and lname == MODULES_LINE:
                dev_mods.setdefault(int(m.group(1)), []).extend(events)
            elif pname == HOST_PLANE:
                host.extend(e for e in events
                            if e[0].startswith(HOST_PREFIXES))
    spans = [e for e in host if e[0].startswith(SPAN_PREFIX)]
    if not dev_ops:
        return None
    if window is None:
        marks = {name: start for name, start, dur in spans}
        if {SPAN_PREFIX + "window_start",
                SPAN_PREFIX + "window_end"} <= set(marks):
            window = (marks[SPAN_PREFIX + "window_start"],
                      marks[SPAN_PREFIX + "window_end"])
    if window is None:
        every = [e for evs in dev_ops.values() for e in evs]
        window = (min(e[1] for e in every),
                  max(e[1] + e[2] for e in every))
    lo, hi = window

    def cut(events):
        for name, start, dur in events:
            s, e = max(start, lo), min(start + dur, hi)
            if e > s:
                yield name, s, e

    n = len(dev_ops)
    busy, ops, modules = {}, {}, {}
    gap_device = min(dev_ops)
    for dev in sorted(dev_ops):
        intervals = []
        for name, s, e in cut(dev_ops[dev]):
            intervals.append((s, e))
            ops[name] = ops.get(name, 0.0) + (e - s) * 1e-9 / n
        busy[dev] = union_seconds(intervals) * 1e-9
        if dev == gap_device:
            in_time = gaps(intervals, lo, hi)
        for name, s, e in cut(dev_mods.get(dev, [])):
            fam = modules.setdefault(module_family(name),
                                     {"launches": 0, "seconds": 0.0})
            fam["launches"] += 1
            fam["seconds"] += (e - s) * 1e-9
    idle = []
    every_gap = sorted(in_time, key=lambda g: g[0] - g[1])
    rest = sum(e - s for s, e in every_gap[ATTRIBUTED_GAPS:])
    for s, e in every_gap[:ATTRIBUTED_GAPS]:
        best, cover = "unattributed", 0.0
        for name, ss, dd in spans:
            c = min(e, ss + dd) - max(s, ss)
            if c > cover:
                best, cover = name[len(SPAN_PREFIX):], c
        idle.append((best, (e - s) * 1e-9))
    if rest:
        idle.append(("shorter_gaps_together", rest * 1e-9))
    idle_in, host_spans = overlap_with(in_time), {}
    for name, s, e in cut(host):
        sp = host_spans.setdefault(
            name, {"count": 0, "seconds": 0.0, "idle_overlap_s": 0.0})
        sp["count"] += 1
        sp["seconds"] += (e - s) * 1e-9
        sp["idle_overlap_s"] += idle_in(s, e) * 1e-9
    return {"devices": n, "window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy.values()) / n, "busy_s_per_device": busy,
            "ops": ops, "modules": modules, "idle_gaps": idle,
            "idle_gaps_device": gap_device, "host_spans": host_spans}


def read_xplane(path):
    """The planes of one ``.xplane.pb`` in ``reduce_planes``' form.  Only
    device planes and the host's ``cb.`` and ``qt.`` spans are kept."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_dev = DEVICE_PLANE.match(plane.name)
        if not is_dev and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [(e.name, float(e.start_ns), float(e.duration_ns))
                      for e in line.events
                      if is_dev or e.name.startswith(HOST_PREFIXES)]
            if events:
                lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes


def reduce_trace(trace_dir, window=None):
    return reduce_planes(read_xplane(newest_xplane(trace_dir)), window)


def breakdown(red, top=10):
    """The contract's ``breakdown``: the operations that took most device
    time and the longest idle gaps, at most ``top`` of each."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    merged = {}
    for what, s in red["idle_gaps"]:
        merged[what] = merged.get(what, 0.0) + s
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in
                          sorted(merged.items(), key=lambda kv: -kv[1])[:top]]}
