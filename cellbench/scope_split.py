"""The traced window's device time split by the program's own layers.

A trace event of ``XLA Ops`` is named by its whole HLO line and nothing
else (no op-name stat on this runtime), and the reduced trace
(``ctx["trace"]["ops"]``: HLO line -> device seconds) has lost which
program an operation ran in.  The program keeps a table from each
instruction of its fused programs (the line up to its opcode: name and
result shape) to the ``op_name`` its compiled text gives it, which carries
the ``jax.named_scope`` it was traced under:
``quiver_tpu.telemetry.device_scopes()``.  This file joins the two: an
operation belongs to a layer when name AND result shape are in the table
(so a helper program's ``%fusion`` is not taken for the step's), the layer
is the first ``qt.<layer>[.<part>]`` of its ``op_name``, and it ran in the
backward pass when the ``op_name`` contains ``transpose(`` (JAX's name for
the transposed half of ``value_and_grad``).

After ``programs/`` this is the second place under ``cellbench/`` that
imports from ``quiver_tpu``.  What it reads is a table of names, not code
under test: every second it sums is the trace's.  On a program without
the table (a parent commit of the PR that brought it) every reader over
this file finds nothing to read and returns None.
"""

import re
import sys

SCOPE = re.compile(r"qt(?:\.[A-Za-z0-9_]+)+")
NAME = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+) = ")
COMMENT = re.compile(r"/\*.*?\*/")
LONGEST = 10

_memo = {}


def instruction_of(line):
    """``%name = <result shape>``: an HLO line up to its opcode, or None."""
    m = NAME.match(line)
    if m is None:
        return None
    rest = line[m.end():]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        else:
            return None
        shape = rest[:i + 1]
    else:
        shape = rest.split(" ", 1)[0]
    return f"{m.group(1)} = {COMMENT.sub('', shape)}"


def scope_table():
    """The program's table merged over its programs, or None where the
    program has none to give."""
    try:
        from quiver_tpu.telemetry import device_scopes
    except ImportError:
        return None
    merged = {}
    for table in device_scopes().values():
        merged.update(table)
    return merged


def classify(op_name):
    """``(scope, pass)`` of an ``op_name``: ``("qt.sampler.hop3",
    "forward")``; ``(None, None)`` where it names no ``qt.`` scope."""
    m = SCOPE.search(op_name or "")
    if m is None:
        return None, None
    return m.group(0), "backward" if "transpose(" in op_name else "forward"


def split_ops(ops, table):
    """Seconds of ``ops`` (HLO line -> seconds) by ``(scope, pass)``, with
    what no scope claims under ``(None, None)``; and each operation beside
    its class, longest first."""
    by, rows = {}, []
    for line, seconds in ops.items():
        key = classify(table.get(instruction_of(line)))
        by[key] = by.get(key, 0.0) + seconds
        rows.append((seconds, line, key))
    rows.sort(key=lambda r: -r[0])
    return by, rows


def split(ctx):
    """``{"ms": {(scope, pass): ms per traced step}, "attributed_pct":
    share of all operation seconds under any qt. scope}`` for a traced
    train cell; None where the four older readers find nothing either (no
    trace, no step, not a train cell) or the program has no table.
    Memoised per process; the first call logs the table a person reads."""
    f, t = ctx["facts"], ctx["trace"]
    if f["kind"] != "train" or t is None or not f.get("traced_steps"):
        return None
    if "split" not in _memo:
        table = scope_table()
        if table is None:
            return None
        steps = f["traced_steps"]
        by, rows = split_ops(t["ops"], table)
        total = sum(by.values())
        named = total - by.get((None, None), 0.0)
        _memo["split"] = {
            "ms": {k: 1e3 * s / steps for k, s in by.items() if k[0]},
            "attributed_pct": 100.0 * named / total if total > 0 else 0.0}
        _log(by, rows, steps, total, len(table))
    return _memo["split"]


def scope_ms(ctx, scope, pass_=None):
    """ms per traced step under ``scope`` and its parts (``qt.sampler``
    takes ``qt.sampler.hop1`` in), one pass or both."""
    s = split(ctx)
    if s is None:
        return None
    return sum(v for (sc, p), v in s["ms"].items()
               if (sc == scope or sc.startswith(scope + "."))
               and pass_ in (None, p)) + 0.0


def _log(by, rows, steps, total, entries):
    def say(seconds, key, rest=""):
        what = " ".join(key) if key[0] else "(no qt. scope)"
        print(f"  {1e3 * seconds / steps:9.3f} ms  "
              f"{100 * seconds / total if total else 0:5.1f}%  {what}{rest}",
              file=sys.stderr, flush=True)

    print(f"scope_split: {len(rows)} operations over {steps} traced steps, "
          f"{1e3 * total / steps:.3f} ms a step; the program's table names "
          f"{entries} instructions", file=sys.stderr)
    for key, seconds in sorted(by.items(), key=lambda kv: -kv[1]):
        say(seconds, key)
    print(f"  the {LONGEST} longest operations:", file=sys.stderr)
    for seconds, line, key in rows[:LONGEST]:
        say(seconds, key, ": " + line[:120])
