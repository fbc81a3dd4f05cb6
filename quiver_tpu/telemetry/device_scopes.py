"""Device scopes: one naming scheme, ``qt.<layer>[.<part>]``, from the
program's layers down to the device trace.

The traced pipelines run their layers under ``jax.named_scope`` with the
names below.  A scope is metadata of the HLO: it changes no instruction,
and on the TPU runtime it does NOT reach a ``jax.profiler`` trace by
itself — an ``XLA Ops`` event is named by its whole HLO line
(``%fusion.5 = s32[2048]{0:T(1024)S(1)} fusion(...), kind=kLoop, ...``) and
carries no op-name stat.  It does reach the compiled HLO text
(``metadata={op_name="jit(qt_fused_train_step)/qt.sampler.hop3/..."}``), so
instruction -> scope is a join of the trace with the program's own
compiled text.  This module keeps that text reachable:

  * :func:`register_program` — ``pipeline.py``'s wrappers hand each fused
    program over on its first call: the jitted function and the abstract
    values of its arguments, never a buffer;
  * :func:`device_scopes` — on demand, lowers each registered program
    once more from those abstract values (which finds the executable the
    first call built or read from the persistent cache: nothing compiles),
    parses its text and answers ``{program: {instruction: op_name}}``,
    where ``instruction`` is an HLO line up to its opcode (name and result
    shape), as a trace event spells it.

Under ``jax.value_and_grad`` the forward pass of a scope reads
``jvp(qt.model)`` and the backward pass ``transpose(jvp(qt.model))``.

A ``conditional`` or a ``while`` is an event of the trace too, and it lasts
as long as the operations of the branch or body it ran, which the trace
lists besides, each under the scope it was traced in.  Summed by layer the
two would count that time twice, so the table puts the wrapper under a
scope of its own, ``qt.flow``, in front of the name it was traced under.

The persistent cache's key strips debug information, scope names among
it: a program whose scopes were renamed but whose instructions were not
is a cache HIT and its text still carries the old names.  A text with no
``qt.`` name at all is therefore taken as stale and compiled once more
with the persistent cache off and JAX's in-memory caches dropped.  Whoever renames a scope without changing a program
otherwise should rename the jitted function too: the module's name IS in
the key.
"""

from __future__ import annotations

import contextlib
import re
import sys
import threading
import time
from collections import Counter
from typing import Dict, Optional, Tuple

__all__ = ["PREFIX", "SAMPLER", "FEATURE_GATHER", "MODEL", "MODEL_PROJECT",
           "MODEL_ATTENTION", "OPTIMIZER", "FLOW", "EXCHANGE", "sampler_hop",
           "exchange", "HOST_SAMPLE", "HOST_GETITEM", "HOST_LOOKUP",
           "HOST_STEP_TRAIN", "HOST_STEP_EPOCH", "HOST_STEP_EVAL", "PLACE",
           "LAUNCH",
           "register_program", "device_scopes", "parse_hlo_scopes",
           "instruction_key", "scoped"]

PREFIX = "qt."
SAMPLER = PREFIX + "sampler"
FEATURE_GATHER = PREFIX + "feature.gather"
MODEL = PREFIX + "model"
# parts of a typed model (``models.rgat.RGNN``), nested under ``qt.model``:
# the per-relation projections of sources and targets with the skip, and
# the scores, the relation's softmax and the weighted sum
MODEL_PROJECT = MODEL + ".project"
MODEL_ATTENTION = MODEL + ".attention"
OPTIMIZER = PREFIX + "optimizer"
# an instruction that runs whole computations (the ``conditional`` a
# ``lax.cond`` becomes, a ``while``): no layer's own time, see above
FLOW = PREFIX + "flow"
# the part of a SHARDED layer that is there because the table is another
# chip's (``dist/``): owner search, slot ranks, request buckets, both
# ``all_to_all``s and the unpacking.  Always the LAST name under the layer
# it serves (``qt.sampler.hop3/qt.exchange``, ``qt.feature.gather/
# qt.exchange``): a whole-layer reader takes the first name and so counts
# a layer with its exchange, a part reader the last
EXCHANGE = PREFIX + "exchange"

# -- the host's side of the same scheme -----------------------------------
# What ``telemetry.span`` is handed at the library's step-path boundaries.
# It puts PREFIX in front for the profiler (``qt.sampler.sample`` on the
# trace's ``/host:CPU`` plane, on the device trace's clock) and keys
# ``SpanTracer.summary()`` by the name as it stands here.  A host span
# times how long the CALLER's thread is held, never the device (no
# ``block=``); a part (``<span>.place``, ``<span>.launch``) runs inside its
# parent's interval on its thread, and a layer's own time is its span minus
# its parts.
HOST_SAMPLE = "sampler.sample"      # GraphSageSampler / DistGraphSampler
HOST_GETITEM = "feature.getitem"    # Feature.__getitem__
HOST_LOOKUP = "feature.lookup"      # DistFeature.lookup
HOST_STEP_TRAIN = "step.train"      # the fused and the data-parallel step
HOST_STEP_EPOCH = "step.epoch"      # pipeline.make_scan_epoch
HOST_STEP_EVAL = "step.eval"        # pipeline.make_fused_eval_fn
# parts of a sharded call: its arguments put onto the mesh (everything
# before the program is called), and the jitted call until it returns to
# Python
PLACE = ".place"
LAUNCH = ".launch"


def sampler_hop(n: int) -> str:
    """Scope of hop ``n`` of a k-hop pipeline, counted from 1 at the
    seeds."""
    return f"{SAMPLER}.hop{n}"


@contextlib.contextmanager
def exchange(layer: str):
    """``layer``'s exchange: ``<layer>/qt.exchange`` around what is traced
    inside."""
    import jax

    with jax.named_scope(layer), jax.named_scope(EXCHANGE):
        yield


# -- the parse ------------------------------------------------------------
_NAME = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%?[\w.\-]+) \(.*\{\s*$")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"[, ]calls=(%?[\w.\-]+)")
_RUNS = re.compile(
    r"[, ](?:branch_computations|true_computation|condition|body)=")
_COMMENT = re.compile(r"/\*.*?\*/")
_OPERAND = re.compile(r"%[\w.\-]+")
# the TPU compiler turns ``lax.ragged_dot`` into kernels of its own and
# gives them these names in place of the traced ``op_name``
_RENAMED = "ragged-dot-"


def _split(line: str) -> Optional[Tuple[str, str, str]]:
    """``(name, result shape, what follows it)`` of an HLO instruction
    line; None for a line that is no instruction."""
    m = _NAME.match(line)
    if m is None:
        return None
    rest = line[m.end():]
    if rest.startswith("("):        # a tuple: to its closing parenthesis
        end = _closing(rest)
        if end is None:
            return None
        shape = rest[:end + 1]
    else:
        shape = rest.split(" ", 1)[0]
    return m.group(1), shape, rest[len(shape):]


def _closing(text: str) -> Optional[int]:
    """Where the parenthesis that ``text`` opens with closes."""
    depth = 0
    for i, ch in enumerate(text):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return i
    return None


def instruction_key(line: str) -> Optional[str]:
    """``%name = <result shape>``: an HLO instruction line up to its
    opcode, the part a line of ``as_text()`` and the name of a trace event
    have in common (the text prints operands bare, the trace with their
    shapes).  None for a line that is no instruction."""
    parts = _split(line)
    if parts is None:
        return None
    return f"{parts[0]} = {_COMMENT.sub('', parts[1])}"


def _operands(line: str) -> list:
    """The names an instruction line reads: what stands between its
    opcode's parentheses (the text prints operands bare)."""
    after = _split(line)[2]
    start = after.find("(")
    end = _closing(after[start:]) if start >= 0 else None
    return [] if end is None else _OPERAND.findall(after[start:start + end])


def parse_hlo_scopes(text: str) -> Tuple[Optional[str], Dict[str, str]]:
    """``(module name, {instruction: op_name})`` of one compiled HLO
    text.  A fusion takes the ``op_name`` of its own metadata and, where
    it has none, the commonest among the instructions of the computation
    it calls; instructions with neither (``bitcast``, ``copy-done``,
    ``get-tuple-element``) are left out.  A ``conditional`` or ``while``
    goes under ``qt.flow/``: its event spans those of the computation it
    ran, which carry their own names.  A kernel the compiler renamed
    (``ragged-dot-*``) has lost its scopes: it takes those of what it
    reads, of a backward operand where it has one (:func:`_adopt`)."""
    module, table = None, {}
    inside: Dict[str, Counter] = {}     # computation -> its op_names
    orphans = []                        # (instruction, called computation)
    operands: Dict[str, tuple] = {}     # instruction name -> (key, operands)
    comp = None
    for line in text.splitlines():
        key = instruction_key(line)
        if key is None:
            m = _COMPUTATION.match(line)
            if m is not None:
                comp = m.group(1)
            elif module is None:
                m = _MODULE.match(line)
                module = m.group(1) if m else None
            continue
        operands[key.split(" = ", 1)[0]] = (key, _operands(line))
        m = _OP_NAME.search(line)
        if _RUNS.search(line):
            table[key] = f"{FLOW}/{m.group(1) if m else ''}"
        elif m is not None:
            table[key] = m.group(1)
            inside.setdefault(comp, Counter())[m.group(1)] += 1
        else:
            m = _CALLS.search(line)
            if m is not None:
                orphans.append((key, m.group(1)))
    for key, called in orphans:
        names = inside.get(called)
        if names:
            table[key] = names.most_common(1)[0][0]
    table.update({key: _adopt(key.split(" = ", 1)[0], op, table, operands)
                  for key, op in table.items() if op.startswith(_RENAMED)})
    return module, table


def _adopt(name: str, op: str, table: Dict[str, str],
           operands: Dict[str, tuple], depth: int = 8) -> str:
    """``<scopes of an operand>/<op>`` for a renamed kernel.  Each operand
    answers with its own ``op_name`` or, where it has none (``bitcast``,
    ``copy``, ``get-tuple-element``, async slices), with those of its own
    operands, up to ``depth`` steps.  Of the answers under a ``qt.`` scope
    the first that ran in the backward pass (``transpose(``) wins, else
    the first; with none, ``op`` stays as the compiler wrote it."""

    def answers(n, left):
        for o in operands.get(n, (None, ()))[1]:
            key = operands.get(o, (None,))[0]
            named = table.get(key)
            if named is None:
                if key is not None and left > 1:
                    yield from answers(o, left - 1)
            elif PREFIX in named and not named.startswith(_RENAMED):
                yield named

    found = list(answers(name, depth))
    if not found:
        return op
    best = next((f for f in found if "transpose(" in f), found[0])
    return best.rsplit("/", 1)[0] + "/" + op


def scoped(table: Dict[str, str]) -> int:
    """How many instructions of a parsed table sit under a ``qt.`` scope.
    None at all, for a program this build registered, means stale names."""
    return sum(PREFIX in op for op in table.values())


# -- the registry ---------------------------------------------------------
_lock = threading.Lock()
_programs: Dict[str, tuple] = {}    # name -> (jitted, abstract arguments)
_tables: Dict[str, tuple] = {}      # name -> (entry it was read from, table)


def _log(msg: str) -> None:
    print(f"[quiver_tpu] device_scopes: {msg}", file=sys.stderr, flush=True)


def _abstract(x):
    """Shape, dtype and, for a COMMITTED array only, sharding: exactly
    what the call was specialised on, so that lowering these again finds
    the call's own executable in memory (nothing compiles, nothing is
    read) instead of building a twin under another cache key."""
    import jax

    t = jax.typeof(x)
    sharding = x.sharding if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=sharding,
                                weak_type=getattr(t, "weak_type", False))


def register_program(jitted, args) -> None:
    """Keep ``jitted`` and the abstract values of ``args`` (shape, dtype,
    sharding) under the program's name, ``jit_<function name>``; the newest
    entry of a name wins.  No buffer stays reachable through the entry:
    call it BEFORE the program where an argument is donated.  Never raises
    into the step that calls it."""
    try:
        import jax

        name = "jit_" + jitted.__name__
        entry = (jitted, jax.tree_util.tree_map(_abstract, args))
    except Exception as e:      # a boundary that must keep the step running
        _log(f"could not register {jitted!r}: {e!r}")
        return
    with _lock:
        _programs[name] = entry


def _compiled_text(jitted, abstract, cache: bool) -> str:
    if cache:
        return jitted.lower(*abstract).compile().as_text()
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    # whether the cache is used is decided once per process: reset to
    # have the flag read again, on the way in and on the way out; and the
    # executable the call holds in memory is the stale one, so drop those
    # too (every program of the process builds again on its next call:
    # this path is for the run after a scope was renamed, not for a loop)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    try:
        return jitted.lower(*abstract).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _read(name: str, jitted, abstract) -> Dict[str, str]:
    t0 = time.perf_counter()
    _, table = parse_hlo_scopes(_compiled_text(jitted, abstract, True))
    if not scoped(table):
        _log(f"{name}: the compiled text names no {PREFIX}* scope, so the "
             f"executable came out of the compile cache from a build with "
             f"other names; compiling it once more with the cache off")
        _, table = parse_hlo_scopes(_compiled_text(jitted, abstract, False))
    _log(f"{name}: {len(table)} instructions named, {scoped(table)} under "
         f"{PREFIX}* scopes, read in {time.perf_counter() - t0:.1f} s")
    return table


def device_scopes() -> Dict[str, Dict[str, str]]:
    """``{program name: {instruction: op_name}}`` for every registered
    program whose compiled text could be had, memoised per registration.
    Never raises: a program that cannot be lowered, compiled or printed is
    logged once on standard error and left out."""
    with _lock:
        programs = dict(_programs)
    out = {}
    for name, entry in programs.items():
        memo = _tables.get(name)
        if memo is None or memo[0] is not entry:
            try:
                table = _read(name, *entry)
            except Exception as e:   # the caller is a reader, not the step
                _log(f"{name}: no compiled text ({e!r}); left out")
                table = {}
            memo = _tables[name] = (entry, table)
        if memo[1]:
            out[name] = dict(memo[1])
    return out
