"""Where the time goes: phase scopes in the slot step, host spans around it.

Two kinds of marks, one vocabulary:

**Phase scopes** (device side).  ``phase(name)`` is
``jax.named_scope("slot." + name)``: every operation traced inside it
carries ``slot.<name>`` in its ``op_name`` metadata, so a profiler trace of
the compiled program can be split by what the slot step was doing.  Scopes
are metadata only and cost nothing at run time.  The phases, in slot order:

  ``service``     busy servers work down their in-flight task
  ``schedule``    idle servers start a queued task (durations drawn here)
  ``arrivals``    Poisson count, replica draws, locality classes
  ``candidates``  what the router is handed: pod candidate draws, or full
                  Balanced-Pandas' random tie priority
  ``route``       the ``route_commit`` kernel with its layout changes
  ``accumulate``  run statistics and in-scan collectors

``op_phases(hlo_text)`` maps the top-level instructions of a compiled
module (those a device trace shows as events) to these phases.  XLA does
not keep the scope on every instruction it emits (the ``reduce-window``
of a cumulative sum carries no ``op_name``), so an unscoped leaf inherits
a phase by a fixed data-flow rule; loop and branch containers, whose
events span their bodies, map to ``CONTAINER``.

**Host spans**.  ``span(name)`` opens ``jax.profiler.TraceAnnotation``
(the span lands in a profiler trace on the device events' clock) and
records ``(name, parent, start, end)`` from ``time.perf_counter`` in a
bounded in-memory buffer, with a count and a total per name.  The buffer
is always on: a span costs microseconds, against sweep calls of seconds.
``spans()`` reads it, ``totals()`` the per-name sums, ``reset()`` clears
both.  ``core.simulate_sweep`` opens ``sweep.call`` around
``sweep.grid``, ``sweep.launch`` and ``sweep.summarize``; on a mesh,
``sweep.launch`` holds ``sweep.place``, which puts the grid on the
devices, and ``core.simulator.sweep_devices()`` counts the devices of
the last dispatch.
"""
from __future__ import annotations

import collections
import contextlib
import re
import threading
import time
from typing import NamedTuple, Optional

import jax

PHASES = ("service", "schedule", "arrivals", "candidates", "route",
          "accumulate")
SCOPE = "slot."
CONTAINER = "container"       # while / conditional / call: readers skip it
KERNEL = "route_commit"       # the routing kernel's custom-call name
MAX_SPANS = 8192              # buffer bound: the oldest spans drop first

CONTAINER_OPS = frozenset({"while", "conditional", "call"})
# no device work of their own: they take a phase only to pass it on
PLUMBING_OPS = frozenset({"parameter", "constant", "tuple",
                          "get-tuple-element", "bitcast", "after-all"})


def phase(name: str):
    """``jax.named_scope`` for one phase of the slot step."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; phases: {PHASES}")
    return jax.named_scope(SCOPE + name)


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------


class Span(NamedTuple):
    """One closed host span; times from ``time.perf_counter`` (s)."""
    name: str
    parent: Optional[str]     # the innermost span open around it, or None
    start: float
    end: float


_BUFFER: collections.deque = collections.deque(maxlen=MAX_SPANS)
_TOTALS: dict = {}            # name -> [count, total seconds]
_OPEN = threading.local()     # per thread: names of the spans now open


@contextlib.contextmanager
def span(name: str):
    """Time the block as host span ``name`` (see module docstring)."""
    stack = _OPEN.__dict__.setdefault("names", [])
    parent = stack[-1] if stack else None
    stack.append(name)
    start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        end = time.perf_counter()
        stack.pop()
        _BUFFER.append(Span(name, parent, start, end))
        tot = _TOTALS.setdefault(name, [0, 0.0])
        tot[0] += 1
        tot[1] += end - start


def spans(name: Optional[str] = None) -> list:
    """The buffered spans, oldest first (only ``name``'s if given)."""
    return [s for s in list(_BUFFER) if name is None or s.name == name]


def totals() -> dict:
    """``{name: (count, total seconds)}`` of every span closed since the
    last ``reset`` (not bounded by the buffer)."""
    return {n: (c, t) for n, (c, t) in _TOTALS.items()}


def reset() -> None:
    """Drop the buffered spans and the per-name totals."""
    _BUFFER.clear()
    _TOTALS.clear()


# ---------------------------------------------------------------------------
# compiled module -> phase of each instruction
# ---------------------------------------------------------------------------

_COMP_HEAD = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(
    r"\b(condition|body|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")


class Instr(NamedTuple):
    """One instruction of a compiled module's text."""
    name: str
    opcode: str
    computation: str
    operands: tuple
    op_name: str                 # its ``op_name`` metadata ("" if none)
    callees: tuple               # computations it runs as top-level code


def _drop_shape(rest: str) -> str:
    """Drop the leading result shape (a tuple shape nests parentheses)."""
    rest = rest.lstrip()
    if not rest.startswith("("):
        return rest.split(" ", 1)[1] if " " in rest else ""
    depth = 0
    for i, ch in enumerate(rest):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return rest[i + 1:].lstrip()
    return ""


def _parse_instr(line: str, computation: str) -> Optional[Instr]:
    m = _INSTR.match(line)
    if m is None:
        return None
    rest = _drop_shape(m.group(3))
    paren = rest.find("(")
    if paren <= 0:
        return None
    opcode = rest[:paren].strip()
    depth, end = 0, len(rest)
    for i in range(paren, len(rest)):
        depth += rest[i] == "("
        depth -= rest[i] == ")"
        if depth == 0:
            end = i
            break
    attrs = rest[end + 1:]
    callees = []
    if opcode in CONTAINER_OPS:
        callees = [c for _, c in _CALLED.findall(attrs)]
        for group in _BRANCHES.findall(attrs):
            callees += _REF.findall(group)
    op = _OP_NAME.search(attrs)
    return Instr(m.group(2), opcode, computation,
                 tuple(_REF.findall(rest[paren + 1:end])),
                 op.group(1) if op else "", tuple(callees))


def top_level(hlo_text: str) -> list:
    """The ``Instr`` of every top-level instruction of a compiled module:
    those of the entry computation and of every computation that a
    ``while``, ``conditional`` or ``call`` runs, in text order per
    computation.  Fusion bodies and the reducers of ``reduce`` or
    ``sort`` are left out: they run inside one device event."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMP_HEAD.match(line)
            if m:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
        elif line.strip() == "}":
            cur = None
        else:
            ins = _parse_instr(line, cur)
            if ins is not None:
                comps[cur].append(ins)
    out, seen, todo = [], set(), [entry]
    while todo:
        c = todo.pop(0)
        if c in seen or c not in comps:
            continue
        seen.add(c)
        out += comps[c]
        for ins in comps[c]:
            todo += ins.callees
    return out


def _scoped_phase(ins: Instr) -> Optional[str]:
    """The phase named in the instruction's own ``op_name``, if any (the
    innermost, where scopes nest)."""
    found = None
    for part in ins.op_name.split("/"):
        if part.startswith(SCOPE) and part[len(SCOPE):] in PHASES:
            found = part[len(SCOPE):]
    return found


def op_phases(hlo_text: str) -> dict:
    """``{instruction name: phase or CONTAINER}`` over ``top_level``.

    The rules, in order:

    1. ``while``, ``conditional`` and ``call`` map to ``CONTAINER``: their
       device event spans the events of their bodies.
    2. The routing kernel's custom call (``route_commit.N``) maps to
       ``route``.
    3. An instruction whose ``op_name`` holds ``slot.<phase>`` takes that
       phase.
    4. Any other instruction inherits within its computation, repeated
       until nothing changes: the phase of the first instruction that
       consumes it and has one; else that of the first operand that has
       one.  An unscoped op that XLA emits on the way into a phase's work
       (a ``reduce-window`` of the candidate draws) thereby belongs to
       that phase, and one at the end of a computation to the phase that
       made its input.  An instruction with no phased neighbour at all
       (a loop's own counter test, the entry's copies in and out) stays
       unmapped.
    """
    out: dict = {}
    by_comp: dict = {}
    for ins in top_level(hlo_text):
        by_comp.setdefault(ins.computation, []).append(ins)
    for instrs in by_comp.values():
        phase_of: dict = {}
        for ins in instrs:
            if ins.opcode in CONTAINER_OPS:
                out[ins.name] = CONTAINER
            elif (ins.opcode == "custom-call"
                  and ins.name.split(".")[0] == KERNEL):
                phase_of[ins.name] = "route"
            elif _scoped_phase(ins) is not None:
                phase_of[ins.name] = _scoped_phase(ins)
        users: dict = {}
        for ins in instrs:
            for o in ins.operands:
                users.setdefault(o, []).append(ins.name)
        open_ = [ins for ins in instrs
                 if ins.opcode not in CONTAINER_OPS
                 and ins.name not in phase_of]
        while open_:
            left = []
            for ins in open_:
                p = next((phase_of[u] for u in users.get(ins.name, ())
                          if u in phase_of), None)
                if p is None:
                    p = next((phase_of[o] for o in ins.operands
                              if o in phase_of), None)
                if p is None:
                    left.append(ins)
                else:
                    phase_of[ins.name] = p
            if len(left) == len(open_):
                break
            open_ = left
        out.update(phase_of)
    return out
