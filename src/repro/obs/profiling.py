"""``jax.profiler`` hooks: host spans and named phases of the solve.

:func:`annotate` wraps host-side phases — engine/layout builds, relax
dispatch, the serving scheduler's dispatch and result shaping — in a
``jax.profiler.TraceAnnotation`` so they show up as named spans in
TensorBoard / Perfetto captures taken with ``jax.profiler.trace()``.
When the profiler is missing (stripped builds, very old jax) it degrades
to a ``nullcontext``: annotation must never be able to break a solve.
A host-side context manager inside ``jit`` would only fire at trace
time, so these sites live at the jit call boundaries (see
``core/sssp.py`` / ``serve/``).

:func:`phase` names a part of the jitted solve itself: a
``jax.named_scope``, which adds HLO ``op_name`` metadata and no
instruction, so a phased program computes exactly what an unphased one
does.  :data:`PHASES` is the whole vocabulary.  Every name is dotted,
which no JAX primitive's name is: an ``op_name`` path ends in the
primitive (``.../gather``), so a bare ``gather`` scope would match ops
it never held.  :func:`phase_table` reads a compiled module's text back
into ``{instruction: phase}``, the join from a profiler trace's op
events (which carry only the instruction's name) to the phases.
"""
from __future__ import annotations

import contextlib
import re

import jax

__all__ = ["annotate", "phase", "phase_table", "PhaseTable", "PHASES",
           "PROFILER_AVAILABLE"]

try:
    from jax.profiler import TraceAnnotation as _TraceAnnotation
    PROFILER_AVAILABLE = True
except Exception:                                   # pragma: no cover
    _TraceAnnotation = None
    PROFILER_AVAILABLE = False

# The solve loop's phases, outer before inner.  ``round.*`` nest inside
# ``sssp.round`` and ``transition.*`` inside ``sssp.transition``: a
# sub-phase is named ``<outer's second part>.<part>``.  A sub-phase of a
# sub-phase keeps the whole name of its outer one
# (``transition.pull.scan``), so it still counts as a ``transition.*``.
PHASES = (
    "sssp.round",           # one relaxation round (any backend)
    "round.compact",        # compact the frontier, bound its rows' windows
    "round.gather",         # dist/frontier/parent at each edge's source
    "round.reduce",         # per-destination min and its winner
    "round.apply",          # commit improvements
    "round.count",          # the round's counters
    "sssp.bootstrap",       # first step's upper bound (Algo 2 l.18-20)
    "sssp.transition",      # step transition (runs when the frontier empties)
    "transition.pending",   # smallest pending path length
    "transition.window",    # computeST and the next window's gap
    "transition.pull",      # Function 1's pull phase
    "transition.pull.scan",    # responder/requester gathers and masks
    "transition.pull.reduce",  # per-requester min and its winner
    "transition.pull.apply",   # commit improvements, count
)


def annotate(name: str):
    """Context manager naming the enclosed host-side phase for profilers."""
    if _TraceAnnotation is None:                    # pragma: no cover
        return contextlib.nullcontext()
    return _TraceAnnotation(name)


def phase(name: str):
    """Context manager naming the enclosed part of a jitted solve."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; expected one of {PHASES}")
    return jax.named_scope(name)


class PhaseTable(dict):
    """``{instruction_name: phase}`` of one compiled module.

    ``module`` is the module's name, which a trace's op events carry
    beside the instruction's, so that another program's instruction of
    the same name is not taken for this one's.  ``looped`` names the
    instructions that a loop inside their phase runs many times each
    time the phase runs (the binary search of ``transition.window``):
    counting how often a phase ran leaves them out.
    """

    def __init__(self, entries=(), module=None, looped=frozenset()):
        super().__init__(entries)
        self.module = module
        self.looped = frozenset(looped)


_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")


def _innermost(op_name: str):
    for part in reversed(op_name.split("/")):
        if part in PHASES:
            return part
    return None


def phase_table(hlo_text: str) -> PhaseTable:
    """``{instruction_name: phase}`` of an optimized HLO module's text.

    An instruction whose ``op_name`` metadata is a scope path gets the
    innermost component of it that is in :data:`PHASES`, or none.  One
    that the compiler made with no such path (split reductions, copies,
    wrapped ops) takes, in this order, the one phase that the
    instructions of its fused computation name, the phase of the
    control-flow instruction that runs its computation, the one phase
    its users name, or the one its operands name.  An instruction left
    without a phase gets no entry.
    """
    comp = None
    members, comp_of, refs, called, placed = {}, {}, {}, {}, set()
    lines = {}
    table = {}
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and " = " not in line:
            comp = m.group(1)
            members[comp] = []
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        members[comp].append(name)
        comp_of[name] = comp
        lines[name] = line
        body = line.split(" = ", 1)[1].split(", metadata={", 1)[0]
        refs[name] = _REF.findall(body)
        op = _OP_NAME.search(line)
        if op and "/" in op.group(1):
            placed.add(name)
            ph = _innermost(op.group(1))
            if ph is not None:
                table[name] = ph
    callers = {}
    for name, rs in refs.items():
        called[name] = [r for r in rs if r in members]
        for c in called[name]:
            callers.setdefault(c, []).append(name)
    users = {}
    for name, rs in refs.items():
        for r in rs:
            if r in comp_of:
                users.setdefault(r, []).append(name)

    def one(names):
        got = {table[n] for n in names if n in table}
        return next(iter(got)) if len(got) == 1 else None

    changed = True
    while changed:
        changed = False
        for name in comp_of:
            if name in table or name in placed:
                continue
            ph = (one(i for c in called[name] for i in members[c])
                  or one(callers.get(comp_of[name], ()))
                  or one(users.get(name, ()))
                  or one(r for r in refs[name] if r in comp_of))
            if ph is not None:
                table[name] = ph
                changed = True
    # computations that a loop inside some phase runs
    inner = [c for name, cs in called.items()
             if " while(" in lines[name] and name in table for c in cs]
    looped = set()
    while inner:
        c = inner.pop()
        if c not in looped:
            looped.add(c)
            inner += [cc for i in members[c] for cc in called[i]]
    module = _MODULE.search(hlo_text)
    return PhaseTable(table, module=module.group(1) if module else None,
                      looped={i for c in looped for i in members[c]})
