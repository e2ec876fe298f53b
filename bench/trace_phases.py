"""Device time of a traced window by the solve's named phases.

The program names the parts of its jitted solve (``repro.obs.profiling``
``PHASES``) and, through ``Solver.phase_table(spec)``, says which of
the compiled program's instructions each phase holds.  This charges the
window's device op time to those phases:

* the window, the device planes and the leaf rule are those of
  ``trace_reduce.reduce_trace``: a leaf op's time is clipped to the
  ``bench:window`` span and averaged over the device planes, so the
  phases' seconds sum to the leaf time that its ``device_ops`` covers.
  The leaf rule holds within each op line: a TPU plane has one, while
  a CPU trace spreads XLA's ops over a line per host thread, which
  overlap without nesting;
* an op goes to its phase in the table, when the table holds its name
  and the op's module is the table's ``module``; every other op goes
  to ``(unphased)``.  A CPU op event names its module in a stat; on a
  TPU the op runs inside an event of the plane's ``XLA Modules`` line;
* a phase's ``runs`` is the largest number of window events of any one
  of its ops that no loop inside the phase repeats (the table's
  ``looped``): how many times the phase ran.

``phases`` maps each phase to ``[seconds, runs]``.  ``group`` adds up
an outer phase (``sssp.round``) with its sub-phases (``round.*``).
"""
from __future__ import annotations

import bisect
import warnings
from collections import defaultdict

from bench.trace_reduce import _leaves, _op_name

UNPHASED = "(unphased)"


def _stats(ev) -> dict:
    with warnings.catch_warnings():
        # the profiler's binding warns as it first makes the stats type
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(ev.stats)


def _module(name) -> str:
    """A module's name without the program id some profilers append."""
    return str(name).split("(", 1)[0]


def _op_lines(plane, op_line, op_stat, module_line):
    """Each op line of a device plane as ``[(start, end, (name,
    module)), ...]``.  An op's module is its ``hlo_module`` stat (CPU),
    or else the event of the plane's ``module_line`` that holds its
    start (TPU: one event per run of a program)."""
    runs = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                   _module(ev.name))
                  for line in plane.lines if line.name.startswith(module_line)
                  for ev in line.events)
    starts = [r[0] for r in runs]

    def module_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return runs[i][2] if i >= 0 and runs[i][1] >= t else None

    out = []
    for line in plane.lines:
        if not line.name.startswith(op_line):
            continue
        ops = []
        for ev in line.events:
            st = _stats(ev)
            if op_stat is not None and op_stat not in st:
                continue
            a = int(ev.start_ns)
            ops.append((a, int(ev.start_ns + ev.duration_ns),
                        (ev.name, st.get("hlo_module") or module_at(a))))
        out.append(ops)
    return out


def reduce_phases(path, op_phases: dict, *, device_plane: str = "/device:TPU:",
                  op_line: str = "XLA Ops",
                  host_plane: str = "/host:CPU",
                  window_span: str = "bench:window",
                  module_line: str = "XLA Modules",
                  op_stat: str = None) -> dict:
    """``{"phases": {phase: [seconds, runs]}, "leaf_s": seconds}`` of the
    trace at ``path``; ``op_phases`` is ``{instruction: phase}`` of the
    program named by its ``module`` attribute (``Solver.phase_table``).
    With ``op_stat``, only the events that carry that stat are ops: a
    CPU trace's op lines also hold the thread pool's events and an
    ``end:`` marker inside each op."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    windows, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith(host_plane):
            windows += [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                        for line in plane.lines for ev in line.events
                        if ev.name == window_span]
        if plane.name.startswith(device_plane):
            lines = _op_lines(plane, op_line, op_stat, module_line)
            if any(lines):
                devices.append(lines)
    if not windows:
        raise ValueError(f"no {window_span!r} span in {path}")
    if not devices:
        raise ValueError(f"no op events on a {device_plane!r} plane in "
                         f"{path}")
    lo, hi = windows[0]
    module = getattr(op_phases, "module", None)
    looped = getattr(op_phases, "looped", frozenset())
    seconds = defaultdict(float)
    counts = defaultdict(lambda: defaultdict(int))
    for i, lines in enumerate(devices):
        for ops in lines:
            for a, b, (name, mod) in _leaves(ops):
                if not (b > lo and a < hi):
                    continue
                op = _op_name(name)
                ours = module is None or mod is None or \
                    _module(mod) == module
                ph = op_phases.get(op, UNPHASED) if ours else UNPHASED
                seconds[ph] += (min(b, hi) - max(a, lo)) / 1e9 / len(devices)
                if not (ours and op in looped):
                    counts[ph][(i, mod, op)] += 1
    phases = {ph: [seconds[ph], max(counts[ph].values(), default=0)]
              for ph in seconds}
    return {"phases": phases, "leaf_s": sum(seconds.values())}


def group(phases: dict, outer: str):
    """``(seconds, runs)`` of phase ``outer`` (``sssp.round``) with its
    sub-phases (``round.*``), or ``None`` where none of them ran."""
    prefix = outer.split(".", 1)[1] + "."
    got = [v for p, v in phases.items()
           if p == outer or p.startswith(prefix)]
    if not got:
        return None
    return sum(s for s, _ in got), max(r for _, r in got)
