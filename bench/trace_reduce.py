"""Reduce a profiler trace (``.xplane.pb``) to busy time, ops and gaps.

* The window is the host span named ``window_span`` (the harness opens
  ``bench:window`` around its measured window).
* Busy time is the union of the op events on each device plane's op
  line, clipped to the window, averaged over the device planes.
* Device ops are the leaf op events' durations summed by name (the
  HLO instruction's name, the text before `` = ``).  An op whose event
  holds others, as a ``while`` holds its body's, is left out there, so
  that no time is counted twice; it still counts as busy.
* Idle gaps are the stretches of the window in which no op runs on a
  device; each is charged to the innermost host span (one whose name
  starts with a prefix of ``span_prefixes``) open at its midpoint, or
  to ``"(no span)"``.

On a TPU the device planes are ``/device:TPU:<i>`` and their op line is
``XLA Ops``.  Plane and line prefixes are parameters so that a trace
recorded on the CPU, where XLA's ops run on host threads, can check
the arithmetic.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Iterable, List, Tuple

TOP = 10
NO_SPAN = "(no span)"


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _leaves(ops):
    """The events that hold no other event (ops sorted by start)."""
    ops = sorted(ops, key=lambda e: (e[0], -e[1]))
    holds = [False] * len(ops)
    stack = []
    for i, (a, b, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        if stack and b <= ops[stack[-1]][1]:
            holds[stack[-1]] = True
        stack.append(i)
    return [e for e, h in zip(ops, holds) if not h]


def _op_name(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce_trace(path, *, device_plane: str = "/device:TPU:",
                 op_line: str = "XLA Ops", host_plane: str = "/host:CPU",
                 window_span: str = "bench:window",
                 span_prefixes=("bench:", "repro:")) -> dict:
    """``busy_s``, ``window_s``, ``n_devices``, ``device_ops`` and
    ``idle_gaps`` (each a ``[[name, seconds], ...]`` list, largest
    first, at most ten) of the trace at ``path``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith(host_plane):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tuple(span_prefixes)):
                        a = int(ev.start_ns)
                        spans.append((a, a + int(ev.duration_ns), ev.name))
        if plane.name.startswith(device_plane):
            ops = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                    ev.name)
                   for line in plane.lines if line.name.startswith(op_line)
                   for ev in line.events]
            if ops:
                devices.append(ops)
    windows = [(a, b) for a, b, name in spans if name == window_span]
    if not windows:
        raise ValueError(f"no {window_span!r} span in {path}")
    if not devices:
        raise ValueError(f"no op events on a {device_plane!r} plane in "
                         f"{path}")
    lo, hi = windows[0][0], windows[0][1]
    op_time = defaultdict(float)
    busy_ns = 0
    merged = []
    for ops in devices:
        for a, b, name in _leaves(ops):
            if b > lo and a < hi:
                op_time[_op_name(name)] += \
                    (min(b, hi) - max(a, lo)) / len(devices)
        busy = _union(_clip([(a, b) for a, b, _ in ops], lo, hi))
        busy_ns += sum(b - a for a, b in busy)
        merged.extend(busy)
    # a gap is idle on every device
    busy_any = _union(merged)
    gaps, t = [], lo
    for a, b in busy_any:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    inner = sorted((s for s in spans if s[2] != window_span),
                   key=lambda s: s[0])
    starts = [s[0] for s in inner]
    gap_time = defaultdict(float)
    for a, b in gaps:
        mid = (a + b) // 2
        name = NO_SPAN
        # the innermost open span is the latest-starting one covering mid
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if inner[i][1] > mid:
                name = inner[i][2]
                break
        gap_time[name] += (b - a) / 1e9

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:TOP]

    return {"busy_s": busy_ns / len(devices) / 1e9,
            "window_s": (hi - lo) / 1e9,
            "n_devices": len(devices),
            "device_ops": top({k: v / 1e9 for k, v in op_time.items()}),
            "idle_gaps": top(gap_time)}
