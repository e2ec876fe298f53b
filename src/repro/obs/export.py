"""Exporters: Prometheus text exposition and JSONL snapshots.

Two consumers, two formats, both derived from a
:meth:`MetricsRegistry.snapshot` dict:

* :func:`to_prometheus` — the Prometheus/OpenMetrics text exposition
  (``# HELP`` / ``# TYPE`` headers, ``_bucket``/``_sum``/``_count``
  histogram series) for scrape endpoints;
* :func:`write_jsonl_snapshot` — append-only JSONL dumps for offline
  perf-trajectory analysis (one snapshot per line).

A solve's timeline is a ``jax.profiler.trace()`` capture, whose device
ops carry the solve's named phases (:mod:`repro.obs.profiling`).

:func:`parse_prometheus` is a deliberately strict mini-parser used by
tests and the CI smoke step to prove the exposition is well-formed —
it is not a general Prometheus client.
"""
from __future__ import annotations

import json
import math
import re
import time

__all__ = ["to_prometheus", "parse_prometheus", "write_jsonl_snapshot"]

_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?P<labels>\{[^{}]*\})?\s+"
    r"(?P<value>[-+]?(?:\d+\.?\d*(?:[eE][-+]?\d+)?|Inf|NaN))$")


def _fmt(v) -> str:
    """Prometheus sample-value formatting (+Inf / NaN spelled out)."""
    f = float(v)
    if math.isnan(f):
        return "NaN"
    if math.isinf(f):
        return "+Inf" if f > 0 else "-Inf"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _labels_str(labels: dict, extra: dict = None) -> str:
    merged = dict(labels or {})
    merged.update(extra or {})
    if not merged:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(merged.items()))
    return "{" + inner + "}"


def to_prometheus(snapshot: dict) -> str:
    """Render a :meth:`MetricsRegistry.snapshot` as text exposition."""
    by_name: dict = {}
    for full_name, entry in snapshot.items():
        base = full_name.split("{", 1)[0]
        by_name.setdefault(base, []).append(entry)
    lines = []
    for base in sorted(by_name):
        series = by_name[base]
        kind = series[0]["type"]
        help_text = next((s.get("help") for s in series if s.get("help")),
                         None)
        if help_text:
            lines.append(f"# HELP {base} {help_text}")
        lines.append(f"# TYPE {base} {kind}")
        for entry in series:
            labels = entry.get("labels", {})
            if kind == "histogram":
                # bucket keys are canonical bound strings ("0.1", "+Inf");
                # order by numeric value, not lexically
                for bound in sorted(entry["buckets"],
                                    key=lambda k: float(k.replace("Inf",
                                                                  "inf"))):
                    lines.append(
                        f"{base}_bucket{_labels_str(labels, {'le': bound})} "
                        f"{entry['buckets'][bound]}")
                lines.append(f"{base}_sum{_labels_str(labels)} "
                             f"{_fmt(entry['sum'])}")
                lines.append(f"{base}_count{_labels_str(labels)} "
                             f"{entry['count']}")
            else:
                lines.append(f"{base}{_labels_str(labels)} "
                             f"{_fmt(entry['value'])}")
    return "\n".join(lines) + "\n"


def parse_prometheus(text: str) -> dict:
    """Strictly parse a text exposition back into ``{sample_name: value}``.

    Raises ``ValueError`` on any malformed line; histogram invariants
    (cumulative ``_bucket`` counts ending at ``_count``) are checked by
    the tests on top of this.
    """
    samples: dict = {}
    for lineno, raw in enumerate(text.splitlines(), 1):
        line = raw.strip()
        if not line:
            continue
        if line.startswith("#"):
            if not (line.startswith("# HELP ") or line.startswith("# TYPE ")):
                raise ValueError(f"line {lineno}: bad comment {raw!r}")
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            raise ValueError(f"line {lineno}: bad sample {raw!r}")
        key = m.group("name") + (m.group("labels") or "")
        if key in samples:
            raise ValueError(f"line {lineno}: duplicate sample {key!r}")
        samples[key] = float(m.group("value").replace("Inf", "inf"))
    return samples


def write_jsonl_snapshot(snapshot: dict, path, meta: dict = None) -> None:
    """Append one ``{"ts", ..., "metrics"}`` JSON line to ``path``."""
    record = {"ts": time.time(), **(meta or {}), "metrics": snapshot}
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")
