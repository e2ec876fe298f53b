"""What decides ``correct``: served answers against the plain reference.

Every number here is a count of answers that disagree with the float32
reference (:mod:`bench.reference`), and each has the limit 0: the
configuration states exact shortest paths in float32, and an exact
comparison has no tolerance.

* ``dist_mismatch`` — tree vertices whose distance differs from the
  reference bit for bit (an unreached vertex must read +inf).
* ``parent_bad`` — reached tree vertices whose parent is not a tight
  edge: the edge ``parent -> v`` must exist and the reference distance
  of the parent plus its float32 weight must give the reference
  distance of ``v``.  Ties make the parent itself ambiguous, so a tight
  parent is what the GAP verifier asks for.
* ``answers_wrong`` — served k-nearest lists and distance-bounded sets
  that differ from the reference's: other vertices, other distances, or
  a listed vertex without a tight parent.
* ``answers_missing`` — requests of the window that failed or had not
  come back a minute after the window closed.
"""
from __future__ import annotations

import numpy as np

from bench import reference

LIMITS = {"dist_mismatch": 0, "parent_bad": 0, "answers_wrong": 0,
          "answers_missing": 0}


def _tight(adj, ref_dist, vs, parent) -> np.ndarray:
    """Per vertex of ``vs``: is ``parent[v] -> v`` a tight reference edge."""
    vs = np.asarray(vs, np.int64)
    p = np.asarray(parent, np.int64)[vs]
    w = adj.edge_weight(p, vs)
    ok = p >= 0
    via = np.full(vs.shape, np.nan, ref_dist.dtype)
    via[ok] = ref_dist[p[ok]] + w[ok]
    return ok & (via == ref_dist[vs])


def tree_numbers(adj, source: int, dist, parent, ref_dist) -> dict:
    """``dist_mismatch`` and ``parent_bad`` of one served tree."""
    dist = np.asarray(dist)
    same = (dist == ref_dist) | (np.isinf(dist) & np.isinf(ref_dist))
    reached = np.flatnonzero(np.isfinite(ref_dist))
    reached = reached[reached != source]
    bad = int((~_tight(adj, ref_dist, reached, parent)).sum())
    if int(np.asarray(parent)[source]) != source:
        bad += 1
    return {"dist_mismatch": int((~same).sum()), "parent_bad": bad}


def query_wrong(adj, kind: str, source: int, param, answer: dict) -> bool:
    """Whether one served ``knear``/``bounded`` answer is wrong.

    ``answer`` holds what the serving path shaped: ``nearest`` (a list
    of ``(vertex, dist)``) for k-nearest, ``dist`` (+inf outside the
    settled set) for bounded, and ``parent`` for both.
    """
    if kind == "knear":
        ref_d, _, settled = reference.dijkstra(adj, source, k=int(param))
        want = reference.nearest(ref_d, settled, source, int(param))
        got = [(int(v), float(d)) for v, d in answer["nearest"]]
        if [d for _, d in got] != [d for _, d in want]:
            return True
        vs = np.asarray([v for v, _ in got], np.int64)
        if len(set(vs.tolist())) != vs.size or not settled[vs].all():
            return True
        if not (ref_d[vs].astype(np.float64) ==
                np.asarray([d for _, d in got])).all():
            return True
    elif kind == "bounded":
        ref_d, _, settled = reference.dijkstra(adj, source,
                                               bound=float(param))
        dist = np.asarray(answer["dist"])
        vs = np.flatnonzero(np.isfinite(dist))
        want = np.flatnonzero(settled & (ref_d <= np.float32(param)))
        if not np.array_equal(vs, want) or not (dist[vs] == ref_d[vs]).all():
            return True
    else:
        raise ValueError(f"no answer check for query kind {kind!r}")
    vs = vs[vs != source]
    return not _tight(adj, ref_d, vs, answer["parent"]).all()


def verdict(numbers: dict) -> bool:
    """``correct``: every number within its limit."""
    return all(numbers[k] <= LIMITS[k] for k in numbers)
