"""Plain shortest-path reference: a binary-heap Dijkstra over numpy rows.

Independent of the program: it builds its own adjacency from the
generator's ``(u, v, w)`` list (one edge per ordered pair, the lightest
of any parallel edges; each edge both ways, or each arc ``u -> v``
alone where the graph is directed) and computes in the precision it is
given.  In float32 every tentative distance is ``dist[x] + w``
rounded to float32, the sum the configuration states, so distances
are comparable bit for bit with any float32 engine that relaxes
``dist[u] + w``.  The same code in bfloat16 is the lower-precision
control.

Ties pop in ``(distance, vertex id)`` order, the order in which the
served k-nearest lists break them.
"""
from __future__ import annotations

import dataclasses
import heapq

import numpy as np


@dataclasses.dataclass(frozen=True)
class Adjacency:
    """CSR of out-arcs, rows sorted by neighbour id."""
    n: int
    row_ptr: np.ndarray
    col: np.ndarray
    w: np.ndarray
    key: np.ndarray          # row * n + col, ascending

    def edge_weight(self, a, b) -> np.ndarray:
        """Weight of edge ``a -> b`` per pair; NaN where there is none."""
        a = np.asarray(a, np.int64)
        b = np.asarray(b, np.int64)
        want = a * self.n + b
        pos = np.minimum(np.searchsorted(self.key, want),
                         self.key.shape[0] - 1)
        hit = (a >= 0) & (b >= 0) & (self.key[pos] == want)
        return np.where(hit, self.w[pos], np.nan).astype(self.w.dtype)

    def astype(self, dtype) -> "Adjacency":
        return dataclasses.replace(self, w=self.w.astype(dtype))


def adjacency(n: int, u, v, w, dtype=np.float32,
              directed: bool = False) -> Adjacency:
    """CSR with the lightest of any parallel edges kept: symmetrized, or
    with ``directed`` the arcs ``u -> v`` as they are."""
    u, v, w = np.asarray(u, np.int64), np.asarray(v, np.int64), np.asarray(w)
    if directed:
        s, d, ww = u, v, w.astype(dtype)
    else:
        s = np.concatenate([u, v])
        d = np.concatenate([v, u])
        ww = np.concatenate([w, w]).astype(dtype)
    keep = s != d
    s, d, ww = s[keep], d[keep], ww[keep]
    order = np.lexsort((ww.astype(np.float64), d, s))
    s, d, ww = s[order], d[order], ww[order]
    first = np.ones(s.shape[0], bool)
    first[1:] = (s[1:] != s[:-1]) | (d[1:] != d[:-1])
    s, d, ww = s[first], d[first], ww[first]
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(s, minlength=n), out=row_ptr[1:])
    return Adjacency(n=n, row_ptr=row_ptr, col=d, w=ww, key=s * n + d)


def dijkstra(adj: Adjacency, source: int, *, bound=None, k=None):
    """Label-setting Dijkstra from ``source`` in ``adj.w.dtype``.

    Returns ``(dist, parent, settled)``: ``settled`` marks the vertices
    whose distance is final.  With ``bound`` the search stops at the
    first vertex farther than it; with ``k`` it stops once the source,
    ``k`` more vertices and every vertex tied with the last of them
    are settled.  Without either it settles the source's component.
    """
    dt = adj.w.dtype
    dist = np.full(adj.n, np.inf, dt)
    parent = np.full(adj.n, -1, np.int64)
    settled = np.zeros(adj.n, bool)
    dist[source] = 0
    parent[source] = source
    heap = [(0.0, int(source))]
    rp, col, w = adj.row_ptr, adj.col, adj.w
    n_settled, last = 0, None
    while heap:
        d, x = heapq.heappop(heap)
        if settled[x]:
            continue
        if bound is not None and d > bound:
            break
        if k is not None and n_settled > k and d > last:
            break
        settled[x] = True
        n_settled, last = n_settled + 1, d
        lo, hi = rp[x], rp[x + 1]
        if lo == hi:
            continue
        nb = col[lo:hi]
        nd = dist[x] + w[lo:hi]
        better = nd < dist[nb]
        if better.any():
            nb, nd = nb[better], nd[better]
            dist[nb] = nd
            parent[nb] = x
            for dd, vv in zip(nd.astype(np.float64).tolist(), nb.tolist()):
                heapq.heappush(heap, (dd, vv))
    return dist, parent, settled


def nearest(dist, settled, source: int, k: int) -> list:
    """The ``k`` nearest settled vertices, ``[(vertex, dist)]`` ascending."""
    vs = np.flatnonzero(settled)
    vs = vs[vs != source]
    order = vs[np.lexsort((vs, dist[vs].astype(np.float64)))][:k]
    return [(int(x), float(dist[x])) for x in order]
