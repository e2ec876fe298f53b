"""The least memory traffic a shortest-path solve's logical work needs.

The exact counters of a solve say what work it did, whatever backend
did it: ``n_trav + n_pull_trav`` edge traversals and ``n_updates``
improved distances.  Each traversal has to read at least the edge's
other end and its weight (4 + 4 bytes, int32 and float32); each update
has to write a distance and a parent (4 + 4 bytes).  Everything else a
round touches (the frontier, the windows, edges it skips) is what an
implementation spends beyond this, so the share of the roofline that
this traffic gives can only grow when a backend wastes less.
"""
from __future__ import annotations

TRAVERSAL_BYTES = 4 + 4      # neighbour id + weight
UPDATE_BYTES = 4 + 4         # distance + parent


def min_bytes(n_trav: int, n_pull_trav: int, n_updates: int) -> int:
    return (int(n_trav) + int(n_pull_trav)) * TRAVERSAL_BYTES + \
        int(n_updates) * UPDATE_BYTES
