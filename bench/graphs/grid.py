"""Square-grid road analogue: the benchmark's own copy.

A ``side x side`` four-neighbour lattice with weights uniform on
[``weight_low``, ``weight_high``), as the program's ``road_grid`` builds
it and as the 9th DIMACS Challenge's square-grid family lays it out:
degree at most 4 and a diameter of about ``2 * side`` edges.

The lattice and its weights are fixed by the configuration's
``structure_seed``, and ``--seed`` picks one of the square's eight
symmetries as the vertex labelling: every seed solves the same trees on
an isomorphic graph whose edge list keeps the lattice's locality.  With
weights drawn from each seed a window's four trees varied by about 2.5%
from seed to seed, against 0.01% between two runs of one seed
(PERF.md).

Tree roots are a fixed stratified design and not a uniform draw: on a
lattice a tree's rounds follow the root's eccentricity, which doubles
from the centre to a corner.  The roots are the centres of a 4 x 4
tiling, ordered so that every four consecutive roots hold one corner
cell, one inner cell and two edge cells.  ``trial_sources`` finds them
in a labelled graph by a numbering that does not depend on labels:
vertices in the order they first occur in the edge list.
"""
from __future__ import annotations

import numpy as np

# (row, col) tile of each root, in window order: corner, inner, edge,
# edge, repeated over the four quadrants
_TILES = ((0, 0), (1, 1), (0, 1), (1, 0),
          (0, 3), (1, 2), (0, 2), (1, 3),
          (3, 3), (2, 2), (3, 2), (2, 3),
          (3, 0), (2, 1), (3, 1), (2, 0))


def _lattice(side: int):
    idx = np.arange(side * side).reshape(side, side)
    u = np.concatenate([idx[:, :-1].ravel(), idx[:-1, :].ravel()])
    v = np.concatenate([idx[:, 1:].ravel(), idx[1:, :].ravel()])
    return u, v


def _first_seen(u, v) -> np.ndarray:
    """Vertex labels in the order they first occur in the edge list."""
    labels, first = np.unique(np.column_stack([u, v]).ravel(),
                              return_index=True)
    return labels[np.argsort(first)]


def generate(cfg: dict, rng: np.random.Generator):
    """``(n, u, v, w)``: the configuration's lattice, labelled by one of
    its symmetries drawn from ``rng``."""
    side = int(cfg["side"])
    u, v = _lattice(side)
    fixed = np.random.default_rng(int(cfg["structure_seed"]))
    w = fixed.uniform(cfg["weight_low"], cfg["weight_high"], u.shape[0])
    r, c = np.divmod(np.arange(side * side), side)
    if rng.integers(0, 2):
        r, c = c, r
    flip = rng.integers(0, 4)
    if flip & 1:
        r = side - 1 - r
    if flip & 2:
        c = side - 1 - c
    label = r * side + c
    return side * side, label[u], label[v], w


def trial_sources(n, u, v, rng: np.random.Generator, count: int,
                  directed: bool = False):
    """``count`` roots: the stratified design, repeated.  A lattice is
    undirected: a directed grid configuration is refused."""
    if directed:
        raise ValueError("the grid generator makes undirected lattices only")
    side = int(round(np.sqrt(n)))
    base = _first_seen(*_lattice(side))
    rank = np.empty(n, np.int64)
    rank[base] = np.arange(n)
    design = [((2 * r + 1) * side // 8) * side + (2 * c + 1) * side // 8
              for r, c in _TILES]
    roots = _first_seen(u, v)[rank[design]]
    return np.resize(roots, count).astype(np.int64)


def tiny(cfg: dict) -> dict:
    """The same shape at a size the CPU rehearsal can hold."""
    return dict(cfg, side=16)
