"""Graph500 / GAP ``kron`` generator: the benchmark's own copy.

RMAT recursion with probabilities A/B/C/D, ``edge_factor * 2**scale``
undirected edges with self loops redrawn, vertex labels permuted, and
weights uniform on (0, 1] as the Graph500 SSSP kernel draws them.  The
algorithm follows the program's ``data/generators.py`` (kept apart so
that a change to the program cannot change the benchmark's data).

As in GAP, the graph and its trial roots are fixed: the edges and
weights come from the configuration's ``structure_seed``, and the roots
are drawn uniformly, with GAP's fixed root seed, from the largest
connected component (so that every trial solves a whole tree), in a
fixed order.  A directed configuration (``"undirected": false``) keeps
the same list as arcs ``u -> v``, and its roots come from the largest
strongly connected component: each tree then reaches that component
and everything it points to.  ``--seed`` draws the vertex labelling,
Graph500's permutation step: every seed solves the same trees on an
isomorphic graph laid out differently in memory.  With random roots a window's
five trees varied by about 6% in rounds from seed to seed (PERF.md).

``generate`` returns plain ``(n, u, v, w)`` arrays, and
``trial_sources`` finds the roots in them by a numbering that does not
depend on labels: vertices in the order they first occur in the edge
list, which relabelling keeps.
"""
from __future__ import annotations

import numpy as np


def _rmat_pairs(rng, m: int, scale: int, a: float, b: float, c: float):
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    for bit in range(scale):
        r1 = rng.random(m)
        r2 = rng.random(m)
        u_bit = r1 > ab
        v_bit = np.where(u_bit, r2 > c_norm, r2 > a_norm)
        u |= u_bit.astype(np.int64) << bit
        v |= v_bit.astype(np.int64) << bit
    return u, v


GAP_ROOT_SEED = 27491095      # GAP's kRandSeed, which its SourcePicker uses
N_ROOTS = 16


def generate(cfg: dict, rng: np.random.Generator):
    """``(n, u, v, w)``: the configuration's graph, labelled by ``rng``."""
    fixed = np.random.default_rng(int(cfg["structure_seed"]))
    n, u, v, w = _structure(cfg, fixed)
    label = rng.permutation(n)
    return n, label[u], label[v], w


def _structure(cfg: dict, rng: np.random.Generator):
    scale, ef = int(cfg["scale"]), int(cfg["edge_factor"])
    n = 1 << scale
    m = ef * n
    us, vs, have = [], [], 0
    while have < m:                       # redraw self loops until m edges
        u, v = _rmat_pairs(rng, m - have, scale, cfg["rmat_a"],
                           cfg["rmat_b"], cfg["rmat_c"])
        keep = u != v
        us.append(u[keep])
        vs.append(v[keep])
        have += int(keep.sum())
    u = np.concatenate(us)[:m]
    v = np.concatenate(vs)[:m]
    perm = rng.permutation(n)
    u, v = perm[u], perm[v]
    w = 1.0 - rng.random(m)               # uniform on (0, 1]
    return n, u, v, w


def trial_sources(n, u, v, rng: np.random.Generator, count: int,
                  directed: bool = False):
    """``count`` roots: the fixed design of ``N_ROOTS``, repeated."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components
    adj = coo_matrix((np.ones(u.shape[0], np.int8), (u, v)), shape=(n, n))
    _, comp = connected_components(adj, directed=directed,
                                   connection="strong")
    seq = np.column_stack([u, v]).ravel()
    labels, first = np.unique(seq, return_index=True)
    by_first = labels[np.argsort(first)]        # the label-free numbering
    giant = by_first[comp[by_first] == np.bincount(comp).argmax()]
    pick = np.random.default_rng(GAP_ROOT_SEED).choice(giant.size, N_ROOTS)
    return np.resize(giant[pick], count).astype(np.int64)


def tiny(cfg: dict) -> dict:
    """The same shape at a size the CPU rehearsal can hold."""
    return dict(cfg, scale=8)
