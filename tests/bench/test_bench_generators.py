"""The benchmark's own generators give fixed inputs for a fixed seed.

Checksums of tiny graphs and streams, so a change to the benchmark's
data shows here.  Nothing is compared with the program's generators:
the program may change its own freely.  A directed Kronecker graph
(``directed=True``) is the same edge list, kept as arcs, with its roots
in the largest strongly connected component.
"""
import hashlib

import numpy as np
import pytest

from bench import cell, loadgen
from bench.graphs import grid, kronecker

KRON = dict(scale=6, edge_factor=4, rmat_a=0.57, rmat_b=0.19, rmat_c=0.19,
            structure_seed=27491095)
GRID = dict(side=8, weight_low=0.1, weight_high=1.0, structure_seed=9)
OPEN_MIX = {"loop": "open", "tier": "routed", "max_batch": 8,
            "mix": [["knear", 0.5], ["bounded", 0.5]],
            "sources": "zipf_degree", "zipf_a": 1.1, "k_range": [4, 64],
            "bound_w_scale": [2.0, 8.0], "rate_qps": 4.0}


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("mod,cfg,directed,n,m,want,trial", [
    (kronecker, KRON, False, 64, 256, "fb18c948e23d9547",
     [33, 50, 16, 10, 35, 1, 45, 8]),
    (kronecker, KRON, True, 64, 256, "fb18c948e23d9547",
     [4, 20, 16, 43, 52, 1, 45, 52]),
    (grid, GRID, False, 64, 112, "90c3ece7edc6fb8a",
     [9, 27, 25, 11, 57, 43, 41, 59]),
])
def test_graph_and_roots_are_fixed_by_the_seed(mod, cfg, directed, n, m,
                                               want, trial):
    got_n, u, v, w = mod.generate(cfg, cell.rng(12345, cell.GRAPH))
    assert (got_n, u.shape[0]) == (n, m)
    assert not (u == v).any()
    assert digest(u, v, w) == want
    roots = mod.trial_sources(got_n, u, v, cell.rng(12345, 1), 8,
                              directed=directed)
    assert roots.tolist() == trial


def _reach(n, u, v) -> np.ndarray:
    """``r[a, b]``: b is reachable from a over the arcs (a dense closure)."""
    r = np.eye(n, dtype=bool)
    r[u, v] = True
    while True:
        nxt = (r.astype(np.int32) @ r.astype(np.int32)) > 0
        if np.array_equal(nxt, r):
            return r
        r = nxt


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 9])
def test_directed_roots_lie_in_the_largest_strong_component(seed):
    n, u, v, _ = kronecker.generate(dict(KRON, scale=8),
                                    cell.rng(seed, cell.GRAPH))
    r = _reach(n, u, v)
    strong = r & r.T                  # a row is its vertex's component
    size = strong.sum(axis=1)
    roots = kronecker.trial_sources(n, u, v, None, kronecker.N_ROOTS,
                                    directed=True)
    assert (size[roots] == size.max()).all()
    assert strong[roots[0]][roots].all()       # one component
    assert size.max() < r[roots[0]].sum()      # which reaches further


def test_kronecker_weights_lie_in_the_half_open_unit_interval():
    _, _, _, w = kronecker.generate(KRON, cell.rng(7, cell.GRAPH))
    assert (w > 0).all() and (w <= 1).all()


def test_kronecker_seeds_relabel_one_graph_and_its_roots():
    cfg = dict(KRON, scale=9)
    seen = []
    for seed in (1, 2, 3):
        n, u, v, w = kronecker.generate(cfg, cell.rng(seed, cell.GRAPH))
        roots = kronecker.trial_sources(n, u, v, None, kronecker.N_ROOTS)
        deg = np.bincount(u, minlength=n) + np.bincount(v, minlength=n)
        # the edge list keeps its order, so a relabelling maps root to root
        first = {int(x): i for i, x in reversed(list(enumerate(
            np.column_stack([u, v]).ravel())))}
        seen.append((np.sort(w).tolist(), deg[roots].tolist(),
                     [first[int(r)] for r in roots]))
    assert seen[0] == seen[1] == seen[2]


@pytest.mark.parametrize("mod,cfg,directed", [
    (kronecker, dict(KRON, scale=8), False),
    (kronecker, dict(KRON, scale=8), True),
    (grid, dict(GRID, side=16), False)])
def test_every_seed_solves_the_same_trees(mod, cfg, directed):
    """Seeds relabel one graph: each design root's reference distances are
    the same multiset under every seed."""
    from bench import reference
    first = None
    for seed in (1, 2, 3, 4):
        n, u, v, w = mod.generate(cfg, cell.rng(seed, cell.GRAPH))
        adj = reference.adjacency(n, u, v, w, directed=directed)
        roots = mod.trial_sources(n, u, v, None, 4, directed=directed)
        got = [np.sort(reference.dijkstra(adj, int(r))[0]).tolist()
               for r in roots]
        first = got if first is None else first
        assert got == first


def test_grid_refuses_a_directed_graph():
    n, u, v, _ = grid.generate(GRID, cell.rng(0, cell.GRAPH))
    with pytest.raises(ValueError, match="undirected"):
        grid.trial_sources(n, u, v, None, 4, directed=True)


def test_grid_roots_keep_their_eccentricity_under_every_seed():
    side = 16
    n, u, v, _ = grid.generate(dict(GRID, side=side), cell.rng(0, 0))

    def ecc(x):
        r, c = divmod(int(x), side)
        return max(r, side - 1 - r) + max(c, side - 1 - c)

    want = [ecc(x) for x in grid.trial_sources(n, u, v, cell.rng(0, 1), 16)]
    for seed in range(1, 9):
        got = grid.trial_sources(n, u, v, cell.rng(seed, 1), 16)
        assert [ecc(x) for x in got] == want


def test_open_stream_is_fixed_by_the_seed():
    n, u, v, w = grid.generate(GRID, cell.rng(12345, cell.GRAPH))
    graph = {"n": n, "max_w": float(np.float32(w).max()),
             "deg": np.bincount(u, minlength=n) + np.bincount(v, minlength=n)}
    reqs = loadgen.requests(OPEN_MIX, graph, cell.rng(12345, 1), 16,
                            seconds=4.0)
    assert [r.kind for r in reqs][:6] == ["bounded"] * 4 + ["knear",
                                                            "bounded"]
    assert digest(np.array([r.source for r in reqs]),
                  np.array([r.param for r in reqs], float),
                  np.array([r.due_s for r in reqs])) == "8dc0fb603e963dd4"


def test_open_stream_draws_the_same_multiset_for_every_seed():
    n, u, v, w = grid.generate(GRID, cell.rng(0, cell.GRAPH))
    graph = {"n": n, "max_w": 1.0,
             "deg": np.bincount(u, minlength=n) + np.bincount(v, minlength=n)}
    first = None
    for seed in range(4):
        reqs = loadgen.requests(OPEN_MIX, graph, cell.rng(seed, 1), 40,
                                seconds=10.0)
        kinds = sorted((r.kind, r.param) for r in reqs)
        due = [r.due_s for r in reqs] + [10.0]
        gaps = np.sort(np.diff(due))
        assert reqs[-1].due_s < 10.0
        if first is None:
            first = kinds, gaps
        assert kinds == first[0]
        np.testing.assert_allclose(gaps, first[1], rtol=1e-9)
