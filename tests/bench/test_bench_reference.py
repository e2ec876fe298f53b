"""The plain reference agrees with the program on the CPU at small sizes,
for every query kind the cells send: trees (single tier), k-nearest
lists and distance-bounded sets (routed tier, as served).
"""
import numpy as np
import pytest

from bench import cell, compare, reference
from bench.graphs import grid, kronecker

GRAPHS = {
    "kron": (kronecker, dict(scale=9, edge_factor=16, rmat_a=0.57,
                             rmat_b=0.19, rmat_c=0.19,
                             structure_seed=27491095)),
    "grid": (grid, dict(side=16, weight_low=0.1, weight_high=1.0,
                        structure_seed=9)),
}


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def graph(request):
    from repro.api import EngineConfig, Solver
    from repro.core.graph import build_csr
    mod, cfg = GRAPHS[request.param]
    n, u, v, w = mod.generate(cfg, cell.rng(99, cell.GRAPH))
    roots = mod.trial_sources(n, u, v, cell.rng(99, 1), 4)
    host = build_csr(n, u, v, w)
    adj = reference.adjacency(n, u, v, w)
    single = Solver.open(host, EngineConfig())
    routed = Solver.open(host, EngineConfig(tier="routed", max_batch=4))
    yield adj, roots, single, routed
    routed.close()


def test_trees_match_bit_for_bit(graph):
    from repro.api import SolveSpec
    adj, roots, single, _ = graph
    for s in roots.tolist():
        res = single.solve(SolveSpec.tree(s))
        ref, _, _ = reference.dijkstra(adj, s)
        nums = compare.tree_numbers(adj, s, np.asarray(res.dist),
                                    np.asarray(res.parent), ref)
        assert nums == {"dist_mismatch": 0, "parent_bad": 0}


@pytest.mark.parametrize("k", [1, 4, 37])
def test_served_knear_lists_match(graph, k):
    from repro.api import SolveSpec
    adj, roots, _, routed = graph
    res = routed.solve(SolveSpec.knear(roots.tolist(), k))
    for i, s in enumerate(roots.tolist()):
        answer = {"nearest": res.nearest(slot=i), "dist": res.dist[i],
                  "parent": res.parent[i]}
        assert not compare.query_wrong(adj, "knear", s, k, answer)


@pytest.mark.parametrize("scale", [0.5, 2.0, 5.0])
def test_served_bounded_sets_match(graph, scale):
    from repro.api import SolveSpec
    adj, roots, _, routed = graph
    bound = float(np.float32(scale * np.max(adj.w)))
    res = routed.solve(SolveSpec.bounded(roots.tolist(), bound))
    for i, s in enumerate(roots.tolist()):
        answer = {"dist": res.dist[i], "parent": res.parent[i],
                  "nearest": None}
        assert not compare.query_wrong(adj, "bounded", s, bound, answer)


def test_reference_equals_a_plain_relaxation_to_fixpoint(graph):
    adj, roots, _, _ = graph
    s = int(roots[0])
    src = np.repeat(np.arange(adj.n), np.diff(adj.row_ptr))
    dist = np.full(adj.n, np.inf, np.float32)
    dist[s] = 0
    while True:
        cand = dist[src] + adj.w
        new = dist.copy()
        np.minimum.at(new, adj.col, cand)
        if np.array_equal(new, dist):
            break
        dist = new
    ref, _, _ = reference.dijkstra(adj, s)
    assert np.array_equal(ref, dist)


def test_parallel_edges_keep_the_lightest():
    adj = reference.adjacency(3, [0, 0, 1], [1, 1, 2], [0.5, 0.25, 1.0])
    assert adj.edge_weight([0, 1, 1, 0], [1, 0, 2, 2]).tolist()[:3] == \
        [0.25, 0.25, 1.0]
    assert np.isnan(adj.edge_weight([0], [2])[0])


def test_a_wrong_answer_is_caught(graph):
    adj, roots, _, _ = graph
    s = int(roots[0])
    ref, parent, _ = reference.dijkstra(adj, s)
    bad = ref.copy()
    v = int(np.flatnonzero(np.isfinite(ref) & (ref > 0))[0])
    bad[v] = np.nextafter(bad[v], np.float32(np.inf))
    assert compare.tree_numbers(adj, s, bad, parent, ref)["dist_mismatch"] == 1
    wrong_parent = parent.copy()
    wrong_parent[v] = v
    assert compare.tree_numbers(adj, s, ref, wrong_parent,
                                ref)["parent_bad"] == 1
