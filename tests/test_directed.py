"""Directed graphs on the single tier, against the plain references.

``build_csr(..., symmetrize=False)`` makes a graph marked directed: each
slot is one arc, read forwards only.  Its trees must be exact:

* distances bit for bit those of the float32 ``bellman_ford`` over the
  same arcs, and within the repo's usual tolerance of the float64
  ``dijkstra_host``; every parent on a tight arc; unreachable vertices
  at ``inf`` with parent ``-1``;
* on every single-tier path: the compacted ``segment_min`` round, the
  same round with caps that make it fall back to the dense one, the
  dense batched round, ``blocked_pallas`` and the routed tier;
* on graphs that break the undirected reading: a directed Kronecker
  graph, arc pairs with their own weights, chains of out-degree 1,
  sinks and unreachable parts, and arcs whose mirror is lighter.

An undirected graph's solves stay what they were: ``dist``, ``parent``
and every logical counter are pinned to their values before graphs
carried the mark.  The paths that read slots as mirrored edges refuse a
directed graph with ``ConfigError``.
"""
import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import EngineConfig, SolveSpec, Solver
from repro.core import relax, sssp
from repro.core.baselines import bellman_ford, dijkstra_host
from repro.core.config import ConfigError
from repro.core.distributed import shard_blocked, shard_graph
from repro.core.graph import build_blocked, build_csr
from repro.data.generators import kronecker, road_grid
from repro.delta import EdgeDelta, patch_host
from repro.serve.registry import GraphRegistry

BE = relax.get_backend("segment_min")


def _rmat_arcs(scale, edge_factor, rng):
    """Graph500 R-MAT pairs (A/B/C = 0.57/0.19/0.19), self loops
    dropped, labels permuted: each pair is one arc."""
    m = edge_factor << scale
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for bit in range(scale):
        r1, r2 = rng.random(m), rng.random(m)
        ub = r1 > 0.76
        u |= ub.astype(np.int64) << bit
        v |= np.where(ub, r2 > 0.19 / 0.24, r2 > 0.57 / 0.76) << bit
    keep = u != v
    perm = rng.permutation(1 << scale)
    return perm[u[keep]], perm[v[keep]]


def _kron(scale, edge_factor, seed):
    rng = np.random.default_rng(seed)
    u, v = _rmat_arcs(scale, edge_factor, rng)
    return build_csr(1 << scale, u, v, 1.0 - rng.random(u.size),
                     symmetrize=False)


def _pairs():
    """Most arcs come with their reverse, at a weight of its own."""
    rng = np.random.default_rng(31)
    n, m = 300, 1500
    u, v = rng.integers(0, n, m), rng.integers(0, n, m)
    back = rng.random(m) < 0.8
    return build_csr(n, np.concatenate([u, v[back]]),
                     np.concatenate([v, u[back]]),
                     rng.uniform(0.05, 1.0, m + int(back.sum())),
                     symmetrize=False)


def _chains():
    """A hub feeds chains whose vertices have one out-arc each, onward;
    a few arcs jump between chains."""
    rng = np.random.default_rng(32)
    n_chains, length = 12, 20
    u, v = [], []
    for c in range(n_chains):
        first = 1 + c * length
        u += [0] + list(range(first, first + length - 1))
        v += [first] + list(range(first + 1, first + length))
    n = 1 + n_chains * length
    jump_u = rng.integers(1, n, 15)
    u = np.concatenate([u, jump_u])
    v = np.concatenate([v, rng.integers(1, n, 15)])
    return build_csr(n, u, v, rng.uniform(0.1, 1.0, u.size),
                     symmetrize=False)


def _sinks():
    """A third of the vertices have no out-arc; a quarter have no
    in-arc, so they are reached from no root but themselves."""
    rng = np.random.default_rng(33)
    n, m = 400, 2400
    u = rng.integers(0, n, m)
    v = rng.integers(0, n, m)
    u = np.where(u % 3 == 0, u + 1, u) % n          # sinks: u % 3 == 0
    v = np.where(v % 4 == 1, v + 1, v) % n          # sources: v % 4 == 1
    keep = (u % 3 != 0) & (v % 4 != 1) & (u != v)
    return build_csr(n, u[keep], v[keep], rng.uniform(0.05, 1.0, keep.sum()),
                     symmetrize=False)


def _mirror_lighter():
    """Every arc ``u -> v`` has a reverse ``v -> u`` three times heavier:
    read backwards, each slot offers a path that does not exist."""
    rng = np.random.default_rng(34)
    u, v = _rmat_arcs(9, 8, rng)
    w = rng.uniform(0.3, 1.0, u.size)
    return build_csr(512, np.concatenate([u, v]), np.concatenate([v, u]),
                     np.concatenate([w, 3.0 * w]), symmetrize=False)


GRAPHS = {
    "kron8": lambda: _kron(8, 16, 41),
    "kron10": lambda: _kron(10, 8, 42),
    "pairs": _pairs,
    "chains": _chains,
    "sinks": _sinks,
    "mirror_lighter": _mirror_lighter,
}


@functools.lru_cache(maxsize=None)
def _graph(name):
    hg = GRAPHS[name]()
    deg = np.asarray(hg.deg)
    rng = np.random.default_rng(len(name))
    others = rng.choice(np.flatnonzero(deg > 0), 2, replace=False)
    return hg, [int(np.argmax(deg))] + [int(s) for s in others]


def _solve(hg, roots, mode):
    """``[(dist, parent, metrics)]``, one per root, on one path."""
    if mode in ("compact", "blocked"):
        cfg = (EngineConfig(backend="blocked_pallas", block_v=128,
                            tile_e=128) if mode == "blocked"
               else EngineConfig())
        with Solver.open(hg, cfg) as s:
            return [tuple(s.solve(SolveSpec.tree(r))) for r in roots]
    if mode == "fallback":
        # caps this small overflow on any wider frontier or window: the
        # solve runs both the compacted and the dense branch
        g = hg.to_device()
        run = jax.jit(lambda g, s: sssp._run(g, g, s, BE, 10**6, 3.0, 0.9,
                                             caps=(2, 8))[:3])
        return [run(g, jnp.int32(r)) for r in roots]
    cfg = EngineConfig(tier="routed", max_batch=4) if mode == "routed" \
        else EngineConfig()
    with Solver.open(hg, cfg) as s:
        d, p, m = s.solve(SolveSpec.tree(roots))
    if mode == "routed":
        return [(d[i], p[i], m[i]) for i in range(len(roots))]
    return [(d[i], p[i], jax.tree.map(lambda x: np.asarray(x)[i], m))
            for i in range(len(roots))]


def _tight_arcs(hg, dist, parent, root):
    """Every reached vertex but the root hangs off an arc ``parent ->
    v`` whose float32 candidate is ``dist[v]``."""
    lightest = {}
    for a, b, w in zip(hg.src.tolist(), hg.dst.tolist(), hg.w.tolist()):
        lightest[(a, b)] = min(lightest.get((a, b), np.inf), w)
    for v in np.flatnonzero(np.isfinite(dist)):
        if v == root:
            assert parent[v] == root
            continue
        p = int(parent[v])
        assert (p, int(v)) in lightest, (p, v)
        assert np.float32(dist[p]) + np.float32(lightest[(p, int(v))]) \
            == dist[v], v


MODES = ["compact", "fallback", "dense", "blocked"]


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_directed_trees_match_the_references(name, mode):
    hg, roots = _graph(name)
    assert hg.directed
    g = hg.to_device()
    for root, (dist, parent, _) in zip(roots, _solve(hg, roots, mode)):
        dist, parent = np.asarray(dist), np.asarray(parent)
        bf, _, _ = bellman_ford(g, root)
        np.testing.assert_array_equal(dist, np.asarray(bf),
                                      err_msg=f"{name}/{mode}/{root}")
        # float32 path sums against float64 ones: each of a path's adds
        # rounds once, so the repo's usual rtol/atol covers them
        ref, _ = dijkstra_host(hg, root)
        np.testing.assert_allclose(np.where(np.isfinite(dist), dist, -1.0),
                                   np.where(np.isfinite(ref), ref, -1.0),
                                   rtol=1e-4, atol=1e-5)
        assert (parent[~np.isfinite(dist)] == -1).all()
        _tight_arcs(hg, dist, parent, root)


@pytest.mark.parametrize("name", ["kron10", "mirror_lighter", "pairs"])
def test_directed_paths_agree_on_every_logical_counter(name):
    """The four single-tier paths run the same logical solve."""
    hg, roots = _graph(name)
    runs = {mode: _solve(hg, roots, mode) for mode in MODES}
    for mode, got in runs.items():
        for (d0, p0, m0), (d1, p1, m1) in zip(runs["compact"], got):
            np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
            np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
            for f in sssp.LOGICAL_METRIC_FIELDS:
                assert int(np.asarray(getattr(m0, f))) == \
                    int(np.asarray(getattr(m1, f))), (mode, f)
    pulled = [int(m.n_pull_relax) for _, _, m in runs["compact"]]
    assert all(int(m.n_pull_relax) <= int(m.n_relax)
               for _, _, m in runs["compact"])
    assert max(pulled) > 0


def test_routed_tier_serves_directed_trees_and_goals_exactly():
    hg, roots = _graph("kron10")
    g = hg.to_device()
    trees = _solve(hg, roots, "routed")
    bf = [np.asarray(bellman_ford(g, r)[0]) for r in roots]
    for (dist, parent, _), ref, root in zip(trees, bf, roots):
        np.testing.assert_array_equal(np.asarray(dist), ref)
        _tight_arcs(hg, np.asarray(dist), np.asarray(parent), root)
    bound = float(np.median(bf[0][np.isfinite(bf[0])]))
    with Solver.open(hg, EngineConfig(tier="routed", max_batch=4)) as s:
        near = s.solve(SolveSpec.knear(roots[0], 10)).nearest()
        within = np.asarray(s.solve(SolveSpec.bounded(roots[0],
                                                      bound)).dist)
    order = np.argsort(bf[0], kind="stable")
    want = [int(v) for v in order if v != roots[0]][:10]
    assert [v for v, _ in near] == want
    assert [d for _, d in near] == [float(bf[0][v]) for v in want]
    inside = bf[0] <= bound
    np.testing.assert_array_equal(within[inside], bf[0][inside])


@pytest.mark.parametrize("alt", [False, True], ids=["plain", "alt"])
def test_directed_p2p_is_exact(alt):
    hg, roots = _graph("mirror_lighter")
    bf = np.asarray(bellman_ford(hg.to_device(), roots[0])[0])
    targets = np.flatnonzero(np.isfinite(bf))[-3:]
    cfg = EngineConfig(use_alt=True, n_landmarks=4) if alt \
        else EngineConfig()
    with Solver.open(hg, cfg) as s:
        for t in targets:
            res = s.solve(SolveSpec.p2p(roots[0], int(t)))
            assert res.distance() == bf[t]


def test_a_symmetric_graph_marked_directed_gives_the_same_tree():
    """Marking a symmetric slot set directed costs work, never
    exactness: the forwards pull makes the same requests, and the
    stricter leaf rule only relaxes more."""
    und = kronecker(9, 8, seed=5)
    drc = build_csr(und.n, und.src, und.dst, und.w, symmetrize=False)
    np.testing.assert_array_equal(und.src, drc.src)
    np.testing.assert_array_equal(und.dst, drc.dst)
    src = int(np.argmax(und.deg))
    for cfg in (EngineConfig(), EngineConfig(backend="blocked_pallas",
                                             block_v=128, tile_e=128)):
        with Solver.open(und, cfg) as a, Solver.open(drc, cfg) as b:
            ra, rb = a.solve(SolveSpec.tree(src)), b.solve(
                SolveSpec.tree(src))
        np.testing.assert_array_equal(np.asarray(ra.dist),
                                      np.asarray(rb.dist))
        np.testing.assert_array_equal(np.asarray(ra.parent),
                                      np.asarray(rb.parent))
        assert int(ra.metrics.n_pull_relax) == int(rb.metrics.n_pull_relax)
        assert int(ra.metrics.n_relax) <= int(rb.metrics.n_relax)


def test_the_leaf_rule_follows_the_mark():
    deg = jnp.asarray([0, 1, 2, 1])
    front = jnp.ones(4, bool)
    dist = jnp.asarray([1.0, 1.0, 1.0, 0.0])
    assert relax.leaf_pruned(front, dist, deg).tolist() == \
        [False, False, True, True]
    assert relax.leaf_pruned(front, dist, deg, directed=True).tolist() == \
        [False, True, True, True]


def test_the_mark_is_static_and_survives_every_handoff():
    hg, roots = _graph("pairs")
    und = kronecker(6, 4, seed=1)
    assert not und.directed and not und.to_device().directed
    g = hg.to_device()
    assert g.directed and g._replace(w=g.w).directed
    # aux data, not a leaf: the two marks compile two programs
    assert all(isinstance(x, jax.Array) for x in jax.tree_util.tree_leaves(g))
    assert jax.tree_util.tree_structure(g) != \
        jax.tree_util.tree_structure(g._replace(directed=False))
    assert jax.device_put(g).directed
    assert build_blocked(hg, block_v=128, tile_e=128).directed
    for graph in (hg, g):
        with Solver.open(graph) as s:
            assert s.directed and s.device_graph.directed
            assert s.solve(SolveSpec.tree(roots[0])).directed
    reg = GraphRegistry(config=EngineConfig(tier="routed"))
    reg.register("d", hg)
    assert reg.engine("d").g.directed
    new_host, _ = patch_host(und, EdgeDelta(
        reweight=[(int(und.src[0]), int(und.dst[0]), 0.5)]))
    assert not new_host.directed


def test_a_layout_of_the_other_mark_is_refused():
    """The same slots under the other mark would be read the other way."""
    hg, _ = _graph("pairs")
    with pytest.raises(ConfigError, match="directed"):
        Solver.open(hg, layout=hg.to_device()._replace(directed=False))
    blocked = build_blocked(hg, block_v=128, tile_e=128)
    with pytest.raises(ConfigError, match="directed"):
        Solver.open(hg, EngineConfig(backend="blocked_pallas", block_v=128,
                                     tile_e=128),
                    layout=dataclasses.replace(blocked, directed=False))


def test_the_pull_names_its_sub_phases():
    hg, roots = _graph("kron8")
    with Solver.open(hg) as s:
        table = s.phase_table(SolveSpec.tree(roots[0]))
    assert {"transition.pull.scan", "transition.pull.reduce",
            "transition.pull.apply"} <= set(table.values())


# ----------------------------------------------------------------------
# refusals: the paths that read slots as mirrored edges
# ----------------------------------------------------------------------

def test_the_sharded_tier_refuses_a_directed_graph():
    hg, _ = _graph("pairs")
    with pytest.raises(ConfigError, match="directed"):
        shard_graph(hg, 4)
    with pytest.raises(ConfigError, match="directed"):
        shard_blocked(hg, 4, block_v=128, tile_e=128)
    with pytest.raises(ConfigError, match="directed"):
        Solver.open(hg, EngineConfig(tier="sharded"))


def test_deltas_refuse_a_directed_graph():
    hg, _ = _graph("pairs")
    edit = EdgeDelta(reweight=[(int(hg.src[0]), int(hg.dst[0]), 0.5)],
                     symmetrize=False)
    with pytest.raises(ConfigError, match="directed"):
        patch_host(hg, edit)
    for spec in (hg, hg.to_device()):
        reg = GraphRegistry(config=EngineConfig(tier="routed"))
        reg.register("d", spec)
        with pytest.raises(ConfigError, match="directed"):
            reg.apply_delta("d", edit)
    with Solver.open(hg, EngineConfig(tier="routed")) as s:
        with pytest.raises(ConfigError, match="directed"):
            s.apply_delta(edit)


def test_bidirectional_p2p_and_fused_rounds_refuse_a_directed_graph():
    hg, roots = _graph("pairs")
    cfg = EngineConfig(use_alt=True, n_landmarks=2,
                       p2p_mode="bidirectional")
    with Solver.open(hg, cfg) as s:
        with pytest.raises(ConfigError, match="directed"):
            s.solve(SolveSpec.p2p(roots[0], roots[1]))
    with Solver.open(hg, EngineConfig(backend="blocked_pallas", block_v=128,
                                      tile_e=128, fused_rounds=2)) as s:
        with pytest.raises(ConfigError, match="directed"):
            s.solve(SolveSpec.tree(roots[0]))


# ----------------------------------------------------------------------
# undirected graphs: the program they ran before the mark
# ----------------------------------------------------------------------

def _digest(a):
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


# (dist, parent, n_rounds, n_steps, n_extended, n_trav, n_pull_trav,
# n_relax, n_updates, n_pruned) of a tree from the highest-degree vertex,
# as solved before graphs were marked; every path gives the same
PINNED = {
    "kron": ("f0731fc08a3cee07", "4859629906eea896",
             (20, 6, 523, 1831, 656, 15493, 583, 0)),
    "grid": ("48b18219ee5df9e6", "cadad3b5d5a35278",
             (61, 20, 262, 918, 62, 777, 261, 0)),
}
UNDIRECTED = {"kron": lambda: kronecker(9, 8, seed=21),
              "grid": lambda: road_grid(16, seed=22)}


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("name", sorted(PINNED))
def test_undirected_solves_are_pinned(name, mode):
    hg = UNDIRECTED[name]()
    assert not hg.directed
    root = int(np.argmax(hg.deg))
    (dist, parent, m), = _solve(hg, [root] if mode != "dense"
                                else [root, 0], mode)[:1]
    want_d, want_p, counts = PINNED[name]
    assert _digest(np.asarray(dist, np.float32)) == want_d
    assert _digest(np.asarray(parent, np.int32)) == want_p
    fields = [f for f in sssp.LOGICAL_METRIC_FIELDS if f != "n_pull_relax"]
    assert tuple(int(np.asarray(getattr(m, f))) for f in fields) == counts
    assert 0 < int(np.asarray(m.n_pull_relax)) <= int(np.asarray(m.n_relax))
