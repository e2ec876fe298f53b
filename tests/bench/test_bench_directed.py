"""The harness on a directed configuration, and unchanged on the undirected.

``data/kron-directed.json`` is a tiny Kronecker configuration with
``"undirected": false``: a fixture that ``BENCHMARK.json`` does not
hold.  On it the harness hands the program each arc once, unmirrored,
and the reference and the control judge by the arcs.  On the
benchmark's own configurations the inputs, the program's CSR and the
reference's adjacency are those the harness made before it took
directed graphs: their digests are pinned.
"""
import dataclasses
import hashlib

import numpy as np
import pytest

from bench import cell, compare, control, loadgen, manifest, reference, run

BENCH = manifest.load()
FIXTURE = "kron-directed"
CELL = "kron-directed-tree"
MAN = dict(
    BENCH,
    configs=BENCH["configs"] + [{
        "name": FIXTURE, "source": "https://arxiv.org/abs/1508.03619",
        "file": "tests/bench/data/kron-directed.json", "reduced": ["scale"],
        "why": "test fixture: GAP kron's edge list kept as arcs"}],
    workloads=BENCH["workloads"] + [{
        "name": CELL, "config": FIXTURE, "traffic": "tree-trials",
        "chips": 1, "why": "test fixture: trees over out-arcs"}])


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


@pytest.fixture(scope="module")
def inp():
    return cell.build(MAN, CELL, 2 ** 31 + 7, 1.0)


@pytest.fixture(scope="module")
def roots(inp):
    return sorted({r.source for r in inp.requests})[:3]


def _mirrored(inp):
    return reference.adjacency(inp.n, inp.u, inp.v, inp.w)


def _directed(inp):
    return reference.adjacency(inp.n, inp.u, inp.v, inp.w, directed=True)


def test_the_fixture_is_directed(inp):
    assert inp.directed and inp.edges == inp.u.shape[0] == 16 * 256
    assert not cell.build(BENCH, "kron17-tree", 1, 1.0,
                          rehearse=True).directed


def test_directed_reference_equals_a_relaxation_over_the_arcs(inp, roots):
    """Bellman-Ford over the input arcs as they are, in float32, to its
    fixpoint: the directed reference's distances bit for bit."""
    adj = _directed(inp)
    w = inp.w.astype(np.float32)
    for s in roots:
        dist = np.full(inp.n, np.inf, np.float32)
        dist[s] = 0
        while True:
            new = dist.copy()
            np.minimum.at(new, inp.v, dist[inp.u] + w)
            if np.array_equal(new, dist):
                break
            dist = new
        ref, _, _ = reference.dijkstra(adj, s)
        assert np.array_equal(ref, dist)
        assert not np.array_equal(ref, reference.dijkstra(_mirrored(inp),
                                                          s)[0])


def test_both_arcs_of_a_pair_are_kept_with_their_own_weights():
    u, v, w = [0, 1, 0, 1], [1, 0, 1, 2], [0.5, 0.75, 0.25, 1.0]
    adj = reference.adjacency(3, u, v, w, directed=True)
    got = adj.edge_weight([0, 1, 1], [1, 0, 2]).tolist()
    assert got == [0.25, 0.75, 1.0]
    assert np.isnan(adj.edge_weight([2], [1])[0])
    both = reference.adjacency(3, u, v, w)
    assert both.edge_weight([0, 1, 2], [1, 0, 1]).tolist() == [0.25, 0.25,
                                                               1.0]


def _answers(adj, inp, roots):
    out = []
    for s in roots:
        d, p, _ = reference.dijkstra(adj, s)
        out.append((loadgen.Request(kind="tree", source=s), d, p))
    return {"answers": out, "failed": 0}


def test_check_passes_the_arcs_trees_and_catches_mirrored_ones(inp, roots):
    """An engine that reads arcs backwards, or both ways, is caught."""
    ok = run.check(inp, _answers(_directed(inp), inp, roots))
    assert ok == {"dist_mismatch": 0, "parent_bad": 0, "answers_missing": 0}
    bad = run.check(inp, _answers(_mirrored(inp), inp, roots))
    assert bad["dist_mismatch"] > 0 and not compare.verdict(bad)


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 5])
def test_control_is_not_correct_on_the_arcs(seed):
    nums = control.readings(MAN, CELL, seed, 4.0, trees=2)
    assert not compare.verdict(nums), nums


def _cycle(inp):
    """Every vertex has an out-arc: the warm-up needs its stand-in."""
    n = 8
    return dataclasses.replace(
        inp, n=n, u=np.arange(n), v=(np.arange(n) + 1) % n, w=np.ones(n),
        requests=[loadgen.Request(kind="tree", source=0)])


@pytest.mark.parametrize("case,per_edge", [
    ("fixture", 1), ("cycle", 1), ("kron17-tree", 2), ("road256-tree", 2)])
def test_open_solver_hands_the_program_each_arc_once(case, per_edge,
                                                     inp, monkeypatch):
    """``m`` slots for a directed graph, ``2m`` for an undirected one; the
    warm-up's stand-in graph (road256 and the cycle) has the cell's."""
    import jax
    from repro.api import Solver
    real = Solver.open.__func__
    slots = []

    def record(cls, graph, *args, **kw):
        slots.append(graph.m)
        return real(cls, graph, *args, **kw)

    monkeypatch.setattr(Solver, "open", classmethod(record))
    got = {"fixture": inp, "cycle": _cycle(inp)}.get(case) or \
        cell.build(BENCH, case, 3, 1.0, rehearse=True)
    run.open_solver(got, jax.devices()[:1]).close()
    assert set(slots) == {per_edge * got.edges}
    own_root = (cell.degree(got.n, got.u, got.v, got.directed) == 0).any()
    assert len(slots) == (1 if own_root else 2)
    assert own_root == (case not in ("cycle", "road256-tree"))


# (seed 4294967311): edge list, request sources, the program's CSR
# (src, dst, w, row_ptr, deg, rtow) and the reference's adjacency
# (row_ptr, col, w, key), as the harness made them before it took
# directed configurations
PINNED = {
    ("kron17-tree", True): ("011525bb2292cc00", "3f90d503a1b222ee",
                            "96d752a04f455b1e", "edbe57fb684065aa"),
    ("road256-tree", True): ("1044f65e7d56e5db", "0e81155451f79ea7",
                             "52cdfcbd38119057", "bd372441667c29d7"),
    ("kron17-tree", False): ("079b76a4a5a39582", "78a1014c6fe653e9",
                             "2d596255c7bff44d", "f762e0648bd877b3"),
    ("road256-tree", False): ("a43633a28d14feae", "bbfad663c8559772",
                              "70985d440be528c4", "0743d0b2f0405b06"),
}


@pytest.mark.parametrize("name,tiny", sorted(PINNED))
def test_undirected_cells_build_what_they_built(name, tiny):
    got = cell.build(BENCH, name, 4294967311, 51.0, rehearse=tiny)
    host = run.host_graph(got)
    adj = reference.adjacency(got.n, got.u, got.v, got.w,
                              directed=got.directed)
    assert (digest(got.u, got.v, got.w),
            digest(np.array([r.source for r in got.requests], np.int64)),
            digest(host.src, host.dst, host.w, host.row_ptr, host.deg,
                   host.rtow),
            digest(adj.row_ptr, adj.col, adj.w, adj.key)) == \
        PINNED[(name, tiny)]
