"""The compacted ``segment_min`` round against the dense one.

The compacted round relaxes only the leaf-pruned frontier's in-window
slots (a binary search of each weight-sorted row) and runs the dense
round when they overflow its caps.  Both must give bit for bit the same
``dist``, ``parent``, frontier and logical counters:

* round by round, on graphs with repeated weights in a row, zero-weight
  edges and unreachable parts, with and without the ALT cut;
* over whole solves of every goal, with caps that make some rounds
  overflow the vertex cap or the slot cap, so both branches run in one
  solve (the ring's per-round records agree too);
* in ``repair_relax``, whose window is everything;
* the batched program (routed tier, warm-up, landmarks) keeps the dense
  round: no ``round.compact`` phase, ``n_compact_rounds`` 0.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import EngineConfig, SolveSpec, Solver
from repro.core import relax, sssp
from repro.core.graph import build_csr
from repro.core.landmarks import build_landmarks
from repro.data.generators import kronecker, road_grid
from repro.obs import materialize_trace

BE = relax.get_backend("segment_min")
TRACE = 1024


def _ties():
    """Integer weights 1-3: long runs of equal weights in every row."""
    rng = np.random.default_rng(5)
    n, m = 200, 1600
    return build_csr(n, rng.integers(0, n, m), rng.integers(0, n, m),
                     rng.integers(1, 4, m).astype(np.float64))


def _split():
    """Two components and isolated vertices."""
    rng = np.random.default_rng(6)
    u = np.concatenate([rng.integers(0, 100, 500),
                        rng.integers(120, 200, 400)])
    v = np.concatenate([rng.integers(0, 100, 500),
                        rng.integers(120, 200, 400)])
    return build_csr(230, u, v, rng.uniform(0.1, 1.0, u.size))


def _zero():
    """A quarter of the edges weigh exactly 0."""
    rng = np.random.default_rng(7)
    n, m = 180, 1400
    w = rng.uniform(0.0, 1.0, m)
    w[rng.random(m) < 0.25] = 0.0
    return build_csr(n, rng.integers(0, n, m), rng.integers(0, n, m), w)


MAKERS = {
    "kron": lambda: kronecker(8, 6, seed=3),
    "grid": lambda: road_grid(14, seed=4),
    "ties": _ties,
    "split": _split,
    "zero": _zero,
}


@functools.lru_cache(maxsize=None)
def _graph(name):
    host = MAKERS[name]()
    return host, host.to_device(), int(np.argmax(host.deg))


@functools.lru_cache(maxsize=None)
def _landmarks(name):
    return build_landmarks(_graph(name)[1], n_landmarks=4).alt_data


def _assert_round_equal(a, b, what):
    for x, y, part in ((a[0], b[0], "dist"), (a[1], b[1], "parent"),
                       (a[2].improved, b[2].improved, "frontier")):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{what}: {part}")
    for f in ("n_trav", "n_relax", "n_updates", "n_extended", "n_pruned"):
        assert int(getattr(a[2], f)) == int(getattr(b[2], f)), (what, f)


@pytest.mark.parametrize("alt", [False, True], ids=["plain", "alt"])
@pytest.mark.parametrize("name", sorted(MAKERS))
def test_compact_round_matches_dense(name, alt):
    """Windowed rounds from the same states: the compacted branch (caps
    that never overflow) returns what the dense round returns."""
    host, g, src = _graph(name)
    caps = (g.n, g.m)
    alt_lb = None
    if alt:
        alt_lb = relax.alt_lower_bounds(_landmarks(name).D, jnp.int32(0),
                                        jnp.float32(1e-5), jnp.float32(1))

    @jax.jit
    def both(dist, parent, frontier, lb, ub, bound):
        args = (g, dist, parent, frontier, lb, ub, alt_lb,
                None if alt_lb is None else bound)
        return (relax._segment_min_relax(*args),
                relax._segment_min_compact_relax(*args, caps=caps))

    dmax = float(np.max(_dense(name)[0][np.isfinite(_dense(name)[0])]))
    bound = jnp.float32(0.6 * dmax)
    gap = jnp.float32(dmax / 8)
    dist = jnp.full((g.n,), jnp.inf).at[src].set(0.0)
    parent = jnp.full((g.n,), -1, jnp.int32).at[src].set(src)
    frontier = jnp.zeros((g.n,), bool).at[src].set(True)
    lb, ub = jnp.float32(0.0), gap
    rounds = 0
    while float(lb) <= dmax:
        dense, compact = both(dist, parent, frontier, lb, ub, bound)
        _assert_round_equal(dense, compact, f"{name} round {rounds}")
        assert float(compact[2].n_compact) == 1.0
        rounds += 1
        dist, parent, frontier = dense[0], dense[1], dense[2].improved
        if not bool(jnp.any(frontier)):
            lb, ub = ub, ub + gap
            frontier = relax.window_frontier(dist, lb, lb, ub, g.max_w)
    assert rounds > 5


def _solve(name, goal="tree", caps=None, alt=False):
    """``(dist, parent, metrics, trace)`` of one solve through
    ``sssp._run``; ``caps`` None runs the dense round."""
    host, g, src = _graph(name)
    gp = {"tree": 0, "p2p": g.n - 1, "bounded": float(np.median(host.w)) * 3,
          "knear": 12}[goal]
    gp = sssp.goal_param_array(goal, gp)
    alt_data = _landmarks(name) if alt else None

    def run(g, s, gp, alt_data):
        return sssp._run(g, g, s, BE, 10**6, 3.0, 0.9, goal, gp,
                         trace_capacity=TRACE, alt_data=alt_data, caps=caps)

    d, p, m, buf = jax.jit(run)(g, jnp.int32(src), gp, alt_data)
    return np.asarray(d), np.asarray(p), jax.tree.map(np.asarray, m), \
        materialize_trace(buf)


@functools.lru_cache(maxsize=None)
def _dense(name, goal="tree", alt=False):
    return _solve(name, goal, None, alt)


def _assert_solve_equal(ref, out, what):
    np.testing.assert_array_equal(ref[0], out[0], err_msg=f"{what}: dist")
    np.testing.assert_array_equal(ref[1], out[1], err_msg=f"{what}: parent")
    for f in sssp.LOGICAL_METRIC_FIELDS:
        assert int(getattr(ref[2], f)) == int(getattr(out[2], f)), (what, f)
    assert ref[3].dropped == out[3].dropped == 0
    for col in ("frontier", "stepped") + sssp.LOGICAL_METRIC_FIELDS:
        np.testing.assert_array_equal(ref[3].columns[col],
                                      out[3].columns[col],
                                      err_msg=f"{what}: ring {col}")


# vertex cap 2 overflows on any wider frontier, slot cap 8 on any round
# with more in-window slots; the other cap is out of reach
CAPS = {"derived": None, "kv_overflow": (2, 1 << 16),
        "c_overflow": (4096, 8)}


@pytest.mark.parametrize("caps", sorted(CAPS))
@pytest.mark.parametrize("name", sorted(MAKERS))
def test_compact_tree_solve_matches_dense(name, caps):
    host, g, _ = _graph(name)
    ref = _dense(name)
    out = _solve(name, caps=CAPS[caps] or relax.compact_caps(g.n, g.m))
    _assert_solve_equal(ref, out, f"{name}/{caps}")
    assert float(ref[2].n_compact_rounds) == 0
    compacted = out[3].columns["n_compact_rounds"]
    assert float(out[2].n_compact_rounds) == compacted.sum()
    if caps != "derived":
        # both branches ran in one solve
        assert 0 < compacted.sum() < out[3].n_records, compacted


@pytest.mark.parametrize("goal", ["p2p", "p2p_alt", "bounded", "knear"])
@pytest.mark.parametrize("name", ["grid", "kron"])
def test_compact_goal_solve_matches_dense(name, goal):
    _, g, _ = _graph(name)
    kind, alt = goal.split("_")[0], goal.endswith("alt")
    ref = _dense(name, kind, alt)
    out = _solve(name, kind, (g.n // 4, g.m // 8), alt)
    _assert_solve_equal(ref, out, f"{name}/{goal}")
    assert float(out[2].n_compact_rounds) > 0
    if alt:
        assert int(out[2].n_pruned) > 0


@pytest.mark.parametrize("name", sorted(MAKERS))
def test_repair_relax_matches_dense(name):
    """Repair's window is [0, inf): whole rows, compacted or not."""
    host, g, src = _graph(name)
    dist = jnp.full((g.n,), jnp.inf).at[src].set(0.0)
    parent = jnp.full((g.n,), -1, jnp.int32).at[src].set(src)
    frontier = jnp.zeros((g.n,), bool).at[src].set(True)
    args = (g, dist, parent, frontier, BE, 10**6, 0)
    ref = sssp._repair_jit(*args)
    out = sssp.repair_relax(g, dist, parent, frontier)
    small = sssp._repair_jit(*args, caps=(8, 64))
    for got, what in ((out, "derived"), (small, "small caps")):
        np.testing.assert_array_equal(np.asarray(ref[0]),
                                      np.asarray(got[0]), err_msg=what)
        np.testing.assert_array_equal(np.asarray(ref[1]),
                                      np.asarray(got[1]), err_msg=what)
        for f in sssp.LOGICAL_METRIC_FIELDS:
            assert int(getattr(ref[2], f)) == int(getattr(got[2], f)), \
                (what, f)
    assert float(ref[2].n_compact_rounds) == 0
    assert 0 < float(small[2].n_compact_rounds) < int(small[2].n_rounds) + 1


@pytest.mark.parametrize("kv", [3, 64], ids=["search", "scatter"])
def test_compact_frontier_lists_the_mask_in_order(kv):
    """A few ranks search the prefix count, many write each vertex."""
    mask = np.random.default_rng(kv).random(200) < 0.1
    want = np.flatnonzero(mask)[:kv]
    got = np.asarray(relax.compact_frontier(jnp.asarray(mask), kv))
    np.testing.assert_array_equal(got[:want.size], want)
    assert (got[want.size:] == 0).all()


def test_caps_come_from_shapes():
    # the benchmark cells: GAP kron scale 17 and the 256-side grid
    assert relax.compact_caps(1 << 17, 4_194_304) == (32_768, 65_536)
    assert relax.compact_caps(65_536, 261_120) == (16_384, 4_096)
    assert relax.compact_caps(1, 0) == (1, 1)


def test_batched_program_keeps_the_dense_round():
    host, g, src = _graph("kron")
    srcs = [src, 0]
    assert "round.compact" in sssp.compiled_text(g, src)
    assert "round.compact" not in sssp.compiled_text(g, srcs, batched=True)
    _, _, m = sssp.sssp_batch(g, srcs)
    assert (np.asarray(m.n_compact_rounds) == 0).all()
    assert (np.asarray(m.n_rounds) > 0).all()


def test_routed_tier_runs_no_compacted_round():
    host, _, src = _graph("grid")
    with Solver.open(host, EngineConfig(tier="routed", max_batch=2)) as s:
        res = s.solve(SolveSpec.tree(src))
    assert float(res.metrics["n_compact_rounds"]) == 0
    assert int(res.metrics["n_rounds"]) > 0


def test_compact_counter_counts_the_rings_compacted_records():
    host, _, src = _graph("kron")
    with Solver.open(host, EngineConfig(trace=True)) as s:
        res = s.solve(SolveSpec.tree(src))
    col = res.trace.columns["n_compact_rounds"]
    assert res.trace.dropped == 0
    assert set(np.unique(col)) <= {0.0, 1.0}
    assert float(res.metrics.n_compact_rounds) == float(col.sum()) > 0
