"""Unit tests for the dynamic-stepping / traversal-optimization formulas."""
import numpy as np
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import stats, stepping, traversal
from repro.core.graph import build_csr, RATIO_NUM
from repro.data.generators import kronecker


@pytest.fixture(scope="module")
def graph():
    return kronecker(10, 8, seed=3)


def test_sum_d_matches_numpy(graph):
    g = graph.to_device()
    rng = np.random.default_rng(0)
    dist = rng.random(graph.n).astype(np.float32)
    dist[rng.random(graph.n) < 0.3] = np.inf
    for x in [0.0, 0.3, 0.7, 1.5]:
        got = int(stats.sum_d(jnp.asarray(dist), g.deg, jnp.float32(x)))
        want = int(graph.deg[dist >= x].sum())
        assert got == want


def test_sum_d_grid_matches_pointwise(graph):
    g = graph.to_device()
    rng = np.random.default_rng(1)
    dist = rng.random(graph.n).astype(np.float32) * 2
    grid = jnp.linspace(0.0, 2.0, 64)
    got = np.asarray(stats.sum_d_grid(jnp.asarray(dist), g.deg, grid))
    want = np.array([int(graph.deg[dist >= float(x)].sum()) for x in grid])
    np.testing.assert_array_equal(got, want)


def test_high_d_balances_degree_mass(graph):
    """highD splits VS(x) into halves of ~equal total degree."""
    g = graph.to_device()
    dist = jnp.zeros(graph.n)
    hd = float(stats.high_d(dist, g.deg, jnp.float32(0.0)))
    deg = graph.deg
    below = deg[deg < hd].sum()
    total = deg.sum()
    # bucketed approximation: within a factor ~2 of an exact split
    assert 0.2 < below / total < 0.8, (hd, below / total)


def test_max_w_quantiles(graph):
    g = graph.to_device()
    for r in [0.0, 0.25, 0.5, 0.9, 1.0]:
        got = float(stats.max_w_of(g.rtow, jnp.float32(r)))
        want = float(np.quantile(graph.w, r))
        np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-3)
    frac = (graph.w <= float(stats.max_w_of(g.rtow, jnp.float32(0.5)))).mean()
    assert abs(frac - 0.5) < 0.02


@settings(max_examples=50, deadline=None)
@given(st.floats(0.01, 0.99), st.floats(1.0, 1e5))
def test_ratio_formula_bounds(p, hd):
    """Eq (2): ratio in (0, 1), decreasing in highD."""
    r = float(stepping.ratio(jnp.float32(p), jnp.float32(hd)))
    assert 0.0 < r < 1.0
    r2 = float(stepping.ratio(jnp.float32(p), jnp.float32(hd * 2)))
    assert r2 <= r + 1e-6


def test_gap_full_width_for_low_degree():
    """Eq (3): highD <= alpha => gap = maxW(G, 1) (Road regime).

    A path graph has degree <= 2 (the paper's Road has highD(0)=3); a 2-D
    lattice's interior degree is 4, which correctly does NOT trigger the
    full-width branch."""
    rng = np.random.default_rng(0)
    n = 256
    u = np.arange(n - 1)
    v = np.arange(1, n)
    g = build_csr(n, u, v, rng.random(n - 1) + 0.1).to_device()
    dist = jnp.zeros(n)
    gap = float(stepping.gap(dist, g.deg, g.rtow, g.n_edges2,
                             jnp.float32(0.0)))
    np.testing.assert_allclose(gap, float(g.rtow[-1]), rtol=1e-6)


def test_profit_terms_signs(graph):
    g = graph.to_device()
    dist = jnp.asarray(
        np.random.default_rng(2).random(graph.n).astype(np.float32))
    lb, y = jnp.float32(0.5), jnp.float32(0.8)
    grid = jnp.linspace(0.0, 0.5, 32)
    sd_grid = stats.sum_d_grid(dist, g.deg, grid)
    sd_lb = stats.sum_d(dist, g.deg, lb)
    pushed, long_, pulled = traversal.profit_terms(
        grid, lb, y, sd_grid, sd_lb, g.n_edges2, g.rtow[-1])
    assert np.all(np.asarray(pushed) >= -1e-6)
    assert np.all(np.asarray(long_) >= -1e-6)
    assert np.all(np.asarray(pulled) >= -1e-6)
    # pushed mass grows as x decreases (more settled band pushed)
    p = np.asarray(pushed)
    assert p[0] >= p[-1] - 1e-3


@settings(max_examples=40, deadline=None)
@given(st.floats(0.0, 1e6), st.floats(0.0, 1e6), st.floats(0.25, 4.0))
def test_gap_positive_on_degenerate_luts(sd, hd, mult):
    """w_floor clamp: every window is strictly positive — static and
    adaptive (``mult``-rescaled) alike — even on degenerate weight LUTs
    (all-zero, all-duplicate, and zero-heavy quantile tables)."""
    two_e = jnp.float32(1000.0)
    for lut in (np.zeros(64), np.full(64, 0.5),
                np.concatenate([np.zeros(63), [2.0]])):
        rtow = jnp.asarray(lut, jnp.float32)
        g0 = float(stepping.gap_from_stats(jnp.float32(sd), jnp.float32(hd),
                                           rtow, two_e))
        ga = float(stepping.gap_from_stats(jnp.float32(sd), jnp.float32(hd),
                                           rtow, two_e,
                                           mult=jnp.float32(mult)))
        # both inherit the >= max(w_floor, 1e-12) clamp: a shrunken
        # adaptive window can never hit zero and stall the solve loop
        # (the clamp is f32, and float32(1e-12) lies just below 1e-12)
        floor = np.float32(1e-12)
        assert g0 >= floor, (lut[:3], sd, hd)
        assert ga >= floor, (lut[:3], sd, hd, mult)


def test_gap_mult_one_matches_static(graph):
    """mult=1 reproduces the static window bitwise (the adaptive policy
    starts from the static program's exact widths)."""
    g = graph.to_device()
    dist = jnp.asarray(
        np.random.default_rng(5).random(graph.n).astype(np.float32))
    for x in [0.0, 0.3, 0.9]:
        g_static = stepping.gap(dist, g.deg, g.rtow, g.n_edges2,
                                jnp.float32(x))
        g_mult = stepping.gap(dist, g.deg, g.rtow, g.n_edges2,
                              jnp.float32(x), mult=jnp.float32(1.0))
        assert np.float32(g_static) == np.float32(g_mult)


def test_adaptive_update_clamps_and_snapshots():
    """Feedback clamps hold under extreme counters, and the counter
    snapshots always advance to the observed values."""
    pol = stepping.DEFAULT_ADAPTIVE
    # the clamps hold in f32: compare with the bounds rounded to float32
    # (float32(0.995) lies just above 0.995)
    f32 = np.float32
    ps = stepping.policy_init(stepping.SteppingParams())
    # hammer the "too wide" signal: mult must stop at mult_min
    for r in range(1, 30):
        ps = stepping.adaptive_update(ps, jnp.int32(100 * r),
                                      jnp.int32(1000 * r), jnp.int32(0))
    assert float(ps.mult) == pytest.approx(pol.mult_min)
    assert float(ps.alpha) >= f32(pol.alpha_min)
    assert float(ps.beta) >= f32(pol.beta_min)
    assert int(ps.last_rounds) == 100 * 29
    # hammer "too narrow": mult must stop at mult_max
    ps2 = stepping.policy_init(stepping.SteppingParams())
    for r in range(1, 30):
        ps2 = stepping.adaptive_update(ps2, jnp.int32(r),
                                       jnp.int32(10 * r), jnp.int32(10 * r))
    assert float(ps2.mult) == pytest.approx(pol.mult_max)
    assert float(ps2.alpha) <= f32(pol.alpha_max)
    assert float(ps2.beta) <= f32(pol.beta_max)


def test_compute_st_within_bounds(graph):
    g = graph.to_device()
    dist = jnp.asarray(
        np.random.default_rng(3).random(graph.n).astype(np.float32))
    st_ = float(traversal.compute_st(dist, g.deg, g.rtow, g.n_edges2,
                                     jnp.float32(0.2), jnp.float32(0.5)))
    assert 0.0 <= st_ <= 0.5 + 1e-6
