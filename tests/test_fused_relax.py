"""The multi-round fused relaxation megakernel (kernels/edge_relax).

Three layers of parity, all bitwise:

* ``schedule_tiles``'s segmented prefix-sum scatter against a
  reimplementation of the argsort compaction it replaced (property-style
  sweep over empty / single-tile / full-frontier buckets and random
  mixes);
* the Pallas megakernel paths (``relax_fused`` / ``relax_partials``)
  against their jnp reference twins, including the in-kernel counter
  vectors and under ``vmap`` (the batched engine's usage);
* the fused blocked engine end-to-end against the unfused blocked and
  segment_min engines — dist, parent and every logical metric counter —
  plus the perf acceptance pair: kernel invocations per solve drop while
  the compacted tile schedule does not grow.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import relax
from repro.core.config import ConfigError
from repro.core.graph import build_blocked
from repro.core.sssp import LOGICAL_METRIC_FIELDS, sssp, sssp_batch
from repro.data.generators import kronecker, road_grid
from repro.kernels.edge_relax import ops
from repro.kernels.edge_relax.edge_relax import schedule_tiles


def _oracle_schedule(active):
    """The pre-refactor compaction: stable argsort moves active tiles to
    the front (preserving dst-sorted layout order), then the last active
    tile is repeated over the inactive slots."""
    order = np.argsort(~active, kind="stable").astype(np.int32)
    n = int(active.sum())
    sched = order.copy()
    if n:
        sched[n:] = sched[n - 1]
    else:
        sched[:] = 0
    return sched, n


def _schedule_case(rng, nt, tile_e, block_v, frontier=None, tile_first=None,
                   pad_frac=0.3):
    src_local = rng.integers(0, block_v, nt * tile_e).astype(np.int32)
    w = rng.uniform(0.1, 2.0, nt * tile_e).astype(np.float32)
    w[rng.random(nt * tile_e) < pad_frac] = np.inf   # padding slots
    if frontier is None:
        frontier = (rng.random(block_v) < 0.4)
    if tile_first is None:
        tile_first = (rng.random(nt) < 0.2)
    return (frontier.astype(np.int8), src_local, w,
            np.asarray(tile_first, bool))


def test_schedule_prefix_sum_matches_argsort_oracle():
    block_v, tile_e = 16, 4
    rng = np.random.default_rng(0)
    cases = []
    # empty frontier, no forced tiles -> nothing scheduled
    cases.append(_schedule_case(rng, 6, tile_e, block_v,
                                frontier=np.zeros(block_v, bool),
                                tile_first=np.zeros(6, bool)))
    # empty frontier, forced first tiles only
    tf = np.zeros(8, bool)
    tf[[0, 5]] = True
    cases.append(_schedule_case(rng, 8, tile_e, block_v,
                                frontier=np.zeros(block_v, bool),
                                tile_first=tf))
    # exactly one active tile (single-tile bucket)
    fr = np.zeros(block_v, bool)
    fr[3] = True
    src = np.full(8 * tile_e, 5, np.int32)
    src[:tile_e] = 3
    w = np.full(8 * tile_e, np.inf, np.float32)
    w[:tile_e] = 1.0
    cases.append((fr.astype(np.int8), src, w, np.zeros(8, bool)))
    # full frontier -> every non-padding tile active
    cases.append(_schedule_case(rng, 7, tile_e, block_v,
                                frontier=np.ones(block_v, bool),
                                pad_frac=0.0))
    # random mixes
    for nt in (1, 2, 5, 13):
        cases.append(_schedule_case(rng, nt, tile_e, block_v))
    for fr, src_local, w, tf in cases:
        nt = tf.shape[0]
        sched, sched_n = schedule_tiles(jnp.asarray(fr),
                                        jnp.asarray(src_local),
                                        jnp.asarray(w), jnp.asarray(tf),
                                        tile_e)
        touched = (fr[src_local] > 0) & np.isfinite(w)
        active = touched.reshape(nt, tile_e).any(axis=1) | tf
        ref_sched, ref_n = _oracle_schedule(active)
        assert int(sched_n) == ref_n
        np.testing.assert_array_equal(np.asarray(sched), ref_sched)


def _mid_solve_state(g, bg, seed=0):
    """A plausible mid-solve state over the padded vertex range: some
    settled vertices, a partial frontier, the rest unreached."""
    rng = np.random.default_rng(seed)
    n_out = bg.n_blocks * bg.block_v
    dist = np.full(n_out, np.inf, np.float32)
    seeds = rng.choice(g.n, min(30, g.n // 2), replace=False)
    dist[seeds] = rng.uniform(0.0, 3.0, seeds.size).astype(np.float32)
    parent = np.full(n_out, -1, np.int32)
    parent[seeds] = rng.integers(0, g.n, seeds.size)
    frontier = np.zeros(n_out, bool)
    frontier[seeds[: seeds.size // 2]] = True
    return jnp.asarray(dist), jnp.asarray(parent), jnp.asarray(frontier)


def test_fused_kernel_matches_ref():
    g = road_grid(12, seed=2)
    bg = build_blocked(g.to_device(), block_v=64, tile_e=64)
    fs = relax.fused_slab(bg)
    dist, parent, frontier = _mid_solve_state(g, bg)
    lb, ub = jnp.float32(0.5), jnp.float32(2.5)
    out = {}
    for use_kernel in (True, False):
        out[use_kernel] = ops.relax_fused(
            dist, parent, frontier, bg.deg, fs.src, fs.dst, fs.w,
            fs.tile_dst, fs.tile_first, lb, ub, block_v=bg.block_v,
            tile_e=bg.tile_e, fused_rounds=3, use_kernel=use_kernel)
    for a, b, what in zip(out[True], out[False],
                          ("dist", "parent", "frontier", "counters")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=what)
    # the counter fold is exact: at least one round ran and counted work
    cnt = np.asarray(out[True][3])
    assert cnt[list(ops.FUSED_COUNTERS).index("n_rounds")] >= 1
    assert cnt[list(ops.FUSED_COUNTERS).index("n_tiles")] > 0


def test_fused_kernel_vmap_matches_loop():
    g = road_grid(12, seed=2)
    bg = build_blocked(g.to_device(), block_v=64, tile_e=64)
    fs = relax.fused_slab(bg)
    states = [_mid_solve_state(g, bg, seed=s) for s in range(3)]
    dists = jnp.stack([s[0] for s in states])
    parents = jnp.stack([s[1] for s in states])
    fronts = jnp.stack([s[2] for s in states])
    lbs = jnp.asarray([0.0, 0.5, 1.0], jnp.float32)   # incl. the lb<=0 clamp
    ubs = jnp.asarray([1.5, 2.5, 3.0], jnp.float32)

    def one(d, p, f, lb, ub):
        return ops.relax_fused(d, p, f, bg.deg, fs.src, fs.dst, fs.w,
                               fs.tile_dst, fs.tile_first, lb, ub,
                               block_v=bg.block_v, tile_e=bg.tile_e,
                               fused_rounds=3)

    batched = jax.vmap(one)(dists, parents, fronts, lbs, ubs)
    for i in range(3):
        single = one(dists[i], parents[i], fronts[i], lbs[i], ubs[i])
        for a, b, what in zip(single, batched,
                              ("dist", "parent", "frontier", "counters")):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[i],
                                          err_msg=f"slot {i}: {what}")
    # slot 0 entered with lb<=0 (bootstrap): the clamp must hold it to 1
    n_rounds_i = list(ops.FUSED_COUNTERS).index("n_rounds")
    assert int(np.asarray(batched[3])[0, n_rounds_i]) == 1


def test_partials_kernel_matches_ref():
    g = road_grid(12, seed=2)
    bg = build_blocked(g.to_device(), block_v=64, tile_e=64)
    fs = relax.fused_slab(bg)
    dist, parent, frontier = _mid_solve_state(g, bg, seed=1)
    paths = relax.leaf_pruned(frontier, dist, bg.deg).astype(jnp.int8)
    lb, ub = jnp.float32(0.2), jnp.float32(2.0)
    out = {}
    for use_kernel in (True, False):
        out[use_kernel] = ops.relax_partials(
            dist, paths, parent, fs.src, fs.dst, fs.w, fs.tile_dst,
            fs.tile_first, jnp.argsort(fs.tile_dst, stable=True), lb, ub,
            block_v=bg.block_v, tile_e=bg.tile_e,
            n_dst_blocks=bg.n_dst_blocks, use_kernel=use_kernel)
    for a, b, what in zip(out[True], out[False],
                          ("best", "winner", "counters")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=what)


def test_fused_engine_end_to_end_parity():
    """The tentpole acceptance on representative graphs: the fused
    blocked engine is bitwise-identical (dist/parent/logical metrics) to
    the unfused blocked and segment_min engines, launches >= 2x fewer
    kernels on the round-heavy graph, and never grows the compacted
    tile schedule."""
    for name, g, need_2x in [("road", road_grid(16, seed=2), True),
                             ("kron", kronecker(8, 8, seed=1), False)]:
        src = int(np.argmax(g.deg))
        dg = g.to_device()
        d_sm, p_sm, m_sm = sssp(dg, src)
        runs = {}
        for fr in (0, 4):
            d, p, m = sssp(dg, src, backend="blocked_pallas",
                           fused_rounds=fr, block_v=64, tile_e=64)
            np.testing.assert_array_equal(np.asarray(d), np.asarray(d_sm),
                                          err_msg=f"{name}/fused={fr}")
            np.testing.assert_array_equal(np.asarray(p), np.asarray(p_sm),
                                          err_msg=f"{name}/fused={fr}")
            for f in LOGICAL_METRIC_FIELDS:
                assert int(getattr(m, f)) == int(getattr(m_sm, f)), \
                    (name, fr, f)
            runs[fr] = m
        inv0 = float(runs[0].n_invocations)
        inv4 = float(runs[4].n_invocations)
        assert inv4 < inv0, name
        if need_2x:
            assert inv4 * 2 <= inv0, (name, inv0, inv4)
        assert float(runs[4].n_tiles_scanned) \
            == float(runs[0].n_tiles_scanned), name


def test_fused_engine_batch_parity():
    g = road_grid(16, seed=2)
    dg = g.to_device()
    srcs = np.array([0, 17, 200], np.int32)
    d0, p0, m0 = sssp_batch(dg, srcs, backend="blocked_pallas",
                            block_v=64, tile_e=64)
    d4, p4, m4 = sssp_batch(dg, srcs, backend="blocked_pallas",
                            fused_rounds=4, block_v=64, tile_e=64)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d4))
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p4))
    np.testing.assert_array_equal(np.asarray(m0.n_rounds),
                                  np.asarray(m4.n_rounds))
    assert (np.asarray(m4.n_invocations)
            < np.asarray(m0.n_invocations)).all()


def test_fused_rounds_needs_blocked_backend():
    g = road_grid(8, seed=2).to_device()
    with pytest.raises(ConfigError):
        sssp(g, 0, backend="segment_min", fused_rounds=2)
