"""Pure-jnp oracles for the edge_relax kernels.

These are the validation twins of the Pallas kernels AND the arrays-only
reference path a caller selects by name (``use_kernel=False``); every
reduction resolves ties
exactly like the kernels (min value, then min source id), so the two
paths are bitwise-interchangeable.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .edge_relax import DEFAULT_BLOCK_V, DEFAULT_TILE_E, INT_MAX


def edge_relax_ref(dist_block, frontier_block, src_local, dst_local, w,
                   lb, ub, alt_lb=None, prune_bound=None, *,
                   block_v: int = DEFAULT_BLOCK_V, n_dst_blocks: int = 1):
    """Returns ``(vals, winners)`` matching the Pallas kernel contract:
    per-destination min candidate plus the smallest block-local source id
    achieving it (INT_MAX where no in-window candidate exists).  With
    ``alt_lb`` the kernel's ALT cut is mirrored on the value path:
    candidates with ``cand + alt_lb[dst] > prune_bound`` never enter the
    scatter-min."""
    n_out = n_dst_blocks * block_v
    cand = dist_block[src_local] + w
    ok = (frontier_block[src_local] > 0) & (cand >= lb) & (cand < ub)
    if alt_lb is not None:
        ok = ok & (cand + alt_lb[dst_local] <= prune_bound)
    cand = jnp.where(ok, cand, jnp.inf)
    best = jax.ops.segment_min(cand, dst_local, num_segments=n_out)
    win = jnp.where(ok & (cand <= best[dst_local]), src_local, INT_MAX)
    winner = jax.ops.segment_min(win, dst_local, num_segments=n_out)
    return best, winner


def _slab_counters(pa_src, w, dst, p_src, ok, tile_first, tile_e: int,
                   fail=None):
    """The fused kernels' logical counters, computed slab-wide (exact:
    tiles outside the compacted schedule contribute zero to each).

    ``ok`` is the pre-prune in-window mask and ``fail`` the ALT cut
    (None without ALT): ``n_trav`` counts all of ``ok``, ``n_relax`` the
    parent-excluded survivors and ``n_pruned`` the parent-excluded cuts,
    so ``n_relax(unpruned) == n_relax(pruned) + n_pruned`` per round."""
    nt = w.shape[0] // tile_e
    touched = pa_src & jnp.isfinite(w)
    active = touched.reshape(nt, tile_e).any(axis=1) | (tile_first > 0)
    notpar = dst != p_src
    if fail is None:
        rlx = jnp.sum((ok & notpar).astype(jnp.int32))
        prn = jnp.int32(0)
    else:
        rlx = jnp.sum((ok & notpar & ~fail).astype(jnp.int32))
        prn = jnp.sum((ok & notpar & fail).astype(jnp.int32))
    return (jnp.sum(ok.astype(jnp.int32)), rlx,
            jnp.sum(active.astype(jnp.int32)), prn)


def edge_relax_fused_ref(dist, parent, frontier, deg, src, dst, w,
                         tile_dst, tile_first, lb, ub, alt_lb=None,
                         prune_ub=None, prune_infl=None, prune_tgt=None, *,
                         block_v: int = DEFAULT_BLOCK_V,
                         tile_e: int = DEFAULT_TILE_E,
                         fused_rounds: int = 4):
    """Arrays-only twin of :func:`..edge_relax.edge_relax_fused`.

    Same contract bit-for-bit: up to ``fused_rounds`` windowed rounds
    (one while ``lb <= 0``), early exit when a round improves nothing,
    counters per ``FUSED_COUNTERS``.  The per-round segment-min over the
    whole slab equals the kernel's scheduled-tile accumulation because
    min is order-independent and unscheduled tiles only carry
    out-of-window candidates.
    """
    n_out = dist.shape[0]
    lb = jnp.float32(lb)
    ub = jnp.float32(ub)
    maxr = jnp.where(lb <= 0.0, 1, fused_rounds).astype(jnp.int32)

    def cond(c):
        return c[4] > 0

    def body(c):
        dist, parent, front, cnt, _go, r = c
        paths = (front > 0) & ((dist <= 0.0) | (deg > 1))
        pa_src = paths[src]
        cand = dist[src] + w
        ok = pa_src & (cand >= lb) & (cand < ub)
        fail = None
        if alt_lb is not None:
            # the per-round bound recompute the fused kernel performs
            # from its resident dist
            bound = jnp.minimum(jnp.float32(prune_ub),
                                dist[prune_tgt] * jnp.float32(prune_infl))
            fail = cand + alt_lb[dst] > bound
        trav, rlx, sched_n, prn = _slab_counters(
            pa_src, w, dst, parent[src], ok, tile_first, tile_e, fail)
        if fail is not None:
            ok = ok & ~fail
        cand = jnp.where(ok, cand, jnp.inf)
        best = jax.ops.segment_min(cand, dst, num_segments=n_out)
        win = jnp.where(ok & (cand <= best[dst]), src, INT_MAX)
        winner = jax.ops.segment_min(win, dst, num_segments=n_out)
        improved = best < dist
        cnt = cnt + jnp.stack([
            trav, rlx,
            jnp.sum(improved.astype(jnp.int32)),
            jnp.sum((improved & (deg > 1)).astype(jnp.int32)),
            jnp.any(front > 0).astype(jnp.int32),
            sched_n, jnp.int32(1), prn])
        go = (jnp.any(improved) & (r + 1 < maxr)).astype(jnp.int32)
        return (jnp.where(improved, best, dist),
                jnp.where(improved, winner, parent),
                improved.astype(jnp.int32), cnt, go, r + 1)

    init = (dist, parent, frontier.astype(jnp.int32),
            jnp.zeros((8,), jnp.int32), jnp.int32(1), jnp.int32(0))
    dist2, parent2, front2, cnt, _, _ = jax.lax.while_loop(cond, body, init)
    return dist2, parent2, front2, cnt


def edge_relax_partials_ref(dist_src, paths_src, parent_src, src, dst, w,
                            tile_dst, tile_first, lb, ub, alt_lb=None,
                            prune_bound=None, *, block_v: int = DEFAULT_BLOCK_V,
                            tile_e: int = DEFAULT_TILE_E,
                            n_dst_blocks: int = 1):
    """Arrays-only twin of :func:`..edge_relax.edge_relax_partials`:
    one-shot (min, winner) partials over a whole slab set plus the
    ``PARTIAL_COUNTERS`` vector."""
    n_out = n_dst_blocks * block_v
    pa_src = paths_src[src] > 0
    cand = dist_src[src] + w
    ok = pa_src & (cand >= lb) & (cand < ub)
    fail = None
    if alt_lb is not None:
        fail = cand + alt_lb[dst] > prune_bound
    trav, rlx, sched_n, prn = _slab_counters(
        pa_src, w, dst, parent_src[src], ok, tile_first, tile_e, fail)
    if fail is not None:
        ok = ok & ~fail
    cand = jnp.where(ok, cand, jnp.inf)
    best = jax.ops.segment_min(cand, dst, num_segments=n_out)
    win = jnp.where(ok & (cand <= best[dst]), src, INT_MAX)
    winner = jax.ops.segment_min(win, dst, num_segments=n_out)
    cnt = jnp.stack([trav, rlx, sched_n, prn])
    return best, winner, cnt
