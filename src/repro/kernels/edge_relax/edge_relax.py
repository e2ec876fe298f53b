"""Pallas TPU kernel for the EIC windowed edge relaxation (paper Algo 2 l.10-17).

One grid step processes one *scheduled* edge tile against its destination
block:

    cand[e] = dist[src[e]] + w[e]          if frontier[src[e]] and
                                              lb <= cand[e] < ub
    out[j]  = min over e with dst[e] == j  of cand[e]
    win[j]  = min src[e] over the edges achieving out[j]   (deterministic
              parent recovery: smallest source id among the winners)

TPU adaptation: the MPI CAS loop becomes a dense masked min-reduction.
Edges arrive pre-bucketed by (src block, dst block) with every bucket
padded to a tile boundary — the :class:`~repro.core.graph.BlockedGraph`
layout — so each tile belongs to exactly one destination block and the
source-distance block and the destination output block both fit in VMEM.
Mosaic has no vector-indexed gather, so both sides are broadcast-compare
reduces over (TILE_E x BLOCK_V) planes, which are VPU-shaped: the
source gather ``dist[src]`` selects through an ``src == lane`` plane and
the scatter-min through a ``dst == lane`` plane, with no data-dependent
reads or writes; tiles revisiting the same output block are combined
in-place (value min, winner min on ties — associative and
order-independent, so the accumulation is deterministic).  Slabs and
outputs stream as 1-D blocks, which Mosaic lays out with the XLA tiling
of their arrays (1024 elements), so compiled kernels need ``tile_e`` and
``block_v`` in multiples of 1024.

**Sparsity-aware ragged grid.**  The grid is 1-D over the slab's tiles
(``grid=(n_tiles,)``), not the dense ``(n_dst_blocks, n_tiles)`` product:
the layout's CSR-of-tiles index (``tile_dst``, non-decreasing) already
restricts every destination block to its own tile range, so no tile is
ever scanned against a foreign block.  On top of the static ranges, a
**frontier-compaction prepass** (:func:`schedule_tiles`) computes an
active-tile bitmap from the round's frontier and compacts the active
tiles to the front of the schedule (stable, so the dst-sorted order —
which the revisiting output BlockSpec requires — is preserved).  Inactive
tail steps are pinned to the last active tile, so consecutive grid steps
see an unchanged block index: Pallas skips the re-fetch DMA and
``pl.when`` skips the compute.  Steps with narrow windows — the common
case under dynamic stepping — touch only the few tiles whose sources sit
in the frontier band.

The schedule, the per-step destination block, and the active count ride
in as scalar-prefetch operands (``PrefetchScalarGridSpec``), which is
what lets the input/output index maps follow a *traced* per-round
schedule while the grid itself stays static (jit/vmap-compatible).
Destination blocks with no tiles at all are never visited; their output
range is masked to +inf / INT_MAX after the call.

**Multi-round fused megakernel (interpret mode only).**  It does not
compile for TPU — whole-slab VMEM residency, vector-indexed gathers and
an nt x nt compaction plane — and refuses ``interpret=False`` with
:data:`FUSED_ON_TPU`.  :func:`edge_relax_fused` executes up
to ``fused_rounds`` complete windowed relaxation rounds in ONE Pallas
invocation over the whole (concatenated, global-source-id) edge slab.
The VMEM residency contract: ``dist``/``parent``/``frontier`` live in
the kernel's output refs for the entire invocation — every round reads
the previous round's state straight from VMEM, recomputes the
frontier-compacted tile schedule in-kernel (the same prefix-sum
compaction as :func:`schedule_tiles`), relaxes only the scheduled
tiles, commits improvements, and exits early once a round improves
nothing (the window has settled) — no XLA round-trip, no HBM bounce of
the O(V) state between rounds.  The logical counters (``n_trav``,
``n_relax``, updates, per-round tile counts) are folded into per-tile
partial sums over the compacted schedule — exact, because a tile left
out of the schedule has no frontier source with finite weight, so every
one of its edges fails the window test and contributes zero — which
eliminates the separate O(E) per-round metrics pass the unfused path
pays in ``core/relax.py``.

When ``fused_rounds`` helps vs hurts: fusing pays off when windows are
wide (many rounds per step, each reusing the resident state — typical
for the first steps on skewed-degree graphs) and costs nothing when
they are narrow (the kernel exits after one round).  It can *hurt* on
small graphs, where per-invocation fixed cost is negligible anyway and
the fused kernel's whole-slab residency (the full edge slab plus 2
O(V) carries must fit in VMEM at once) forfeits the per-source-block
streaming of the unfused path; keep ``fused_rounds=0`` there, or when
VMEM cannot hold slab + state.  The window bounds stay constant within
a step, which is what makes in-kernel round chaining exact; during the
bootstrap step (``lb <= 0``) the upper bound tightens after every
round, so the wrapper clamps the invocation to a single round there.

:func:`edge_relax_partials` runs the same gridded tile kernel for the
sharded engines: one invocation relaxes ALL of a shard's source-block
slabs (each tile fetching its own source block of the shard's local
state, the schedule ordered by destination block across the slabs) and
returns (min, winner) partials plus the logical counters summed in the
kernel, ready for the collective exchange — one launch per shard and no
flat O(E) metrics pass.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.config import KERNEL_TILING, ConfigError, resolve_interpret

DEFAULT_TILE_E = 1024
DEFAULT_BLOCK_V = 1024
INT_MAX = jnp.iinfo(jnp.int32).max

# counter slots of the fused kernels' in-kernel metric accumulator
FUSED_COUNTERS = ("n_trav", "n_relax", "n_updates", "n_extended",
                  "n_rounds", "n_tiles", "n_exec", "n_pruned")
PARTIAL_COUNTERS = ("n_trav", "n_relax", "n_tiles", "n_pruned")


def _compact(active, order):
    """Stable compaction of the active tiles, visited in ``order``.

    Returns ``(sched, sched_n)``: ``sched[i]`` is the tile to run at grid
    step ``i`` (the active tiles of ``order``, in that order, then the
    last active tile repeated so inactive steps never change the block
    index) and ``sched_n`` the number of active tiles.  A segmented
    prefix-sum scatter, not an argsort: an active tile's exclusive rank
    is its slot; inactive tiles scatter to a dropped out-of-range slot.
    """
    nt = active.shape[0]
    act = active[order]
    pos = jnp.cumsum(act.astype(jnp.int32)) - 1
    sched_n = pos[-1] + 1
    idx = jnp.arange(nt, dtype=jnp.int32)
    sched = jnp.zeros((nt,), jnp.int32).at[
        jnp.where(act, pos, nt)].set(order, mode="drop")
    last = sched[jnp.maximum(sched_n - 1, 0)]
    sched = jnp.where(idx < sched_n, sched, last)
    return sched, sched_n


def _active_tiles(touched, tile_first, tile_e: int):
    nt = tile_first.shape[0]
    return touched.reshape(nt, tile_e).any(axis=1) | (tile_first > 0)


def schedule_tiles(frontier_block, src_local, w, tile_first, tile_e: int):
    """Frontier-compaction prepass: compact the active tiles to the front.

    A tile is *active* when any of its edges has a frontier source and a
    finite weight (padding slots carry ``w=+inf``), or when it is the
    forced first tile of a non-empty (src-block, dst-block) bucket
    (``tile_first`` — those visits guarantee every non-empty destination
    block's output is initialized even on rounds where its bucket is
    entirely outside the frontier).

    Returns ``(sched, sched_n)``: ``sched[i]`` is the tile to run at grid
    step ``i`` — active tiles first, in layout (dst-sorted) order, then
    the last active tile repeated so inactive steps never change the
    block index — and ``sched_n`` the number of active tiles.
    """
    touched = (frontier_block[src_local] > 0) & jnp.isfinite(w)
    active = _active_tiles(touched, tile_first, tile_e)
    return _compact(active, jnp.arange(active.shape[0], dtype=jnp.int32))


def _check_compiled_geometry(block_v: int, tile_e: int, interpret: bool):
    """``EngineConfig.resolve`` refuses other geometries on a TPU
    (:func:`~repro.core.config.check_kernel_geometry`); Mosaic would."""
    assert interpret or not (block_v % KERNEL_TILING
                             or tile_e % KERNEL_TILING), (
        f"compiled edge_relax kernels need block_v and tile_e in multiples "
        f"of {KERNEL_TILING}; got block_v={block_v}, tile_e={tile_e}")


def _tiles_kernel(sched_ref, sched_src_ref, sched_dst_ref, na_ref, lbub_ref,
                  dsrc_ref, *rest, block_v: int, alt: bool, counters: bool):
    """One scheduled edge tile against its source and destination blocks.

    ``dsrc_ref`` is the tile's source block of ``dist`` with non-frontier
    sources already set to +inf, so the window test alone decides which
    edges relax.  The source-side gathers and the destination scatter-min
    are broadcast-compare planes over (tile_e x block_v): Mosaic has no
    vector-indexed gather, and the planes are VPU-shaped.
    """
    rest = list(rest)
    par_ref = rest.pop(0) if counters else None
    src_ref, dst_ref, w_ref = rest[:3]
    rest = rest[3:]
    alt_ref = rest.pop(0) if alt else None
    val_ref, win_ref = rest[:2]
    cnt_ref = rest[2] if counters else None
    i = pl.program_id(0)
    b = sched_dst_ref[i]
    sb = sched_src_ref[i]
    prev = jnp.maximum(i - 1, 0)
    is_first = (i == 0) | (b != sched_dst_ref[prev])

    @pl.when(is_first)
    def _init():
        val_ref[...] = jnp.full_like(val_ref, jnp.inf)
        win_ref[...] = jnp.full_like(win_ref, INT_MAX)

    if counters:
        @pl.when(i == 0)
        def _zero():
            for k in range(len(PARTIAL_COUNTERS)):
                cnt_ref[k] = jnp.int32(0)

    @pl.when(i < na_ref[0])
    def _accumulate():
        lb = lbub_ref[0]
        ub = lbub_ref[1]
        src = src_ref[...]
        w = w_ref[...][:, None]
        dst = dst_ref[...][:, None]
        te = src.shape[0]
        bs = dsrc_ref.shape[0]
        src_lanes = jax.lax.broadcasted_iota(jnp.int32, (te, bs), 1)
        shit = (src - sb * bs)[:, None] == src_lanes
        lanes = jax.lax.broadcasted_iota(jnp.int32, (te, block_v), 1)
        d_src = jnp.min(jnp.where(shit, dsrc_ref[...][None, :], jnp.inf),
                        axis=1, keepdims=True)
        cand = d_src + w
        in_win = (cand >= lb) & (cand < ub)
        dhit = dst == b * block_v + lanes
        ok = in_win
        if alt:
            # ALT goal-directed cut: a candidate whose admissible
            # remaining-distance bound already exceeds the best known
            # s->t length can never improve it (alt_ref is this dst
            # block's slice of the per-vertex bound array)
            alt_e = jnp.min(jnp.where(dhit, alt_ref[...][None, :], jnp.inf),
                            axis=1, keepdims=True)
            fail = cand + alt_e > lbub_ref[2]
            ok = in_win & ~fail
        cand = jnp.where(ok, cand, jnp.inf)
        plane = jnp.where(dhit, cand, jnp.inf)
        tile_min = jnp.min(plane, axis=0)       # [BLOCK_V]
        winners = jnp.where(dhit & ok & (cand <= tile_min[None, :]),
                            src[:, None], INT_MAX)
        tile_win = jnp.min(winners, axis=0)     # [BLOCK_V]

        prev_v = val_ref[...]
        prev_w = win_ref[...]
        better = tile_min < prev_v
        tie = tile_min == prev_v
        val_ref[...] = jnp.minimum(prev_v, tile_min)
        win_ref[...] = jnp.where(
            better, tile_win,
            jnp.where(tie, jnp.minimum(prev_w, tile_win), prev_w))

        if counters:
            # the relaxation back along the parent edge never improves,
            # so it is counted out of n_relax but left in the min
            p_src = jnp.max(jnp.where(shit, par_ref[...][None, :], -2),
                            axis=1, keepdims=True)
            notpar = dst != p_src
            cnt_ref[0] += jnp.sum(in_win.astype(jnp.int32))
            cnt_ref[1] += jnp.sum((ok & notpar).astype(jnp.int32))
            if alt:
                cnt_ref[3] += jnp.sum((in_win & notpar & fail
                                       ).astype(jnp.int32))


def _relax_tiles(dsrc, parent_src, src, dst, w, sched, sched_src, sched_dst,
                 sched_n, lbub, alt_lb, *, src_block: int, block_v: int,
                 tile_e: int, n_out: int, interpret: bool):
    """Run the scheduled tiles of a slab set in one gridded kernel.

    ``sched``/``sched_src``/``sched_dst`` give each grid step its tile,
    the tile's ``src_block``-wide source block (into ``dsrc``/
    ``parent_src``; ``src`` ids index those arrays directly) and its
    destination block; steps of one destination block are consecutive.
    With ``parent_src`` the kernel also returns the in-kernel counters
    ``(n_trav, n_relax, 0, n_pruned)``.
    """
    alt = alt_lb is not None
    counters = parent_src is not None
    by_src = pl.BlockSpec((src_block,), lambda i, s, ss, *_: (ss[i],))
    by_tile = pl.BlockSpec((tile_e,), lambda i, s, *_: (s[i],))
    by_dst = pl.BlockSpec((block_v,), lambda i, s, ss, sd, *_: (sd[i],))
    in_specs = [by_src] + [by_src] * counters + [by_tile] * 3 + [by_dst] * alt
    operands = [dsrc] + [parent_src] * counters + [src, dst, w] \
        + [alt_lb] * alt
    out_specs = [by_dst, by_dst]
    out_shape = [jax.ShapeDtypeStruct((n_out,), jnp.float32),
                 jax.ShapeDtypeStruct((n_out,), jnp.int32)]
    if counters:
        out_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        out_shape.append(jax.ShapeDtypeStruct((len(PARTIAL_COUNTERS),),
                                              jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # sched, sched_src, sched_dst, n_active, lbub: the window bounds
        # are genuinely scalars, which is what SMEM is for
        num_scalar_prefetch=5,
        grid=(sched.shape[0],),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
    )
    return pl.pallas_call(
        functools.partial(_tiles_kernel, block_v=block_v, alt=alt,
                          counters=counters),
        grid_spec=grid_spec,
        out_shape=tuple(out_shape),
        interpret=interpret,
    )(sched, sched_src, sched_dst, sched_n[None], lbub, *operands)


def _lbub(lb, ub, *extra):
    return jnp.stack([jnp.float32(x) for x in (lb, ub) + extra])


@functools.partial(jax.jit, static_argnames=("block_v", "tile_e",
                                             "n_dst_blocks", "interpret"))
def edge_relax(dist_block, frontier_block, src_local, dst_local, w,
               tile_dst, tile_first, bucket_nonempty, lb, ub,
               alt_lb=None, prune_bound=None, *,
               block_v: int = DEFAULT_BLOCK_V, tile_e: int = DEFAULT_TILE_E,
               n_dst_blocks: int = 1, interpret: Optional[bool] = None):
    """Relax one source-block edge slab against its active tile schedule.

    dist_block/frontier_block: [Bs] f32 / int8 (src block local).
    src_local/dst_local/w: [NT * tile_e] tile-aligned edge slab
    (``src_local`` is block-local, ``dst_local`` indexes the full
    ``n_dst_blocks * block_v`` destination range; padding edges carry
    w=+inf).  ``tile_dst`` [NT] is the CSR-of-tiles destination-block
    index (non-decreasing), ``tile_first`` [NT] the forced-active first
    tile of each non-empty bucket, ``bucket_nonempty`` [n_dst_blocks] the
    static has-edges mask (see :func:`repro.core.graph.bucket_edges`).

    Returns ``(vals, winners, n_tiles)``: per-destination min candidate
    and the block-local source id achieving it over the
    ``n_dst_blocks * block_v`` range (INT_MAX where no candidate; ties
    broken toward the smallest source id), plus the number of tiles the
    compacted schedule actually ran.
    """
    interpret = resolve_interpret(interpret)
    _check_compiled_geometry(block_v, tile_e, interpret)
    e = src_local.shape[0]
    if e % tile_e != 0 or e == 0:
        raise ValueError(f"slab length {e} is not tile-aligned "
                         f"(tile_e={tile_e}); bucket it with "
                         "repro.core.graph.bucket_edges")
    sched, sched_n = schedule_tiles(frontier_block, src_local, w,
                                    tile_first, tile_e)
    dsrc = jnp.where(frontier_block > 0, dist_block, jnp.inf)
    alt = alt_lb is not None
    lbub = _lbub(lb, ub, *([prune_bound] if alt else []))
    vals, wins = _relax_tiles(
        dsrc, None, src_local, dst_local, w, sched, jnp.zeros_like(sched),
        tile_dst[sched], sched_n, lbub, alt_lb,
        src_block=dist_block.shape[0], block_v=block_v, tile_e=tile_e,
        n_out=n_dst_blocks * block_v, interpret=interpret)
    # destination blocks without any tile are never visited by the grid:
    # mask their (uninitialized) output range to the no-candidate value
    visited = jnp.repeat(bucket_nonempty, block_v)
    return (jnp.where(visited, vals, jnp.inf),
            jnp.where(visited, wins, INT_MAX), sched_n)


# ---------------------------------------------------------------------------
# multi-round fused megakernel
# ---------------------------------------------------------------------------



FUSED_ON_TPU = (
    "the fused multi-round megakernel (fused_rounds > 0 on the "
    "single-device blocked backend) does not compile for TPU: it keeps the "
    "whole edge slab and the O(V) state in VMEM at once, gathers by vector "
    "index and compacts its schedule with an nt x nt plane, none of which "
    "Mosaic lowers; use fused_rounds=0 (the gridded edge_relax kernel) or "
    "use_kernel=False for the jnp reference")


def _tile_pass(dist_src, paths_src, parent_src, src, dst, w, tdst, tfirst,
               lb, ub, n_out: int, *, block_v: int, tile_e: int, go,
               alt_lb=None, prune_bound=None):
    """One frontier-compacted pass over a whole edge slab (all buckets).

    Pure-value core shared by both fused kernel modes: computes the
    compacted tile schedule (in-kernel prefix-sum compaction, the
    broadcast-compare twin of :func:`schedule_tiles`'s scatter), then
    folds the scheduled tiles' scatter-min AND the logical counters into
    one loop.  ``dist_src``/``paths_src``/``parent_src`` span the slab's
    source-id range; ``src`` ids index that range directly (global for
    the single-device fused slab, shard-local for shard slabs), so the
    per-tile winner min is already the deterministic min-id tiebreak.
    ``go`` gates the tile loop (0 => schedule only, zero tiles run).

    With ``alt_lb`` (the per-vertex ALT bound over the full destination
    range) candidates with ``cand + alt_lb[dst] > prune_bound`` are
    dropped before the scatter-min; parent-excluded drops are counted so
    ``n_relax(unpruned) == n_relax(pruned) + n_pruned`` holds per round
    (mirroring :func:`repro.core.relax.alt_prune` — ``n_trav`` stays the
    in-window count, unaffected by pruning).

    Returns ``(val, win, n_trav, n_relax, n_pruned, sched_n)`` over
    ``n_out`` destinations; counters are exact (a tile outside the
    schedule has no frontier source with finite weight, so every edge in
    it fails the window test and contributes zero to every counter).
    """
    nt = tdst.shape[0]
    touched = (paths_src[src] > 0) & jnp.isfinite(w)
    active = touched.reshape(nt, tile_e).any(axis=1) | (tfirst > 0)
    pos = jnp.cumsum(active.astype(jnp.int32)) - 1
    sched_n = pos[nt - 1] + 1
    # prefix-sum compaction as a compare plane (no data-dependent writes
    # in-kernel): slot k holds the tile whose exclusive rank is k
    ksel = jax.lax.broadcasted_iota(jnp.int32, (nt, nt), 0)
    isel = jax.lax.broadcasted_iota(jnp.int32, (nt, nt), 1)
    hit = (pos[None, :] == ksel) & active[None, :]
    sched = jnp.min(jnp.where(hit, isel, nt), axis=1)

    def tile_body(k, carry):
        val, win, trav, rlx, prn = carry
        t = sched[k]
        b = tdst[t]
        lo = t * tile_e
        src_t = jax.lax.dynamic_slice(src, (lo,), (tile_e,))
        dst_t = jax.lax.dynamic_slice(dst, (lo,), (tile_e,))
        w_t = jax.lax.dynamic_slice(w, (lo,), (tile_e,))
        cand = dist_src[src_t] + w_t
        ok = (paths_src[src_t] > 0) & (cand >= lb) & (cand < ub)
        trav = trav + jnp.sum(ok.astype(jnp.int32))
        notpar = dst_t != parent_src[src_t]
        if alt_lb is not None:
            fail = cand + alt_lb[dst_t] > prune_bound
            prn = prn + jnp.sum((ok & notpar & fail).astype(jnp.int32))
            ok = ok & ~fail
        cand = jnp.where(ok, cand, jnp.inf)
        rlx = rlx + jnp.sum((ok & notpar).astype(jnp.int32))
        cols = b * block_v + jax.lax.broadcasted_iota(
            jnp.int32, (tile_e, block_v), 1)
        hit2 = dst_t[:, None] == cols
        plane = jnp.where(hit2, cand[:, None], jnp.inf)
        tile_min = jnp.min(plane, axis=0)
        winners = jnp.where(hit2 & ok[:, None] & (cand[:, None] <= tile_min),
                            src_t[:, None], INT_MAX)
        tile_win = jnp.min(winners, axis=0)
        off = b * block_v
        prev_v = jax.lax.dynamic_slice(val, (off,), (block_v,))
        prev_w = jax.lax.dynamic_slice(win, (off,), (block_v,))
        better = tile_min < prev_v
        tie = tile_min == prev_v
        val = jax.lax.dynamic_update_slice(
            val, jnp.minimum(prev_v, tile_min), (off,))
        win = jax.lax.dynamic_update_slice(
            win, jnp.where(better, tile_win,
                           jnp.where(tie, jnp.minimum(prev_w, tile_win),
                                     prev_w)), (off,))
        return val, win, trav, rlx, prn

    n_eff = jnp.where(go > 0, sched_n, 0)
    val0 = jnp.full((n_out,), jnp.inf, jnp.float32)
    win0 = jnp.full((n_out,), INT_MAX, jnp.int32)
    val, win, trav, rlx, prn = jax.lax.fori_loop(
        0, n_eff, tile_body,
        (val0, win0, jnp.int32(0), jnp.int32(0), jnp.int32(0)))
    return val, win, trav, rlx, prn, sched_n


def _fused_kernel(*refs, block_v: int, tile_e: int, fused_cap: int,
                  alt: bool = False):
    """Up to ``fused_cap`` windowed rounds, state resident in output refs.

    With ``alt`` the prefetch path carries ``lbub = [lb, ub, prune_ub,
    infl]`` plus the target id, and the prune bound is recomputed from
    the *resident* dist at every round start as
    ``min(prune_ub, dist[tgt] * infl)`` — the exact bound the unfused
    path computes between kernel invocations, which is what keeps the
    fused/unfused pruning decisions (and ``n_pruned``) bitwise-equal.
    """
    if alt:
        (lbub_ref, maxr_ref, tgt_ref, dist_in, parent_in, front_in,
         deg_ref, src_ref, dst_ref, w_ref, tdst_ref, tfirst_ref, alt_ref,
         dist_out, parent_out, front_out, cnt_ref) = refs
    else:
        (lbub_ref, maxr_ref, dist_in, parent_in, front_in, deg_ref,
         src_ref, dst_ref, w_ref, tdst_ref, tfirst_ref,
         dist_out, parent_out, front_out, cnt_ref) = refs
    dist_out[...] = dist_in[...]
    parent_out[...] = parent_in[...]
    front_out[...] = front_in[...]
    cnt_ref[...] = jnp.zeros_like(cnt_ref)
    lb = lbub_ref[0]
    ub = lbub_ref[1]
    max_r = maxr_ref[0]
    deg = deg_ref[...]
    src = src_ref[...]
    dst = dst_ref[...]
    w = w_ref[...]
    tdst = tdst_ref[...]
    tfirst = tfirst_ref[...]
    alt_lb = alt_ref[...] if alt else None
    n_out = deg.shape[0]

    def round_body(r, go):
        # rounds past the early exit are schedule-only no-ops (go=0)
        dist = dist_out[...]
        parent = parent_out[...]
        front = front_out[...]
        paths = ((front > 0) & ((dist <= 0.0) | (deg > 1))).astype(jnp.int32)
        bound = (jnp.minimum(lbub_ref[2], dist[tgt_ref[0]] * lbub_ref[3])
                 if alt else None)
        val, win, trav, rlx, prn, sched_n = _tile_pass(
            dist, paths, parent, src, dst, w, tdst, tfirst, lb, ub,
            n_out, block_v=block_v, tile_e=tile_e, go=go,
            alt_lb=alt_lb, prune_bound=bound)
        improved = val < dist
        any_imp = jnp.any(improved)

        @pl.when(go > 0)
        def _commit():
            dist_out[...] = jnp.where(improved, val, dist)
            parent_out[...] = jnp.where(improved, win, parent)
            front_out[...] = improved.astype(jnp.int32)
            cnt_ref[...] = cnt_ref[...] + jnp.stack([
                trav, rlx,
                jnp.sum(improved.astype(jnp.int32)),
                jnp.sum((improved & (deg > 1)).astype(jnp.int32)),
                jnp.any(front > 0).astype(jnp.int32),
                sched_n, jnp.int32(1), prn])

        return jnp.where(go > 0,
                         (any_imp & (r + 1 < max_r)).astype(jnp.int32),
                         jnp.int32(0))

    jax.lax.fori_loop(0, fused_cap, round_body, jnp.int32(1))


@functools.partial(jax.jit, static_argnames=("block_v", "tile_e",
                                             "fused_rounds", "interpret"))
def edge_relax_fused(dist, parent, frontier, deg, src, dst, w, tile_dst,
                     tile_first, lb, ub, alt_lb=None, prune_ub=None,
                     prune_infl=None, prune_tgt=None, *,
                     block_v: int = DEFAULT_BLOCK_V,
                     tile_e: int = DEFAULT_TILE_E, fused_rounds: int = 4,
                     interpret: Optional[bool] = None):
    """Run up to ``fused_rounds`` relaxation rounds in one invocation.

    ``dist``/``parent``/``frontier``/``deg`` span the padded vertex range
    ``[0, n_out)`` (source range == destination range — the single-device
    blocked layout); ``src``/``dst``/``w`` are the whole concatenated
    tile-aligned slab with *global* source ids, ``tile_dst``/``tile_first``
    its CSR-of-tiles index.  The invocation is clamped to one round while
    ``lb <= 0`` (the bootstrap step retightens ``ub`` between rounds).

    Returns ``(dist, parent, frontier, counts)`` after the last executed
    round; ``counts`` is the int32 ``FUSED_COUNTERS`` vector summed over
    executed rounds.
    """
    if not resolve_interpret(interpret):
        raise ConfigError(FUSED_ON_TPU)
    e = src.shape[0]
    if e % tile_e != 0 or e == 0:
        raise ValueError(f"slab length {e} is not tile-aligned "
                         f"(tile_e={tile_e})")
    if fused_rounds < 1:
        raise ValueError(f"fused_rounds must be >= 1, got {fused_rounds}")
    alt = alt_lb is not None
    scal = [jnp.float32(lb), jnp.float32(ub)]
    if alt:
        scal += [jnp.float32(prune_ub), jnp.float32(prune_infl)]
    lbub = jnp.stack(scal)
    # the bootstrap step tightens ub after every round — chaining rounds
    # in-kernel there would relax against a stale bound
    maxr = jnp.where(jnp.float32(lb) <= 0.0, 1, fused_rounds
                     ).astype(jnp.int32)
    n_out = dist.shape[0]
    nt = e // tile_e
    whole = lambda shape: pl.BlockSpec(shape, lambda i, *_: (0,))
    in_specs = ([whole((n_out,))] * 4 + [whole((e,))] * 3
                + [whole((nt,))] * 2)
    prefetch = [lbub, maxr[None]]
    operands = [dist, parent, frontier.astype(jnp.int32), deg,
                src, dst, w, tile_dst, tile_first.astype(jnp.int32)]
    if alt:
        prefetch.append(jnp.asarray(prune_tgt, jnp.int32)[None])
        in_specs.append(whole((n_out,)))
        operands.append(alt_lb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),   # lbub, maxr (+ tgt with ALT)
        grid=(1,),
        in_specs=in_specs,
        out_specs=(whole((n_out,)), whole((n_out,)), whole((n_out,)),
                   whole((8,))),
    )
    dist2, parent2, front2, cnt = pl.pallas_call(
        functools.partial(_fused_kernel, block_v=block_v, tile_e=tile_e,
                          fused_cap=fused_rounds, alt=alt),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((n_out,), jnp.float32),
                   jax.ShapeDtypeStruct((n_out,), jnp.int32),
                   jax.ShapeDtypeStruct((n_out,), jnp.int32),
                   jax.ShapeDtypeStruct((8,), jnp.int32)),
        interpret=True,
    )(*prefetch, *operands)
    return dist2, parent2, front2, cnt


@functools.partial(jax.jit, static_argnames=("block_v", "tile_e",
                                             "n_dst_blocks", "interpret"))
def edge_relax_partials(dist_src, paths_src, parent_src, src, dst, w,
                        tile_dst, tile_first, tile_order, lb, ub,
                        alt_lb=None, prune_bound=None, *,
                        block_v: int = DEFAULT_BLOCK_V,
                        tile_e: int = DEFAULT_TILE_E, n_dst_blocks: int = 1,
                        interpret: Optional[bool] = None):
    """One round of (min, winner) partials over a whole slab set.

    ``dist_src``/``paths_src``/``parent_src`` cover the slab's *local*
    source range; ``src`` ids index it directly (all of a shard's
    source-block slabs concatenated, ids offset by their block, so every
    tile's sources lie in one ``block_v`` source block).  The tiles run
    in one gridded kernel, scheduled in destination-block order across
    the slabs so each output block is visited in one consecutive run:
    ``tile_order`` [NT] lists the tiles sorted (stably) by ``tile_dst``,
    a per-layout constant built with the layout
    (:func:`~repro.core.distributed.shard_blocked`).
    Returns ``(val, win, counts)``: per-destination (min, winner)
    partials over ``n_dst_blocks * block_v`` — winners are local source
    ids, lift them with the shard's owner-block offset — and the int32
    ``PARTIAL_COUNTERS`` vector, counted in the kernel.
    """
    interpret = resolve_interpret(interpret)
    _check_compiled_geometry(block_v, tile_e, interpret)
    e = src.shape[0]
    if e % tile_e != 0 or e == 0:
        raise ValueError(f"slab length {e} is not tile-aligned "
                         f"(tile_e={tile_e})")
    nb = n_dst_blocks
    touched = (paths_src[src] > 0) & jnp.isfinite(w)
    active = _active_tiles(touched, tile_first, tile_e)
    sched, sched_n = _compact(active, tile_order)
    sched_dst = tile_dst[sched]
    # padding slots carry their slab's first source id, so a tile's first
    # slot names its source block
    sched_src = src[sched * tile_e] // block_v
    dsrc = jnp.where(paths_src > 0, dist_src, jnp.inf)
    alt = alt_lb is not None
    lbub = _lbub(lb, ub, *([prune_bound] if alt else []))
    val, win, cnt = _relax_tiles(
        dsrc, parent_src, src, dst, w, sched, sched_src, sched_dst, sched_n,
        lbub, alt_lb, src_block=block_v, block_v=block_v, tile_e=tile_e,
        n_out=nb * block_v, interpret=interpret)
    # only the scheduled tiles' destination blocks were written; the
    # forced first tiles cover every block that has edges
    step = jnp.arange(sched.shape[0], dtype=jnp.int32)
    visited = jnp.zeros((nb,), bool).at[
        jnp.where(step < sched_n, sched_dst, nb)].set(True, mode="drop")
    visited = jnp.repeat(visited, block_v)
    return (jnp.where(visited, val, jnp.inf),
            jnp.where(visited, win, INT_MAX), cnt.at[2].set(sched_n))
