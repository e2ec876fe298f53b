"""Pluggable relaxation backends for the EIC engines (paper Algo 2 l.8-17).

The windowed edge relaxation is the algorithm's inner loop and the only
part that differs between execution strategies (dense ``segment_min``,
blocked Pallas kernels, per-shard relaxation under ``shard_map``).  This
module owns that hot path:

* the **backend interface** — ``relax_window(layout, dist, parent,
  frontier, lb, ub) -> (new_dist, new_parent, RoundMetrics)`` — with a
  registry (:func:`get_backend` / :func:`available_backends`) so engines,
  benchmarks and services select implementations by name;
* the **shared relaxation primitives** (leaf pruning, windowed candidate
  generation, deterministic segment-min + winner recovery, update
  application, partial combination) that every engine builds from — the
  distributed engines in ``core/distributed.py`` compose these with their
  collectives instead of duplicating the relax logic.

Registered backends:

``segment_min``
    The dense flat-edge-list path (extracted from the original
    ``sssp._relax_round``): one masked ``segment_min`` over all edges plus
    a min-source winner pass.  Layout = the ``DeviceGraph`` itself.  Its
    compacted round (``relax_compact``, the unbatched single-tier solve
    and repair) relaxes only the frontier's in-window slots, found by a
    binary search of each weight-sorted row, and falls back to the
    dense round when they overflow caps derived from the graph's shapes.

``blocked_pallas`` (alias ``blocked``)
    The TPU hot path: a :class:`~repro.core.graph.BlockedGraph` layout
    (edges bucketed by (src block x dst block), every bucket tile-aligned
    with a CSR-of-tiles index) drives the ``kernels/edge_relax`` Pallas
    kernel once per source block over a *ragged* tile grid: each
    destination block iterates only its own tile range, and a
    frontier-compaction prepass skips tiles with no frontier source this
    round entirely.  Per-source-block (min, winner) partials are combined
    with the same deterministic min/min-src rule.  The kernel is
    interpreted on CPU and compiled on a TPU (``interpret`` resolves from
    the platform).  The same per-shard machinery
    (:func:`blocked_partials`) backs ``core/distributed.py``'s
    ``backend="blocked"`` inside ``shard_map``.

Determinism note: every backend resolves ties toward the smallest source
id, so ``dist``/``parent`` (and the logical traversal metrics) are
bitwise-identical across backends — the parity tests in
``tests/test_relax_backends.py`` assert exactly that.  The *physical*
tile counters (``n_tiles_scanned`` / ``n_tiles_dense``) are
layout-specific and excluded from cross-backend parity.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .config import ConfigError
from .graph import DeviceGraph, BlockedGraph, build_blocked
from ..kernels.edge_relax.ops import relax_bucket, relax_fused, relax_partials
from ..obs import profiling

INT_MAX = jnp.iinfo(jnp.int32).max
INF = jnp.float32(jnp.inf)


class RoundMetrics(NamedTuple):
    """Per-round relaxation outcome.

    The logical counters (trav/relax/updates/extended) are identical
    across backends; the tile/invocation counters are *physical* — they
    describe the blocked layout's work (0 for layouts without tiles) and
    are excluded from cross-backend parity.
    """
    improved: jnp.ndarray    # [N] bool — vertices whose dist improved
    n_trav: jnp.ndarray      # scalar int32 — in-window edge touches (push)
    n_relax: jnp.ndarray     # scalar int32 — relaxations attempted
    n_updates: jnp.ndarray   # scalar int32 — successful dist improvements
    n_extended: jnp.ndarray  # scalar int32 — non-leaf dist improvements
    n_pruned: jnp.ndarray    # scalar int32 — candidates cut by the ALT bound
    # physical counters are f32: the dense comparator accumulates
    # n_dst_blocks * n_tiles per round, which overflows int32 on large
    # graphs (and x64 is disabled, so int64 is unavailable)
    n_tiles_scanned: jnp.ndarray  # scalar f32 — edge tiles actually run
    n_tiles_dense: jnp.ndarray    # scalar f32 — dense-grid tile cost
    n_invocations: jnp.ndarray    # scalar f32 — kernel launches (sync units)
    n_compact: jnp.ndarray        # scalar f32 — 1 if the compacted round ran


# ---------------------------------------------------------------------------
# shared relaxation primitives
# ---------------------------------------------------------------------------

def nonleaf(deg: jnp.ndarray, directed: bool = False) -> jnp.ndarray:
    """Vertices whose pop can relax anything.  Undirected, a vertex of
    degree 1 is a leaf: its one edge leads back to its parent, which it
    cannot improve.  Directed, its one arc may lead onward, so only a
    vertex with no out-arc is a leaf.  ``directed`` is static."""
    return deg > (0 if directed else 1)


def leaf_pruned(frontier: jnp.ndarray, dist: jnp.ndarray,
                deg: jnp.ndarray, directed: bool = False) -> jnp.ndarray:
    """Algo 2 l.8: paths reaching a leaf are never extended."""
    return frontier & ((dist <= 0.0) | nonleaf(deg, directed))


def edge_candidates(d_src, f_src, p_src, dst, w, lb, ub):
    """Algo 2 l.10-11: windowed candidate lengths over gathered edge values.

    ``d_src``/``f_src``/``p_src`` are dist/frontier/parent gathered at each
    edge's source.  Returns ``(cand, in_window, active)`` where ``cand`` is
    +inf outside the active set; ``active`` additionally excludes the
    relaxation back along the parent edge (which can never improve).
    """
    cand_len = d_src + w
    in_window = f_src & (cand_len >= lb) & (cand_len < ub)
    active = in_window & (dst != p_src)
    return jnp.where(active, cand_len, INF), in_window, active


def segment_partial_min(cand, seg, num_segments: int):
    """Per-destination min of candidates (a shard's local partial)."""
    return jax.ops.segment_min(cand, seg, num_segments=num_segments)


def winner_partial(cand, mask, ids, seg, best, num_segments: int):
    """Deterministic winner recovery: min ``ids`` among candidates that
    achieve ``best`` at their segment (masked; INT_MAX where none)."""
    win = jnp.where(mask & (cand <= best[seg]), ids, INT_MAX)
    return jax.ops.segment_min(win, seg, num_segments=num_segments)


def segment_min_with_winner(cand, mask, ids, seg, num_segments: int):
    """The fused (min, argmin-by-min-id) segment reduction."""
    best = segment_partial_min(cand, seg, num_segments)
    return best, winner_partial(cand, mask, ids, seg, best, num_segments)


def apply_updates(dist, parent, best, winner, gate=None):
    """Commit improvements: ``dist``/``parent`` where ``best < dist``
    (optionally gated by an extra per-vertex mask)."""
    improved = best < dist
    if gate is not None:
        improved = improved & gate
    return (jnp.where(improved, best, dist),
            jnp.where(improved, winner, parent), improved)


def combine_block_partials(vals, wins):
    """Combine stacked (min, winner) partials over the leading axis with
    the deterministic min-value / min-id-on-tie rule."""
    best = jnp.min(vals, axis=0)
    winner = jnp.min(jnp.where(vals <= best[None, :], wins, INT_MAX),
                     axis=0)
    return best, winner


def window_frontier(dist, st, lb, ub, max_w):
    """Function 1's frontier: the push band [max(0, lb - maxW), st] of
    settled vertices whose edges may reach into the window, plus the
    window occupants themselves."""
    lb0 = jnp.maximum(0.0, lb - max_w)
    return ((dist >= lb0) & (dist <= st)) | ((dist >= lb) & (dist < ub))


def settled_mask(dist, lb):
    """Vertices whose distance is final under the stepping invariant.

    Every vertex with ``dist < lb`` is settled: all shorter paths were
    relaxed in earlier windows, and any pending candidate has length
    >= lb.  This is the predicate the early-exit query goals (p2p /
    distance-bounded / k-nearest in :mod:`repro.core.sssp`) test against.
    """
    return dist < lb


# ---------------------------------------------------------------------------
# ALT (A*, landmarks, triangle inequality) goal-directed pruning primitives
# ---------------------------------------------------------------------------
#
# With per-landmark distance vectors D[l, v] = d(L_l, v), the triangle
# inequality gives an admissible lower bound on the remaining distance
# v -> t:  d(L,t) <= d(L,v) + d(v,t)  =>  d(L,t) - d(L,v) <= d(v,t)
# (valid on directed graphs); on symmetric graphs the reverse difference
# d(L,v) - d(L,t) <= d(t,v) = d(v,t) holds too, so |.| applies.  A p2p
# candidate with dist[v] + w + lb[v] provably above the best known s->t
# length can never lie on an improving s->t path and is dropped inside
# the relaxation.
#
# Exactness under f32: the engine's committed distances, the landmark
# vectors, and the prune bound are all independently rounded path sums,
# so the raw triangle inequality can be violated by accumulated rounding
# even though it holds in exact arithmetic.  Both sides therefore carry
# a margin derived from the worst-case relative error of a length-H f32
# nonneg sum (H = hop bound from the landmark BFS, delta ~ H * 2^-24):
# the per-vertex bound is *deflated* by delta * (D[l,t] + D[l,v]) — an
# absolute slack covering the error of both landmark sums — and the
# prune bound is *inflated* by (1 + 4 delta).  Every candidate on the
# engine's own returned shortest path then survives pruning, which is
# what keeps pruned d(s,t)/parent chains bitwise-identical to the
# unpruned solve (the gate tests in tests/test_alt_p2p.py).

def alt_lower_bounds(D, t, delta, sym):
    """Admissible per-vertex lower bounds ``lb[v] <~ d(v, t)``.

    ``D`` is the ``[L, N]`` f32 landmark distance matrix, ``t`` the
    target id, ``delta`` the f32 rounding-slack factor and ``sym`` a
    traced 0/1 f32 flag (1 => the graph is symmetric and the reverse
    difference is admissible too).  Unreachable pairs resolve exactly:
    both-infinite differences contribute 0; a one-sided infinity means v
    and t lie in different components of the landmark's reach, where an
    infinite bound is correct.
    """
    Dt = D[:, t][:, None]                      # [L, 1]
    fwd = Dt - D                               # d(L,t) - d(L,v)
    rev = jnp.where(sym > 0, D - Dt, -INF)
    diff = jnp.maximum(fwd, rev)
    # deflate finite bounds by the accumulated-rounding slack; infinite
    # bounds stay infinite (different components), nan (inf - inf, both
    # unreachable from L) carries no information -> 0
    adj = jnp.where(jnp.isinf(diff), diff, diff - delta * (D + Dt))
    adj = jnp.where(jnp.isnan(adj), 0.0, adj)
    return jnp.max(jnp.maximum(adj, 0.0), axis=0)


def alt_seed_ub(D, source, t, infl, sym):
    """Landmark-seeded upper bound on d(source, t) (symmetric graphs):
    ``min_l d(L,s) + d(L,t)``, inflated by ``infl`` so it dominates the
    engine's own f32 path sum.  +inf when the graph is not symmetric
    (d(s,L) is unknown there) or no landmark reaches both endpoints."""
    seed = jnp.min(D[:, source] + D[:, t]) * infl
    return jnp.where(sym > 0, seed, INF)


def alt_prune(cand, active, lb_dst, prune_bound):
    """Split ``active`` candidates by the ALT test: returns
    ``(kept, pruned)`` masks where pruned candidates satisfy
    ``cand + lb[dst] > prune_bound`` (cand is +inf outside ``active``,
    so inactive lanes land in neither)."""
    pruned = active & (cand + lb_dst > prune_bound)
    return active & ~pruned, pruned


class AltData(NamedTuple):
    """The traced ALT operand bundle a p2p solve carries through ``jit``.

    ``D`` is the ``[L, N]`` f32 landmark distance matrix, ``delta`` the
    scalar f32 rounding-slack factor (``2^-24 * (2 H + 64)`` for hop
    bound ``H``) and ``sym`` a scalar f32 0/1 flag (1 => the graph is
    symmetric, enabling the reverse difference and the landmark-seeded
    upper bound).  Built by :class:`repro.core.landmarks.LandmarkSet`;
    a plain pytree so presence/absence is the only retrace axis.
    """
    D: jnp.ndarray
    delta: jnp.ndarray
    sym: jnp.ndarray


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RelaxBackend:
    """A pluggable implementation of the windowed relaxation hot path.

    ``prepare(graph, **opts)`` builds the backend's layout pytree once per
    graph (host-side, outside ``jit``); ``relax_window(layout, dist,
    parent, frontier, lb, ub)`` executes one synchronized round.
    ``relax_compact``, where a backend has one, is the same round with a
    keyword ``caps`` (:func:`compact_caps`) that works in proportion to
    the frontier; a ``vmap`` would run every one of its branches, so
    batched solves keep ``relax_window``.
    """
    name: str
    prepare: Callable[..., Any]
    relax_window: Callable[..., Any]
    relax_compact: Optional[Callable[..., Any]] = None


_REGISTRY: dict = {}


def register_backend(backend: RelaxBackend, aliases=()) -> RelaxBackend:
    # annotate layout builds at the source: every prepare() — from the
    # facade, the serving registry, or direct engine calls — shows up as
    # one repro:relax_prepare:<name> span in jax.profiler captures
    prepare = backend.prepare
    scope = f"repro:relax_prepare:{backend.name}"

    def profiled_prepare(g, **opts):
        with profiling.annotate(scope):
            return prepare(g, **opts)

    backend = dataclasses.replace(backend, prepare=profiled_prepare)
    _REGISTRY[backend.name] = backend
    for alias in aliases:
        _REGISTRY[alias] = backend
    return backend


def available_backends() -> tuple:
    """Canonical backend names (aliases resolve but are not listed)."""
    return tuple(sorted({b.name for b in _REGISTRY.values()}))


def get_backend(name) -> RelaxBackend:
    if isinstance(name, RelaxBackend):
        return name
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown relax backend {name!r}; available: "
            f"{available_backends()}") from None


# ---------------------------------------------------------------------------
# backend: segment_min (dense flat edge list)
# ---------------------------------------------------------------------------

def _segment_min_prepare(g: DeviceGraph, **_opts) -> DeviceGraph:
    return g            # the flat edge list is its own layout


def _windowed(d_src, f_src, p_src, dst, w, lb, ub, alt_lb, prune_bound):
    """:func:`edge_candidates` with the ALT cut applied: ``(cand,
    in_window, active, pruned)`` (``pruned`` is None without ALT)."""
    cand, in_window, active = edge_candidates(d_src, f_src, p_src, dst, w,
                                              lb, ub)
    pruned = None
    if alt_lb is not None:
        active, pruned = alt_prune(cand, active, alt_lb[dst], prune_bound)
        cand = jnp.where(active, cand, INF)
    return cand, in_window, active, pruned


def _settle(g: DeviceGraph, dist, parent, src, dst, cand, in_window, active,
            pruned, n_compact: float):
    """Reduce a round's candidates per destination, commit, and count."""
    with profiling.phase("round.reduce"):
        best, winner = segment_min_with_winner(cand, active, src, dst, g.n)
    with profiling.phase("round.apply"):
        new_dist, new_parent, improved = apply_updates(dist, parent, best,
                                                       winner)
    with profiling.phase("round.count"):
        rm = RoundMetrics(
            improved=improved,
            n_trav=jnp.sum(in_window.astype(jnp.int32)),
            n_relax=jnp.sum(active.astype(jnp.int32)),
            n_updates=jnp.sum(improved.astype(jnp.int32)),
            n_extended=jnp.sum((improved & nonleaf(g.deg, g.directed))
                               .astype(jnp.int32)),
            n_pruned=(jnp.int32(0) if pruned is None
                      else jnp.sum(pruned.astype(jnp.int32))),
            n_tiles_scanned=jnp.float32(0),
            n_tiles_dense=jnp.float32(0),
            n_invocations=jnp.float32(0),
            n_compact=jnp.float32(n_compact))
    return new_dist, new_parent, rm


def _segment_min_relax(g: DeviceGraph, dist, parent, frontier, lb, ub,
                       alt_lb=None, prune_bound=None):
    with profiling.phase("round.gather"):
        paths = leaf_pruned(frontier, dist, g.deg, g.directed)
        cand, in_window, active, pruned = _windowed(
            dist[g.src], paths[g.src], parent[g.src], g.dst, g.w, lb, ub,
            alt_lb, prune_bound)
    return _settle(g, dist, parent, g.src, g.dst, cand, in_window, active,
                   pruned, 0.0)


def compact_caps(n: int, m: int) -> tuple:
    """``(kv, c)`` of a compacted round on a graph of ``n`` vertices and
    ``m`` edge slots: it holds up to ``kv = n / 4`` frontier vertices and
    ``c`` in-window slots, the least power of two of at least ``m / 64``.
    """
    return max(n // 4, 1), 1 << max(-(-m // 64) - 1, 0).bit_length()


# A compacted round runs at the smallest of two sizes that holds it: the
# caps, or the caps over RUNG, since most rounds' frontiers and windows
# are far below the caps and the round's work scales with its size.
RUNG = 16


def _rungs(cap: int) -> list:
    return sorted({max(cap // RUNG, 1), cap})


def _fit(size, caps, branches, fallback):
    """Run the first of ``branches`` whose entry of ``caps`` (ascending)
    holds ``size``, else ``fallback``."""
    i = sum((size > cap).astype(jnp.int32) for cap in caps)
    return jax.lax.switch(i, list(branches) + [fallback])


def _first(pred, lo, hi, steps: int):
    """The first index in ``[lo, hi)`` where the monotone ``pred`` holds,
    or ``hi``: ``steps`` halvings, enough for the longest range."""
    def step(_, c):
        lo, hi = c
        mid = (lo + hi) // 2
        ok = pred(mid)
        open_ = lo < hi
        return (jnp.where(open_ & ~ok, mid + 1, lo),
                jnp.where(open_ & ok, mid, hi))
    return jax.lax.fori_loop(0, steps, step, (lo, hi))[0]


def compact_frontier(mask, kv: int):
    """The first ``kv`` ids where ``mask`` holds, in order, 0 past the
    last.  A small ``kv`` binary-searches the prefix count for each rank
    (``kv log n`` reads); a large one writes each vertex to its rank."""
    n = mask.shape[0]
    cs = jnp.cumsum(mask.astype(jnp.int32))
    rank = jnp.arange(kv, dtype=jnp.int32)
    if kv * n.bit_length() >= n:
        return jnp.zeros((kv,), jnp.int32).at[jnp.where(mask, cs - 1, kv)] \
            .set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    at = _first(lambda i: cs.at[i].get(mode="clip") > rank,
                jnp.zeros_like(rank), jnp.full_like(rank, n), n.bit_length())
    return jnp.where(rank < cs[-1], at, 0)


def row_windows(g: DeviceGraph, d, verts, live, lb, ub):
    """``(lo, hi)``: the slots of each vertex's row whose candidate
    ``d + w`` lies in ``[lb, ub)``, as ``[lo, hi)``.

    Rows are sorted by weight (``build_csr``), and for a fixed ``d`` the
    f32 sum ``d + w`` that :func:`edge_candidates` forms is monotone in
    ``w``, so the window is one run of the row, bounded by a binary
    search for the first sum ``>= lb`` and the first ``>= ub``.  A search
    runs as many steps as the longest row among ``live`` vertices needs;
    other vertices get an empty range.  The search for ``lb`` runs only
    when some row's first sum lies below it (the push band at a step's
    start): every other row is in the window from its first slot.
    """
    r0 = g.row_ptr[verts]
    r1 = jnp.where(live, g.row_ptr[verts + 1], r0)
    steps = 32 - jax.lax.clz(jnp.max(r1 - r0))

    def first_at_least(bound):
        return _first(lambda e: d + g.w.at[e].get(mode="clip") >= bound,
                      r0, r1, steps)

    below = (r0 < r1) & (d + g.w.at[r0].get(mode="clip") < lb)
    lo = jax.lax.cond(jnp.any(below), lambda: first_at_least(lb),
                      lambda: r0)
    return lo, jnp.maximum(first_at_least(ub), lo)


def expand_ranges(lo, hi, c: int):
    """Lay the ranges ``[lo, hi)`` end to end over ``c`` slots: ``(owner,
    slot, valid)``, where slot ``j`` holds edge ``slot[j]`` of range
    ``owner[j]`` while ``valid[j]``."""
    length = hi - lo
    end = jnp.cumsum(length)
    off = end - length
    ids = jnp.arange(lo.shape[0], dtype=jnp.int32)
    owner = jnp.zeros((c,), jnp.int32).at[
        jnp.where(length > 0, off, c)].set(ids, mode="drop")
    owner = jax.lax.cummax(owner)
    j = jnp.arange(c, dtype=jnp.int32)
    valid = j < end[-1]
    return owner, jnp.where(valid, (lo - off)[owner] + j, 0), valid


def _segment_min_compact_relax(g: DeviceGraph, dist, parent, frontier, lb,
                               ub, alt_lb=None, prune_bound=None, *,
                               caps=None):
    """The ``segment_min`` round over the frontier's in-window slots only.

    The leaf-pruned frontier is compacted to at most ``kv`` vertices,
    each row's in-window run is found by :func:`row_windows` and the
    runs are laid over ``c`` slots (``caps``, default
    :func:`compact_caps`; each at the smaller :data:`RUNG` size where
    the round fits it).  The candidates are the ones the dense round
    leaves finite, so ``dist``, ``parent``, the frontier and every
    logical counter come out bit for bit as :func:`_segment_min_relax`'s;
    a round with more vertices or slots than the caps runs that dense
    round instead.
    """
    kv, c = compact_caps(g.n, g.m) if caps is None else caps
    with profiling.phase("round.compact"):
        paths = leaf_pruned(frontier, dist, g.deg, g.directed)
        count = jnp.sum(paths.astype(jnp.int32))

    def dense():
        return _segment_min_relax(g, dist, parent, frontier, lb, ub,
                                  alt_lb, prune_bound)

    def over_slots(verts, d, lo, hi, cc):
        with profiling.phase("round.compact"):
            owner, slot, valid = expand_ranges(lo, hi, cc)
        with profiling.phase("round.gather"):
            src = verts[owner]
            dst = g.dst[slot]
            cand, in_window, active, pruned = _windowed(
                d[owner], valid, parent[src], dst, g.w[slot], lb, ub,
                alt_lb, prune_bound)
        return _settle(g, dist, parent, src, dst, cand, in_window, active,
                       pruned, 1.0)

    def over_vertices(kvv):
        with profiling.phase("round.compact"):
            verts = compact_frontier(paths, kvv)
            live = jnp.arange(kvv, dtype=jnp.int32) < count
            d = dist[verts]
            lo, hi = row_windows(g, d, verts, live, lb, ub)
            total = jnp.sum(hi - lo)
        return _fit(total, _rungs(c),
                    [partial(over_slots, verts, d, lo, hi, cc)
                     for cc in _rungs(c)], dense)

    return _fit(count, _rungs(kv),
                [partial(over_vertices, k) for k in _rungs(kv)], dense)


SEGMENT_MIN = register_backend(RelaxBackend(
    name="segment_min", prepare=_segment_min_prepare,
    relax_window=_segment_min_relax,
    relax_compact=_segment_min_compact_relax))


# ---------------------------------------------------------------------------
# backend: blocked_pallas (BlockedGraph layout -> edge_relax kernel)
# ---------------------------------------------------------------------------

def _blocked_prepare(g, **opts) -> BlockedGraph:
    return build_blocked(g, **opts)


def _combine_bucket_partials(slab_of, n_src_blocks, dist_src, paths_src,
                             src_base, lb, ub, *, block_v, n_dst_blocks,
                             tile_e, use_kernel, interpret, alt_lb=None,
                             prune_bound=None):
    """Shared core of the blocked partial computations: relax every
    source block's bucketed slab, lift winners to global source ids
    (deterministic INT_MAX-preserving offset), combine deterministically.
    ``slab_of(s)`` returns source block ``s``'s ``(src_local, dst, w,
    tile_dst, tile_first, bucket_nonempty)`` arrays."""
    paths_i8 = paths_src.astype(jnp.int8)
    vals, wins = [], []
    n_tiles = jnp.int32(0)
    for s in range(n_src_blocks):
        lo = s * block_v
        best_sb, win_local, nt = relax_bucket(
            dist_src[lo:lo + block_v], paths_i8[lo:lo + block_v],
            *slab_of(s), lb, ub, block_v=block_v,
            n_dst_blocks=n_dst_blocks, tile_e=tile_e,
            use_kernel=use_kernel, interpret=interpret, alt_lb=alt_lb,
            prune_bound=prune_bound)
        vals.append(best_sb)
        wins.append(jnp.where(win_local == INT_MAX, INT_MAX,
                              win_local + (src_base + lo)))
        n_tiles = n_tiles + nt
    best, winner = combine_block_partials(jnp.stack(vals), jnp.stack(wins))
    return best, winner, n_tiles


def blocked_partials(bg: BlockedGraph, dist_src, paths_src, lb, ub,
                     alt_lb=None, prune_bound=None):
    """Per-destination (min, winner) partials of one blocked layout.

    ``dist_src``/``paths_src`` cover the layout's *source* range
    ``[src_base, src_base + n_blocks * block_v)`` (the full padded graph
    for ``build_blocked`` layouts, the owner block for
    :func:`~repro.core.graph.slice_for_shard` slabs).  Returns ``(best,
    winner, n_tiles)`` over the global ``n_out`` destination range —
    winners are *global* source ids (``src_base`` applied), so shard
    partials feed the distributed exchange unchanged and single-device
    partials feed :func:`apply_updates` directly.
    """
    return _combine_bucket_partials(
        lambda s: bg.slabs[s], bg.n_blocks, dist_src, paths_src,
        bg.src_base, lb, ub, block_v=bg.block_v,
        n_dst_blocks=bg.n_dst_blocks, tile_e=bg.tile_e,
        use_kernel=bg.use_kernel, interpret=bg.interpret, alt_lb=alt_lb,
        prune_bound=prune_bound)


def blocked_shard_partials(src_local, dst, w, tile_dst, tile_first,
                           bucket_nonempty, dist_src, paths_src, src_base,
                           lb, ub, *, block_v: int, n_dst_blocks: int,
                           tile_e: int, use_kernel: bool, interpret: bool,
                           alt_lb=None, prune_bound=None):
    """`shard_map` twin of :func:`blocked_partials`.

    Same computation over one shard's *stacked* uniform slabs
    (``src_local``/``dst``/``w`` are ``[S, NT*tile_e]``,
    ``tile_dst``/``tile_first`` ``[S, NT]``, ``bucket_nonempty``
    ``[S, n_dst_blocks]`` — shapes identical across shards, a shard_map
    requirement) with a *traced* ``src_base`` (the shard's owner-block
    offset).  ``dist_src``/``paths_src`` are the shard's local source
    slice.  Returns global-id ``(best, winner, n_tiles)`` over the
    ``n_dst_blocks * block_v`` destination range, ready for the engines'
    collective merge.
    """
    return _combine_bucket_partials(
        lambda s: (src_local[s], dst[s], w[s], tile_dst[s], tile_first[s],
                   bucket_nonempty[s]),
        src_local.shape[0], dist_src, paths_src, src_base, lb, ub,
        block_v=block_v, n_dst_blocks=n_dst_blocks, tile_e=tile_e,
        use_kernel=use_kernel, interpret=interpret, alt_lb=alt_lb,
        prune_bound=prune_bound)


def _blocked_relax(bg: BlockedGraph, dist, parent, frontier, lb, ub,
                   alt_lb=None, prune_bound=None):
    bv = bg.block_v
    pad = bg.n_out - dist.shape[0]
    dist_p = jnp.pad(dist, (0, pad), constant_values=jnp.inf)
    parent_p = jnp.pad(parent, (0, pad), constant_values=-1)
    frontier_p = jnp.pad(frontier, (0, pad))
    paths = leaf_pruned(frontier_p, dist_p, bg.deg, bg.directed)
    alt_p = None if alt_lb is None else jnp.pad(
        alt_lb, (0, bg.n_out - alt_lb.shape[0]), constant_values=jnp.inf)

    best, winner, n_tiles = blocked_partials(bg, dist_p, paths, lb, ub,
                                             alt_p, prune_bound)

    # Traversal counters are cheap jnp reductions over the slabs (the
    # kernel owns only the scatter-min); the parent-edge exclusion in
    # `active` cannot change the kernel's min/winner — relaxing back
    # along the parent edge never improves the parent's dist.
    n_trav = jnp.int32(0)
    n_relax = jnp.int32(0)
    n_pruned = jnp.int32(0)
    for sb, slab in enumerate(bg.slabs):
        src_g = slab.src_local + sb * bv
        cand, in_window, active = edge_candidates(
            dist_p[src_g], paths[src_g], parent_p[src_g], slab.dst,
            slab.w, lb, ub)
        if alt_p is not None:
            active, pruned = alt_prune(cand, active, alt_p[slab.dst],
                                       prune_bound)
            n_pruned = n_pruned + jnp.sum(pruned.astype(jnp.int32))
        n_trav = n_trav + jnp.sum(in_window.astype(jnp.int32))
        n_relax = n_relax + jnp.sum(active.astype(jnp.int32))

    new_dist, new_parent, improved = apply_updates(dist_p, parent_p, best,
                                                   winner)
    n = bg.n
    improved = improved[:n]
    rm = RoundMetrics(
        improved=improved,
        n_trav=n_trav,
        n_relax=n_relax,
        n_updates=jnp.sum(improved.astype(jnp.int32)),
        n_extended=jnp.sum((improved & nonleaf(bg.deg[:n], bg.directed))
                           .astype(jnp.int32)),
        n_pruned=n_pruned,
        n_tiles_scanned=n_tiles.astype(jnp.float32),
        n_tiles_dense=jnp.float32(bg.dense_grid_tiles),
        n_invocations=jnp.float32(bg.n_blocks),
        n_compact=jnp.float32(0))
    return new_dist[:n], new_parent[:n], rm


BLOCKED_PALLAS = register_backend(RelaxBackend(
    name="blocked_pallas", prepare=_blocked_prepare,
    relax_window=_blocked_relax), aliases=("blocked",))


# ---------------------------------------------------------------------------
# fused megakernel entry points (multi-round single-device / whole-shard
# partials — see kernels/edge_relax/edge_relax.py for the kernel contract)
# ---------------------------------------------------------------------------

class FusedSlab(NamedTuple):
    """A :class:`~repro.core.graph.BlockedGraph`'s per-source-block slabs
    concatenated into one tile-aligned slab with *global* source ids —
    the operand layout of the fused megakernel.  Built once per solve
    (outside the round loop); tile indices stay dst-sorted within each
    source block, which is all the scheduled scatter-min requires."""
    src: jnp.ndarray          # [sum NT * tile_e] global source ids
    dst: jnp.ndarray          # [sum NT * tile_e] global destination ids
    w: jnp.ndarray            # [sum NT * tile_e] weights (+inf padding)
    tile_dst: jnp.ndarray     # [sum NT] per-tile destination block
    tile_first: jnp.ndarray   # [sum NT] forced first tile per bucket


def fused_slab(bg: BlockedGraph) -> FusedSlab:
    """Concatenate a blocked layout's slabs for the fused megakernel."""
    bv = bg.block_v
    return FusedSlab(
        src=jnp.concatenate([s.src_local + i * bv
                             for i, s in enumerate(bg.slabs)]),
        dst=jnp.concatenate([s.dst for s in bg.slabs]),
        w=jnp.concatenate([s.w for s in bg.slabs]),
        tile_dst=jnp.concatenate([s.tile_dst for s in bg.slabs]),
        tile_first=jnp.concatenate([s.tile_first for s in bg.slabs]))


def blocked_fused_rounds(bg: BlockedGraph, fs: FusedSlab, dist, parent,
                         frontier, lb, ub, *, fused_rounds: int,
                         alt_lb=None, prune_ub=None, prune_infl=None,
                         prune_tgt=None):
    """Up to ``fused_rounds`` relaxation rounds in one kernel invocation.

    The fused twin of calling :func:`_blocked_relax` once per round:
    bitwise-identical dist/parent/frontier and logical counters, but the
    state stays resident in the kernel across rounds and the counters
    are folded into the scheduled tile pass (no separate O(E) metrics
    pass).  Returns ``(dist, parent, frontier, counts)`` over the
    *unpadded* vertex range; ``counts`` is the kernel's int32
    ``FUSED_COUNTERS`` vector.

    With ``alt_lb`` (ALT p2p pruning) the kernel recomputes the prune
    bound at every in-kernel round start as
    ``min(prune_ub, dist[prune_tgt] * prune_infl)`` — exactly what the
    unfused path computes per round — so fused and unfused pruning
    decisions (and the ``n_pruned`` counter) stay bitwise-identical.

    The kernel's leaf rule is the undirected one, so a directed layout
    raises :class:`~repro.core.config.ConfigError`.
    """
    if bg.directed:
        raise ConfigError("fused_rounds runs the undirected leaf rule in "
                          "its kernel and cannot solve a directed graph; "
                          "drop fused_rounds")
    if bg.n_pad != bg.n_out or bg.src_base != 0:
        raise ValueError(
            "the fused megakernel needs a whole-graph blocked layout "
            f"(source range == destination range); got n_pad={bg.n_pad}, "
            f"n_out={bg.n_out}, src_base={bg.src_base}")
    n = bg.n
    pad = bg.n_out - dist.shape[0]
    dist_p = jnp.pad(dist, (0, pad), constant_values=jnp.inf)
    parent_p = jnp.pad(parent, (0, pad), constant_values=-1)
    frontier_p = jnp.pad(frontier, (0, pad))
    alt_p = None if alt_lb is None else jnp.pad(
        alt_lb, (0, bg.n_out - alt_lb.shape[0]), constant_values=jnp.inf)
    dist2, parent2, front2, cnt = relax_fused(
        dist_p, parent_p, frontier_p, bg.deg, fs.src, fs.dst, fs.w,
        fs.tile_dst, fs.tile_first, lb, ub, block_v=bg.block_v,
        tile_e=bg.tile_e, fused_rounds=fused_rounds,
        use_kernel=bg.use_kernel, interpret=bg.interpret, alt_lb=alt_p,
        prune_ub=prune_ub, prune_infl=prune_infl, prune_tgt=prune_tgt)
    return dist2[:n], parent2[:n], front2[:n] > 0, cnt


def blocked_shard_partials_fused(src_local, dst, w, tile_dst, tile_first,
                                 tile_order, dist_src, paths_src, parent_src,
                                 src_base,
                                 lb, ub, *, block_v: int, n_dst_blocks: int,
                                 tile_e: int, use_kernel: bool,
                                 interpret: bool, alt_lb=None,
                                 prune_bound=None):
    """Whole-shard fused twin of :func:`blocked_shard_partials`.

    One kernel invocation relaxes ALL of a shard's stacked slabs
    (``src_local``/``dst``/``w`` ``[S, NT*tile_e]``,
    ``tile_dst``/``tile_first`` ``[S, NT]``, ``tile_order`` ``[S*NT]``
    the destination-major schedule order) against the shard's local
    ``dist_src``/``paths_src``/``parent_src`` slice, folding ``n_trav``/
    ``n_relax``/tile counts into the scheduled tile pass — replacing one
    launch per source block plus the flat O(E) metrics pass.  Returns
    ``(best, winner, n_tiles, n_trav, n_relax, n_pruned)`` with *global*
    winner ids (``src_base`` applied, INT_MAX preserved).
    """
    n_sb = src_local.shape[0]
    offs = (jnp.arange(n_sb, dtype=jnp.int32) * block_v)[:, None]
    best, win_local, cnt = relax_partials(
        dist_src, paths_src, parent_src,
        (src_local + offs).reshape(-1), dst.reshape(-1), w.reshape(-1),
        tile_dst.reshape(-1), tile_first.reshape(-1), tile_order, lb, ub,
        block_v=block_v, tile_e=tile_e, n_dst_blocks=n_dst_blocks,
        use_kernel=use_kernel, interpret=interpret, alt_lb=alt_lb,
        prune_bound=prune_bound)
    winner = jnp.where(win_local == INT_MAX, INT_MAX, win_local + src_base)
    return best, winner, cnt[2], cnt[0], cnt[1], cnt[3]
