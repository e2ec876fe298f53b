"""Distributed EIC SSSP via ``shard_map`` (DESIGN.md §4).

The MPI design of the paper (one vertex-owner process per rank, async RELAX /
REQUEST messages) maps onto two bulk-synchronous TPU engines:

* **v1 — replicated-dist / all-reduce-min** (paper-faithful baseline).
  ``dist``/``parent`` replicated on every device; the edge list is 1-D
  partitioned.  Each round every device relaxes its local in-window edges
  into a dense candidate array and a global ``pmin`` merges.  Collective
  volume: 2 × O(N) per round (cand f32 + winner i32 all-reduce).

* **v2 — sharded-dist / all-to-all reduce-scatter-min** (beyond-paper).
  Vertices are block-partitioned; each device owns ``dist``/``parent`` for
  its block and the edge slab whose *sources* it owns (the paper's
  owner-process layout).  Candidates are segment-min'ed per destination
  block and exchanged with ``all_to_all`` (a reduce-scatter-min), so memory
  is O(N/P) per device and collective volume halves to O(N) send+recv per
  round.  The paper's *bucket fusion* becomes ``fused_rounds`` local-only
  relaxation sub-rounds (edges whose dst block is local) between exchanges.
  The pull phase is executed as a mirrored push (undirected graphs store
  both directions), reusing the same exchange primitive.

Both engines share the exact heuristic formulas with the single-device
engine via the ``*_from_stats`` variants (stats are psum-reduced partials),
and both build their per-shard relaxation from the shared primitives in
:mod:`repro.core.relax` (windowed candidates, deterministic segment-min +
winner recovery, update application) — the engines only add the collective
merge (``pmin`` / ``all_to_all``).  Tie-breaking and the traversal-metric
definitions match the single-device engine exactly, so ``dist``/``parent``
*and* logical metrics are identical across engines (asserted by
``tests/test_relax_backends.py``).

**Relaxation backends.**  Each engine's per-shard push partial is
pluggable (``backend=``): ``"segment_min"`` (default) computes it with a
masked segment reduction over the shard's flat edge slab; ``"blocked"``
computes it with the sparsity-aware blocked layout — per-shard
:func:`~repro.core.graph.slice_for_shard` slabs (sources = owner block,
destinations = the global padded range, per-bucket tile ranges) relaxed
by ONE partials-megakernel launch per shard per round
(:func:`repro.core.relax.blocked_shard_partials_fused`), which folds the
``n_trav``/``n_relax`` counters into its frontier-compacted tile
schedule so no flat O(E) candidate pass runs.  Both backends produce
bitwise-identical ``dist``/``parent``/logical metrics; only the physical
tile/invocation counters differ (0 under ``segment_min``).

``fused_rounds`` is backend-dependent on the sharded tier: under
``segment_min`` it is the paper's bucket fusion (local-only waves
between exchanges — extra local relaxations, so logical metrics are
exempt from parity); under ``blocked`` it groups up to ``fused_rounds``
*complete* synchronized rounds per stepping-loop body, which keeps
bitwise dist/parent/logical-metric parity by construction.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from types import SimpleNamespace
from typing import NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import relax, stats, stepping, traversal
from .config import (ConfigError, EngineConfig, as_resolved,
                     resolve_interpret)
from .graph import (DEFAULT_BLOCK_V, DEFAULT_TILE_E, BlockedEdges,
                    HostGraph, bucket_tiles, check_layout_bytes,
                    layout_bytes, shard_block_v, slice_for_shard)
from .relax import INF, INT_MAX
from .sssp import (SsspMetrics, _check_goal_bounds, _goal_reached,
                   _zero_metrics, goal_param_array)
from ..obs import profiling
from ..obs.trace import trace_append, trace_init

DIST_BACKENDS = ("segment_min", "blocked")


class _AltCtx(NamedTuple):
    """Per-solve ALT pruning context (closed over by the loop bodies, not
    part of any loop carry — every field is loop-invariant)."""
    lb: jnp.ndarray      # [n_pad] f32 per-vertex lower bound to the target
    seed: jnp.ndarray    # f32 landmark-seeded upper bound on d(s, t)
    infl: jnp.ndarray    # f32 prune-bound inflation (1 + 4 delta)
    tgt: jnp.ndarray     # int32 target vertex


def _make_alt_ctx(alt_d, source, gp, n_pad):
    """Build the :class:`_AltCtx` for one (source, target) p2p solve.

    ``alt_d`` is the replicated :class:`~repro.core.relax.AltData`
    bundle; the bound vector is padded with +inf so block-padding
    vertices (which hold no real edges) index safely."""
    infl = 1.0 + 4.0 * alt_d.delta
    lb_v = relax.alt_lower_bounds(alt_d.D, gp, alt_d.delta, alt_d.sym)
    lb_v = jnp.pad(lb_v, (0, n_pad - lb_v.shape[0]),
                   constant_values=jnp.inf)
    seed = relax.alt_seed_ub(alt_d.D, source, gp, infl, alt_d.sym)
    return _AltCtx(lb=lb_v, seed=seed, infl=infl,
                   tgt=jnp.asarray(gp, jnp.int32))


def _dtrace_record(buf, iters, frontier_size, lb, ub, st_, stepped, m0, m1):
    """Append one per-iteration trace record (inside a shard_map body).

    Every input is replicated across shards by construction — the window
    scalars are replicated state, the counters are psum-reduced, and
    ``frontier_size`` is globally reduced by the caller — so the ring is
    replicated too and exits the shard_map under an out_spec of ``P()``.
    Same column semantics as the single-device ``_trace_record``.
    """
    ivals = {
        "iter": iters,
        "frontier": frontier_size,
        "stepped": stepped.astype(jnp.int32),
        "n_rounds": m1.n_rounds - m0.n_rounds,
        "n_steps": m1.n_steps - m0.n_steps,
        "n_extended": m1.n_extended - m0.n_extended,
        "n_trav": m1.n_trav - m0.n_trav,
        "n_pull_trav": m1.n_pull_trav - m0.n_pull_trav,
        "n_relax": m1.n_relax - m0.n_relax,
        "n_pull_relax": m1.n_pull_relax - m0.n_pull_relax,
        "n_updates": m1.n_updates - m0.n_updates,
        "n_pruned": m1.n_pruned - m0.n_pruned,
    }
    fvals = {
        "lb": lb, "ub": ub, "st": st_,
        "n_tiles_scanned": m1.n_tiles_scanned - m0.n_tiles_scanned,
        "n_tiles_dense": m1.n_tiles_dense - m0.n_tiles_dense,
        "n_invocations": m1.n_invocations - m0.n_invocations,
        "n_compact_rounds": m1.n_compact_rounds - m0.n_compact_rounds,
    }
    return trace_append(buf, ivals, fvals)


class ShardedGraph(NamedTuple):
    """Edge slabs partitioned by source-owner + replicated weight stats.

    Shapes: ``src/dst/w`` are ``[P, E_max]`` (sharded on axis 0); ``deg`` is
    ``[P, B]`` (sharded, the owner's block); scalars replicated.
    """
    src: jnp.ndarray       # [P, E_max] int32 — global source id (owner-local block)
    dst: jnp.ndarray       # [P, E_max] int32 — global destination id
    w: jnp.ndarray         # [P, E_max] float32 (+inf padding)
    deg: jnp.ndarray       # [P, B] int32
    rtow: jnp.ndarray      # [RATIO_NUM] float32 (replicated)
    n_edges2: jnp.ndarray  # scalar int32
    n_true: jnp.ndarray    # scalar int32 — real vertex count (pre-padding)


def _refuse_directed(g, what: str) -> None:
    """The sharded engines are built for undirected graphs (their pull
    is a mirrored push, their leaves have degree 1): a directed graph
    raises :class:`~repro.core.config.ConfigError` here."""
    if getattr(g, "directed", False):
        raise ConfigError(f"{what}: the sharded tier solves undirected "
                          "graphs only, and this one is directed; use the "
                          "single or routed tier")


def shard_graph(g: HostGraph, n_shards: int) -> ShardedGraph:
    """Host-side partitioner: block vertex ownership, edges by src owner."""
    _refuse_directed(g, "shard_graph")
    p = n_shards
    block = -(-g.n // p)          # ceil
    n_pad = block * p
    owner = g.src // block
    order = np.argsort(owner, kind="stable")
    src, dst, w = g.src[order], g.dst[order], g.w[order]
    owner = owner[order]
    counts = np.bincount(owner, minlength=p)
    e_max = max(int(counts.max()), 1)
    # pad ragged slabs: padding edges carry w=inf (never in-window)
    s_sl = np.zeros((p, e_max), np.int32)
    d_sl = np.zeros((p, e_max), np.int32)
    w_sl = np.full((p, e_max), np.inf, np.float32)
    offs = np.zeros(p + 1, np.int64)
    np.cumsum(counts, out=offs[1:])
    for q in range(p):
        c = counts[q]
        s_sl[q, :c] = src[offs[q]:offs[q] + c]
        d_sl[q, :c] = dst[offs[q]:offs[q] + c]
        w_sl[q, :c] = w[offs[q]:offs[q] + c]
        s_sl[q, c:] = q * block  # in-block padding source
    deg = np.zeros(n_pad, np.int32)
    deg[:g.n] = g.deg
    return ShardedGraph(
        src=jnp.asarray(s_sl), dst=jnp.asarray(d_sl), w=jnp.asarray(w_sl),
        deg=jnp.asarray(deg.reshape(p, block)),
        rtow=jnp.asarray(g.rtow), n_edges2=jnp.int32(g.m),
        n_true=jnp.int32(g.n))


def graph_specs(axis):
    """PartitionSpecs matching :class:`ShardedGraph` for mesh axis ``axis``."""
    return ShardedGraph(src=P(axis), dst=P(axis), w=P(axis), deg=P(axis),
                        rtow=P(), n_edges2=P(), n_true=P())


class BlockedShards(NamedTuple):
    """Stacked per-shard blocked slabs (leading axis sharded on the mesh).

    Each shard's slice is one :func:`~repro.core.graph.slice_for_shard`
    layout with uniform shapes across shards: ``S`` source blocks of
    ``block_v`` vertices tile the owner block, every slab padded to the
    same ``NT`` tiles.
    """
    src_local: jnp.ndarray       # [P, S, NT*tile_e] int32 block-local src
    dst: jnp.ndarray             # [P, S, NT*tile_e] int32 global dst id
    w: jnp.ndarray               # [P, S, NT*tile_e] f32 (+inf padding)
    tile_dst: jnp.ndarray        # [P, S, NT] int32 dst block per tile
    tile_first: jnp.ndarray      # [P, S, NT] bool forced-first tiles
    bucket_nonempty: jnp.ndarray  # [P, S, NB] bool bucket-has-edges
    tile_order: jnp.ndarray      # [P, S*NT] int32 tiles by dst block


@dataclasses.dataclass(frozen=True)
class BlockedShardMeta:
    """Static geometry of a :class:`BlockedShards` layout (jit cache key)."""
    block_v: int
    tile_e: int
    n_src_blocks: int
    n_dst_blocks: int
    dense_grid_tiles: int        # global per-round cost of the dense scan
    use_kernel: bool
    interpret: bool


def blocked_specs(axis):
    """PartitionSpecs matching :class:`BlockedShards` for mesh ``axis``."""
    return BlockedShards(*([P(axis)] * len(BlockedShards._fields)))


def place_on_mesh(arrays, mesh, specs):
    """``device_put`` each field of a sharded layout (``ShardedGraph``,
    ``BlockedShards``) onto ``mesh`` by its PartitionSpec, once, so every
    shard's slab sits on its owner device."""
    return type(arrays)(*(jax.device_put(x, NamedSharding(mesh, s))
                          for x, s in zip(arrays, specs)))


def _shard_tiles(g, n_shards: int, block_v: int, tile_e: int):
    """``(source blocks over all shards, tiles per slab)`` of the stacked
    layout: every slab of every shard is padded to the worst slab's tile
    count.  One bincount, no slab materialized."""
    n = int(np.asarray(g.deg).shape[0])
    block = -(-n // n_shards)
    bv = shard_block_v(block, block_v)
    n_dst = (block * n_shards) // bv
    tiles = bucket_tiles(g.src, g.dst, src_blocks=n_dst, n_dst_blocks=n_dst,
                         block_v=bv, tile_e=tile_e)
    return n_dst, max(int(tiles.sum(axis=1).max()), 1)


def shard_layout_bytes(g, n_shards: int, *, block_v: int = DEFAULT_BLOCK_V,
                       tile_e: int = DEFAULT_TILE_E) -> int:
    """Padded bytes :func:`shard_blocked` would allocate for ``g``."""
    n_src, nt = _shard_tiles(g, n_shards, block_v, tile_e)
    return layout_bytes(n_src * nt, tile_e)


def shard_blocked(g, n_shards: Optional[int] = None, *,
                  block_v: int = DEFAULT_BLOCK_V,
                  tile_e: int = DEFAULT_TILE_E,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None,
                  max_bytes: Optional[int] = None
                  ) -> Tuple[BlockedShards, BlockedShardMeta]:
    """Build the stacked per-shard blocked layout for the engines.

    ``g`` is a :class:`~repro.core.graph.HostGraph` (with ``n_shards``)
    or a :class:`ShardedGraph` (shard count taken from its slab axis; the
    flat edge slabs are unpacked host-side).  Host-side, once per graph —
    pass the result to ``sssp_distributed*(..., backend="blocked",
    blocked=...)`` so repeated calls don't re-bucket.

    ``use_kernel`` defaults to True: the engines relax each shard through
    the gridded ``edge_relax_partials`` kernel (interpreted on CPU,
    compiled on a TPU).  Pass ``use_kernel=False`` to pin the
    bitwise-identical jnp reference (layout, frontier-compaction
    schedule, and tile metrics are shared by both paths).  The padded
    size is checked against ``max_bytes`` (default: the device's memory)
    before any slab is built.
    """
    _refuse_directed(g, "shard_blocked")
    if use_kernel is None:
        use_kernel = True
    interpret = resolve_interpret(interpret)
    if isinstance(g, ShardedGraph):
        if n_shards is None:
            n_shards = int(g.src.shape[0])
        w_flat = np.asarray(g.w).reshape(-1)
        real = np.isfinite(w_flat)                  # padding carries w=inf
        n = int(g.n_true)
        g = SimpleNamespace(
            src=np.asarray(g.src).reshape(-1)[real],
            dst=np.asarray(g.dst).reshape(-1)[real],
            w=w_flat[real],
            deg=np.asarray(g.deg).reshape(-1)[:n])
    elif n_shards is None:
        raise ValueError("n_shards is required for a HostGraph")
    kw = dict(block_v=block_v, tile_e=tile_e, use_kernel=use_kernel,
              interpret=interpret)
    # size the uniform tile padding with one cheap counting pass (no slab
    # arrays materialized): block_v divides the owner block, so the
    # global src-block id is just src // bv and one bincount covers
    # every (src block, dst block) bucket at once
    n_dst, nt = _shard_tiles(g, n_shards, block_v, tile_e)
    check_layout_bytes(n_dst * nt, tile_e, "shard_blocked", max_bytes)
    bgs = [slice_for_shard(g, q, n_shards, n_tiles=nt, max_bytes=max_bytes,
                           **kw)
           for q in range(n_shards)]
    slabs = {f: jnp.stack([jnp.stack([getattr(slab, f) for slab in bg.slabs])
                           for bg in bgs])
             for f in BlockedEdges._fields}
    # the partials kernel visits a shard's tiles destination-major across
    # its slabs; the order is fixed by the layout, so it is sorted here once
    order = np.argsort(np.asarray(slabs["tile_dst"]).reshape(n_shards, -1),
                       axis=1, kind="stable").astype(np.int32)
    stacked = BlockedShards(**slabs, tile_order=jnp.asarray(order))
    meta = BlockedShardMeta(
        block_v=bgs[0].block_v, tile_e=tile_e,
        n_src_blocks=bgs[0].n_blocks, n_dst_blocks=bgs[0].n_dst_blocks,
        dense_grid_tiles=sum(bg.dense_grid_tiles for bg in bgs),
        use_kernel=use_kernel, interpret=interpret)
    return stacked, meta


# ---------------------------------------------------------------------------
# shared distributed statistics (local partial + psum)
# ---------------------------------------------------------------------------

def _dstats_gap(dist_l, deg_l, rtow, n_edges2, x, params, axes, mult=None):
    hist = jax.lax.psum(stats.degree_hist(dist_l, deg_l, x), axes)
    hd = stats.high_d_from_hist(hist)
    sd = jax.lax.psum(stats.sum_d(dist_l, deg_l, x), axes)
    # the psum'd partials are replicated, so an adaptive ``mult`` (itself
    # replicated loop state) keeps the gap replicated across shards
    return (stepping.gap_from_stats(sd, hd, rtow, n_edges2, params, mult),
            sd, hd)


def _dstats_compute_st(dist_l, deg_l, rtow, n_edges2, lb, ub, params, axes,
                       mult=None):
    gap_lb, _, _ = _dstats_gap(dist_l, deg_l, rtow, n_edges2, lb, params,
                               axes, mult)
    gap_ub, sd_ub, _ = _dstats_gap(dist_l, deg_l, rtow, n_edges2, ub, params,
                                   axes, mult)
    grid = traversal.st_grid_points(ub)
    ghist = jax.lax.psum(stats.grid_hist(dist_l, deg_l, grid), axes)
    sd_grid = stats.sum_d_grid_from_hist(ghist)
    st = traversal.compute_st_from_stats(grid, sd_grid, sd_ub, gap_lb,
                                         gap_ub, rtow, n_edges2, ub)
    return st, gap_ub


# ---------------------------------------------------------------------------
# v2: sharded dist + all-to-all reduce-scatter-min
# ---------------------------------------------------------------------------

class _V2State(NamedTuple):
    dist: jnp.ndarray      # [B] local block
    parent: jnp.ndarray    # [B]
    frontier: jnp.ndarray  # [B]
    lb: jnp.ndarray
    ub: jnp.ndarray
    st: jnp.ndarray
    done: jnp.ndarray
    iters: jnp.ndarray
    metrics: SsspMetrics


@lru_cache(maxsize=64)
def _build_engine(mesh, axes, version, block, n_pad, params, max_iters,
                  fused_rounds, capacity, goal="tree", batch=False,
                  bmeta: Optional[BlockedShardMeta] = None,
                  trace_cap: int = 0, policy: str = "static",
                  alt: bool = False):
    """Build + jit one distributed engine (cached so repeated calls with
    the same mesh/shape/config reuse the compiled executable).

    ``goal`` is static (part of the compiled program, like the
    single-device engine); ``batch`` switches the body to the multi-source
    entry point (``lax.map`` over a ``[S]`` sources axis).  ``bmeta``
    selects the blocked relaxation backend: the engine then takes a
    :class:`BlockedShards` layout as its second argument and computes the
    push partials with the ragged-grid kernel instead of ``segment_min``.
    ``trace_cap > 0`` adds a replicated per-round trace ring as a fourth
    output (part of this cache key: 0 compiles the exact untraced
    program).  ``alt`` appends a replicated
    :class:`~repro.core.relax.AltData` operand (p2p goal-directed
    pruning; part of the cache key, so non-ALT solves compile the exact
    pre-ALT program).
    """
    in_specs = (graph_specs(axes), P(), P())
    if bmeta is not None:
        # blocked engines also take the layout and a per-shard owner-block
        # offset.  The offset rides in as *data* (not lax.axis_index): an
        # axis_index-derived value flowing into consumers of the
        # interpret-mode Pallas outputs inside the stepping while_loop
        # makes the XLA SPMD partitioner reject the module (PartitionId
        # in a nested while, jax 0.4.x) — data sidesteps it entirely.
        in_specs = (graph_specs(axes), blocked_specs(axes), P(axes), P(),
                    P())
    if alt:
        # the landmark matrix is replicated across the mesh (the serving
        # registry places it with a replicated NamedSharding up front)
        in_specs = in_specs + (relax.AltData(D=P(), delta=P(), sym=P()),)
    out_specs = (P(axes), P(axes), P())

    axis_sizes = tuple(mesh.shape[a] for a in
                       ((axes,) if isinstance(axes, str) else axes))
    if version == "v1":
        body = _v1_body(n_pad, block, axes, params, max_iters, goal, batch,
                        bmeta=bmeta, axis_sizes=axis_sizes,
                        trace_cap=trace_cap, policy=policy, alt=alt)
        out_specs = (P(), P(), P())
    elif version == "v2":
        body = _v2_body(n_pad, block, axes, params, max_iters, fused_rounds,
                        axis_sizes, goal=goal, batch=batch, bmeta=bmeta,
                        trace_cap=trace_cap, policy=policy, alt=alt)
    elif version == "v3":
        cap = capacity or max(block // 16, 8)
        body = _v2_body(n_pad, block, axes, params, max_iters, fused_rounds,
                        axis_sizes, goal=goal, batch=batch,
                        compact_capacity=cap, bmeta=bmeta,
                        trace_cap=trace_cap, policy=policy, alt=alt)
    else:
        raise ValueError(version)
    if version in ("v2", "v3") and batch:
        # per-shard [S, B] slabs concatenate into a global [S, n_pad]
        out_specs = (P(None, axes), P(None, axes), P())
    if trace_cap > 0:
        # the trace ring is computed from replicated values only
        out_specs = out_specs + (P(),)

    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def _resolve_backend(backend: str) -> str:
    if backend == "blocked_pallas":      # single-device layout's name
        backend = "blocked"
    if backend not in DIST_BACKENDS:
        raise ValueError(f"unknown distributed relax backend {backend!r}; "
                         f"expected one of {DIST_BACKENDS}")
    return backend


def _resolve_blocked(sg: ShardedGraph, backend: str, blocked, build_opts):
    """Normalize the (backend, blocked layout) pair for the entry points."""
    if _resolve_backend(backend) == "segment_min":
        if blocked is not None:
            raise ValueError("blocked layout passed with "
                             "backend='segment_min'")
        return None, None
    if blocked is None:
        # convenience one-off build; callers that relax repeatedly should
        # shard_blocked() once and pass the result
        blocked = shard_blocked(sg, **build_opts)
    arrays, bmeta = blocked
    if arrays.src_local.shape[0] != sg.src.shape[0]:
        raise ValueError(
            f"blocked layout has {arrays.src_local.shape[0]} shards, "
            f"graph has {sg.src.shape[0]}")
    return arrays, bmeta


def _dist_engine_args(sg: ShardedGraph, config, version, max_iters,
                      fused_rounds, alpha, beta, capacity, backend,
                      block_v, tile_e, policy=None):
    """Resolve the distributed engine knobs from either an
    :class:`~repro.core.config.EngineConfig` or the loose kwargs — never
    both (:meth:`EngineConfig.from_loose` is the shared gate, so loose
    kwargs go through exactly the config validation).  Returns
    ``(version, max_iters, fused_rounds, params_alpha, params_beta,
    capacity, backend, trace_cap, policy, blocked_build_opts)``."""
    config = EngineConfig.from_loose(
        config, "engine", defaults={"tier": "sharded"},
        shard_version=version, max_iters=max_iters,
        fused_rounds=fused_rounds, alpha=alpha, beta=beta,
        compact_capacity=capacity, shard_backend=backend,
        block_v=block_v, tile_e=tile_e, policy=policy)
    r = as_resolved(config, n=int(sg.n_true), m=int(sg.n_edges2),
                    n_devices=int(sg.src.shape[0])).require("sharded")
    return (r.shard_version, r.max_iters, r.fused_rounds, r.alpha,
            r.beta, r.compact_capacity, r.shard_backend, r.trace_cap,
            r.policy, r.blocked_opts())


def sssp_distributed(sg: ShardedGraph, source: int, mesh, axes=("graph",), *,
                     version=None, max_iters=None,
                     fused_rounds=None, alpha=None,
                     beta=None, capacity=None,
                     goal: str = "tree", goal_param=None,
                     backend=None, blocked=None,
                     block_v=None, tile_e=None, policy=None, config=None,
                     landmarks=None):
    """Run distributed EIC SSSP on ``mesh`` (axes flattened over ``axes``).

    versions: v1 replicated/pmin, v2 sharded/all_to_all dense exchange,
    v3 frontier-compacted exchange (top-C candidates per destination block;
    falls back to the dense exchange on bucket overflow — exact always).

    ``goal``/``goal_param`` select the same early-exit query variants as
    the single-device engine (:data:`repro.core.sssp.GOALS`): the settled
    test is evaluated distributively (owner-local settled check + pmax for
    p2p, psum'd settled count for knear) so a sharded p2p/bounded/knear
    query stops stepping as early as the single-device one.

    ``backend`` selects the per-shard push-partial implementation (see
    :data:`DIST_BACKENDS`); with ``"blocked"``, pass ``blocked=`` a
    prebuilt :func:`shard_blocked` layout to amortize bucketing across
    calls (``block_v``/``tile_e`` size the one-off build otherwise).
    Results are bitwise-identical across backends.

    ``config`` accepts an :class:`~repro.core.config.EngineConfig` (or a
    resolved one, tier ``"sharded"``) in place of every loose engine
    kwarg above — the :class:`repro.api.Solver` facade's path.

    ``landmarks`` (a :class:`~repro.core.landmarks.LandmarkSet` or raw
    :class:`~repro.core.relax.AltData`) enables exact ALT goal-directed
    pruning for p2p goals — the facade/registry build and cache the set
    per graph and pass it here.
    """
    (version, max_iters, fused_rounds, alpha, beta, capacity, backend,
     trace_cap, policy, build_opts) = _dist_engine_args(
        sg, config, version, max_iters, fused_rounds, alpha, beta,
        capacity, backend, block_v, tile_e, policy)
    params = stepping.SteppingParams(alpha=alpha, beta=beta)
    p, _ = sg.src.shape
    block = sg.deg.shape[1]
    gp = goal_param_array(goal, goal_param)
    _check_goal_bounds(goal, gp, int(sg.n_true))
    axes_key = axes if isinstance(axes, str) else tuple(axes)
    arrays, bmeta = _resolve_blocked(sg, backend, blocked, build_opts)
    alt_data = None
    if goal == "p2p" and landmarks is not None:
        alt_data = getattr(landmarks, "alt_data", landmarks)
    fn = _build_engine(mesh, axes_key, version, block, p * block, params,
                       max_iters, fused_rounds, capacity, goal, False,
                       bmeta, trace_cap, policy, alt_data is not None)
    alt_op = () if alt_data is None else (alt_data,)
    with profiling.annotate(f"repro:sssp_dist_dispatch:{version}"):
        if arrays is not None:
            bases = jnp.arange(p, dtype=jnp.int32) * block
            return fn(sg, arrays, bases, jnp.int32(source), gp, *alt_op)
        return fn(sg, jnp.int32(source), gp, *alt_op)


def sssp_distributed_batch(sg: ShardedGraph, sources, mesh, axes=("graph",),
                           *, version=None,
                           max_iters=None, fused_rounds=None,
                           alpha=None, beta=None,
                           capacity=None, goal: str = "tree",
                           goal_params=None, backend=None,
                           blocked=None, block_v=None,
                           tile_e=None, policy=None, config=None,
                           landmarks=None):
    """Batched multi-source distributed SSSP — the sharded serving tier's
    entry point.

    Sources are scanned *sequentially* inside one compiled ``shard_map``
    program (``lax.map``), not vmapped: the sharded tier exists for graphs
    whose per-device state is the memory budget, so slots must not
    multiply the O(N/P) dist/parent footprint.  One compile still serves
    every batch of the same width, and per-batch dispatch overhead is paid
    once per batch instead of once per source.  All slots share the static
    ``goal`` kind with per-slot ``goal_params``; returns ``(dist, parent,
    metrics)`` with a leading ``[S]`` axis (dist/parent ``[S, n_pad]``).
    ``backend``/``blocked``/``config`` select the per-shard relaxation
    exactly as in :func:`sssp_distributed`.
    """
    (version, max_iters, fused_rounds, alpha, beta, capacity, backend,
     trace_cap, policy, build_opts) = _dist_engine_args(
        sg, config, version, max_iters, fused_rounds, alpha, beta,
        capacity, backend, block_v, tile_e, policy)
    params = stepping.SteppingParams(alpha=alpha, beta=beta)
    p, _ = sg.src.shape
    block = sg.deg.shape[1]
    sources = jnp.asarray(sources, jnp.int32)
    if goal == "tree" and goal_params is None:
        goal_params = [0] * sources.shape[0]
    gp = goal_param_array(goal, goal_params)
    if gp.shape != sources.shape:
        raise ValueError(f"goal_params shape {gp.shape} != sources shape "
                         f"{sources.shape}")
    _check_goal_bounds(goal, gp, int(sg.n_true))
    axes_key = axes if isinstance(axes, str) else tuple(axes)
    arrays, bmeta = _resolve_blocked(sg, backend, blocked, build_opts)
    alt_data = None
    if goal == "p2p" and landmarks is not None:
        alt_data = getattr(landmarks, "alt_data", landmarks)
    fn = _build_engine(mesh, axes_key, version, block, p * block, params,
                       max_iters, fused_rounds, capacity, goal, True,
                       bmeta, trace_cap, policy, alt_data is not None)
    alt_op = () if alt_data is None else (alt_data,)
    with profiling.annotate(f"repro:sssp_dist_batch_dispatch:{version}"):
        if arrays is not None:
            bases = jnp.arange(p, dtype=jnp.int32) * block
            return fn(sg, arrays, bases, sources, gp, *alt_op)
        return fn(sg, sources, gp, *alt_op)


# --- v1 -------------------------------------------------------------------

def _v1_body(n_pad, block, axes, params, max_iters, goal="tree", batch=False,
             bmeta=None, axis_sizes=(), trace_cap=0, policy="static",
             alt=False):
    axis_names = (axes,) if isinstance(axes, str) else tuple(axes)
    adaptive = policy == "adaptive"

    def run(sg: ShardedGraph, *args):
        if alt:
            args, alt_d = args[:-1], args[-1]
        else:
            alt_d = None
        if bmeta is not None:
            bl, base_arr, source, goal_param = args
            bl = jax.tree.map(lambda x: x[0], bl)    # drop the shard axis
            base = base_arr[0]       # owner-block offset as data (see
            me = base // block       # _build_engine on why not axis_index)
        else:
            source, goal_param = args
            bl = None
            me = jnp.int32(0)
            for name, size in zip(axis_names, axis_sizes):
                me = me * size + jax.lax.axis_index(name)
            base = me * block
        src = sg.src.reshape(-1)
        dst = sg.dst.reshape(-1)
        w = sg.w.reshape(-1)
        deg_l = sg.deg.reshape(-1)               # local block [B]
        deg = jax.lax.all_gather(deg_l, axes, tiled=True)  # replicated [N]
        rtow, n_edges2 = sg.rtow, sg.n_edges2
        max_w = rtow[-1]
        high_d0 = stats.high_d(jnp.zeros((n_pad,), jnp.float32), deg, 0.0)

        def relax_round(dist, parent, frontier, lb, ub, metrics, ac=None,
                        pb=None):
            paths = relax.leaf_pruned(frontier, dist, deg)
            n_prn = jnp.int32(0)
            if bmeta is None:
                cand, in_window, active = relax.edge_candidates(
                    dist[src], paths[src], parent[src], dst, w, lb, ub)
                if ac is not None:
                    active, pruned = relax.alt_prune(cand, active,
                                                     ac.lb[dst], pb)
                    cand = jnp.where(active, cand, INF)
                    n_prn = jax.lax.psum(
                        jnp.sum(pruned.astype(jnp.int32)), axes)
                best = jax.lax.pmin(
                    relax.segment_partial_min(cand, dst, n_pad), axes)
                winner = jax.lax.pmin(
                    relax.winner_partial(cand, active, src, dst, best,
                                         n_pad), axes)
                n_tiles = jnp.float32(0)
                touched = jax.lax.psum(
                    jnp.sum(in_window.astype(jnp.int32)), axes)
                relaxed = jax.lax.psum(
                    jnp.sum(active.astype(jnp.int32)), axes)
                n_inv = jnp.float32(0)
            else:
                # dist/frontier are replicated; the partials megakernel
                # reads only the shard's owner block (its source range)
                # and folds the n_trav/n_relax sums into its scheduled
                # tile pass — one launch per shard, no flat O(E)
                # candidate pass
                dist_src = jax.lax.dynamic_slice(dist, (base,), (block,))
                paths_src = jax.lax.dynamic_slice(paths, (base,), (block,))
                parent_src = jax.lax.dynamic_slice(parent, (base,),
                                                   (block,))
                best_l, win_l, nt, trav, rlx, prn = \
                    relax.blocked_shard_partials_fused(
                        bl.src_local, bl.dst, bl.w, bl.tile_dst,
                        bl.tile_first, bl.tile_order, dist_src, paths_src,
                        parent_src,
                        base, lb, ub, block_v=bmeta.block_v,
                        n_dst_blocks=bmeta.n_dst_blocks,
                        tile_e=bmeta.tile_e, use_kernel=bmeta.use_kernel,
                        interpret=bmeta.interpret,
                        alt_lb=None if ac is None else ac.lb,
                        prune_bound=pb)
                best = jax.lax.pmin(best_l, axes)
                winner = jax.lax.pmin(
                    jnp.where(best_l <= best, win_l, INT_MAX), axes)
                n_tiles = jax.lax.psum(nt.astype(jnp.float32), axes)
                touched = jax.lax.psum(trav, axes)
                relaxed = jax.lax.psum(rlx, axes)
                n_prn = jax.lax.psum(prn, axes)
                n_inv = jax.lax.psum(jnp.float32(1), axes)
            new_dist, new_parent, improved = relax.apply_updates(
                dist, parent, best, winner)
            metrics = metrics._replace(
                n_rounds=metrics.n_rounds + jnp.where(jnp.any(frontier), 1, 0),
                n_extended=metrics.n_extended +
                jnp.sum((improved & (deg > 1)).astype(jnp.int32)),
                n_trav=metrics.n_trav + touched,
                n_relax=metrics.n_relax + relaxed,
                n_updates=metrics.n_updates +
                jnp.sum(improved.astype(jnp.int32)),
                n_pruned=metrics.n_pruned + n_prn,
                n_tiles_scanned=metrics.n_tiles_scanned + n_tiles,
                n_tiles_dense=metrics.n_tiles_dense + jnp.float32(
                    0 if bmeta is None else bmeta.dense_grid_tiles),
                n_invocations=metrics.n_invocations + n_inv,
            )
            return new_dist, new_parent, improved, metrics

        def pull_round(dist, parent, st, lb, ub, metrics, ac=None, pb=None):
            # mirrored push from the settled band (undirected store); the
            # requester receiving the update is ``dst`` here, so ALT cuts
            # requests with cand + lb[dst] > bound (the mirrored twin of
            # the single-device requester-side alt_lb[src] cut — the
            # directed edge sets pair up one-to-one, so counts match)
            dv = dist[src]
            mask = (dv >= st) & (dv < lb) & (dv + w < ub)
            cand = jnp.where(mask, dv + w, INF)
            n_prn = jnp.int32(0)
            if ac is not None:
                mask, pruned = relax.alt_prune(cand, mask, ac.lb[dst], pb)
                cand = jnp.where(mask, cand, INF)
                n_prn = jax.lax.psum(
                    jnp.sum(pruned.astype(jnp.int32)), axes)
            best = jax.lax.pmin(
                relax.segment_partial_min(cand, dst, n_pad), axes)
            winner = jax.lax.pmin(
                relax.winner_partial(cand, mask, src, dst, best, n_pad),
                axes)
            new_dist, new_parent, improved = relax.apply_updates(
                dist, parent, best, winner, gate=dist > lb)
            scans = jax.lax.psum(jnp.sum(
                ((dist[src] > lb) & (w < ub - st)).astype(jnp.int32)), axes)
            requests = jax.lax.psum(jnp.sum(mask.astype(jnp.int32)), axes)
            metrics = metrics._replace(
                n_pull_trav=metrics.n_pull_trav + scans,
                n_extended=metrics.n_extended +
                jnp.sum((improved & (deg > 1)).astype(jnp.int32)),
                n_relax=metrics.n_relax + requests,
                n_pull_relax=metrics.n_pull_relax + requests,
                n_updates=metrics.n_updates +
                jnp.sum(improved.astype(jnp.int32)),
                n_pruned=metrics.n_pruned + n_prn,
                n_rounds=metrics.n_rounds + 1,
            )
            return new_dist, new_parent, metrics

        def transition(dist, parent, lb, ub, metrics, gp, ps=None, ac=None):
            pend = dist[src] + w
            pend = jnp.where(pend >= ub, pend, INF)
            if ac is not None:
                # a pending candidate the ALT bound would cut can never
                # improve the target, so skipping it in fast-forward/
                # termination is exact for the p2p contract
                bound_eff = jnp.minimum(ac.seed, dist[ac.tgt] * ac.infl)
                pend = jnp.where(pend + ac.lb[dst] > bound_eff, INF, pend)
            min_pending = jax.lax.pmin(jnp.min(pend), axes)
            done = ~jnp.isfinite(min_pending)
            if ps is not None:
                # observe -> adapt: the counters are psum'd/replicated, so
                # the policy state stays replicated too
                ps = stepping.adaptive_update(ps, metrics.n_rounds,
                                              metrics.n_relax,
                                              metrics.n_updates)
                tparams = stepping.effective_params(ps)
                mult = ps.mult
            else:
                tparams, mult = params, None
            st_next = traversal.compute_st(dist, deg, rtow, n_edges2, lb, ub,
                                           tparams, mult=mult)
            lb2 = ub
            gap2 = stepping.gap(dist, deg, rtow, n_edges2, lb2, tparams, mult)
            ub2 = lb2 + gap2
            ffwd = (min_pending >= ub2) & ~done
            lb2 = jnp.where(ffwd, min_pending, lb2)
            gap3 = stepping.gap(dist, deg, rtow, n_edges2, lb2, tparams, mult)
            ub2 = jnp.where(ffwd, lb2 + gap3, ub2)
            st_next = jnp.minimum(st_next, lb2)

            def with_pull(args):
                return pull_round(*args[:2], st_next, lb2, ub2, args[2],
                                  ac, None if ac is None else bound_eff)

            dist, parent, metrics = jax.lax.cond(
                st_next < lb2, with_pull, lambda a: a,
                (dist, parent, metrics))
            # dist is replicated here, so the single-device goal test applies
            done = done | _goal_reached(goal, gp, dist, lb2)
            frontier = relax.window_frontier(dist, st_next, lb2, ub2,
                                             max_w) & ~done
            metrics = metrics._replace(
                n_steps=metrics.n_steps + jnp.where(done, 0, 1))
            out = (dist, parent, frontier, lb2, ub2, st_next, done, metrics)
            return out if ps is None else out + (ps,)

        def cond(s):
            # index access: the carry is a 9-tuple (static policy) or a
            # 10-tuple with the trailing PolicyState (adaptive)
            return (~s[6]) & (s[7] < max_iters)

        def run_one(source, gp):
            dist0 = jnp.full((n_pad,), INF, jnp.float32).at[source].set(0.0)
            parent0 = jnp.full((n_pad,), -1,
                               jnp.int32).at[source].set(source)
            frontier0 = jnp.zeros((n_pad,), bool).at[source].set(True)
            metrics0 = _zero_metrics()._replace(n_extended=jnp.int32(1))
            ac = None if alt_d is None else _make_alt_ctx(alt_d, source,
                                                          gp, n_pad)

            def body(s):
                (dist, parent, frontier, lb, ub, st_, done, iters,
                 metrics) = s[:9]
                # per-round prune bound from dist at round start (the
                # same recompute the single-device fused kernel does)
                pb = None if ac is None else jnp.minimum(
                    ac.seed, dist[ac.tgt] * ac.infl)
                dist, parent, frontier, metrics = relax_round(
                    dist, parent, frontier, lb, ub, metrics, ac, pb)
                # first-step ub bootstrap
                def tighten(ub):
                    mask = (deg.astype(jnp.float32) >= high_d0) & (dist > 0)
                    return jnp.minimum(ub,
                                       jnp.min(jnp.where(mask, dist, INF)))
                ub = jax.lax.cond(lb <= 0.0, tighten, lambda u: u, ub)

                if adaptive:
                    def trans(args):
                        return transition(*args[:5], gp, ps=args[5], ac=ac)

                    def keep(args):
                        dist, parent, lb, ub, metrics, ps = args
                        return (dist, parent, frontier, lb, ub, st_, done,
                                metrics, ps)

                    (dist, parent, frontier, lb, ub, st2, done, metrics,
                     ps) = jax.lax.cond(jnp.any(frontier), keep, trans,
                                        (dist, parent, lb, ub, metrics,
                                         s[9]))
                    return (dist, parent, frontier, lb, ub, st2, done,
                            iters + 1, metrics, ps)

                def trans(args):
                    return transition(*args, gp, ac=ac)

                def keep(args):
                    dist, parent, lb, ub, metrics = args
                    return dist, parent, frontier, lb, ub, st_, done, metrics

                (dist, parent, frontier, lb, ub, st2, done, metrics) = \
                    jax.lax.cond(jnp.any(frontier), keep, trans,
                                 (dist, parent, lb, ub, metrics))
                return (dist, parent, frontier, lb, ub, st2, done,
                        iters + 1, metrics)

            init = (dist0, parent0, frontier0, jnp.float32(0.0), INF,
                    jnp.float32(0.0), jnp.bool_(False), jnp.int32(0),
                    metrics0)
            if adaptive:
                init = init + (stepping.policy_init(params),)
            if trace_cap <= 0:
                out = jax.lax.while_loop(cond, body, init)
                return out[0], out[1], out[8]

            def traced_body(carry):
                s, buf = carry
                s1 = body(s)
                m0, m1 = s[8], s1[8]
                stepped = (m1.n_steps > m0.n_steps) | (s1[6] & ~s[6])
                # dist/frontier are replicated in v1: a local sum is global
                fsz = jnp.sum(s[2].astype(jnp.int32))
                buf = _dtrace_record(buf, s[7], fsz, s[3], s[4], s[5],
                                     stepped, m0, m1)
                return s1, buf

            out, buf = jax.lax.while_loop(
                lambda c: cond(c[0]), traced_body,
                (init, trace_init(trace_cap)))
            return out[0], out[1], out[8], buf

        if batch:
            return jax.lax.map(lambda a: run_one(*a), (source, goal_param))
        return run_one(source, goal_param)

    return run


# ---------------------------------------------------------------------------
# incremental repair (repro.delta): lean Bellman loops over the shards
# ---------------------------------------------------------------------------

@lru_cache(maxsize=64)
def _build_repair_engine(mesh, axes, version, block, n_pad, max_iters,
                         capacity):
    """Build + jit one distributed *repair* engine.

    The repair loop is the stepping engines' relaxation round with the
    window pinned to ``[0, +inf)`` and no step transitions: each round
    relaxes the current frontier through the shard's segment-min partial
    and the version's collective merge (v1 replicated ``pmin``, v2 dense
    ``all_to_all`` exchange, v3 frontier-compacted exchange), and the
    next frontier is exactly the vertices the round improved.  Fed a
    valid upper-bound state (see :func:`repair_distributed`), the
    fixpoint dist/parent are bitwise-identical to a from-scratch solve —
    the same primitives, merge rule, and tie-breaks as the full engines.
    """
    axis_names = (axes,) if isinstance(axes, str) else tuple(axes)
    axis_sizes = tuple(mesh.shape[a] for a in axis_names)
    p = n_pad // block
    in_specs = (graph_specs(axes), P(), P(), P())
    out_specs = (P(), P(), P()) if version == "v1" \
        else (P(axes), P(axes), P())

    def run_v1(sg: ShardedGraph, dist0, parent0, frontier0):
        src = sg.src.reshape(-1)
        dst = sg.dst.reshape(-1)
        w = sg.w.reshape(-1)
        deg = jax.lax.all_gather(sg.deg.reshape(-1), axes, tiled=True)

        def body(c):
            dist, parent, frontier, metrics, iters, _ = c
            paths = relax.leaf_pruned(frontier, dist, deg)
            cand, in_window, active = relax.edge_candidates(
                dist[src], paths[src], parent[src], dst, w,
                jnp.float32(0.0), INF)
            best = jax.lax.pmin(
                relax.segment_partial_min(cand, dst, n_pad), axes)
            winner = jax.lax.pmin(
                relax.winner_partial(cand, active, src, dst, best, n_pad),
                axes)
            dist2, parent2, improved = relax.apply_updates(dist, parent,
                                                           best, winner)
            metrics = metrics._replace(
                n_rounds=metrics.n_rounds
                + jnp.where(jnp.any(frontier), 1, 0),
                n_trav=metrics.n_trav + jax.lax.psum(
                    jnp.sum(in_window.astype(jnp.int32)), axes),
                n_relax=metrics.n_relax + jax.lax.psum(
                    jnp.sum(active.astype(jnp.int32)), axes),
                n_updates=metrics.n_updates
                + jnp.sum(improved.astype(jnp.int32)),
                n_extended=metrics.n_extended
                + jnp.sum((improved & (deg > 1)).astype(jnp.int32)))
            # dist/frontier are replicated in v1: a local any is global
            go = jnp.any(improved).astype(jnp.int32)
            return dist2, parent2, improved, metrics, iters + 1, go

        def cond(c):
            # the go flag is carried: collectives may not appear in a
            # while_loop cond (and jnp.any is local-only elsewhere)
            return (c[5] > 0) & (c[4] < max_iters)

        init = (dist0, parent0, frontier0, _zero_metrics(), jnp.int32(0),
                jnp.any(frontier0).astype(jnp.int32))
        out = jax.lax.while_loop(cond, body, init)
        return out[0], out[1], out[3]

    def run_v2(sg: ShardedGraph, dist0, parent0, frontier0):
        me = jnp.int32(0)
        for name, size in zip(axis_names, axis_sizes):
            me = me * size + jax.lax.axis_index(name)
        base = me * block
        src = sg.src.reshape(-1)
        dst = sg.dst.reshape(-1)
        w = sg.w.reshape(-1)
        deg_l = sg.deg.reshape(-1)
        src_l = src - base
        dist_l = jax.lax.dynamic_slice(dist0, (base,), (block,))
        parent_l = jax.lax.dynamic_slice(parent0, (base,), (block,))
        frontier_l = jax.lax.dynamic_slice(frontier0, (base,), (block,))

        def dense_exchange(best_g, win_g):
            recv_v = jax.lax.all_to_all(best_g.reshape(p, block), axes,
                                        split_axis=0, concat_axis=0)
            recv_w = jax.lax.all_to_all(win_g.reshape(p, block), axes,
                                        split_axis=0, concat_axis=0)
            return relax.combine_block_partials(recv_v, recv_w)

        def compact_exchange(best_g, win_g):
            cap = capacity
            rows_v = best_g.reshape(p, block)
            rows_w = win_g.reshape(p, block)
            n_finite = jnp.sum(jnp.isfinite(rows_v), axis=1)
            overflow = jax.lax.pmax(
                jnp.any(n_finite > cap).astype(jnp.int32), axes) > 0

            def compact(_):
                neg, idx = jax.lax.top_k(-rows_v, cap)
                vals = -neg
                srcs = jnp.take_along_axis(rows_w, idx, axis=1)
                rv = jax.lax.all_to_all(vals, axes, split_axis=0,
                                        concat_axis=0)
                ri = jax.lax.all_to_all(idx, axes, split_axis=0,
                                        concat_axis=0)
                rs = jax.lax.all_to_all(srcs, axes, split_axis=0,
                                        concat_axis=0)
                return relax.segment_min_with_winner(
                    rv.reshape(-1), jnp.isfinite(rv.reshape(-1)),
                    rs.reshape(-1), ri.reshape(-1), block)

            return jax.lax.cond(overflow,
                                lambda _: dense_exchange(best_g, win_g),
                                compact, None)

        merge = compact_exchange if capacity else dense_exchange

        def body(c):
            dist_l, parent_l, frontier_l, metrics, iters, _ = c
            paths = relax.leaf_pruned(frontier_l, dist_l, deg_l)
            cand, in_window, active = relax.edge_candidates(
                dist_l[src_l], paths[src_l], parent_l[src_l], dst, w,
                jnp.float32(0.0), INF)
            best_g, win_g = relax.segment_min_with_winner(cand, active,
                                                          src, dst, n_pad)
            best_l, winner_l = merge(best_g, win_g)
            dist2, parent2, improved = relax.apply_updates(
                dist_l, parent_l, best_l, winner_l)
            any_front = jax.lax.pmax(
                jnp.any(frontier_l).astype(jnp.int32), axes)
            go = jax.lax.pmax(jnp.any(improved).astype(jnp.int32), axes)
            metrics = metrics._replace(
                n_rounds=metrics.n_rounds + any_front,
                n_trav=metrics.n_trav + jax.lax.psum(
                    jnp.sum(in_window.astype(jnp.int32)), axes),
                n_relax=metrics.n_relax + jax.lax.psum(
                    jnp.sum(active.astype(jnp.int32)), axes),
                n_updates=metrics.n_updates + jax.lax.psum(
                    jnp.sum(improved.astype(jnp.int32)), axes),
                n_extended=metrics.n_extended + jax.lax.psum(
                    jnp.sum((improved & (deg_l > 1)).astype(jnp.int32)),
                    axes))
            return dist2, parent2, improved, metrics, iters + 1, go

        def cond(c):
            return (c[5] > 0) & (c[4] < max_iters)

        go0 = jax.lax.pmax(jnp.any(frontier_l).astype(jnp.int32), axes)
        init = (dist_l, parent_l, frontier_l, _zero_metrics(),
                jnp.int32(0), go0)
        out = jax.lax.while_loop(cond, body, init)
        return out[0], out[1], out[3]

    body = run_v1 if version == "v1" else run_v2
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def repair_distributed(sg: ShardedGraph, dist, parent, frontier, mesh,
                       axes=("graph",), *, version="v2",
                       max_iters: int = 1_000_000, capacity: int = 0):
    """Incremental repair of a distributed SSSP state after an edge delta.

    ``dist``/``parent``/``frontier`` are the host-invalidated tentative
    state over the true (or padded) vertex range, as produced by
    :func:`repro.delta.repair_state` from an
    :class:`~repro.delta.AppliedDelta`: invalidated subtree entries reset
    to ``(+inf, -1)`` and the frontier seeded from vertices incident to
    the changed edges.  The engine re-relaxes to fixpoint with the
    version's collective merge (see :func:`_build_repair_engine`); the
    result is bitwise-identical to a from-scratch
    :func:`sssp_distributed` solve on the patched graph, at a cost
    proportional to the delta's blast radius.

    Returns ``(dist, parent, metrics)`` over the padded ``n_pad`` range
    (slice ``[:n]`` for the true vertices); metrics count only the
    repair's own relaxation work.
    """
    if version not in ("v1", "v2", "v3"):
        raise ValueError(f"unknown version {version!r}; expected "
                         "v1/v2/v3")
    p, _ = sg.src.shape
    block = int(sg.deg.shape[1])
    n_pad = int(p) * block
    dist = jnp.asarray(dist, jnp.float32)
    pad = n_pad - dist.shape[0]
    dist = jnp.pad(dist, (0, pad), constant_values=jnp.inf)
    parent = jnp.pad(jnp.asarray(parent, jnp.int32), (0, pad),
                     constant_values=-1)
    frontier = jnp.pad(jnp.asarray(frontier, bool), (0, pad))
    axes_key = axes if isinstance(axes, str) else tuple(axes)
    cap = (capacity or max(block // 16, 8)) if version == "v3" else 0
    fn = _build_repair_engine(mesh, axes_key, version, block, n_pad,
                              max_iters, cap)
    with profiling.annotate(f"repro:repair_dist_dispatch:{version}"):
        return fn(sg, dist, parent, frontier)


# --- v2 -------------------------------------------------------------------

def _v2_body(n_pad, block, axes, params, max_iters, fused_rounds,
             axis_sizes, goal="tree", batch=False, compact_capacity: int = 0,
             bmeta=None, trace_cap=0, policy="static", alt=False):
    p = n_pad // block
    axis_names = (axes,) if isinstance(axes, str) else tuple(axes)
    adaptive = policy == "adaptive"

    def run(sg: ShardedGraph, *args):
        if alt:
            args, alt_d = args[:-1], args[-1]
        else:
            alt_d = None
        if bmeta is not None:
            bl, base_arr, source, goal_param = args
            bl = jax.tree.map(lambda x: x[0], bl)    # drop the shard axis
            base = base_arr[0]       # owner-block offset as data (see
            me = base // block       # _build_engine on why not axis_index)
        else:
            source, goal_param = args
            bl = None
            me = jnp.int32(0)
            for name, size in zip(axis_names, axis_sizes):
                me = me * size + jax.lax.axis_index(name)
            base = me * block
        src = sg.src.reshape(-1)          # global ids, sources owned locally
        dst = sg.dst.reshape(-1)
        w = sg.w.reshape(-1)
        deg_l = sg.deg.reshape(-1)        # [B] local block degrees
        rtow, n_edges2 = sg.rtow, sg.n_edges2
        max_w = rtow[-1]
        src_l = src - base                # local source index

        own_src = jnp.zeros((block,), jnp.float32)
        high_d0_hist = jax.lax.psum(
            stats.degree_hist(own_src, deg_l, 0.0), axes)
        high_d0 = stats.high_d_from_hist(high_d0_hist)

        def goal_reached(dist_l, lb, gp):
            """Distributed twin of sssp._goal_reached: ``dist`` lives
            block-sharded here, so the settled test is owner-local with a
            collective merge (pmax for the p2p hit, psum for the knear
            settled count).  Matches the single-device decision exactly —
            same lb, same settled invariant — so early exit keeps bitwise
            dist/parent parity."""
            if goal == "tree":
                return jnp.bool_(False)
            if goal == "p2p":
                own = (gp // block) == me
                loc = jnp.clip(gp - base, 0, block - 1)
                hit = own & relax.settled_mask(dist_l, lb)[loc]
                return jax.lax.pmax(hit.astype(jnp.int32), axes) > 0
            if goal == "bounded":
                return lb > gp
            if goal == "knear":
                n_settled = jax.lax.psum(jnp.sum(
                    relax.settled_mask(dist_l, lb).astype(jnp.int32)), axes)
                return n_settled >= gp + 1
            raise ValueError(f"unknown goal {goal!r}")

        def alt_bound(dist_l, ac):
            """The replicated per-round ALT prune bound: ``dist[target]``
            lives on its owner block, so one pmin broadcasts it (same
            own/loc pattern as the p2p goal test)."""
            own = (ac.tgt // block) == me
            loc = jnp.clip(ac.tgt - base, 0, block - 1)
            td = jax.lax.pmin(jnp.where(own, dist_l[loc], INF), axes)
            return jnp.minimum(ac.seed, td * ac.infl)

        def dense_exchange(best_g, win_g):
            """all_to_all reduce-scatter-min of per-block candidate partials."""
            recv_v = jax.lax.all_to_all(best_g.reshape(p, block), axes,
                                        split_axis=0, concat_axis=0)
            recv_w = jax.lax.all_to_all(win_g.reshape(p, block), axes,
                                        split_axis=0, concat_axis=0)
            return relax.combine_block_partials(recv_v, recv_w)

        def compact_exchange(best_g, win_g):
            """v3: exchange only the C best candidates per destination
            block — comm ∝ frontier cut, not N.  Falls back to the dense
            exchange when any block overflows C finite candidates (exact)."""
            cap = compact_capacity
            rows_v = best_g.reshape(p, block)
            rows_w = win_g.reshape(p, block)
            n_finite = jnp.sum(jnp.isfinite(rows_v), axis=1)
            overflow = jax.lax.pmax(
                jnp.any(n_finite > cap).astype(jnp.int32), axes) > 0

            def compact(_):
                # C smallest candidates per destination block
                neg, idx = jax.lax.top_k(-rows_v, cap)        # [p, cap]
                vals = -neg
                srcs = jnp.take_along_axis(rows_w, idx, axis=1)
                rv = jax.lax.all_to_all(vals, axes, split_axis=0,
                                        concat_axis=0)        # [p, cap]
                ri = jax.lax.all_to_all(idx, axes, split_axis=0,
                                        concat_axis=0)
                rs = jax.lax.all_to_all(srcs, axes, split_axis=0,
                                        concat_axis=0)
                flat_v = rv.reshape(-1)
                flat_i = ri.reshape(-1)
                flat_s = rs.reshape(-1)
                return relax.segment_min_with_winner(
                    flat_v, jnp.isfinite(flat_v), flat_s, flat_i, block)

            def dense(_):
                return dense_exchange(best_g, win_g)

            return jax.lax.cond(overflow, dense, compact, None)

        def merge(best_g, win_g):
            """Global per-destination partials -> the local block's
            ``(best_l, winner_l)`` via the version's collective."""
            if compact_capacity:
                return compact_exchange(best_g, win_g)
            return dense_exchange(best_g, win_g)

        def exchange(cand, mask):
            """Per-destination (min, winner) partials merged across shards;
            returns the local block's ``(best_l, winner_l)``."""
            best_g, win_g = relax.segment_min_with_winner(cand, mask, src,
                                                          dst, n_pad)
            return merge(best_g, win_g)

        def blocked_partials(dist_l, paths, parent_l, lb, ub, ac=None,
                             pb=None):
            """Blocked backend's push partial: ONE partials-megakernel
            launch over the shard's stacked tile-indexed slabs
            (see relax.blocked_shard_partials_fused), returning the
            ``(best, winner)`` pair plus the in-kernel tile/n_trav/
            n_relax/n_pruned counters — the flat O(E) candidate pass the
            segment_min branch needs for its metrics is folded into the
            kernel's scheduled tile pass."""
            return relax.blocked_shard_partials_fused(
                bl.src_local, bl.dst, bl.w, bl.tile_dst, bl.tile_first,
                bl.tile_order, dist_l, paths, parent_l, base, lb, ub,
                block_v=bmeta.block_v, n_dst_blocks=bmeta.n_dst_blocks,
                tile_e=bmeta.tile_e, use_kernel=bmeta.use_kernel,
                interpret=bmeta.interpret,
                alt_lb=None if ac is None else ac.lb, prune_bound=pb)

        local_edge = (dst // block) == me
        dst_local = jnp.clip(dst - base, 0, block - 1)

        def fused_local(dist_l, parent_l, frontier_l, lb, ub, metrics):
            """Paper §4.1 bucket fusion: FUSED local-only relaxation waves
            between synchronizations.  Only edges whose destination is
            owned locally relax; cross-shard updates wait for the next
            exchange.  Each wave is sync-free (no collectives)."""
            def wave(_, carry):
                dist_l, parent_l, front, acc, touched = carry
                paths = relax.leaf_pruned(front, dist_l, deg_l)
                cand, _, active = relax.edge_candidates(
                    dist_l[src_l], local_edge & paths[src_l],
                    parent_l[src_l], dst, w, lb, ub)
                best, winner = relax.segment_min_with_winner(
                    cand, active, src, dst_local, block)
                dist2, parent2, improved = relax.apply_updates(
                    dist_l, parent_l, best, winner)
                touched = touched + jnp.sum(active.astype(jnp.int32))
                return dist2, parent2, improved, acc | improved, touched

            dist_l, parent_l, _, acc, touched = jax.lax.fori_loop(
                0, fused_rounds, wave,
                (dist_l, parent_l, frontier_l, frontier_l,
                 jnp.int32(0)))
            metrics = metrics._replace(
                n_trav=metrics.n_trav + jax.lax.psum(touched, axes))
            return dist_l, parent_l, acc, metrics

        def one_round(dist_l, parent_l, frontier_l, lb, ub, metrics,
                      ac=None):
            paths = relax.leaf_pruned(frontier_l, dist_l, deg_l)
            # per-round prune bound from dist at round start (the same
            # recompute the single-device fused kernel does per round)
            pb = None if ac is None else alt_bound(dist_l, ac)
            n_prn = jnp.int32(0)
            if bmeta is None:
                cand, in_window, active = relax.edge_candidates(
                    dist_l[src_l], paths[src_l], parent_l[src_l], dst, w,
                    lb, ub)
                if ac is not None:
                    active, pruned = relax.alt_prune(cand, active,
                                                     ac.lb[dst], pb)
                    cand = jnp.where(active, cand, INF)
                    n_prn = jax.lax.psum(
                        jnp.sum(pruned.astype(jnp.int32)), axes)
                best_g, win_g = relax.segment_min_with_winner(
                    cand, active, src, dst, n_pad)
                n_tiles = jnp.float32(0)
                touched = jax.lax.psum(
                    jnp.sum(in_window.astype(jnp.int32)), axes)
                relaxed = jax.lax.psum(
                    jnp.sum(active.astype(jnp.int32)), axes)
                n_inv = jnp.float32(0)
            else:
                best_g, win_g, nt, trav, rlx, prn = blocked_partials(
                    dist_l, paths, parent_l, lb, ub, ac, pb)
                n_tiles = jax.lax.psum(nt.astype(jnp.float32), axes)
                touched = jax.lax.psum(trav, axes)
                relaxed = jax.lax.psum(rlx, axes)
                n_prn = jax.lax.psum(prn, axes)
                n_inv = jax.lax.psum(jnp.float32(1), axes)
            best_l, winner_l = merge(best_g, win_g)
            dist2, parent2, improved = relax.apply_updates(
                dist_l, parent_l, best_l, winner_l)
            nl_upd = jax.lax.psum(
                jnp.sum((improved & (deg_l > 1)).astype(jnp.int32)), axes)
            upd = jax.lax.psum(jnp.sum(improved.astype(jnp.int32)), axes)
            any_front = jax.lax.pmax(
                jnp.any(frontier_l).astype(jnp.int32), axes)
            metrics = metrics._replace(
                n_rounds=metrics.n_rounds + any_front,
                n_extended=metrics.n_extended + nl_upd,
                n_trav=metrics.n_trav + touched,
                n_relax=metrics.n_relax + relaxed,
                n_updates=metrics.n_updates + upd,
                n_pruned=metrics.n_pruned + n_prn,
                n_tiles_scanned=metrics.n_tiles_scanned + n_tiles,
                n_tiles_dense=metrics.n_tiles_dense + jnp.float32(
                    0 if bmeta is None else bmeta.dense_grid_tiles),
                n_invocations=metrics.n_invocations + n_inv)
            return dist2, parent2, improved, metrics

        def grouped_rounds(dist_l, parent_l, frontier_l, lb, ub, metrics,
                           ac=None):
            """Blocked ``fused_rounds``: up to ``fused_rounds`` COMPLETE
            synchronized rounds (each with its exchange) per stepping-loop
            body.  The round sequence — and with it dist/parent and every
            logical counter — is identical to the unfused engine by
            construction; only the outer while_loop bookkeeping amortizes.
            Clamped to a single round while ``lb <= 0`` so the first-step
            ub bootstrap still applies between rounds."""
            max_r = jnp.where(lb <= 0.0, jnp.int32(1),
                              jnp.int32(fused_rounds))

            def cond_f(c):
                # pure carry reads only — collectives may not appear in a
                # while_loop cond, so ``go`` is computed in the body
                return (c[5] > 0) & (c[4] < max_r)

            def body_f(c):
                dist_l, parent_l, front, metrics, r, _ = c
                dist2, parent2, improved, metrics = one_round(
                    dist_l, parent_l, front, lb, ub, metrics, ac)
                go = jax.lax.pmax(jnp.any(improved).astype(jnp.int32),
                                  axes)
                return dist2, parent2, improved, metrics, r + 1, go

            dist_l, parent_l, frontier_l, metrics, _, _ = \
                jax.lax.while_loop(cond_f, body_f,
                                   (dist_l, parent_l, frontier_l, metrics,
                                    jnp.int32(0), jnp.int32(1)))
            return dist_l, parent_l, frontier_l, metrics

        def relax_round(dist_l, parent_l, frontier_l, lb, ub, metrics,
                        ac=None):
            if fused_rounds > 0 and bmeta is not None:
                return grouped_rounds(dist_l, parent_l, frontier_l, lb, ub,
                                      metrics, ac)
            if fused_rounds > 0:
                # segment_min bucket fusion's local waves stay unpruned
                # (metrics-exempt already; the full rounds still prune)
                dist_l, parent_l, frontier_l, metrics = fused_local(
                    dist_l, parent_l, frontier_l, lb, ub, metrics)
            return one_round(dist_l, parent_l, frontier_l, lb, ub, metrics,
                             ac)

        def pull_round(dist_l, parent_l, st, lb, ub, metrics, ac=None,
                       pb=None):
            # mirrored push from the settled band (undirected store); the
            # requester's dist is remote, so the unsettled gate applies on
            # the local (destination-owner) side after the exchange.
            # Under ALT the requester receiving the update is ``dst``, so
            # requests with cand + lb[dst] > bound are cut (the mirrored
            # twin of the single-device requester-side alt_lb[src] cut).
            dv = dist_l[src_l]
            mask = (dv >= st) & (dv < lb) & (dv + w < ub)
            cand = jnp.where(mask, dv + w, INF)
            n_prn = jnp.int32(0)
            if ac is not None:
                mask, pruned = relax.alt_prune(cand, mask, ac.lb[dst], pb)
                cand = jnp.where(mask, cand, INF)
                n_prn = jax.lax.psum(
                    jnp.sum(pruned.astype(jnp.int32)), axes)
            best_l, winner_l = exchange(cand, mask)
            dist2, parent2, improved = relax.apply_updates(
                dist_l, parent_l, best_l, winner_l, gate=dist_l > lb)
            # scan/request sums equal the single-device definitions by edge
            # symmetry: every directed edge lives on exactly one shard.
            scans = jax.lax.psum(jnp.sum(
                ((dv > lb) & (w < ub - st)).astype(jnp.int32)), axes)
            reqs = jax.lax.psum(jnp.sum(mask.astype(jnp.int32)), axes)
            nl_upd = jax.lax.psum(
                jnp.sum((improved & (deg_l > 1)).astype(jnp.int32)), axes)
            upd = jax.lax.psum(jnp.sum(improved.astype(jnp.int32)), axes)
            metrics = metrics._replace(
                n_pull_trav=metrics.n_pull_trav + scans,
                n_extended=metrics.n_extended + nl_upd,
                n_relax=metrics.n_relax + reqs,
                n_pull_relax=metrics.n_pull_relax + reqs,
                n_updates=metrics.n_updates + upd,
                n_pruned=metrics.n_pruned + n_prn,
                n_rounds=metrics.n_rounds + 1)
            return dist2, parent2, metrics

        def dgap(dist_l, x, tparams=params, mult=None):
            g_, _, _ = _dstats_gap(dist_l, deg_l, rtow, n_edges2, x, tparams,
                                   axes, mult)
            return g_

        def transition(dist_l, parent_l, lb, ub, metrics, gp, ps=None,
                       ac=None):
            pend = dist_l[src_l] + w
            pend = jnp.where(pend >= ub, pend, INF)
            if ac is not None:
                # a pending candidate the ALT bound would cut can never
                # improve the target, so skipping it in fast-forward/
                # termination is exact for the p2p contract
                bound_eff = alt_bound(dist_l, ac)
                pend = jnp.where(pend + ac.lb[dst] > bound_eff, INF, pend)
            min_pending = jax.lax.pmin(jnp.min(pend), axes)
            done = ~jnp.isfinite(min_pending)
            if ps is not None:
                # observe -> adapt: the metrics counters are psum'd, so the
                # policy state stays replicated across shards
                ps = stepping.adaptive_update(ps, metrics.n_rounds,
                                              metrics.n_relax,
                                              metrics.n_updates)
                tparams = stepping.effective_params(ps)
                mult = ps.mult
            else:
                tparams, mult = params, None
            st_next, gap_ub = _dstats_compute_st(
                dist_l, deg_l, rtow, n_edges2, lb, ub, tparams, axes, mult)
            lb2 = ub
            ub2 = lb2 + gap_ub
            ffwd = (min_pending >= ub2) & ~done
            lb2 = jnp.where(ffwd, min_pending, lb2)
            gap3 = dgap(dist_l, lb2, tparams, mult)
            ub2 = jnp.where(ffwd, lb2 + gap3, ub2)
            st_next = jnp.minimum(st_next, lb2)

            def with_pull(args):
                return pull_round(args[0], args[1], st_next, lb2, ub2,
                                  args[2], ac,
                                  None if ac is None else bound_eff)

            dist_l, parent_l, metrics = jax.lax.cond(
                st_next < lb2, with_pull, lambda a: a,
                (dist_l, parent_l, metrics))
            done = done | goal_reached(dist_l, lb2, gp)
            frontier = relax.window_frontier(dist_l, st_next, lb2, ub2,
                                             max_w) & ~done
            metrics = metrics._replace(
                n_steps=metrics.n_steps + jnp.where(done, 0, 1))
            out = (dist_l, parent_l, frontier, lb2, ub2, st_next, done,
                   metrics)
            return out if ps is None else out + (ps,)

        def cond(s):
            return (~s.done) & (s.iters < max_iters)

        def run_one(source, gp):
            dist0 = jnp.where(jnp.arange(block) + base == source, 0.0, INF)
            parent0 = jnp.where(jnp.arange(block) + base == source, source,
                                -1).astype(jnp.int32)
            frontier0 = (jnp.arange(block) + base) == source
            metrics0 = _zero_metrics()._replace(n_extended=jnp.int32(1))
            ac = None if alt_d is None else _make_alt_ctx(alt_d, source,
                                                          gp, n_pad)

            def body(s: _V2State):
                dist_l, parent_l, frontier, metrics = relax_round(
                    s.dist, s.parent, s.frontier, s.lb, s.ub, s.metrics,
                    ac)

                def tighten(ub):
                    mask = (deg_l.astype(jnp.float32) >= high_d0) \
                        & (dist_l > 0)
                    local = jnp.min(jnp.where(mask, dist_l, INF))
                    return jnp.minimum(ub, jax.lax.pmin(local, axes))
                ub = jax.lax.cond(s.lb <= 0.0, tighten, lambda u: u, s.ub)

                any_front = jax.lax.pmax(jnp.any(frontier).astype(jnp.int32),
                                         axes) > 0

                def keep(args):
                    dist_l, parent_l, lb, ub, metrics = args
                    return (dist_l, parent_l, frontier, lb, ub, s.st, s.done,
                            metrics)

                def trans(args):
                    return transition(args[0], args[1], args[2], args[3],
                                      args[4], gp, ac=ac)

                (dist_l, parent_l, frontier, lb, ub, st2, done, metrics) = \
                    jax.lax.cond(any_front, keep, trans,
                                 (dist_l, parent_l, s.lb, ub, metrics))
                return _V2State(dist_l, parent_l, frontier, lb, ub, st2,
                                done, s.iters + 1, metrics)

            def body_a(carry):
                s, ps = carry
                dist_l, parent_l, frontier, metrics = relax_round(
                    s.dist, s.parent, s.frontier, s.lb, s.ub, s.metrics,
                    ac)

                def tighten(ub):
                    mask = (deg_l.astype(jnp.float32) >= high_d0) \
                        & (dist_l > 0)
                    local = jnp.min(jnp.where(mask, dist_l, INF))
                    return jnp.minimum(ub, jax.lax.pmin(local, axes))
                ub = jax.lax.cond(s.lb <= 0.0, tighten, lambda u: u, s.ub)

                any_front = jax.lax.pmax(jnp.any(frontier).astype(jnp.int32),
                                         axes) > 0

                def keep(args):
                    dist_l, parent_l, lb, ub, metrics, ps = args
                    return (dist_l, parent_l, frontier, lb, ub, s.st, s.done,
                            metrics, ps)

                def trans(args):
                    return transition(args[0], args[1], args[2], args[3],
                                      args[4], gp, ps=args[5], ac=ac)

                (dist_l, parent_l, frontier, lb, ub, st2, done, metrics,
                 ps) = jax.lax.cond(any_front, keep, trans,
                                    (dist_l, parent_l, s.lb, ub, metrics,
                                     ps))
                return _V2State(dist_l, parent_l, frontier, lb, ub, st2,
                                done, s.iters + 1, metrics), ps

            init = _V2State(dist0, parent0, frontier0, jnp.float32(0.0), INF,
                            jnp.float32(0.0), jnp.bool_(False), jnp.int32(0),
                            metrics0)
            if not adaptive:
                if trace_cap <= 0:
                    out = jax.lax.while_loop(cond, body, init)
                    return out.dist, out.parent, out.metrics

                def traced_body(carry):
                    s, buf = carry
                    s1 = body(s)
                    m0, m1 = s.metrics, s1.metrics
                    stepped = (m1.n_steps > m0.n_steps) | (s1.done & ~s.done)
                    # the frontier is block-sharded here: psum the local
                    # census (one extra collective per iteration, traced
                    # solves only)
                    fsz = jax.lax.psum(
                        jnp.sum(s.frontier.astype(jnp.int32)), axes)
                    buf = _dtrace_record(buf, s.iters, fsz, s.lb, s.ub, s.st,
                                         stepped, m0, m1)
                    return s1, buf

                out, buf = jax.lax.while_loop(
                    lambda c: cond(c[0]), traced_body,
                    (init, trace_init(trace_cap)))
                return out.dist, out.parent, out.metrics, buf

            init_a = (init, stepping.policy_init(params))
            if trace_cap <= 0:
                out, _ = jax.lax.while_loop(lambda c: cond(c[0]), body_a,
                                            init_a)
                return out.dist, out.parent, out.metrics

            def traced_body_a(carry):
                c, buf = carry
                s = c[0]
                c1 = body_a(c)
                s1 = c1[0]
                m0, m1 = s.metrics, s1.metrics
                stepped = (m1.n_steps > m0.n_steps) | (s1.done & ~s.done)
                fsz = jax.lax.psum(
                    jnp.sum(s.frontier.astype(jnp.int32)), axes)
                buf = _dtrace_record(buf, s.iters, fsz, s.lb, s.ub, s.st,
                                     stepped, m0, m1)
                return c1, buf

            (out, _), buf = jax.lax.while_loop(
                lambda c: cond(c[0][0]), traced_body_a,
                (init_a, trace_init(trace_cap)))
            return out.dist, out.parent, out.metrics, buf

        if batch:
            return jax.lax.map(lambda a: run_one(*a), (source, goal_param))
        return run_one(source, goal_param)

    return run
