"""Graph containers and preprocessing for the EIC SSSP framework.

The paper (§4.1) preprocesses every graph by
  (1) sorting each vertex's incident edges in weight order, and
  (2) quantizing the edge-weight distribution into an ``RtoW[RATIO_NUM]``
      lookup table with ``RtoW[x] = maxW(G, x/(RATIO_NUM-1))``.

Host-side construction is done in numpy (the data pipeline is not a TPU
workload); the jit-facing container :class:`DeviceGraph` is a NamedTuple of
jnp arrays so it can flow through ``jax.jit`` / ``shard_map`` unchanged.
An undirected graph stores every edge in both directions; a directed one
(``build_csr(..., symmetrize=False)``) stores each arc ``u -> v`` once, in
``u``'s row.  Which of the two a graph is, is its ``directed`` mark: a
Python bool that rides in the pytree's aux data, so the engines specialize
on it at compile time.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .config import ConfigError, resolve_interpret

RATIO_NUM = 4096          # paper §4.1: RATIO_NUM = 2^12
ST_NUM = 1024             # paper §4.1: ST_NUM = 2^10
FUSED = 256               # paper §4.1: FUSED = 2^8
DEFAULT_ALPHA = 3         # paper §4.1: alpha = 3
DEFAULT_BETA = 0.9        # paper §4.1: beta = 0.9

# Degree-histogram bucketing used by highD(): exact for deg < EXACT_DEG,
# log2 buckets above.  90 buckets covers degree up to 2^31.
EXACT_DEG = 64
N_DEG_BUCKETS = EXACT_DEG + 26


class DeviceGraph(NamedTuple):
    """Immutable device-resident CSR + flat-edge-list graph.

    ``directed`` is static (pytree aux data, not a leaf): ``False`` when
    the slots hold every edge both ways, ``True`` when each slot is one
    arc ``src -> dst`` and nothing is mirrored.
    """
    src: jnp.ndarray       # [M] int32 — source of each directed edge slot
    dst: jnp.ndarray       # [M] int32 — destination
    w: jnp.ndarray         # [M] float32 — weight (sorted ascending within row)
    row_ptr: jnp.ndarray   # [N+1] int32 — CSR offsets into (dst, w)
    deg: jnp.ndarray       # [N] int32 — out-degree (slots in the row)
    rtow: jnp.ndarray      # [RATIO_NUM] float32 — weight quantile LUT
    max_w: jnp.ndarray     # scalar float32 — maxW(G, 1)
    n_edges2: jnp.ndarray  # scalar int32 — M, the slot count (sum of deg):
    #                        2|E| undirected, the arc count directed
    directed: bool = False

    @property
    def n(self) -> int:
        return self.deg.shape[0]

    @property
    def m(self) -> int:
        return self.src.shape[0]


jax.tree_util.register_pytree_node(
    DeviceGraph,
    lambda g: (tuple(g[:-1]), g.directed),
    lambda directed, arrays: DeviceGraph(*arrays, directed=directed),
)


@dataclasses.dataclass(frozen=True)
class HostGraph:
    """numpy-side graph (builder product; converted once per run).
    ``directed`` as in :class:`DeviceGraph`."""
    n: int
    src: np.ndarray
    dst: np.ndarray
    w: np.ndarray
    row_ptr: np.ndarray
    deg: np.ndarray
    rtow: np.ndarray
    max_w: float
    directed: bool = False

    @property
    def m(self) -> int:
        return int(self.src.shape[0])

    def to_device(self) -> DeviceGraph:
        return DeviceGraph(
            src=jnp.asarray(self.src, jnp.int32),
            dst=jnp.asarray(self.dst, jnp.int32),
            w=jnp.asarray(self.w, jnp.float32),
            row_ptr=jnp.asarray(self.row_ptr, jnp.int32),
            deg=jnp.asarray(self.deg, jnp.int32),
            rtow=jnp.asarray(self.rtow, jnp.float32),
            max_w=jnp.float32(self.max_w),
            n_edges2=jnp.int32(self.m),
            directed=self.directed,
        )


def _weight_quantile_lut(w: np.ndarray, ratio_num: int = RATIO_NUM) -> np.ndarray:
    """``RtoW[x] = maxW(G, x/(ratio_num-1))`` — P(w(e) <= maxW(G, r)) = r."""
    if w.size == 0:
        return np.zeros((ratio_num,), np.float32)
    qs = np.linspace(0.0, 1.0, ratio_num)
    return np.quantile(w, qs).astype(np.float32)


# public name for out-of-module builders (repro.delta patches the CSR and
# must recompute the LUT bitwise-identically to build_csr: float64 in)
weight_quantile_lut = _weight_quantile_lut


def build_csr(n: int, eu: np.ndarray, ev: np.ndarray, ew: np.ndarray,
              symmetrize: bool = True) -> HostGraph:
    """Build the preprocessed CSR from an edge list.

    With ``symmetrize`` (the default) ``(eu[i], ev[i], ew[i])`` is one
    undirected edge, both directions are stored, and the graph is marked
    undirected.  Without it, it is one arc ``eu[i] -> ev[i]``, stored
    once, and the graph is marked directed: the engines then relax arcs
    only forwards.  Per-vertex adjacency is sorted by weight ascending
    (paper §4.1 preprocessing), which lets the kernel bound in-window
    edges with a binary search instead of a scan.
    """
    eu = np.asarray(eu, np.int64)
    ev = np.asarray(ev, np.int64)
    ew = np.asarray(ew, np.float64)
    if symmetrize:
        s = np.concatenate([eu, ev])
        d = np.concatenate([ev, eu])
        w = np.concatenate([ew, ew])
    else:
        s, d, w = eu, ev, ew
    # sort by (src, weight) -> weight-sorted rows
    order = np.lexsort((w, s))
    s, d, w = s[order], d[order], w[order]
    deg = np.bincount(s, minlength=n).astype(np.int32)
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=row_ptr[1:])
    # RtoW is built from the stored slots; symmetrizing duplicates every
    # weight, so an undirected graph's quantiles are its edges' own.
    rtow = _weight_quantile_lut(w)
    return HostGraph(
        n=n,
        src=s.astype(np.int32),
        dst=d.astype(np.int32),
        w=w.astype(np.float32),
        row_ptr=row_ptr.astype(np.int32),
        deg=deg,
        rtow=rtow,
        max_w=float(w.max()) if w.size else 0.0,
        directed=not symmetrize,
    )


# ---------------------------------------------------------------------------
# Blocked layout for the Pallas edge-relax kernel (relax backend
# "blocked_pallas" and the distributed "blocked" backend; see core/relax.py).
# ---------------------------------------------------------------------------

# block/tile defaults are the kernel's own (single source of truth)
from ..kernels.edge_relax.edge_relax import (  # noqa: E402
    DEFAULT_BLOCK_V, DEFAULT_TILE_E)


class BlockedEdges(NamedTuple):
    """One source-block edge slab with its CSR-of-tiles index.

    Edges are sorted by destination block and every (src-block, dst-block)
    bucket is padded to a tile boundary, so each ``tile_e``-edge tile
    belongs to exactly one destination block — the kernel's ragged grid
    iterates tiles, not the dense (dst block x tile) product.
    """
    src_local: jnp.ndarray       # [NT*tile_e] int32 — block-local source
    dst: jnp.ndarray             # [NT*tile_e] int32 — global destination id
    w: jnp.ndarray               # [NT*tile_e] float32 (+inf on padding)
    tile_dst: jnp.ndarray        # [NT] int32 — dst block per tile (sorted)
    tile_first: jnp.ndarray      # [NT] bool — first tile of each bucket
    bucket_nonempty: jnp.ndarray  # [n_dst_blocks] bool — bucket has edges


@dataclasses.dataclass(frozen=True)
class BlockedGraph:
    """2-D blocked edge layout: edges bucketed by (src block x dst block).

    Sources are grouped into ``n_blocks`` blocks of ``block_v`` vertices so
    that each slab's source-side ``dist``/``frontier`` slice fits in VMEM;
    within a slab, edges are bucketed by destination block with each
    bucket tile-aligned (see :func:`bucket_edges`), giving the kernel a
    per-bucket tile-range index instead of a full scan.  For the whole
    graph (``build_blocked``) sources and destinations share one blocking
    (``n_blocks == n_dst_blocks``, ``src_base == 0``); a shard slice
    (:func:`slice_for_shard`) covers only its own source block range
    (``src_base = shard * block``) while destinations stay global.
    Static layout parameters are pytree aux data (shapes stay static under
    ``jax.jit``); only the arrays are traced.  ``directed`` is the source
    graph's mark (:class:`DeviceGraph`), which the leaf rule follows.
    """
    n: int                               # true vertex count (pre-padding)
    block_v: int
    n_blocks: int                        # source blocks in this layout
    n_dst_blocks: int                    # destination blocks (global range)
    src_base: int                        # global id of the first source
    tile_e: int
    use_kernel: bool                     # Pallas kernel vs jnp reference
    interpret: bool                      # Pallas interpret mode (CPU)
    dense_grid_tiles: int                # per-round cost of the dense scan
    slabs: Tuple[BlockedEdges, ...]      # one slab per source block
    deg: jnp.ndarray                     # [n_blocks * block_v] int32, 0-padded
    directed: bool = False

    @property
    def n_pad(self) -> int:
        """Padded source-side vertex count."""
        return self.n_blocks * self.block_v

    @property
    def n_out(self) -> int:
        """Padded destination-side vertex count (kernel output range)."""
        return self.n_dst_blocks * self.block_v


jax.tree_util.register_pytree_node(
    BlockedGraph,
    lambda bg: ((bg.slabs, bg.deg),
                (bg.n, bg.block_v, bg.n_blocks, bg.n_dst_blocks,
                 bg.src_base, bg.tile_e, bg.use_kernel, bg.interpret,
                 bg.dense_grid_tiles, bg.directed)),
    lambda aux, ch: BlockedGraph(n=aux[0], block_v=aux[1], n_blocks=aux[2],
                                 n_dst_blocks=aux[3], src_base=aux[4],
                                 tile_e=aux[5], use_kernel=aux[6],
                                 interpret=aux[7], dense_grid_tiles=aux[8],
                                 directed=aux[9], slabs=ch[0], deg=ch[1]),
)


# bytes one padded edge slot costs in a blocked layout: src_local int32,
# dst int32 and w float32; each tile adds its index (tile_dst int32 and
# tile_first bool)
SLOT_BYTES = 12
TILE_INDEX_BYTES = 5


def device_memory_bytes() -> Optional[int]:
    """The default device's memory, where its backend reports it (TPU
    and GPU do; the CPU backend reports none)."""
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("bytes_limit")


def layout_bytes(n_tiles: int, tile_e: int) -> int:
    """Bytes of ``n_tiles`` padded tiles of a blocked layout."""
    return int(n_tiles) * (tile_e * SLOT_BYTES + TILE_INDEX_BYTES)


def check_layout_bytes(n_tiles: int, tile_e: int, what: str,
                       max_bytes: Optional[int] = None) -> int:
    """Padded bytes of a blocked layout, reckoned before it is allocated.

    Raises :class:`~repro.core.config.ConfigError` when they exceed
    ``max_bytes`` (default: the device's memory), so an oversized layout
    fails here instead of filling the host.  Returns the byte count.
    """
    nbytes = layout_bytes(n_tiles, tile_e)
    limit = device_memory_bytes() if max_bytes is None else max_bytes
    if limit is not None and nbytes > limit:
        raise ConfigError(
            f"{what}: the padded blocked layout needs {nbytes} bytes "
            f"({n_tiles} tiles of {tile_e} slots), more than the {limit} "
            f"bytes available; use the segment_min backend")
    return nbytes


def bucket_tiles(src, dst, *, src_blocks: int, n_dst_blocks: int,
                 block_v: int, tile_e: int) -> np.ndarray:
    """Tiles per (source block, destination block) bucket, from one
    bincount (no slab is materialized): ``[src_blocks, n_dst_blocks]``.
    ``src`` ids are relative to the layout's first source."""
    key = (np.asarray(src, np.int64) // block_v) * n_dst_blocks \
        + np.asarray(dst, np.int64) // block_v
    counts = np.bincount(key, minlength=src_blocks * n_dst_blocks)
    return -(-counts.reshape(src_blocks, n_dst_blocks) // tile_e)


def _layout_tiles(src, dst, *, src_blocks: int, n_dst_blocks: int,
                  block_v: int, tile_e: int) -> int:
    """Tiles of a layout whose slabs keep their minimal count (>= 1)."""
    tiles = bucket_tiles(src, dst, src_blocks=src_blocks,
                         n_dst_blocks=n_dst_blocks, block_v=block_v,
                         tile_e=tile_e)
    return int(np.maximum(tiles.sum(axis=1), 1).sum())


def blocked_layout_bytes(g, *, block_v: int = DEFAULT_BLOCK_V,
                         tile_e: int = DEFAULT_TILE_E) -> int:
    """Padded bytes :func:`build_blocked` would allocate for ``g``."""
    nb = max(-(-int(np.asarray(g.deg).shape[0]) // block_v), 1)
    return layout_bytes(_layout_tiles(g.src, g.dst, src_blocks=nb,
                                      n_dst_blocks=nb, block_v=block_v,
                                      tile_e=tile_e), tile_e)


def bucket_edges(src_local, dst, w, *, n_dst_blocks: int, block_v: int,
                 tile_e: int, n_tiles: int = 0,
                 max_bytes: Optional[int] = None):
    """Bucket one slab's edges by destination block, tile-aligned.

    Edges are sorted by ``dst // block_v`` (stable) and each non-empty
    bucket is padded to a multiple of ``tile_e`` — so no tile straddles
    two destination blocks and the kernel can iterate a bucket's tile
    *range* instead of masking a full scan.  Padding slots carry
    ``w=+inf`` (never in-window, never activating a tile).

    ``n_tiles`` > 0 pads the slab to exactly that many tiles (shape
    uniformity across shard slabs under ``shard_map``); 0 keeps the
    minimal count (always >= 1, so the kernel grid is never empty).

    ``max_bytes`` caps the slab's padded bytes (see
    :func:`check_layout_bytes`).

    Returns numpy arrays ``(src_local, dst, w, tile_dst, tile_first,
    bucket_nonempty, tile_ptr)``; ``tile_ptr`` [n_dst_blocks + 1] is the
    CSR-of-tiles index (``tile_dst`` is its expansion).
    """
    src_local = np.asarray(src_local, np.int32)
    dst = np.asarray(dst, np.int32)
    w = np.asarray(w, np.float32)
    db = dst // block_v
    if db.size and (db.min() < 0 or db.max() >= n_dst_blocks):
        raise ValueError(f"dst ids outside the {n_dst_blocks} x {block_v} "
                         "destination range")
    order = np.argsort(db, kind="stable")
    src_local, dst, w, db = (src_local[order], dst[order], w[order],
                             db[order])
    counts = np.bincount(db, minlength=n_dst_blocks).astype(np.int64)
    tiles_per = -(-counts // tile_e)              # ceil; 0 for empty buckets
    nt_real = int(tiles_per.sum())
    nt_min = max(nt_real, 1)
    if n_tiles and n_tiles < nt_min:
        raise ValueError(f"n_tiles={n_tiles} < required {nt_min}")
    nt = n_tiles if n_tiles else nt_min
    check_layout_bytes(nt, tile_e, "bucket_edges", max_bytes)
    tile_ptr = np.zeros(n_dst_blocks + 1, np.int64)
    np.cumsum(tiles_per, out=tile_ptr[1:])
    s_out = np.zeros(nt * tile_e, np.int32)
    d_out = np.zeros(nt * tile_e, np.int32)
    w_out = np.full(nt * tile_e, np.inf, np.float32)
    off = np.zeros(n_dst_blocks + 1, np.int64)
    np.cumsum(counts, out=off[1:])
    # each edge lands at its bucket's tile base + its rank in the bucket
    pos = tile_ptr[db] * tile_e + (np.arange(db.size) - off[db])
    s_out[pos] = src_local
    d_out[pos] = dst
    w_out[pos] = w
    tile_dst = np.zeros(nt, np.int32)
    tile_dst[:nt_real] = np.repeat(np.arange(n_dst_blocks, dtype=np.int32),
                                   tiles_per)
    if nt > nt_real and nt_real:
        # surplus pad tiles repeat the last real block id so a (defensive)
        # visit can never revisit an earlier, already-flushed output block
        tile_dst[nt_real:] = tile_dst[nt_real - 1]
    tile_first = np.zeros(nt, bool)
    tile_first[tile_ptr[:-1][counts > 0]] = True
    tile_first[0] = True                  # >= 1 scheduled tile every round
    return (s_out, d_out, w_out, tile_dst, tile_first, counts > 0,
            tile_ptr.astype(np.int32))


def _slab_edges(s_l, d, ww, *, n_dst_blocks, block_v, tile_e, n_tiles=0):
    se, de, we, td, tf, bne, _ = bucket_edges(
        s_l, d, ww, n_dst_blocks=n_dst_blocks, block_v=block_v,
        tile_e=tile_e, n_tiles=n_tiles)
    return BlockedEdges(src_local=jnp.asarray(se), dst=jnp.asarray(de),
                        w=jnp.asarray(we), tile_dst=jnp.asarray(td),
                        tile_first=jnp.asarray(tf),
                        bucket_nonempty=jnp.asarray(bne))


def _by_src_block(src, *arrays, block_v: int, n_blocks: int):
    """Split edge arrays by source block with one stable sort (one
    ``[lo, hi)`` run per block) instead of one mask per block."""
    sb = np.asarray(src) // block_v
    order = np.argsort(sb, kind="stable")
    bounds = np.searchsorted(sb[order], np.arange(n_blocks + 1))
    arrays = [np.asarray(a)[order] for a in (src,) + arrays]
    for b in range(n_blocks):
        lo, hi = bounds[b], bounds[b + 1]
        yield tuple(a[lo:hi] for a in arrays)


def build_blocked(g, *, block_v: int = DEFAULT_BLOCK_V,
                  tile_e: int = DEFAULT_TILE_E, use_kernel: bool = True,
                  interpret: Optional[bool] = None,
                  max_bytes: Optional[int] = None) -> BlockedGraph:
    """Pre-bucket a graph (``HostGraph`` or ``DeviceGraph``) for the kernel.

    Host-side (concrete shapes are required for the static tile padding);
    call once per graph, outside ``jit``.  The padded size is reckoned
    from the bucket counts first and checked against ``max_bytes``
    (default: the device's memory) before any slab is allocated.
    """
    interpret = resolve_interpret(interpret)
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    w = np.asarray(g.w)
    deg = np.asarray(g.deg)
    n = int(deg.shape[0])
    n_blocks = max(-(-n // block_v), 1)
    check_layout_bytes(
        _layout_tiles(src, dst, src_blocks=n_blocks, n_dst_blocks=n_blocks,
                      block_v=block_v, tile_e=tile_e),
        tile_e, "build_blocked", max_bytes)
    slabs = []
    dense_tiles = 0
    for b, (s_b, d_b, w_b) in enumerate(
            _by_src_block(src, dst, w, block_v=block_v, n_blocks=n_blocks)):
        slabs.append(_slab_edges(s_b - b * block_v, d_b, w_b,
                                 n_dst_blocks=n_blocks, block_v=block_v,
                                 tile_e=tile_e))
        # what the dense (n_dst_blocks x n_tiles) grid scanned per round
        dense_tiles += n_blocks * max(-(-int(s_b.shape[0]) // tile_e), 1)
    deg_pad = np.zeros(n_blocks * block_v, np.int32)
    deg_pad[:n] = deg
    return BlockedGraph(n=n, block_v=block_v, n_blocks=n_blocks,
                        n_dst_blocks=n_blocks, src_base=0, tile_e=tile_e,
                        use_kernel=use_kernel, interpret=interpret,
                        dense_grid_tiles=dense_tiles, slabs=tuple(slabs),
                        deg=jnp.asarray(deg_pad), directed=g.directed)


def shard_block_v(block: int, block_v: int) -> int:
    """Largest divisor of the shard block size that is <= ``block_v``.

    Shard slabs must tile the owner block exactly (the exchanged partials
    reshape to ``(P, block)``), so the requested ``block_v`` is snapped
    down to a divisor of ``block``.
    """
    if block <= 0:
        raise ValueError("block must be positive")
    for d in range(min(block_v, block), 0, -1):
        if block % d == 0:
            return d
    return 1


def slice_for_shard(g, shard: int, n_shards: int, *,
                    block_v: int = DEFAULT_BLOCK_V,
                    tile_e: int = DEFAULT_TILE_E, n_tiles: int = 0,
                    use_kernel: bool = True,
                    interpret: Optional[bool] = None,
                    max_bytes: Optional[int] = None) -> BlockedGraph:
    """Blocked layout for one shard's CSR slice (sources = owner block).

    Vertex ownership matches :func:`repro.core.distributed.shard_graph`:
    shard ``q`` owns the contiguous block ``[q*B, (q+1)*B)`` with
    ``B = ceil(n / n_shards)``, and its slab holds every edge whose
    *source* it owns.  The returned layout's source blocks tile that
    owner block (``src_base = q*B``; ``block_v`` snapped to a divisor of
    ``B`` via :func:`shard_block_v`) while destinations span the full
    padded ``n_shards * B`` range — so the per-destination partials line
    up with the engines' ``all_to_all`` exchange.  ``n_tiles`` > 0 pads
    every slab to that tile count (uniform shapes across shards, a
    ``shard_map`` requirement).  The padded size is checked against
    ``max_bytes`` (default: the device's memory) before allocating.
    """
    interpret = resolve_interpret(interpret)
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    w = np.asarray(g.w)
    deg = np.asarray(g.deg)
    n = int(deg.shape[0])
    if not 0 <= shard < n_shards:
        raise ValueError(f"shard {shard} out of range for {n_shards}")
    block = -(-n // n_shards)
    n_pad = block * n_shards
    bv = shard_block_v(block, block_v)
    n_src_blocks = block // bv
    n_dst_blocks = n_pad // bv
    lo = shard * block
    m_shard = (src >= lo) & (src < lo + block)
    src_s, dst_s, w_s = src[m_shard], dst[m_shard], w[m_shard]
    check_layout_bytes(
        n_src_blocks * n_tiles if n_tiles else _layout_tiles(
            src_s - lo, dst_s, src_blocks=n_src_blocks,
            n_dst_blocks=n_dst_blocks, block_v=bv, tile_e=tile_e),
        tile_e, "slice_for_shard", max_bytes)
    slabs = []
    dense_tiles = 0
    for b, (s_b, d_b, w_b) in enumerate(_by_src_block(
            src_s - lo, dst_s, w_s, block_v=bv, n_blocks=n_src_blocks)):
        slabs.append(_slab_edges(s_b - b * bv, d_b, w_b,
                                 n_dst_blocks=n_dst_blocks, block_v=bv,
                                 tile_e=tile_e, n_tiles=n_tiles))
        dense_tiles += n_dst_blocks * max(-(-int(s_b.shape[0]) // tile_e), 1)
    deg_pad = np.zeros(block, np.int32)
    hi = min(lo + block, n)
    if hi > lo:
        deg_pad[:hi - lo] = deg[lo:hi]
    return BlockedGraph(n=n, block_v=bv, n_blocks=n_src_blocks,
                        n_dst_blocks=n_dst_blocks, src_base=lo,
                        tile_e=tile_e, use_kernel=use_kernel,
                        interpret=interpret, dense_grid_tiles=dense_tiles,
                        slabs=tuple(slabs), deg=jnp.asarray(deg_pad))


def degree_bucket_np(deg: np.ndarray) -> np.ndarray:
    """Bucket index for the highD() histogram (exact < EXACT_DEG, log2 above)."""
    deg = np.asarray(deg)
    small = deg < EXACT_DEG
    log_b = EXACT_DEG + np.clip(
        np.floor(np.log2(np.maximum(deg, 1))).astype(np.int32) - 5, 0, 25)
    return np.where(small, deg, log_b).astype(np.int32)


def degree_bucket(deg: jnp.ndarray) -> jnp.ndarray:
    """jnp version of :func:`degree_bucket_np`."""
    small = deg < EXACT_DEG
    logd = jnp.log2(jnp.maximum(deg, 1).astype(jnp.float32))
    log_b = EXACT_DEG + jnp.clip(jnp.floor(logd).astype(jnp.int32) - 5, 0, 25)
    return jnp.where(small, deg, log_b).astype(jnp.int32)


def bucket_representative() -> jnp.ndarray:
    """Representative degree value per histogram bucket (midpoint of range)."""
    reps = np.arange(N_DEG_BUCKETS, dtype=np.float32)
    for b in range(EXACT_DEG, N_DEG_BUCKETS):
        lo = 2 ** (b - EXACT_DEG + 5)
        reps[b] = 1.5 * lo  # geometric midpoint of [2^k, 2^{k+1})
    return jnp.asarray(reps)
