"""jit'd public wrappers for the edge_relax Pallas kernels.

``interpret=None`` (the default) resolves from the platform through
:func:`repro.core.config.resolve_interpret`: the Pallas interpreter on
CPU, compiled Mosaic kernels on a TPU.  ``use_kernel=False`` selects the
jnp reference twins (``ref.py``) by name; it is never a silent fallback.
"""
from __future__ import annotations

from typing import Optional

from .edge_relax import (DEFAULT_BLOCK_V, DEFAULT_TILE_E, FUSED_COUNTERS,
                         PARTIAL_COUNTERS, edge_relax,
                         edge_relax_fused, edge_relax_partials,
                         schedule_tiles)
from .ref import edge_relax_fused_ref, edge_relax_partials_ref, edge_relax_ref

__all__ = ["edge_relax", "edge_relax_ref", "edge_relax_fused",
           "edge_relax_fused_ref", "edge_relax_partials",
           "edge_relax_partials_ref", "relax_bucket", "relax_fused",
           "relax_partials", "schedule_tiles", "FUSED_COUNTERS",
           "PARTIAL_COUNTERS"]


def relax_bucket(dist_block, frontier_block, src_local, dst_local, w,
                 tile_dst, tile_first, bucket_nonempty, lb, ub, *,
                 block_v: int = DEFAULT_BLOCK_V, n_dst_blocks: int = 1,
                 tile_e: int = DEFAULT_TILE_E, use_kernel: bool = True,
                 interpret: Optional[bool] = None, alt_lb=None,
                 prune_bound=None):
    """Dispatch: Pallas kernel (TPU hot path) or jnp reference fallback.

    Both paths return ``(vals, winners, n_tiles)`` over the full
    ``n_dst_blocks * block_v`` destination range; ``n_tiles`` is the
    number of tiles the frontier-compacted schedule keeps this round
    (the reference path runs the same prepass so the tile metrics are
    backend-independent).
    """
    if use_kernel:
        return edge_relax(dist_block, frontier_block, src_local, dst_local,
                          w, tile_dst, tile_first, bucket_nonempty, lb, ub,
                          alt_lb, prune_bound, block_v=block_v,
                          tile_e=tile_e, n_dst_blocks=n_dst_blocks,
                          interpret=interpret)
    vals, wins = edge_relax_ref(dist_block, frontier_block, src_local,
                                dst_local, w, lb, ub, alt_lb, prune_bound,
                                block_v=block_v, n_dst_blocks=n_dst_blocks)
    _, n_tiles = schedule_tiles(frontier_block, src_local, w, tile_first,
                                tile_e)
    return vals, wins, n_tiles


def relax_fused(dist, parent, frontier, deg, src, dst, w, tile_dst,
                tile_first, lb, ub, *, block_v: int = DEFAULT_BLOCK_V,
                tile_e: int = DEFAULT_TILE_E, fused_rounds: int = 4,
                use_kernel: bool = True, interpret: Optional[bool] = None,
                alt_lb=None, prune_ub=None, prune_infl=None,
                prune_tgt=None):
    """Dispatch for the multi-round fused megakernel (see
    :func:`..edge_relax.edge_relax_fused`); both paths are bitwise
    interchangeable, including the ``FUSED_COUNTERS`` vector."""
    if use_kernel:
        return edge_relax_fused(dist, parent, frontier, deg, src, dst, w,
                                tile_dst, tile_first, lb, ub, alt_lb,
                                prune_ub, prune_infl, prune_tgt,
                                block_v=block_v, tile_e=tile_e,
                                fused_rounds=fused_rounds,
                                interpret=interpret)
    return edge_relax_fused_ref(dist, parent, frontier, deg, src, dst, w,
                                tile_dst, tile_first, lb, ub, alt_lb,
                                prune_ub, prune_infl, prune_tgt,
                                block_v=block_v, tile_e=tile_e,
                                fused_rounds=fused_rounds)


def relax_partials(dist_src, paths_src, parent_src, src, dst, w, tile_dst,
                   tile_first, tile_order, lb, ub, *, block_v: int = DEFAULT_BLOCK_V,
                   tile_e: int = DEFAULT_TILE_E, n_dst_blocks: int = 1,
                   use_kernel: bool = True, interpret: Optional[bool] = None,
                   alt_lb=None, prune_bound=None):
    """Dispatch for the single-round whole-slab partials pass (see
    :func:`..edge_relax.edge_relax_partials`; ``tile_order`` is the
    kernel's destination-major tile schedule, which the reference does not
    need)."""
    if use_kernel:
        return edge_relax_partials(dist_src, paths_src, parent_src, src,
                                   dst, w, tile_dst, tile_first, tile_order,
                                   lb, ub,
                                   alt_lb, prune_bound,
                                   block_v=block_v, tile_e=tile_e,
                                   n_dst_blocks=n_dst_blocks,
                                   interpret=interpret)
    return edge_relax_partials_ref(dist_src, paths_src, parent_src, src,
                                   dst, w, tile_dst, tile_first, lb, ub,
                                   alt_lb, prune_bound,
                                   block_v=block_v, tile_e=tile_e,
                                   n_dst_blocks=n_dst_blocks)
