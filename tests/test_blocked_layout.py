"""Tile-range construction for the blocked layout (core/graph.py).

The CSR-of-tiles index is what the ragged kernel grid trusts blindly, so
its invariants are unit-tested directly: tiles never straddle destination
blocks, empty buckets own zero tiles, `tile_first` marks exactly the
schedulable entry of every non-empty bucket, and shard slices line up
with `shard_graph`'s vertex ownership.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.graph import (BlockedGraph, bucket_edges, build_blocked,
                              shard_block_v, slice_for_shard)
from repro.data.generators import kronecker, road_grid


def _check_bucket_invariants(se, de, we, td, tf, bne, tp, *, n_dst_blocks,
                             block_v, tile_e):
    nt = td.shape[0]
    assert se.shape == de.shape == we.shape == (nt * tile_e,)
    assert tf.shape == (nt,)
    assert bne.shape == (n_dst_blocks,)
    assert tp.shape == (n_dst_blocks + 1,)
    # every real (finite-w) edge sits in a tile owned by its dst block
    real = np.isfinite(we)
    tile_of = np.arange(nt * tile_e) // tile_e
    np.testing.assert_array_equal(td[tile_of[real]],
                                  de[real] // block_v)
    # tile_dst is non-decreasing over the real tile range (out-spec
    # revisiting requires dst-sorted tiles)
    nt_real = int(tp[-1])
    assert (np.diff(td[:max(nt_real, 1)]) >= 0).all()
    # CSR expansion matches tile_dst
    for b in range(n_dst_blocks):
        assert (td[tp[b]:tp[b + 1]] == b).all()
    # tile_first marks the first tile of every non-empty bucket + tile 0
    expect_first = np.zeros(nt, bool)
    expect_first[tp[:-1][bne]] = True
    expect_first[0] = True
    np.testing.assert_array_equal(tf, expect_first)


def test_bucket_edges_empty_and_single_tile_buckets():
    block_v, tile_e, nb = 4, 4, 4
    # bucket 0: 5 edges (2 tiles), bucket 2: 1 edge (single tile),
    # buckets 1 and 3: empty
    dst = np.array([0, 1, 2, 3, 0, 8], np.int32)
    src = np.arange(6, dtype=np.int32)
    w = np.ones(6, np.float32)
    out = bucket_edges(src, dst, w, n_dst_blocks=nb, block_v=block_v,
                      tile_e=tile_e)
    se, de, we, td, tf, bne, tp = out
    _check_bucket_invariants(*out, n_dst_blocks=nb, block_v=block_v,
                             tile_e=tile_e)
    np.testing.assert_array_equal(bne, [True, False, True, False])
    np.testing.assert_array_equal(tp, [0, 2, 2, 3, 3])   # empty buckets: 0 tiles
    assert td.shape == (3,)
    # padding slots never activate a tile
    assert np.isinf(we[~np.isfinite(we)]).all()
    assert (~np.isfinite(we)).sum() == 3 * tile_e - 6


def test_bucket_edges_all_empty_slab():
    out = bucket_edges(np.zeros(0, np.int32), np.zeros(0, np.int32),
                       np.zeros(0, np.float32), n_dst_blocks=3, block_v=8,
                       tile_e=4)
    se, de, we, td, tf, bne, tp = out
    assert td.shape == (1,)                 # grid is never empty
    assert tf[0] and not bne.any()
    assert np.isinf(we).all()


def test_bucket_edges_uniform_padding_and_overflow():
    src = np.zeros(10, np.int32)
    dst = np.arange(10, dtype=np.int32)
    w = np.ones(10, np.float32)
    out = bucket_edges(src, dst, w, n_dst_blocks=2, block_v=8, tile_e=4,
                       n_tiles=7)
    se, de, we, td, tf, bne, tp = out
    assert td.shape == (7,)
    # surplus pad tiles repeat the last real block id (no back-revisit)
    nt_real = int(tp[-1])
    assert (td[nt_real:] == td[nt_real - 1]).all()
    with pytest.raises(ValueError, match="n_tiles"):
        bucket_edges(src, dst, w, n_dst_blocks=2, block_v=8, tile_e=4,
                     n_tiles=1)


def test_build_blocked_invariants():
    g = kronecker(9, 8, seed=3)
    bg = build_blocked(g, block_v=128, tile_e=64)
    assert isinstance(bg, BlockedGraph)
    assert bg.n_blocks == bg.n_dst_blocks == -(-g.n // 128)
    assert bg.src_base == 0
    total_real = 0
    for slab in bg.slabs:
        we = np.asarray(slab.w)
        total_real += int(np.isfinite(we).sum())
        td = np.asarray(slab.tile_dst)
        real = np.isfinite(we)
        tile_of = np.arange(we.shape[0]) // bg.tile_e
        np.testing.assert_array_equal(
            td[tile_of[real]], np.asarray(slab.dst)[real] // bg.block_v)
    assert total_real == g.m                # no edge lost or duplicated
    # the ragged layout's static tile count undercuts the dense grid
    ragged = sum(s.tile_dst.shape[0] for s in bg.slabs)
    assert ragged < bg.dense_grid_tiles


def test_shard_block_v():
    assert shard_block_v(256, 512) == 256
    assert shard_block_v(256, 128) == 128
    assert shard_block_v(100, 64) == 50     # snapped to a divisor
    assert shard_block_v(7, 4) == 1
    with pytest.raises(ValueError):
        shard_block_v(0, 4)


def test_slice_for_shard_partitions_edges():
    g = road_grid(20, seed=2)
    p = 4
    block = -(-g.n // p)
    total = 0
    for q in range(p):
        bg = slice_for_shard(g, q, p, block_v=64, tile_e=32)
        assert bg.src_base == q * block
        assert bg.n_blocks * bg.block_v == block
        assert bg.n_dst_blocks * bg.block_v == block * p
        lo = q * block
        for sb, slab in enumerate(bg.slabs):
            we = np.asarray(slab.w)
            real = np.isfinite(we)
            total += int(real.sum())
            # block-local sources stay inside their src block
            sl = np.asarray(slab.src_local)[real]
            assert ((0 <= sl) & (sl < bg.block_v)).all()
            # ... and the global ids they encode are owned by this shard
            gsrc = sl + lo + sb * bg.block_v
            assert ((gsrc >= lo) & (gsrc < lo + block)).all()
    assert total == g.m


def test_slice_for_shard_uniform_tiles():
    g = kronecker(8, 6, seed=5)
    bgs = [slice_for_shard(g, q, 2, block_v=64, tile_e=64, n_tiles=32)
           for q in range(2)]
    for bg in bgs:
        for slab in bg.slabs:
            assert slab.tile_dst.shape == (32,)
            assert slab.w.shape == (32 * 64,)


def test_layout_bytes_reckoned_before_allocating():
    """The padded size comes from the bucket counts alone; over the limit
    every layout function raises ConfigError with the bytes, before any
    slab."""
    from repro.core.config import ConfigError
    from repro.core.distributed import shard_blocked, shard_layout_bytes
    from repro.core.graph import (SLOT_BYTES, TILE_INDEX_BYTES,
                                  blocked_layout_bytes)
    g = kronecker(9, 8, seed=4)
    kw = dict(block_v=64, tile_e=64)
    bg = build_blocked(g, **kw)
    slots = sum(int(s.w.shape[0]) for s in bg.slabs)
    tiles = sum(int(s.tile_dst.shape[0]) for s in bg.slabs)
    want = slots * SLOT_BYTES + tiles * TILE_INDEX_BYTES
    assert blocked_layout_bytes(g, **kw) == want
    assert build_blocked(g, max_bytes=want, **kw).n_blocks == bg.n_blocks
    with pytest.raises(ConfigError, match=f"needs {want} bytes"):
        build_blocked(g, max_bytes=want - 1, **kw)
    with pytest.raises(ConfigError, match="bucket_edges"):
        bucket_edges(g.src[:10] % 64, g.dst[:10], g.w[:10],
                     n_dst_blocks=8, block_v=64, tile_e=64, max_bytes=100)
    with pytest.raises(ConfigError, match="slice_for_shard"):
        slice_for_shard(g, 1, 4, max_bytes=1000, **kw)
    arrays, _ = shard_blocked(g, 4, **kw)
    sb = shard_layout_bytes(g, 4, **kw)
    assert sb == int(arrays.w.size) * SLOT_BYTES \
        + int(arrays.tile_dst.size) * TILE_INDEX_BYTES
    with pytest.raises(ConfigError, match=f"needs {sb} bytes"):
        shard_blocked(g, 4, max_bytes=sb - 1, **kw)
