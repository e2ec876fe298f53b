"""In-place layout patching for edge deltas.

Three patchers, all gated on bitwise equality with a from-scratch
rebuild of the same structure:

- :func:`patch_host` edits the CSR ``HostGraph`` (the ground truth all
  device layouts derive from).  It reproduces ``build_csr``'s pipeline
  exactly — stable ``lexsort((w, src))`` over [kept edges in old CSR
  order, then adds], degree/row_ptr recompute, and the RtoW quantile LUT
  over float64-promoted weights — so the patched host is bitwise equal
  to rebuilding from the edited edge list.
- :func:`patch_blocked` patches the CSR-of-tiles blocked layout.  A
  directed edit localizes to one (src-block, dst-block) bucket; when the
  per-bucket tile counts of the affected src-block slab are unchanged
  (tile padding absorbs the edit) only the touched buckets' tile slots
  are rewritten, otherwise that one slab is re-bucketed.
- :func:`patch_sharded` patches the distributed per-shard edge slabs,
  rewriting only the shards that own an edited source vertex (the whole
  table is re-padded only when a shard outgrows ``e_max``).

The ``*_with`` variants take an already-patched host so one
:func:`patch_host` call can be shared across every placement of a graph
(the registry's one-patch-N-placements path).
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax.numpy as jnp
import numpy as np

from ..core import distributed, graph
from ..core.config import ConfigError
from .edits import (AppliedDelta, EdgeDelta, KIND_ADD, KIND_DECREASE,
                    KIND_INCREASE, KIND_REMOVE, KIND_SAME)

__all__ = ["patch_host", "patch_blocked", "patch_blocked_with",
           "patch_sharded", "patch_sharded_with"]


def _find_slot(row_ptr: np.ndarray, dst: np.ndarray, u: int, v: int) -> int:
    lo, hi = int(row_ptr[u]), int(row_ptr[u + 1])
    rel = np.nonzero(dst[lo:hi] == v)[0]
    if rel.size == 0:
        raise ValueError(f"directed edge ({u}, {v}) not present in graph")
    return lo + int(rel[0])   # first match in CSR order: deterministic
    # with parallel edges — the lightest copy is the one edited


def patch_host(hg: graph.HostGraph,
               delta: EdgeDelta) -> Tuple[graph.HostGraph, AppliedDelta]:
    """Apply ``delta`` to a host CSR; returns ``(new_host, applied)``.

    Bitwise-identical to ``build_csr`` over the edited edge list (the
    gate ``tests/test_delta.py`` enforces): weights are edited as
    float32 and promoted to float64 only for the quantile LUT, matching
    the builder's float64 pipeline exactly (the promotion is monotone,
    so the stable sort permutation is identical too).

    Edits apply to undirected graphs only: a directed ``hg`` raises
    :class:`~repro.core.config.ConfigError`.
    """
    if hg.directed:
        raise ConfigError("patch_host: edge deltas apply to undirected "
                          "graphs only, and this one is directed; build "
                          "the edited arc list anew with build_csr(..., "
                          "symmetrize=False)")
    n = hg.n
    s = np.asarray(hg.src, np.int64)
    d = np.asarray(hg.dst, np.int64)
    w = np.asarray(hg.w, np.float32).copy()
    row_ptr = np.asarray(hg.row_ptr, np.int64)

    au, av, aw = delta.add
    ru, rv = delta.remove
    wu, wv, ww = delta.reweight
    for name, us, vs in (("add", au, av), ("remove", ru, rv),
                         ("reweight", wu, wv)):
        if us.size and not (np.all((us >= 0) & (us < n))
                            and np.all((vs >= 0) & (vs < n))):
            raise ValueError(f"{name} vertex ids out of range [0, {n})")

    if delta.symmetrize:
        au, av, aw = (np.concatenate([au, av]), np.concatenate([av, au]),
                      np.concatenate([aw, aw]))
        ru, rv = np.concatenate([ru, rv]), np.concatenate([rv, ru])
        wu, wv, ww = (np.concatenate([wu, wv]), np.concatenate([wv, wu]),
                      np.concatenate([ww, ww]))

    # each remove/reweight must target a distinct directed slot (note
    # this rejects symmetrized self-loop removes — expand those to a
    # symmetrize=False delta)
    key = np.concatenate([ru, wu]) * np.int64(n) + np.concatenate([rv, wv])
    if np.unique(key).size != key.size:
        raise ValueError("duplicate remove/reweight target in one delta "
                         "(after symmetrize expansion)")

    rm_slots = np.asarray(
        [_find_slot(row_ptr, d, int(u), int(v)) for u, v in zip(ru, rv)],
        np.int64)
    rw_kinds = np.zeros(wu.size, np.int8)
    for i, (u, v, new_w) in enumerate(zip(wu, wv, ww)):
        slot = _find_slot(row_ptr, d, int(u), int(v))
        old = w[slot]
        rw_kinds[i] = (KIND_INCREASE if new_w > old
                       else KIND_DECREASE if new_w < old else KIND_SAME)
        w[slot] = new_w

    applied = AppliedDelta(
        src=np.concatenate([au, ru, wu]).astype(np.int64),
        dst=np.concatenate([av, rv, wv]).astype(np.int64),
        kind=np.concatenate([np.full(au.size, KIND_ADD, np.int8),
                             np.full(ru.size, KIND_REMOVE, np.int8),
                             rw_kinds]))

    keep = np.ones(s.size, bool)
    keep[rm_slots] = False
    s2 = np.concatenate([s[keep], au])
    d2 = np.concatenate([d[keep], av])
    w2 = np.concatenate([w[keep], aw]).astype(np.float32)

    order = np.lexsort((w2, s2))
    s2, d2, w2 = s2[order], d2[order], w2[order]
    deg = np.bincount(s2, minlength=n).astype(np.int32)
    rp = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=rp[1:])
    new_host = graph.HostGraph(
        n=n, src=s2.astype(np.int32), dst=d2.astype(np.int32), w=w2,
        row_ptr=rp.astype(np.int32), deg=deg,
        rtow=graph.weight_quantile_lut(w2.astype(np.float64)),
        max_w=float(w2.max()) if w2.size else 0.0, directed=hg.directed)
    return new_host, applied


def patch_blocked_with(layout: graph.BlockedGraph,
                       old_host: graph.HostGraph,
                       new_host: graph.HostGraph,
                       applied: AppliedDelta) -> graph.BlockedGraph:
    """Patch a whole-graph blocked layout given an already-patched host."""
    if layout.src_base != 0 or layout.n_blocks != layout.n_dst_blocks:
        raise ValueError("patch_blocked needs a whole-graph blocked layout "
                         "(src_base == 0); patch the sharded table with "
                         "patch_sharded instead")
    bv, te, nb = layout.block_v, layout.tile_e, layout.n_blocks
    n = new_host.n
    changed = applied.kind != KIND_SAME
    rp_new = np.asarray(new_host.row_ptr, np.int64)
    rp_old = np.asarray(old_host.row_ptr, np.int64)
    slabs: List[graph.BlockedEdges] = list(layout.slabs)

    for b in np.unique(applied.src[changed] // bv):
        b = int(b)
        lo_v, hi_v = b * bv, min(b * bv + bv, n)
        e0, e1 = rp_new[lo_v], rp_new[hi_v]
        s_n = (np.asarray(new_host.src[e0:e1], np.int64)
               - lo_v).astype(np.int32)
        d_n = np.asarray(new_host.dst[e0:e1], np.int32)
        w_n = np.asarray(new_host.w[e0:e1], np.float32)
        tp_new = -(-np.bincount(d_n // bv, minlength=nb) // te)
        o0, o1 = rp_old[lo_v], rp_old[hi_v]
        tp_old = -(-np.bincount(
            np.asarray(old_host.dst[o0:o1], np.int64) // bv,
            minlength=nb) // te)

        old = slabs[b]
        if (np.array_equal(tp_old, tp_new)
                and int(old.tile_dst.shape[0]) == max(int(tp_new.sum()), 1)):
            # tile padding absorbs the edit: per-bucket tile counts are
            # unchanged, so tile_dst/tile_first/bucket_nonempty are
            # invariant and only the touched buckets' slots move
            tile_ptr = np.zeros(nb + 1, np.int64)
            np.cumsum(tp_new, out=tile_ptr[1:])
            s_out = np.asarray(old.src_local).copy()
            d_out = np.asarray(old.dst).copy()
            w_out = np.asarray(old.w).copy()
            in_b = changed & (applied.src // bv == b)
            db_of = d_n // bv
            for db in np.unique(applied.dst[in_b] // bv):
                db = int(db)
                a0, a1 = int(tile_ptr[db]) * te, int(tile_ptr[db + 1]) * te
                s_out[a0:a1] = 0
                d_out[a0:a1] = 0
                w_out[a0:a1] = np.inf
                m = db_of == db
                k = int(m.sum())
                s_out[a0:a0 + k] = s_n[m]
                d_out[a0:a0 + k] = d_n[m]
                w_out[a0:a0 + k] = w_n[m]
            slabs[b] = graph.BlockedEdges(
                src_local=jnp.asarray(s_out), dst=jnp.asarray(d_out),
                w=jnp.asarray(w_out), tile_dst=old.tile_dst,
                tile_first=old.tile_first,
                bucket_nonempty=old.bucket_nonempty)
        else:
            slabs[b] = graph._slab_edges(s_n, d_n, w_n, n_dst_blocks=nb,
                                         block_v=bv, tile_e=te)

    sb_counts = np.bincount(np.asarray(new_host.src, np.int64) // bv,
                            minlength=nb)
    dense = int(sum(nb * max(-(-int(c) // te), 1) for c in sb_counts))
    deg_pad = np.zeros(nb * bv, np.int32)
    deg_pad[:n] = new_host.deg
    return dataclasses.replace(layout, dense_grid_tiles=dense,
                               slabs=tuple(slabs), deg=jnp.asarray(deg_pad))


def patch_blocked(layout: graph.BlockedGraph, delta: EdgeDelta, *,
                  host: graph.HostGraph):
    """Patch a blocked layout in place; ``(new_layout, new_host, applied)``.

    ``host`` is the HostGraph the layout was built from — slab data
    alone cannot reproduce the CSR tie order the buckets inherit, so the
    patch runs through :func:`patch_host` first.
    """
    new_host, applied = patch_host(host, delta)
    return patch_blocked_with(layout, host, new_host, applied), \
        new_host, applied


def patch_sharded_with(sg: "distributed.ShardedGraph",
                       new_host: graph.HostGraph,
                       applied: AppliedDelta) -> "distributed.ShardedGraph":
    """Patch the per-shard edge slabs given an already-patched host."""
    p, e_max = sg.src.shape
    block = int(sg.deg.shape[1])
    n = new_host.n
    rp = np.asarray(new_host.row_ptr, np.int64)
    counts = np.bincount(np.asarray(new_host.src, np.int64) // block,
                         minlength=p)
    if int(counts.max() if counts.size else 0) > e_max:
        # a shard outgrew its slab: widen every row (shard_graph's
        # uniform e_max keeps the stacked table rectangular)
        e_max = max(int(counts.max()), 1)
        s2 = np.zeros((p, e_max), np.int32)
        d2 = np.zeros((p, e_max), np.int32)
        w2 = np.full((p, e_max), np.inf, np.float32)
        for q in range(p):
            s2[q, :] = q * block
        shards = np.arange(p)
    else:
        s2 = np.asarray(sg.src).copy()
        d2 = np.asarray(sg.dst).copy()
        w2 = np.asarray(sg.w).copy()
        changed = applied.kind != KIND_SAME
        shards = np.unique(applied.src[changed] // block)
    for q in shards:
        q = int(q)
        lo_v = q * block
        if lo_v >= n:
            continue
        e0, e1 = rp[lo_v], rp[min(lo_v + block, n)]
        c = int(e1 - e0)
        # shard_graph's stable owner sort preserves CSR order, so the
        # shard's slab is exactly the host CSR slice plus padding
        s2[q, :c] = new_host.src[e0:e1]
        d2[q, :c] = new_host.dst[e0:e1]
        w2[q, :c] = new_host.w[e0:e1]
        s2[q, c:] = q * block
        d2[q, c:] = 0
        w2[q, c:] = np.inf
    deg = np.zeros(p * block, np.int32)
    deg[:n] = new_host.deg
    return distributed.ShardedGraph(
        src=jnp.asarray(s2), dst=jnp.asarray(d2), w=jnp.asarray(w2),
        deg=jnp.asarray(deg.reshape(p, block)),
        rtow=jnp.asarray(new_host.rtow), n_edges2=jnp.int32(new_host.m),
        n_true=sg.n_true)


def patch_sharded(sg: "distributed.ShardedGraph", delta: EdgeDelta, *,
                  host: graph.HostGraph):
    """Patch sharded slabs in place; ``(new_sg, new_host, applied)``."""
    new_host, applied = patch_host(host, delta)
    return patch_sharded_with(sg, new_host, applied), new_host, applied
