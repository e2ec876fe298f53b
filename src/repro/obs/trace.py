"""Per-round solve traces: on-device ring buffer + host-side ``SolveTrace``.

The trace plane answers the question the aggregate :class:`SsspMetrics`
cannot: *why* was a round slow, and was the window sized right?  With
``EngineConfig(trace=True)`` every engine (single-device, distributed
v1/v2/v3, fused megakernel) appends one record per ``while_loop``
iteration into a fixed-capacity on-device ring (:class:`TraceBuf`), and
the facade materializes it host-side as a :class:`SolveTrace` attached
to ``SolveResult.trace``.

Design constraints, in order:

* **Bitwise no-op when off.**  The trace knob is static (part of the jit
  / shard_map-closure cache key): with ``trace_capacity == 0`` the
  traced program is *literally the same program* as before this module
  existed — dist/parent/metrics cannot change, not even in their last
  ulp.  With tracing on, the ring only ever *reads* solver state, so the
  outputs still match bitwise; only the compiled program differs.
* **Exact counter deltas.**  One record holds the per-iteration *delta*
  of every logical counter, stored as int32 — summing a trace's counter
  columns (plus the engine's initial metrics, see
  :data:`TRACE_COUNTER_COLUMNS`) reproduces the final ``SsspMetrics``
  exactly, which is what the parity tests assert.
* **Fixed footprint.**  The ring holds ``capacity`` records and
  overwrites the oldest on overflow (``SolveTrace.dropped`` reports how
  many were lost); engines never reallocate on device.

One *record* covers one body iteration of the solve loop: a relaxation
round (or one fused-megakernel invocation covering up to
``fused_rounds`` rounds) plus, when the frontier emptied, the step
transition and its pull phase.  ``stepped == 1`` marks those transition
records; ``n_rounds`` inside a record can exceed 1 only on fused paths.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np
import jax.numpy as jnp
from jax import lax

__all__ = [
    "TRACE_COLUMNS", "TRACE_I32_COLUMNS", "TRACE_F32_COLUMNS",
    "TRACE_COUNTER_COLUMNS", "TraceBuf", "trace_init", "trace_append",
    "SolveTrace", "materialize_trace",
]

# int32 columns: loop position, frontier census, and the per-iteration
# deltas of every logical SsspMetrics counter (bitwise-exact sums).
TRACE_I32_COLUMNS = (
    "iter",           # while-loop iteration index this record describes
    "frontier",       # frontier size at the start of the iteration
    "stepped",        # 1 if this iteration ran the step transition
    "n_rounds",       # logical-counter deltas from here on
    "n_steps",
    "n_extended",
    "n_trav",
    "n_pull_trav",
    "n_relax",
    "n_updates",
    "n_pruned",
)

# float32 columns: the stepping window at the start of the iteration and
# the physical (layout/launch geometry, compacted round) counter deltas,
# which are f32 in SsspMetrics already.
TRACE_F32_COLUMNS = (
    "lb", "ub", "st",
    "n_tiles_scanned", "n_tiles_dense", "n_invocations", "n_compact_rounds",
)

TRACE_COLUMNS = TRACE_I32_COLUMNS + TRACE_F32_COLUMNS

# Columns that are SsspMetrics counter deltas; summing each over the
# records of a non-overflowed trace and adding the engine's initial
# metrics (n_extended starts at 1 for the source pop, the rest at 0)
# reproduces the final SsspMetrics field exactly.
TRACE_COUNTER_COLUMNS = (
    "n_rounds", "n_steps", "n_extended", "n_trav", "n_pull_trav",
    "n_relax", "n_updates", "n_pruned", "n_tiles_scanned",
    "n_tiles_dense", "n_invocations", "n_compact_rounds",
)


class TraceBuf(NamedTuple):
    """The on-device ring: two column-major data planes plus a write count.

    ``n`` counts records *ever written*; the ring slot is ``n % capacity``
    so overflow silently drops the oldest records (the host side reports
    the loss via ``SolveTrace.dropped``).
    """
    idata: jnp.ndarray   # [capacity, len(TRACE_I32_COLUMNS)] int32
    fdata: jnp.ndarray   # [capacity, len(TRACE_F32_COLUMNS)] float32
    n: jnp.ndarray       # scalar int32


def trace_init(capacity: int) -> TraceBuf:
    """A fresh empty ring of ``capacity`` records (device-side)."""
    if capacity <= 0:
        raise ValueError(f"trace capacity must be >= 1, got {capacity}")
    return TraceBuf(
        idata=jnp.zeros((capacity, len(TRACE_I32_COLUMNS)), jnp.int32),
        fdata=jnp.zeros((capacity, len(TRACE_F32_COLUMNS)), jnp.float32),
        n=jnp.int32(0),
    )


def trace_append(buf: TraceBuf, ivals: dict, fvals: dict) -> TraceBuf:
    """Append one record (inside ``jit``); keys must cover every column."""
    irow = jnp.stack([jnp.asarray(ivals[c], jnp.int32)
                      for c in TRACE_I32_COLUMNS])[None, :]
    frow = jnp.stack([jnp.asarray(fvals[c], jnp.float32)
                      for c in TRACE_F32_COLUMNS])[None, :]
    cap = buf.idata.shape[0]
    pos = lax.rem(buf.n, jnp.int32(cap))
    return TraceBuf(
        idata=lax.dynamic_update_slice(buf.idata, irow, (pos, 0)),
        fdata=lax.dynamic_update_slice(buf.fdata, frow, (pos, 0)),
        n=buf.n + 1,
    )


@dataclasses.dataclass(frozen=True)
class SolveTrace:
    """Host-side view of one solve's per-round records (oldest first).

    ``columns`` maps every :data:`TRACE_COLUMNS` name to a 1-D numpy
    array of length :attr:`n_records`.  ``n_recorded`` counts records the
    engine *wrote* (>= ``n_records`` iff the ring overflowed).
    """
    columns: dict
    n_recorded: int
    capacity: int

    @property
    def n_records(self) -> int:
        """Records retained in the ring (== n_recorded unless overflowed)."""
        return min(self.n_recorded, self.capacity)

    @property
    def dropped(self) -> int:
        """Oldest records lost to ring overflow."""
        return max(0, self.n_recorded - self.capacity)

    def __len__(self) -> int:
        return self.n_records

    def records(self) -> list:
        """The trace as a list of per-round dicts (oldest first)."""
        return [{c: self.columns[c][i].item() for c in TRACE_COLUMNS}
                for i in range(self.n_records)]

    def counter_sums(self) -> dict:
        """Summed per-round counter deltas (exact int64 / float64 sums).

        For a non-overflowed trace, ``initial + counter_sums() == final``
        holds bitwise per logical ``SsspMetrics`` field, where *initial*
        is the engine's metric init (``n_extended = 1`` for the source
        pop, everything else 0).
        """
        out = {}
        for c in TRACE_COUNTER_COLUMNS:
            col = self.columns[c]
            if col.dtype.kind == "i":
                out[c] = int(col.astype(np.int64).sum())
            else:
                out[c] = float(col.astype(np.float64).sum())
        return out

    def summary(self) -> dict:
        """Small host-side digest (for logs / demo output)."""
        fr = self.columns["frontier"]
        return {
            "n_records": self.n_records,
            "dropped": self.dropped,
            "n_steps": int(self.columns["stepped"].sum()),
            "max_frontier": int(fr.max()) if len(fr) else 0,
            "mean_frontier": float(fr.mean()) if len(fr) else 0.0,
            **self.counter_sums(),
        }


def _materialize_one(idata, fdata, n) -> SolveTrace:
    cap = idata.shape[0]
    n = int(n)
    kept = min(n, cap)
    # unroll the ring: the oldest retained record sits at n % cap when
    # the ring overflowed, else at 0
    start = n % cap if n > cap else 0
    order = (np.arange(kept) + start) % cap
    cols = {}
    for j, c in enumerate(TRACE_I32_COLUMNS):
        cols[c] = np.asarray(idata)[order, j]
    for j, c in enumerate(TRACE_F32_COLUMNS):
        cols[c] = np.asarray(fdata)[order, j]
    return SolveTrace(columns=cols, n_recorded=n, capacity=cap)


def materialize_trace(buf: TraceBuf):
    """Device ring -> host ``SolveTrace`` (or a list for batched solves).

    Batched engines stack the ring along a leading axis (``vmap`` /
    ``lax.map``); a 3-D buffer materializes to one ``SolveTrace`` per
    batch slot.
    """
    idata = np.asarray(buf.idata)
    fdata = np.asarray(buf.fdata)
    n = np.asarray(buf.n)
    if idata.ndim == 2:
        return _materialize_one(idata, fdata, n)
    return [_materialize_one(idata[i], fdata[i], n[i])
            for i in range(idata.shape[0])]
