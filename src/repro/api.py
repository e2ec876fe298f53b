"""Unified solver facade: ``Solver.open(graph, config) .solve(spec)``.

The paper presents *one* method specialized by two heuristics; this
module is the one declarative surface over every engine the repo grew
around it.  A :class:`Solver` session owns what used to be scattered
across call sites — layout building, backend and engine-tier resolution
(single-device vs whole-mesh sharded vs the routed serving plane, picked
by :meth:`repro.core.config.EngineConfig.resolve`), and device
placement — and every query is a declarative :class:`SolveSpec` value
(goal kind + sources + goal parameters + batch shape) that lowers onto
the existing goal machinery.  Every entry point returns one
:class:`SolveResult` (dist / parent / metrics, lazy ``paths()``
reconstruction) instead of the historical mix of tuples and per-layer
result classes.

::

    from repro.api import EngineConfig, SolveSpec, Solver

    solver = Solver.open(graph)                       # defaults
    res = solver.solve(SolveSpec.p2p(src, dst))       # early-exit query
    res.distance(), res.paths()                       # lazy shaping

    cfg = EngineConfig(backend="blocked_pallas", tier="sharded")
    with Solver.open(graph, cfg) as s:                # whole-mesh engine
        dist, parent, metrics = s.solve(SolveSpec.tree([s0, s1, s2]))

Tier contracts (all bitwise-identical where they overlap — asserted by
``tests/test_api.py``):

* ``single`` — the jitted single-device engine; batch specs run one
  fused ``vmap`` computation.
* ``sharded`` — the v1/v2/v3 ``shard_map`` engines over the device
  mesh; batch specs run the ``lax.map`` batch entry point.  Results are
  sliced back to the true vertex count (padding never escapes).
* ``routed`` — the serving plane (registry + router + per-device
  schedulers); results are the finalized per-query answers, i.e. each
  kind's settled-entries contract (tentative values masked) exactly as
  served traffic sees them.

The legacy ``sssp_p2p``/``sssp_bounded``/``sssp_knear`` wrappers remain
as deprecation shims over the same lowering (see ``repro.core.sssp``);
tier-1 CI rejects internal calls to them.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Optional, Tuple, Union

import numpy as np
import jax

from .core import relax
from .core.config import (ConfigError, EngineConfig, ResolvedEngine,
                          as_resolved)
from .core.graph import BlockedGraph, DeviceGraph, HostGraph
from .core.sssp import (GOALS, compiled_text, normalized_metrics, sssp,
                        sssp_batch)
from .obs import profiling

__all__ = ["EngineConfig", "ConfigError", "SolveSpec", "SolveResult",
           "Solver"]


def _as_id_tuple(v) -> Tuple[int, ...]:
    return tuple(int(x) for x in v)


@dataclasses.dataclass(frozen=True)
class SolveSpec:
    """One declarative shortest-path computation.

    ``kind`` is one of :data:`repro.core.sssp.GOALS` (``tree`` / ``p2p``
    / ``bounded`` / ``knear``); ``sources`` is a vertex id (single
    computation) or a sequence of ids (one fused batch — the result
    gains a leading slot axis).  The goal parameter (``target`` /
    ``bound`` / ``k``) may be a scalar (shared by every slot) or a
    per-source sequence.  Specs are frozen and validate on construction;
    graph-size bounds are checked by the solver before anything traces.
    """

    sources: Union[int, Tuple[int, ...]]
    kind: str = "tree"
    target: Union[int, Tuple[int, ...], None] = None    # p2p
    bound: Union[float, Tuple[float, ...], None] = None  # bounded
    k: Union[int, Tuple[int, ...], None] = None          # knear

    def __post_init__(self):
        if self.kind not in GOALS:
            raise ValueError(f"unknown solve kind {self.kind!r}; expected "
                             f"one of {GOALS}")
        if np.ndim(self.sources) != 0:
            object.__setattr__(self, "sources", _as_id_tuple(self.sources))
            if not self.sources:
                raise ValueError("sources must be non-empty")
        else:
            object.__setattr__(self, "sources", int(self.sources))
        for name, cast in (("target", int), ("bound", float), ("k", int)):
            v = getattr(self, name)
            if v is not None:
                v = (tuple(cast(x) for x in v) if np.ndim(v) != 0
                     else cast(v))
                object.__setattr__(self, name, v)
        need = {"tree": None, "p2p": "target", "bounded": "bound",
                "knear": "k"}[self.kind]
        for name in ("target", "bound", "k"):
            v = getattr(self, name)
            if name != need and v is not None:
                raise ValueError(f"{name} is not a parameter of "
                                 f"{self.kind!r} specs")
        if need is not None and getattr(self, need) is None:
            raise ValueError(f"{self.kind!r} specs require {need}")
        srcs = self.sources if self.batched else (self.sources,)
        if any(s < 0 for s in srcs):
            raise ValueError("vertex ids must be non-negative")
        param = getattr(self, need) if need else None
        if isinstance(param, tuple):
            if not self.batched or len(param) != len(self.sources):
                raise ValueError(
                    f"per-source {need} needs one value per source "
                    f"(got {len(param)} for sources={self.sources!r})")
        if self.kind == "p2p":
            tg = param if isinstance(param, tuple) else (param,)
            if any(t < 0 for t in tg):
                raise ValueError("vertex ids must be non-negative")
        if self.kind == "knear":
            ks = param if isinstance(param, tuple) else (param,)
            if any(x < 1 for x in ks):
                raise ValueError("k must be >= 1")
        if self.kind == "bounded":
            bs = param if isinstance(param, tuple) else (param,)
            if any(b < 0 for b in bs):
                raise ValueError("bound must be >= 0")

    # -- convenience constructors ---------------------------------------

    @classmethod
    def tree(cls, sources) -> "SolveSpec":
        """Full shortest-path tree(s) from ``sources``."""
        return cls(sources=sources, kind="tree")

    @classmethod
    def p2p(cls, sources, target) -> "SolveSpec":
        """Point-to-point: early exit once ``target`` settles."""
        return cls(sources=sources, kind="p2p", target=target)

    @classmethod
    def bounded(cls, sources, bound) -> "SolveSpec":
        """Distance-bounded: every vertex within ``bound``."""
        return cls(sources=sources, kind="bounded", bound=bound)

    @classmethod
    def knear(cls, sources, k) -> "SolveSpec":
        """k-nearest vertices to each source."""
        return cls(sources=sources, kind="knear", k=k)

    # -- lowering helpers -----------------------------------------------

    @property
    def batched(self) -> bool:
        return isinstance(self.sources, tuple)

    @property
    def n_slots(self) -> int:
        return len(self.sources) if self.batched else 1

    @property
    def goal_param(self):
        """The spec's goal parameter, kind-agnostic (None for tree)."""
        return {"tree": None, "p2p": self.target, "bounded": self.bound,
                "knear": self.k}[self.kind]

    def slot_params(self) -> Optional[list]:
        """Per-slot goal parameters (scalar broadcast over the batch)."""
        p = self.goal_param
        if p is None:
            return None
        if isinstance(p, tuple):
            return list(p)
        return [p] * self.n_slots

    def check_bounds(self, n: int) -> None:
        """Reject out-of-range vertex ids against a concrete graph size —
        loudly, host-side: under ``jit`` an o-o-b gather clamps and a
        scatter drops silently, which would return a plausible-looking
        wrong answer."""
        srcs = self.sources if self.batched else (self.sources,)
        bad = [s for s in srcs if not 0 <= s < n]
        if bad:
            raise ValueError(f"source(s) {bad} out of range for graph "
                             f"with n={n}")
        if self.kind == "p2p":
            tg = self.target if isinstance(self.target, tuple) \
                else (self.target,)
            bad = [t for t in tg if not 0 <= t < n]
            if bad:
                raise ValueError(f"target(s) {bad} out of range for graph "
                                 f"with n={n}")


@dataclasses.dataclass
class SolveResult:
    """The one result type every solve path returns.

    ``dist``/``parent`` are ``[N]`` (single spec) or ``[S, N]`` (batch
    spec) arrays; ``metrics`` is the engine's raw
    :class:`~repro.core.sssp.SsspMetrics` counters (scalar or per-slot
    leaves) on the single/sharded tiers and the per-query normalized
    metric dict(s) on the routed tier.  Iterating the result unpacks
    ``(dist, parent, metrics)``, matching the legacy tuple returns, so
    migrated call sites keep their destructuring (``trace`` rides along
    as a named field only).

    ``trace`` is None unless the session's config set ``trace=True``
    (single/sharded tiers): then it is a
    :class:`~repro.obs.trace.SolveTrace` (or one per slot for batch
    specs) of per-round records.

    Shaping is lazy: :meth:`paths`, :meth:`distance`, :meth:`nearest`
    and :meth:`normalized` walk the arrays only when called.
    """

    spec: SolveSpec
    dist: Any
    parent: Any
    metrics: Any
    deg: np.ndarray
    tier: str
    served_by: Optional[Any] = None     # routed: per-slot scheduler names
    trace: Optional[Any] = None         # SolveTrace | list[SolveTrace]

    def __iter__(self):
        return iter((self.dist, self.parent, self.metrics))

    @property
    def batched(self) -> bool:
        return self.spec.batched

    def _slot(self, arr, slot: Optional[int]):
        arr = np.asarray(arr)
        if not self.batched:
            return arr
        if slot is None:
            raise ValueError("batched result: pass slot=")
        return arr[slot]

    def block_until_ready(self) -> "SolveResult":
        jax.block_until_ready(self.dist)
        return self

    # -- lazy shaping ----------------------------------------------------

    def distance(self, target=None, *, slot: Optional[int] = None) -> float:
        """Distance to ``target`` (defaults to a p2p spec's target)."""
        if target is None:
            t = self.spec.target
            if t is None:
                raise ValueError("no target: pass one or use a p2p spec")
            if isinstance(t, tuple):
                if slot is None:
                    raise ValueError("batched result: pass slot=")
                t = t[slot]
            target = t
        return float(self._slot(self.dist, slot)[int(target)])

    def paths(self, targets=None, *, slot: Optional[int] = None):
        """Lazily reconstruct source->target path(s) from ``parent``.

        ``targets`` defaults to a p2p spec's target(s).  Returns one
        vertex-id list (or ``None`` if unreachable); for a batch spec
        with no ``slot``, one list per slot (each slot's own target).
        """
        from .serve.queries import reconstruct_path
        if self.batched and slot is None:
            t = targets if targets is not None else self.spec.target
            if t is None:
                raise ValueError("no targets: pass them or use a p2p spec")
            ts = list(t) if np.ndim(t) != 0 else [t] * self.spec.n_slots
            if len(ts) != self.spec.n_slots:
                raise ValueError(f"{len(ts)} targets for "
                                 f"{self.spec.n_slots} slots")
            return [self.paths(ts[i], slot=i)
                    for i in range(self.spec.n_slots)]
        if targets is None:
            t = self.spec.target
            if t is None:
                raise ValueError("no target: pass one or use a p2p spec")
            targets = t[slot] if isinstance(t, tuple) else t
        src = self.spec.sources[slot] if self.batched else self.spec.sources
        return reconstruct_path(self._slot(self.parent, slot), int(src),
                                int(targets))

    def nearest(self, *, slot: Optional[int] = None) -> list:
        """A knear spec's ``[(vertex, dist)]`` list, ascending."""
        if self.spec.kind != "knear":
            raise ValueError("nearest() needs a knear spec")
        if self.batched and slot is None:
            raise ValueError("batched result: pass slot=")
        k = self.spec.k
        if isinstance(k, tuple):
            k = k[slot]
        d = self._slot(self.dist, slot)
        src = self.spec.sources[slot] if self.batched else self.spec.sources
        finite = np.flatnonzero(np.isfinite(d))
        order = finite[np.argsort(d[finite], kind="stable")]
        order = order[order != int(src)][:int(k)]
        return [(int(v), float(d[v])) for v in order]

    def normalized(self, *, slot: Optional[int] = None) -> dict:
        """Paper §4 normalized metrics for one computation."""
        if isinstance(self.metrics, dict):
            return self.metrics
        if isinstance(self.metrics, list):        # routed batch
            if slot is None:
                raise ValueError("batched result: pass slot=")
            return self.metrics[slot]
        m = self.metrics
        if self.batched:
            if slot is None:
                raise ValueError("batched result: pass slot=")
            m = jax.tree.map(lambda x: np.asarray(x)[slot], m)
        return normalized_metrics(self.deg, self._slot(self.dist, slot), m)


class Solver:
    """One opened solving session over one graph.

    Build with :meth:`open`; the session owns the resolved engine
    (:class:`~repro.core.config.ResolvedEngine`), the device-resident
    graph, and whatever layout/mesh/serving state its tier needs, so
    repeated :meth:`solve` calls amortize every preprocessing step.
    Usable as a context manager (``close`` tears down serving workers;
    single/sharded tiers hold no background state).
    """

    def __init__(self, graph, resolved: ResolvedEngine, *, layout=None,
                 gid: str = "default", tuned=None):
        self.resolved = resolved
        self.config = resolved.config
        self.tier = resolved.tier
        self.gid = gid
        self._tuned = tuned
        self._host = graph
        self.deg = np.asarray(graph.deg)
        self.n = int(self.deg.shape[0])
        self._closed = False
        if self.tier == "single":
            self._open_single(graph, layout)
        elif self.tier == "sharded":
            if layout is not None:
                raise ConfigError("pass prebuilt layouts only to the "
                                  "single tier; the sharded tier builds "
                                  "its per-shard slabs itself")
            self._open_sharded(graph)
        elif self.tier == "routed":
            if layout is not None:
                raise ConfigError("the routed tier builds layouts through "
                                  "its registry; drop layout=")
            self._open_routed(graph)
        else:                                    # pragma: no cover
            raise ConfigError(f"unknown resolved tier {self.tier!r}")

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def open(cls, graph, config: Optional[EngineConfig] = None, *,
             layout=None, gid: str = "default", tuned=None) -> "Solver":
        """Open a solver session on ``graph``.

        ``graph`` is a :class:`~repro.core.graph.HostGraph` or
        :class:`~repro.core.graph.DeviceGraph`; ``config`` an
        :class:`EngineConfig` (default: single-device ``segment_min``).
        ``layout`` optionally reuses a prebuilt single-tier backend
        layout (validated against the config — a mismatched or partial
        layout fails here, not at trace time).

        ``tuned`` is a :class:`~repro.tune.TunedStore` (or a path to
        one): the store's per-``gid`` offline-tuned perf fields
        (``alpha``/``beta``/``policy``/geometry — see
        :data:`repro.tune.TUNED_FIELDS`) are overlaid onto ``config``
        before resolution on the single/sharded tiers, and handed to the
        routed tier's registry for per-graph application.  A missing or
        stale entry (the graph changed since the tune) leaves ``config``
        untouched.
        """
        if not isinstance(graph, (HostGraph, DeviceGraph)):
            raise TypeError(f"expected HostGraph or DeviceGraph, got "
                            f"{type(graph)}")
        if config is None:
            config = EngineConfig()
        if tuned is not None and not hasattr(tuned, "apply"):
            from .tune.store import TunedStore
            tuned = TunedStore(tuned)
        n, m = int(graph.n), int(graph.m)
        resolved = as_resolved(config, n=n, m=m)
        if tuned is not None and resolved.tier != "routed":
            tuned_cfg = tuned.apply(gid, graph, config, n=n, m=m)
            if tuned_cfg != config:
                resolved = as_resolved(tuned_cfg, n=n, m=m)
        return cls(graph, resolved, layout=layout, gid=gid, tuned=tuned)

    def _open_single(self, graph, layout):
        r = self.resolved
        dg = graph.to_device() if isinstance(graph, HostGraph) else graph
        if r.devices is not None:
            dg = jax.device_put(dg, r.resolve_devices()[0])
        self._dg = dg
        self._backend = relax.get_backend(r.backend)
        if layout is not None:
            self._check_layout(layout)
            self._layout = layout
        else:
            with profiling.annotate(f"repro:engine_build:{r.backend}"):
                self._layout = self._backend.prepare(dg, **r.layout_opts())
        self._build_landmarks(dg)

    def _build_landmarks(self, g) -> None:
        """Session-owned ALT artifact: with ``use_alt`` the landmark set
        is built once at open (amortized like the layout) and threaded
        into every p2p solve — without it the engine entry points would
        rebuild the ``[L, N]`` matrix per call."""
        self._landmarks = None
        if self.resolved.use_alt:
            from .core.landmarks import build_landmarks
            with profiling.annotate("repro:landmark_build"):
                self._landmarks = build_landmarks(
                    g, self.resolved.n_landmarks,
                    self.resolved.landmark_strategy)

    def _check_layout(self, layout) -> None:
        """A foreign layout must match the configured backend *and* cover
        the whole graph — a shard slice or an unpadded/mis-sized blocked
        layout would silently drop edges under ``jit``."""
        r = self.resolved
        if r.backend == "blocked_pallas":
            if not isinstance(layout, BlockedGraph):
                raise ConfigError(
                    f"backend 'blocked_pallas' needs a BlockedGraph "
                    f"layout (build_blocked); got {type(layout).__name__}")
            if layout.n != self.n or layout.src_base != 0 \
                    or layout.n_blocks != layout.n_dst_blocks \
                    or layout.n_pad < self.n:
                raise ConfigError(
                    f"blocked layout does not cover this graph: layout "
                    f"n={layout.n} n_pad={layout.n_pad} "
                    f"src_base={layout.src_base} "
                    f"blocks={layout.n_blocks}/{layout.n_dst_blocks} vs "
                    f"graph n={self.n} (shard slices and foreign layouts "
                    f"are rejected before tracing)")
            if r.tile_e is not None and layout.tile_e != r.tile_e:
                raise ConfigError(f"layout tile_e={layout.tile_e} != "
                                  f"config tile_e={r.tile_e}")
            if r.block_v is not None and layout.block_v != r.block_v:
                raise ConfigError(f"layout block_v={layout.block_v} != "
                                  f"config block_v={r.block_v}")
        elif isinstance(layout, BlockedGraph):
            raise ConfigError(f"backend {r.backend!r} cannot consume a "
                              f"BlockedGraph layout")
        else:
            # segment_min's layout IS the edge list: a foreign graph's
            # DeviceGraph would silently answer over the wrong edges
            if not isinstance(layout, DeviceGraph):
                raise ConfigError(
                    f"backend {r.backend!r} layout must be the graph's "
                    f"DeviceGraph edge list; got {type(layout).__name__}")
            # max_w is a cheap fingerprint; compare at the device dtype
            # (f32) — the host value may still be float64
            if (layout.n != self.n or layout.m != int(self._host.m)
                    or np.float32(layout.max_w)
                    != np.float32(self._host.max_w)):
                raise ConfigError(
                    f"layout does not match this graph (layout n={layout.n}"
                    f" m={layout.m} max_w={float(layout.max_w):.6g} vs "
                    f"n={self.n} m={int(self._host.m)} "
                    f"max_w={float(self._host.max_w):.6g})")

    def _open_sharded(self, graph):
        from .core.distributed import (blocked_specs, graph_specs,
                                       place_on_mesh, shard_blocked,
                                       shard_graph)
        r = self.resolved
        devs = r.resolve_devices()
        devs = tuple(devs) if devs is not None else tuple(jax.devices())
        self._devices = devs
        self._mesh = jax.sharding.Mesh(np.array(devs), ("graph",))
        with profiling.annotate("repro:engine_build:sharded"):
            sg = shard_graph(graph, len(devs))
            self._blocked = None
            if r.shard_backend == "blocked":
                arrays, bmeta = shard_blocked(sg, **r.blocked_opts())
                self._blocked = (place_on_mesh(arrays, self._mesh,
                                               blocked_specs("graph")),
                                 bmeta)
            self._sg = place_on_mesh(sg, self._mesh, graph_specs("graph"))
        self._build_landmarks(graph)

    def _open_routed(self, graph):
        from .serve.registry import GraphRegistry
        from .serve.router import QueryRouter
        r = self.resolved
        self._registry = GraphRegistry(config=self.config,
                                       tuned=self._tuned)
        self._registry.register(self.gid, graph)
        self._router = QueryRouter(self._registry,
                                   devices=r.resolve_devices(),
                                   config=self.config)
        self._router_started = False      # submit() starts workers lazily

    # ------------------------------------------------------------------
    # solving
    # ------------------------------------------------------------------

    def solve(self, spec: SolveSpec) -> SolveResult:
        """Run one declarative computation; returns a :class:`SolveResult`."""
        if self._closed:
            raise RuntimeError("solver is closed")
        if not isinstance(spec, SolveSpec):
            raise TypeError(f"expected SolveSpec, got {type(spec)}")
        spec.check_bounds(self.n)
        return {"single": self._solve_single,
                "sharded": self._solve_sharded,
                "routed": self._solve_routed}[self.tier](spec)

    def solve_many(self, specs) -> list:
        """Solve several specs — mixed goal kinds welcome — one
        :class:`SolveResult` per input spec, in order.

        One compiled engine serves one goal kind, so the specs are
        grouped into *plan-compatible sub-batches* (the same grouping
        the serving scheduler applies to its queue): all slots of one
        kind fuse into a single batched solve, and each spec's rows are
        sliced back out of its group's result.  The routed tier submits
        every query up front and drains once, letting its schedulers
        form the sub-batches themselves.
        """
        specs = list(specs)
        for spec in specs:
            if not isinstance(spec, SolveSpec):
                raise TypeError(f"expected SolveSpec, got {type(spec)}")
        if self._closed:
            raise RuntimeError("solver is closed")
        for spec in specs:
            spec.check_bounds(self.n)
        if not specs:
            return []
        if self.tier == "routed" or len(specs) == 1:
            # routed: the scheduler already groups plan-compatibly, and
            # submitting everything before the drain lets one step batch
            # across specs; single spec: nothing to group
            return [self.solve(s) for s in specs]
        # group spec indices by goal kind (the plan-compatibility key on
        # one graph), preserving submission order within a group
        groups: dict = {}
        for i, spec in enumerate(specs):
            groups.setdefault(spec.kind, []).append(i)
        solve = {"single": self._solve_single,
                 "sharded": self._solve_sharded}[self.tier]
        results: list = [None] * len(specs)
        for kind, idxs in groups.items():
            srcs: list = []
            params: list = []
            slots: list = []                  # [start, stop) per spec
            for i in idxs:
                s = specs[i]
                start = len(srcs)
                srcs.extend(s.sources if s.batched else (s.sources,))
                p = s.slot_params()
                params.extend(p if p is not None else [])
                slots.append((start, len(srcs)))
            merged = SolveSpec(
                sources=tuple(srcs), kind=kind,
                **({} if kind == "tree" else
                   {{"p2p": "target", "bounded": "bound",
                     "knear": "k"}[kind]: tuple(params)}))
            out = solve(merged)
            for i, (lo, hi) in zip(idxs, slots):
                spec = specs[i]
                sl = (slice(lo, hi) if spec.batched
                      else lo)                # singleton drops the axis
                metrics = jax.tree.map(
                    lambda x: np.asarray(x)[sl], out.metrics)
                trace = None
                if out.trace is not None:
                    trace = (out.trace[lo:hi] if spec.batched
                             else out.trace[lo])
                results[i] = SolveResult(
                    spec=spec, dist=np.asarray(out.dist)[sl],
                    parent=np.asarray(out.parent)[sl],
                    metrics=metrics, deg=self.deg, tier=self.tier,
                    trace=trace)
        return results

    def _goal_args(self, spec: SolveSpec) -> dict:
        if spec.batched:
            return {"goal": spec.kind, "goal_params": spec.slot_params()}
        return {"goal": spec.kind, "goal_param": spec.goal_param}

    def _materialize_trace(self, out):
        """Split an engine return into ``(dist, parent, metrics, trace)``,
        materializing the device trace ring when the config traces."""
        if self.resolved.trace_cap > 0:
            from .obs import materialize_trace
            dist, parent, metrics, buf = out
            return dist, parent, metrics, materialize_trace(buf)
        dist, parent, metrics = out
        return dist, parent, metrics, None

    def _solve_single(self, spec: SolveSpec) -> SolveResult:
        fn = sssp_batch if spec.batched else sssp
        srcs = list(spec.sources) if spec.batched else spec.sources
        out = fn(self._dg, srcs, config=self.resolved, layout=self._layout,
                 landmarks=self._landmarks, **self._goal_args(spec))
        dist, parent, metrics, trace = self._materialize_trace(out)
        return SolveResult(spec=spec, dist=dist, parent=parent,
                           metrics=metrics, deg=self.deg, tier=self.tier,
                           trace=trace)

    def phase_table(self, spec: SolveSpec) -> profiling.PhaseTable:
        """``{instruction_name: phase}`` of the program that solving
        ``spec`` runs (:func:`repro.obs.profiling.phase_table`): the
        join from a profiler trace's op events to the solve's named
        phases.  The program is compiled, or found in the compile
        cache, and not run.  Single tier only."""
        if self.tier != "single":
            raise ConfigError(f"phase tables cover the single tier; this "
                              f"session is {self.tier!r}")
        if self._closed:
            raise RuntimeError("solver is closed")
        spec.check_bounds(self.n)
        srcs = list(spec.sources) if spec.batched else spec.sources
        text = compiled_text(self._dg, srcs, batched=spec.batched,
                             config=self.resolved, layout=self._layout,
                             landmarks=self._landmarks,
                             **self._goal_args(spec))
        return profiling.phase_table(text)

    def _solve_sharded(self, spec: SolveSpec) -> SolveResult:
        from .core.distributed import (sssp_distributed,
                                       sssp_distributed_batch)
        fn = sssp_distributed_batch if spec.batched else sssp_distributed
        srcs = np.asarray(spec.sources, np.int32) if spec.batched \
            else spec.sources
        out = fn(self._sg, srcs, self._mesh, ("graph",),
                 config=self.resolved, blocked=self._blocked,
                 landmarks=self._landmarks, **self._goal_args(spec))
        dist, parent, metrics, trace = self._materialize_trace(out)
        # padding vertices never escape the facade
        dist = dist[..., :self.n]
        parent = parent[..., :self.n]
        return SolveResult(spec=spec, dist=dist, parent=parent,
                           metrics=metrics, deg=self.deg, tier=self.tier,
                           trace=trace)

    def _solve_routed(self, spec: SolveSpec) -> SolveResult:
        from .serve.queries import Query
        params = spec.slot_params()
        srcs = spec.sources if spec.batched else (spec.sources,)
        futs = []
        for i, s in enumerate(srcs):
            kw = {}
            if spec.kind == "p2p":
                kw["target"] = int(params[i])
            elif spec.kind == "bounded":
                kw["bound"] = float(params[i])
            elif spec.kind == "knear":
                kw["k"] = int(params[i])
            futs.append(self._router.submit(
                Query(gid=self.gid, source=int(s), kind=spec.kind, **kw)))
        self._router.drain()
        results = [f.result(timeout=600) for f in futs]
        if spec.batched:
            dist = np.stack([r.dist for r in results])
            parent = np.stack([r.parent for r in results])
            metrics = [r.metrics for r in results]
            served = [r.served_by for r in results]
        else:
            (r,) = results
            dist, parent, metrics, served = (r.dist, r.parent, r.metrics,
                                             r.served_by)
        return SolveResult(spec=spec, dist=dist, parent=parent,
                           metrics=metrics, deg=self.deg, tier=self.tier,
                           served_by=served)

    # ------------------------------------------------------------------
    # async sessions + streaming deltas (routed tier)
    # ------------------------------------------------------------------

    def submit(self, spec: SolveSpec):
        """Submit a spec asynchronously; returns a
        :class:`concurrent.futures.Future` resolving to the
        :class:`SolveResult`.

        Routed tier only: the first ``submit`` starts the router's
        background workers (one per device plus the mesh scheduler), and
        every slot of the spec is enqueued without a synchronous drain —
        the workers batch and serve them while the caller keeps going.
        Per-slot queries of a batched spec may land in different fused
        batches (even on different devices); the future resolves once
        every slot has.  ``solve()`` remains the synchronous path and
        may be freely mixed with in-flight submissions.
        """
        from concurrent.futures import Future
        from .serve.queries import Query
        if self._closed:
            raise RuntimeError("solver is closed")
        if not isinstance(spec, SolveSpec):
            raise TypeError(f"expected SolveSpec, got {type(spec)}")
        if self.tier != "routed":
            raise ConfigError(
                f"submit() needs the routed tier (async serving plane); "
                f"this session resolved tier={self.tier!r} — open with "
                f"tier='routed' or use solve()")
        spec.check_bounds(self.n)
        if not self._router_started:
            # idempotent: start() on live schedulers is a no-op
            self._router.start()
            self._router_started = True
        params = spec.slot_params()
        srcs = spec.sources if spec.batched else (spec.sources,)
        futs = []
        for i, s in enumerate(srcs):
            kw = {}
            if spec.kind == "p2p":
                kw["target"] = int(params[i])
            elif spec.kind == "bounded":
                kw["bound"] = float(params[i])
            elif spec.kind == "knear":
                kw["k"] = int(params[i])
            futs.append(self._router.submit(
                Query(gid=self.gid, source=int(s), kind=spec.kind, **kw)))
        agg: Future = Future()
        agg.set_running_or_notify_cancel()
        remaining = [len(futs)]
        lock = threading.Lock()

        def _one_done(_f):
            with lock:
                remaining[0] -= 1
                last = remaining[0] == 0
            if agg.done():
                return
            exc = _f.exception()
            if exc is not None:
                agg.set_exception(exc)
                return
            if not last:
                return
            try:
                results = [f.result() for f in futs]
                if spec.batched:
                    dist = np.stack([r.dist for r in results])
                    parent = np.stack([r.parent for r in results])
                    metrics = [r.metrics for r in results]
                    served = [r.served_by for r in results]
                else:
                    (r,) = results
                    dist, parent, metrics, served = (
                        r.dist, r.parent, r.metrics, r.served_by)
                agg.set_result(SolveResult(
                    spec=spec, dist=dist, parent=parent, metrics=metrics,
                    deg=self.deg, tier=self.tier, served_by=served))
            except BaseException as e:      # defensive: never hang agg
                if not agg.done():
                    agg.set_exception(e)

        for f in futs:
            f.add_done_callback(_one_done)
        return agg

    def apply_delta(self, edits) -> dict:
        """Apply an :class:`~repro.delta.EdgeDelta` to the session's graph
        in place (routed tier): delegates to
        :meth:`~repro.serve.registry.GraphRegistry.apply_delta` — cached
        engines get their layouts patched (not rebuilt), placed replicas
        are reused, and queries submitted afterwards serve the patched
        graph.  Single/sharded sessions hold immutable prebuilt state;
        patch those directly with :mod:`repro.delta`
        (``patch_blocked`` / ``patch_sharded`` / ``repair``) or reopen.
        """
        if self._closed:
            raise RuntimeError("solver is closed")
        if self.tier != "routed":
            raise ConfigError(
                f"apply_delta() needs the routed tier; tier={self.tier!r} "
                f"sessions own immutable prebuilt layouts — use "
                f"repro.delta.patch_blocked/patch_sharded/repair, or "
                f"reopen the session on the patched graph")
        report = self._registry.apply_delta(self.gid, edits)
        self._host = report["host"]
        self.deg = np.asarray(report["host"].deg)
        return report

    # ------------------------------------------------------------------
    # lifecycle / introspection
    # ------------------------------------------------------------------

    @property
    def device_graph(self):
        """The single tier's device-resident graph — None elsewhere."""
        return getattr(self, "_dg", None)

    @property
    def landmarks(self):
        """The session's ALT :class:`~repro.core.landmarks.LandmarkSet`
        (``use_alt`` configs, single/sharded tiers) — None otherwise.
        The routed tier's sets live in its registry
        (:meth:`~repro.serve.registry.GraphRegistry.landmark_set`)."""
        return getattr(self, "_landmarks", None)

    @property
    def router(self):
        """The routed tier's :class:`~repro.serve.router.QueryRouter`
        (serving stats, placement, warmup) — None on other tiers."""
        return getattr(self, "_router", None)

    @property
    def registry(self):
        """The routed tier's registry — None on other tiers."""
        return getattr(self, "_registry", None)

    def warmup(self, kinds=("tree",), batch_sizes=None) -> list:
        """Pre-pay builds and jit compiles (routed tier delegates to the
        router; other tiers run one dummy solve per kind)."""
        if self.tier == "routed":
            return self._router.warmup(
                kinds=kinds,
                batch_sizes=batch_sizes or (self.resolved.max_batch,))
        src = int(np.argmax(self.deg))
        rows = []
        for kind in kinds:
            for bs in (batch_sizes or (1,)):
                srcs = [src] * int(bs) if int(bs) > 1 else src
                spec = {"tree": SolveSpec.tree(srcs),
                        "p2p": SolveSpec.p2p(srcs, src),
                        "bounded": SolveSpec.bounded(srcs, 0.0),
                        "knear": SolveSpec.knear(srcs, 1)}[kind]
                self.solve(spec).block_until_ready()
                rows.append({"kind": kind, "batch": int(bs),
                             "tier": self.tier})
        return rows

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        router = getattr(self, "_router", None)
        if router is not None:
            router.stop(cancel_pending=True)

    def __enter__(self) -> "Solver":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __repr__(self) -> str:
        return (f"Solver(tier={self.tier!r}, "
                f"backend={self.resolved.backend!r}, n={self.n}, "
                f"gid={self.gid!r})")
