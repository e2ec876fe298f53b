"""Multi-graph registry: cached device layouts + engines, LRU-evicted.

Serving heterogeneous traffic means holding several preprocessed graphs
at once — each with a device-resident :class:`~repro.core.graph.DeviceGraph`,
one relaxation-backend layout (``BlockedGraph`` bucketing etc.), and the
host-side per-graph serving state (hoisted degree array, eccentricity
hints for batch formation).  Those are exactly the expensive,
re-buildable artifacts, so the registry separates

* the **spec** — how to (re)build a graph, registered once per ``gid``
  and kept forever (a ``HostGraph`` or a zero-arg factory returning one);
* the **engine cache** — at most ``capacity`` built engines, keyed by
  ``(gid, backend, placement)``, recycled LRU.

Placement is the multi-device serving plane's device-affinity axis: the
same graph can be built once per device (the router replicates hot
graphs), with each engine's buffers ``jax.device_put`` on its device so
the jitted query batch runs there without transfers.

**Engine tiers.**  Graphs small enough to fit one device are served by
the single-device vmapped engine (:class:`GraphEngine`).  Graphs above
the registry's vertex/edge shard thresholds are built as
:class:`ShardedGraphEngine` s over :mod:`repro.core.distributed` (v2
sharded-dist ``shard_map``) spanning the whole mesh — both tiers expose
the same ``run_batch`` interface, so the scheduler/planner stack serves
either transparently.

**Concurrency.**  Lookups of built engines take only a short lock.  A
cold build publishes a per-key future and builds *outside* the lock:
concurrent lookups of the same key wait on that future (no duplicate
builds), while lookups of other keys — in particular already-built
engines — proceed immediately instead of serializing behind someone
else's build.

A cache miss on a registered gid transparently rebuilds the engine from
its spec (and re-pays layout preprocessing + jit, which is why the
serving benchmark reports registry hit rates).  :meth:`GraphRegistry.warmup`
pre-pays builds and per-(graph, kind, batch-width) jit compiles before
traffic arrives.
"""
from __future__ import annotations

import collections
import copy
import dataclasses
import os
import threading
import time
import weakref
from concurrent.futures import Future
from typing import Callable, Dict, Optional, Tuple, Union

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core import landmarks as landmarks_mod
from ..core import relax
from ..core.config import EngineConfig, resolve_devices
from ..core.distributed import (blocked_specs, graph_specs, place_on_mesh,
                                shard_blocked, shard_graph,
                                sssp_distributed_batch)
from ..core.graph import DeviceGraph, HostGraph
from ..core.landmarks import LandmarkSet, build_landmarks, hop_bfs
from ..core.sssp import GOALS, repair_relax, sssp_batch
from ..delta import (patch_blocked_with, patch_host, patch_sharded_with,
                     repair_state)
from ..obs import profiling
from ..obs.metrics import MetricsRegistry

__all__ = ["GraphEngine", "ShardedGraphEngine", "GraphRegistry",
           "estimate_eccentricity"]


def _shard_backend_name(backend) -> str:
    """Resolve a relax-backend name/alias to the sharded tier's backend
    (one shared mapping — see :mod:`repro.core.config`)."""
    from ..core.config import _canonical_shard_backend
    return _canonical_shard_backend(backend)


class _StrongRef:
    """weakref.WeakMethod-shaped holder for callables that aren't bound
    methods (plain functions, lambdas)."""

    def __init__(self, cb):
        self._cb = cb

    def __call__(self):
        return self._cb


def estimate_eccentricity(hg, n_landmarks: int = 4,
                          landmarks=None) -> np.ndarray:
    """Per-vertex eccentricity estimate, in hops (host-side, O(k(N + M))).

    One hop-BFS from a landmark ``L_i`` gives hop distances ``h_i(v)``;
    with ``H_i = ecc(L_i)`` (in hops, observed), the triangle inequality
    bounds ``ecc(v) <= H_i + h_i(v)``, and a vertex far from *any*
    landmark is genuinely eccentric — so the estimate is the **max over
    the ``n_landmarks`` highest-degree landmarks** of each per-landmark
    estimate.  A single landmark under-ranks vertices that happen to sit
    near it but far from the rest of the graph; additional vantage
    points recover them.  The absolute value is still crude, but the
    *ordering* is what batch formation needs: sources estimated far run
    more stepping rounds, so grouping nearby estimates keeps a vmapped
    batch from paying one outlier's rounds.  Vertices disconnected from
    a landmark take ``2 * H_i + 1`` for it (worst bucket).

    ``landmarks`` overrides the vantage points with explicit vertex ids
    — an engine that already carries an ALT
    :class:`~repro.core.landmarks.LandmarkSet` reuses its choices, so
    the hint BFS and the ALT preprocessing agree on one landmark set.
    """
    n = hg.n
    if n == 0:
        return np.zeros(0, np.float32)
    row_ptr = np.asarray(hg.row_ptr, np.int64)
    dst = np.asarray(hg.dst, np.int64)
    if landmarks is None:
        if n_landmarks < 1:
            raise ValueError("n_landmarks must be >= 1")
        deg = np.asarray(hg.deg)
        # k distinct max-degree landmarks, ties broken by id (stable)
        landmarks = np.argsort(-deg, kind="stable")[:min(n_landmarks, n)]
    else:
        landmarks = np.asarray(landmarks, np.int64)
        if landmarks.size < 1:
            raise ValueError("landmarks must be non-empty")
    # max over the landmarks that actually *reach* a vertex: on a
    # disconnected graph a foreign component's landmark would otherwise
    # contribute a flat disconnection constant that swamps the local
    # ordering.  Vertices unreached by every landmark share the worst
    # bucket (they have no ordering information at all).
    ecc = np.full(n, -1, np.int64)
    worst = 1
    for lm in landmarks:
        hop = hop_bfs(row_ptr, dst, n, int(lm))
        h_max = int(hop.max())
        ecc = np.where(hop >= 0, np.maximum(ecc, h_max + hop), ecc)
        worst = max(worst, 2 * h_max + 1)
    return np.where(ecc >= 0, ecc, worst).astype(np.float32)


GraphSpec = Union[HostGraph, DeviceGraph, Callable[[], HostGraph]]


class _EngineBase:
    """Shared serving state: eccentricity hints + measured-rounds feedback.

    ``batch_hint`` is what batch formation reads.  It starts as the
    landmark-BFS eccentricity estimate and is EMA-blended with *measured*
    per-source round counts (:meth:`record_rounds`, fed back by the
    scheduler after every batch): vertices that have actually been served
    converge to their true stepping cost, unvisited ones keep the BFS
    prior.  The two are on different scales (hops vs rounds), which is
    fine — grouping only needs a consistent *ordering*, and rounds
    correlate monotonically with hop eccentricity.
    """

    def __init__(self):
        self._ecc_hint: Optional[np.ndarray] = None
        self._batch_hint: Optional[np.ndarray] = None
        self._hint_lock = threading.Lock()
        self.generation = 0     # registry spec generation (stamped on build)
        self.landmarks: Optional[LandmarkSet] = None   # ALT artifact

    @property
    def ecc_hint(self) -> np.ndarray:
        """Lazy landmark-BFS eccentricity estimates (only ecc-aware batch
        formation reads these; FIFO consumers never pay the BFS).  An
        engine carrying an ALT :class:`LandmarkSet` reuses its landmark
        choices as the BFS vantage points."""
        if self._ecc_hint is None:
            lm = (self.landmarks.landmarks
                  if self.landmarks is not None else None)
            self._ecc_hint = estimate_eccentricity(self.host, landmarks=lm)
        return self._ecc_hint

    @property
    def batch_hint(self) -> np.ndarray:
        """Feedback-blended per-vertex stepping-cost estimate (see class
        docstring); identical to ``ecc_hint`` until rounds are fed back."""
        if self._batch_hint is None:
            with self._hint_lock:
                if self._batch_hint is None:
                    self._batch_hint = self.ecc_hint.astype(np.float32,
                                                            copy=True)
        return self._batch_hint

    def peek_batch_hint(self) -> Optional[np.ndarray]:
        """``batch_hint`` only if it is available without running the
        landmark BFS (None otherwise) — safe to call under a scheduler
        lock.  A computed ``ecc_hint`` is promoted (an O(N) copy, no
        BFS); the scheduler pays the BFS itself outside its lock."""
        if self._batch_hint is None and self._ecc_hint is None:
            return None
        return self.batch_hint

    def record_rounds(self, sources, rounds, gamma: float = 0.25) -> None:
        """EMA-blend measured per-source round counts into ``batch_hint``."""
        sources = np.asarray(sources, np.int64)
        rounds = np.asarray(rounds, np.float32)
        if sources.size == 0:
            return
        hint = self.batch_hint
        with self._hint_lock:
            hint[sources] = (1.0 - gamma) * hint[sources] + gamma * rounds


class GraphEngine(_EngineBase):
    """One built (graph, backend) serving entry — the single-device tier.

    Owns the device graph, the backend layout (built once), the hoisted
    host-side degree array, and the batch-formation hints; ``run_batch``
    executes one fused multi-source goal query batch.  With ``device``
    set, graph + layout buffers are ``jax.device_put`` there, making the
    jitted batch device-affine (it runs on that device, no transfers).
    """

    tier = "single"

    def __init__(self, gid: str, hg, backend: str,
                 alpha: float, beta: float, device=None,
                 max_iters: int = 1_000_000, fused_rounds: int = 0,
                 policy: str = "static", landmarks=None,
                 p2p_mode: str = "unidirectional", **backend_opts):
        super().__init__()
        self.gid = gid
        self.host = hg
        self.device = device
        self.max_iters = max_iters
        self.fused_rounds = fused_rounds
        self.policy = policy
        self.p2p_mode = p2p_mode
        g = hg.to_device() if isinstance(hg, HostGraph) else hg
        if device is not None:
            g = jax.device_put(g, device)
            if landmarks is not None:
                # device-affine engines keep the ALT matrix beside the
                # graph so the jitted p2p batch never transfers it
                landmarks = landmarks.placed(device)
        self.landmarks = landmarks
        self.g: DeviceGraph = g
        self.backend = relax.get_backend(backend)
        layout = self.backend.prepare(self.g, **backend_opts)
        if device is not None:
            layout = jax.device_put(layout, device)
        self.layout = layout
        self.alpha = alpha
        self.beta = beta
        # hoisted once: per-slot metric normalization reads this every batch
        self.deg = np.asarray(hg.deg)
        self.n = int(self.deg.shape[0])

    def run_batch(self, sources, goal: str = "tree", goal_params=None):
        """One fused batch; returns ``(dist, parent, metrics)`` with a
        leading slot axis.  Results are *device* arrays — dispatch is
        async, so a caller can overlap host-side work with the device
        computation (the scheduler's double buffering) and force them
        with ``np.asarray`` only when needed."""
        alt = {}
        if goal == "p2p" and self.landmarks is not None:
            alt["landmarks"] = self.landmarks
            if self.p2p_mode == "bidirectional":
                # bidirectional validates as a config only with ALT on
                alt["p2p_mode"] = self.p2p_mode
                alt["use_alt"] = True
        return sssp_batch(
            self.g, np.asarray(sources, np.int32), backend=self.backend,
            layout=self.layout, alpha=self.alpha, beta=self.beta,
            max_iters=self.max_iters,
            fused_rounds=self.fused_rounds or None,
            policy=None if self.policy == "static" else self.policy,
            goal=goal, goal_params=goal_params, **alt)


class ShardedGraphEngine(_EngineBase):
    """The sharded serving tier: one graph spanning the whole device mesh.

    Built for graphs above the registry's shard thresholds, where a
    single device can't (or shouldn't) hold dist/parent + the edge list.
    The graph is block-partitioned with
    :func:`repro.core.distributed.shard_graph`, each slab placed on its
    device via ``NamedSharding``, and batches run through the v2
    sharded-dist ``shard_map`` engine's batch entry point
    (:func:`repro.core.distributed.sssp_distributed_batch`) with the same
    goal semantics as the single-device tier — so the registry/scheduler
    stack serves both tiers through one ``run_batch`` interface.

    ``backend`` selects the per-shard relaxation
    (:data:`repro.core.distributed.DIST_BACKENDS`): ``"blocked"`` builds
    the sparsity-aware per-shard blocked slabs
    (:func:`repro.core.distributed.shard_blocked`, ``block_v``/``tile_e``
    sized) once at engine build and threads them through every batch;
    results are bitwise-identical across backends.
    """

    tier = "sharded"

    def __init__(self, gid: str, hg, alpha: float, beta: float,
                 devices=None, version: str = "v2", fused_rounds: int = 0,
                 backend: str = "segment_min", capacity: int = 0,
                 max_iters: int = 1_000_000, policy: str = "static",
                 landmarks=None, **blocked_opts):
        super().__init__()
        self.gid = gid
        self.host = hg
        self.deg = np.asarray(hg.deg)
        self.n = int(self.deg.shape[0])
        self.alpha = alpha
        self.beta = beta
        self.version = version
        self.fused_rounds = fused_rounds
        self.policy = policy
        self.capacity = capacity
        self.max_iters = max_iters
        self.backend = _shard_backend_name(backend)
        devs = tuple(devices) if devices else tuple(jax.devices())
        self.devices = devs
        self.mesh = Mesh(np.array(devs), ("graph",))
        sg = shard_graph(hg, len(devs))
        # pre-place each slab on its owner device (the engine's layout)
        self.sg = place_on_mesh(sg, self.mesh, graph_specs("graph"))
        self.blocked = None
        if self.backend == "blocked":
            arrays, bmeta = shard_blocked(hg, len(devs), **blocked_opts)
            self.blocked = (place_on_mesh(arrays, self.mesh,
                                          blocked_specs("graph")), bmeta)
        if landmarks is not None:
            # the ALT matrix is replicated across the mesh: every shard
            # prunes with the full per-vertex bound vector
            landmarks = landmarks.placed(
                NamedSharding(self.mesh, PartitionSpec()))
        self.landmarks = landmarks

    def run_batch(self, sources, goal: str = "tree", goal_params=None):
        """Same contract as :meth:`GraphEngine.run_batch` (leading slot
        axis, device arrays); padding vertices are sliced off."""
        lm = self.landmarks if goal == "p2p" else None
        dist, parent, metrics = sssp_distributed_batch(
            self.sg, np.asarray(sources, np.int32), self.mesh, ("graph",),
            version=self.version, fused_rounds=self.fused_rounds,
            capacity=self.capacity, max_iters=self.max_iters,
            alpha=self.alpha, beta=self.beta,
            policy=None if self.policy == "static" else self.policy,
            goal=goal, goal_params=goal_params, backend=self.backend,
            blocked=self.blocked, landmarks=lm)
        return dist[:, :self.n], parent[:, :self.n], metrics


class RegistryStats:
    """Counter-backed registry stats: the same ``stats.hits`` attribute
    surface as the old plain dataclass, but every field is a live
    read-through of a :class:`~repro.obs.metrics.MetricsRegistry` counter
    (``sssp_registry_<field>_total``), so the legacy accessors and the
    metrics snapshot/exposition can never disagree."""

    FIELDS = ("hits", "misses", "builds", "evictions", "build_waits")

    _HELP = {
        "hits": "Engine-cache lookups served from the cache",
        "misses": "Engine-cache lookups that required a build",
        "builds": "Engines built (cold or rebuild after re-register)",
        "evictions": "Engines dropped by LRU capacity pressure",
        "build_waits": "Lookups that waited on another thread's build",
    }

    def __init__(self, metrics):
        self._counters = {
            f: metrics.counter(f"sssp_registry_{f}_total", help=self._HELP[f])
            for f in self.FIELDS}

    def inc(self, field: str, amount: int = 1) -> None:
        self._counters[field].inc(amount)

    @property
    def hits(self) -> int:
        return self._counters["hits"].value

    @property
    def misses(self) -> int:
        return self._counters["misses"].value

    @property
    def builds(self) -> int:
        return self._counters["builds"].value

    @property
    def evictions(self) -> int:
        return self._counters["evictions"].value

    @property
    def build_waits(self) -> int:
        return self._counters["build_waits"].value

    def as_dict(self) -> dict:
        vals = {f: self._counters[f].value for f in self.FIELDS}
        total = vals["hits"] + vals["misses"]
        return {**vals,
                "hit_rate": vals["hits"] / total if total else 1.0}


class GraphRegistry:
    """LRU cache of serving engines over registered graph specs.

    Thread-safe: the LRU state is guarded by a short internal lock, and
    cold builds run outside it behind per-key futures — concurrent
    lookups of the *same* key share one build, lookups of other keys
    (notably already-built engines) never wait (see module docstring).

    ``shard_threshold_n`` / ``shard_threshold_m`` select the engine tier:
    a registered ``HostGraph`` at or above either threshold is served by
    a :class:`ShardedGraphEngine` over ``shard_devices`` (default: every
    local device); smaller graphs get the single-device
    :class:`GraphEngine` (optionally device-affine, see :meth:`engine`).
    ``register(..., tier=...)`` overrides per graph.  ``shard_backend``
    is the sharded tier's default relaxation backend; a per-lookup
    ``backend`` of ``blocked``/``blocked_pallas`` overrides it (the two
    tiers share one name axis, so a blocked-configured router serves
    blocked engines on both).

    **Generations.**  Every :meth:`register` bumps the gid's generation
    counter; engines record the generation they were built from.
    Invalidation listeners (:meth:`add_invalidation_listener`) fire after
    each re-register so a router can rebuild already-placed replicas
    eagerly instead of letting the next query pay the cold build.
    """

    def __init__(self, capacity: Optional[int] = None, *,
                 config: Optional[EngineConfig] = None,
                 backend: Optional[str] = None,
                 alpha: Optional[float] = None, beta: Optional[float] = None,
                 shard_threshold_n: Optional[int] = None,
                 shard_threshold_m: Optional[int] = None,
                 shard_devices=None, shard_version: Optional[str] = None,
                 shard_backend: Optional[str] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 tuned=None, landmark_dir=None,
                 result_cache_capacity: int = 8, **backend_opts):
        # the config is the one option surface — loose kwargs (other than
        # capacity, which sizes this cache) must stay unset alongside it;
        # from_loose is the shared sentinel gate, so loose kwargs build
        # the very config the registry would have been given
        config = EngineConfig.from_loose(
            config, "registry",
            # the loose default pins the sharded tier to segment_min (no
            # blocked derivation), so the stored config agrees with this
            # registry's behavior
            defaults={"shard_backend": "segment_min"},
            backend=backend, alpha=alpha, beta=beta,
            shard_threshold_n=shard_threshold_n,
            shard_threshold_m=shard_threshold_m,
            shard_version=shard_version, shard_backend=shard_backend,
            devices=shard_devices, **backend_opts)
        config.validate_serving()
        backend_opts = {}
        for name in ("block_v", "tile_e", "use_kernel"):
            v = getattr(config, name)
            if v is not None:
                backend_opts[name] = v
        backend_opts["interpret"] = config.interpret
        if capacity is None:
            capacity = config.registry_capacity
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.config = config
        self.default_backend = relax.get_backend(config.backend).name
        self.alpha = config.alpha
        self.beta = config.beta
        self.backend_opts = backend_opts
        self.shard_threshold_n = config.shard_threshold_n
        self.shard_threshold_m = config.shard_threshold_m
        shard_devices = resolve_devices(config.devices)
        self.shard_devices = tuple(shard_devices) if shard_devices else None
        self.shard_version = config.shard_version
        self.shard_backend = config.effective_shard_backend
        # engine-variant knobs ride the config end-to-end (nothing a
        # resolve()-accepted config declares is silently dropped);
        # fused_rounds serves both tiers — the blocked single-device
        # megakernel and the sharded engines' round grouping / waves
        self.fused_rounds = config.fused_rounds
        self.shard_capacity = config.compact_capacity
        self.max_iters = config.max_iters
        self._lock = threading.RLock()
        self._specs: Dict[str, GraphSpec] = {}
        self._tiers: Dict[str, str] = {}
        self._gens: Dict[str, int] = {}
        self._listeners: list = []
        self._engines: "collections.OrderedDict[tuple, object]" \
            = collections.OrderedDict()
        self._building: Dict[tuple, Future] = {}
        # per-gid ALT landmark sets (see repro.core.landmarks): built
        # once per (gid, generation, params) and shared by every engine
        # variant of the gid — backend/device replicas reuse one build
        self._landmark_sets: Dict[str, LandmarkSet] = {}
        # the metrics registry is the shared sink for the whole serving
        # plane: schedulers/routers built on top of this registry default
        # to it, so one snapshot covers every layer
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.stats = RegistryStats(self.metrics)
        # offline-tuned per-gid configs (see repro.tune): a TunedStore or
        # a path to one; consulted at engine build, never on the hot path
        if tuned is not None and not hasattr(tuned, "apply"):
            from ..tune.store import TunedStore
            tuned = TunedStore(tuned)
        self.tuned = tuned
        self._tuned_builds = self.metrics.counter(
            "sssp_registry_tuned_builds_total",
            help="Engines built with a TunedStore override applied")
        # on-disk LandmarkSet cache (next to tuned configs): files keyed
        # by gid + graph fingerprint + build params, so a cold start on
        # an unchanged graph skips the batched landmark tree solve
        self._landmark_dir = (os.fspath(landmark_dir)
                              if landmark_dir is not None else None)
        self._lm_disk = {
            op: self.metrics.counter(
                f"sssp_landmarks_disk_{op}_total",
                help=f"LandmarkSet disk-cache {op}")
            for op in ("loads", "saves")}
        # streaming deltas (repro.delta): per-gid cumulative directed-edit
        # fraction + whether every delta so far was increase/remove-only
        # (the condition for stale-landmark admissibility), and a bounded
        # per-gid cache of full-tree solve states that apply_delta
        # *repairs* instead of evicting
        if result_cache_capacity < 1:
            raise ValueError("result_cache_capacity must be >= 1")
        self.result_cache_capacity = result_cache_capacity
        self._delta_frac: Dict[str, float] = {}
        self._delta_safe: Dict[str, bool] = {}
        self._result_cache: Dict[str, "collections.OrderedDict"] = {}
        self._delta_counters = {
            name: self.metrics.counter(f"sssp_delta_{name}_total", help=h)
            for name, h in (
                ("applied", "Edge-delta batches applied"),
                ("edges", "Directed edge edits applied"),
                ("layout_patches", "Cached engines patched in place"),
                ("repaired", "Cached solve states incrementally repaired"),
                ("reseeded", "Frontier vertices re-seeded by repairs"),
                ("landmarks_kept",
                 "LandmarkSets kept (stale) within the staleness budget"),
                ("landmarks_dropped",
                 "LandmarkSets dropped by deltas beyond the budget"),
            )}

    # ------------------------------------------------------------------
    # specs + tiers
    # ------------------------------------------------------------------

    def register(self, gid: str, graph: GraphSpec, *,
                 tier: Optional[str] = None) -> None:
        """Register (or replace) a graph spec; drops any cached engines
        built from the previous spec.  ``tier`` forces ``"single"`` or
        ``"sharded"``; default auto-selects by the shard thresholds
        (factory specs default to ``"single"`` — their size is unknown
        until built, so pass ``tier="sharded"`` explicitly)."""
        if not (isinstance(graph, (HostGraph, DeviceGraph))
                or callable(graph)):
            raise TypeError(
                f"expected HostGraph/DeviceGraph or factory for {gid!r}, "
                f"got {type(graph)}")
        if tier not in (None, "single", "sharded"):
            raise ValueError(f"tier must be 'single' or 'sharded', "
                             f"got {tier!r}")
        if tier is None:
            tier = "single"
            if isinstance(graph, (HostGraph, DeviceGraph)):
                n, m = int(graph.n), int(graph.m)
                if ((self.shard_threshold_n is not None
                     and n >= self.shard_threshold_n)
                        or (self.shard_threshold_m is not None
                            and m >= self.shard_threshold_m)):
                    tier = "sharded"
        with self._lock:
            replaced = gid in self._specs
            self._specs[gid] = graph
            self._tiers[gid] = tier
            self._gens[gid] = gen = self._gens.get(gid, 0) + 1
            for key in [k for k in self._engines if k[0] == gid]:
                del self._engines[key]
            # the ALT artifact belongs to the replaced spec: a rebuild
            # against the new spec is forced by the generation stamp,
            # dropping eagerly just frees the [L, N] matrix sooner
            self._landmark_sets.pop(gid, None)
            # a fresh spec resets the delta ledger and the repairable
            # result cache (cached states belong to the replaced graph)
            self._delta_frac.pop(gid, None)
            self._delta_safe.pop(gid, None)
            self._result_cache.pop(gid, None)
            # detach in-flight builds of the old spec: lookups from here
            # on start a fresh build of the new spec instead of attaching
            # to a stale future (the old build's owner only resolves its
            # own future — pre-replacement waiters — and the spec guard
            # below keeps its stale engine out of the cache)
            for key in [k for k in self._building if k[0] == gid]:
                del self._building[key]
            listeners = []
            if replaced:
                live = []
                for ref in self._listeners:
                    cb = ref()
                    if cb is not None:       # drop dead (collected) owners
                        live.append(ref)
                        listeners.append(cb)
                self._listeners = live
        # outside the lock: listeners typically rebuild engines (which
        # re-enter the registry); a first registration has no replicas to
        # refresh, so only *re*-registrations notify
        for cb in listeners:
            cb(gid, gen)

    def generation(self, gid: str) -> int:
        """Spec generation of ``gid`` (bumped by every :meth:`register`)."""
        with self._lock:
            if gid not in self._gens:
                raise KeyError(f"graph {gid!r} is not registered "
                               f"(have: {sorted(self._specs)})")
            return self._gens[gid]

    def add_invalidation_listener(self, cb) -> None:
        """Call ``cb(gid, generation)`` after every re-``register`` of an
        existing gid (in the registering thread, outside the registry
        lock).  Exceptions propagate to the ``register`` caller.

        Bound methods are held via ``weakref`` so a discarded owner (a
        router the caller dropped) is unhooked automatically instead of
        being kept alive — and rebuilt for — forever; plain functions
        and lambdas are held strongly (the caller owns their lifetime).
        """
        try:
            ref = weakref.WeakMethod(cb)
        except TypeError:
            ref = _StrongRef(cb)
        with self._lock:
            self._listeners.append(ref)

    def tier(self, gid: str) -> str:
        """The engine tier (``"single"``/``"sharded"``) serving ``gid``."""
        with self._lock:
            if gid not in self._tiers:
                raise KeyError(f"graph {gid!r} is not registered "
                               f"(have: {sorted(self._specs)})")
            return self._tiers[gid]

    @property
    def gids(self) -> tuple:
        with self._lock:
            return tuple(self._specs)

    def cached_keys(self) -> tuple:
        """Currently built (gid, backend, placement) keys, LRU -> MRU."""
        with self._lock:
            return tuple(self._engines)

    # ------------------------------------------------------------------
    # engine lookup / build
    # ------------------------------------------------------------------

    def _resolve(self, gid: str, backend, device):
        with self._lock:      # RLock: atomic with a caller's locked section
            if self._tiers.get(gid) == "sharded":
                # sharded engines key on the *sharded* backend name
                # (segment_min / blocked): a blocked lookup builds a
                # blocked whole-mesh engine, every other lookup shares
                # the registry's default
                sb = (self.shard_backend if backend is None
                      else _shard_backend_name(backend))
                return (gid, sb, "sharded"), None
        backend = (relax.get_backend(backend).name if backend is not None
                   else self.default_backend)
        if device is None:
            return (gid, backend, None), None
        if isinstance(device, int):
            device = jax.devices()[device]
        return (gid, backend, ("dev", device.id)), device

    def peek(self, gid: str, backend: Optional[str] = None,
             device=None):
        """Return the cached engine or None — never builds, never waits,
        never touches LRU order or hit/miss stats (for lock-sensitive
        callers like the scheduler's batch-formation path)."""
        key, _ = self._resolve(gid, backend, device)
        with self._lock:
            return self._engines.get(key)

    def engine(self, gid: str, backend: Optional[str] = None, device=None):
        """Get-or-build the engine for ``(gid, backend, device)``.

        ``device`` pins the single-device tier's buffers to that jax
        device (an index or a ``Device``; None keeps jax's default).
        Sharded-tier gids ignore ``device`` — their one engine spans
        ``shard_devices``.  Marks the entry MRU.
        """
        with self._lock:
            # key and (spec, tier) must come from one consistent view: a
            # racing register(tier=...) between them could file an engine
            # of one tier under the other tier's key
            key, dev = self._resolve(gid, backend, device)
            if gid not in self._specs:
                raise KeyError(f"graph {gid!r} is not registered "
                               f"(have: {sorted(self._specs)})")
            eng = self._engines.get(key)
            if eng is not None:
                self.stats.inc("hits")
                self._engines.move_to_end(key)
                return eng
            self.stats.inc("misses")
            fut = self._building.get(key)
            owner = fut is None
            if owner:
                fut = Future()
                self._building[key] = fut
                spec = self._specs[gid]
                tier = self._tiers[gid]
                gen = self._gens[gid]
            else:
                # same-key build in flight: share it (wait off-lock)
                self.stats.inc("build_waits")
        if not owner:
            return fut.result()
        # we own the build: run it outside the lock so other keys' lookups
        # (and producers) proceed
        try:
            eng = self._build(gid, spec, key[1], dev, tier)
            eng.generation = gen
        except BaseException as exc:
            with self._lock:
                if self._building.get(key) is fut:   # not replaced by a
                    del self._building[key]          # re-register's fresh build
            fut.set_exception(exc)
            raise
        with self._lock:
            if self._building.get(key) is fut:
                del self._building[key]
            self.stats.inc("builds")
            if self._specs.get(gid) is spec:     # not re-registered mid-build
                self._engines[key] = eng
                self._engines.move_to_end(key)
                while len(self._engines) > self.capacity:
                    self._engines.popitem(last=False)
                    self.stats.inc("evictions")
        fut.set_result(eng)
        return eng

    # ------------------------------------------------------------------
    # ALT landmark sets
    # ------------------------------------------------------------------

    def landmark_set(self, gid: str, hg=None, *,
                     n_landmarks: Optional[int] = None,
                     strategy: Optional[str] = None) -> LandmarkSet:
        """Get-or-build the gid's ALT :class:`LandmarkSet`.

        The cache is per-gid and validated on every lookup against the
        spec generation and the build parameters: a re-``register`` (new
        generation) or a changed ``n_landmarks``/``landmark_strategy``
        (a tuned overlay, say) rebuilds; otherwise every engine variant
        of the gid — backends, device replicas, both tiers — shares one
        ``[L, N]`` build.  ``hg`` avoids re-invoking a factory spec when
        the caller already holds the host graph.
        """
        if n_landmarks is None:
            n_landmarks = self.config.n_landmarks
        if strategy is None:
            strategy = self.config.landmark_strategy
        with self._lock:
            if gid not in self._specs:
                raise KeyError(f"graph {gid!r} is not registered "
                               f"(have: {sorted(self._specs)})")
            gen = self._gens[gid]
            spec = self._specs[gid]
            lm = self._landmark_sets.get(gid)
            if (lm is not None and lm.generation == gen
                    and lm.params() == (min(n_landmarks, int(lm.D.shape[1])),
                                        strategy)):
                return lm
        # build outside the lock (a tree-solve batch over the landmarks)
        if hg is None:
            hg = spec() if callable(spec) else spec
        path = self._landmark_path(gid, hg, n_landmarks, strategy)
        if path is not None and os.path.exists(path):
            # disk hit: the filename's graph fingerprint just matched, so
            # the saved set was built for this exact graph + params
            lm = dataclasses.replace(landmarks_mod.load(path),
                                     generation=gen)
            self._lm_disk["loads"].inc()
        else:
            with profiling.annotate(f"repro:landmark_build:{gid}"):
                lm = build_landmarks(hg, n_landmarks, strategy,
                                     generation=gen)
            if path is not None:
                os.makedirs(os.path.dirname(path), exist_ok=True)
                landmarks_mod.save(lm, path)
                self._lm_disk["saves"].inc()
        with self._lock:
            if self._gens.get(gid) == gen:      # not re-registered mid-build
                self._landmark_sets[gid] = lm
        return lm

    def _landmark_path(self, gid, hg, n_landmarks, strategy):
        """Disk-cache path for a gid's LandmarkSet (None when no
        ``landmark_dir``).  Keyed by graph fingerprint + build params —
        any delta moves the fingerprint, so a patched graph simply never
        matches the old file and rebuilds (then saves) a fresh one."""
        if self._landmark_dir is None:
            return None
        from ..tune.store import graph_fingerprint
        safe_gid = "".join(c if c.isalnum() or c in "-_" else "_"
                           for c in gid)
        k = min(int(n_landmarks), int(hg.n))
        return os.path.join(
            self._landmark_dir,
            f"landmarks_{safe_gid}_{graph_fingerprint(hg)}"
            f"_{k}_{strategy}.npz")

    def _build(self, gid, spec, backend, device, tier):
        with profiling.annotate(f"repro:engine_build:{gid}:{tier}"):
            return self._build_inner(gid, spec, backend, device, tier)

    def _build_inner(self, gid, spec, backend, device, tier):
        hg = spec() if callable(spec) else spec
        # per-gid tuned overlay: only the perf fields move (TUNED_FIELDS);
        # a stale fingerprint or an overlay this config can't carry falls
        # back inside TunedStore.apply, so the build never fails on it
        cfg = self.config
        if self.tuned is not None:
            # a graph still inside its delta staleness budget keeps its
            # tuned overlay (perf-only, bitwise-gated) even though the
            # fingerprint moved with the patch
            with self._lock:
                frac = self._delta_frac.get(gid, 0.0)
            stale_ok = 0.0 < frac <= self.config.delta_staleness_budget
            tuned_cfg = self.tuned.apply(gid, hg, cfg, n=int(hg.n),
                                         m=int(hg.m), allow_stale=stale_ok)
            if tuned_cfg != cfg:
                cfg = tuned_cfg
                self._tuned_builds.inc()
        lm = None
        if cfg.use_alt:
            lm = self.landmark_set(gid, hg, n_landmarks=cfg.n_landmarks,
                                   strategy=cfg.landmark_strategy)
        if tier == "sharded":
            # only the blocked layout's geometry opts apply mesh-side
            blocked_opts = {k: v for k, v in self.backend_opts.items()
                            if k in ("block_v", "tile_e", "use_kernel",
                                     "interpret")}
            if backend == "blocked":
                for nm in ("block_v", "tile_e"):
                    v = getattr(cfg, nm)
                    if v is None:
                        blocked_opts.pop(nm, None)
                    else:
                        blocked_opts[nm] = v
            return ShardedGraphEngine(gid, hg, cfg.alpha, cfg.beta,
                                      devices=self.shard_devices,
                                      version=self.shard_version,
                                      fused_rounds=cfg.fused_rounds,
                                      capacity=cfg.compact_capacity,
                                      max_iters=self.max_iters,
                                      backend=backend, policy=cfg.policy,
                                      landmarks=lm, **blocked_opts)
        backend_opts = dict(self.backend_opts)
        is_blocked = relax.get_backend(backend).name == "blocked_pallas"
        if is_blocked:
            for nm in ("block_v", "tile_e"):
                v = getattr(cfg, nm)
                if v is None:
                    backend_opts.pop(nm, None)
                else:
                    backend_opts[nm] = v
        # fused_rounds is a blocked-megakernel knob on the single-device
        # tier; a per-lookup segment_min backend must not inherit it
        fused = cfg.fused_rounds if is_blocked else 0
        return GraphEngine(gid, hg, backend, cfg.alpha, cfg.beta,
                           device=device, max_iters=self.max_iters,
                           fused_rounds=fused, policy=cfg.policy,
                           landmarks=lm, p2p_mode=cfg.p2p_mode,
                           **backend_opts)

    def evict(self, gid: str, backend: Optional[str] = None,
              device=None) -> bool:
        """Drop a cached engine (the spec stays registered)."""
        key, _ = self._resolve(gid, backend, device)
        with self._lock:
            return self._engines.pop(key, None) is not None

    # ------------------------------------------------------------------
    # streaming deltas (repro.delta): patch + repair instead of rebuild
    # ------------------------------------------------------------------

    def delta_frac(self, gid: str) -> float:
        """Cumulative directed-edit fraction (edits / m) the gid has
        absorbed since its last :meth:`register` (the staleness ledger)."""
        with self._lock:
            return self._delta_frac.get(gid, 0.0)

    def cache_result(self, gid: str, source: int, dist, parent) -> None:
        """Cache a **full-tree** solve state for delta repair.

        :meth:`apply_delta` repairs cached states in place instead of
        evicting them, keeping them bitwise-identical to from-scratch
        solves on the patched graph.  Tree goals only: an early-exit
        goal (p2p/bounded/knear) stops with tentative entries beyond its
        horizon, and repairing such a state would relax it toward the
        full-tree fixpoint — no longer the early-exit answer.  LRU per
        gid, at most ``result_cache_capacity`` sources.
        """
        dist = np.asarray(dist, np.float32).copy()
        parent = np.asarray(parent, np.int32).copy()
        with self._lock:
            if gid not in self._specs:
                raise KeyError(f"graph {gid!r} is not registered "
                               f"(have: {sorted(self._specs)})")
            cache = self._result_cache.setdefault(
                gid, collections.OrderedDict())
            cache[int(source)] = (dist, parent)
            cache.move_to_end(int(source))
            while len(cache) > self.result_cache_capacity:
                cache.popitem(last=False)

    def cached_result(self, gid: str, source: int):
        """``(dist, parent)`` numpy arrays for a cached tree solve, or
        ``None``; marks the entry MRU."""
        with self._lock:
            cache = self._result_cache.get(gid)
            if cache is None or int(source) not in cache:
                return None
            cache.move_to_end(int(source))
            return cache[int(source)]

    def apply_delta(self, gid: str, edits) -> dict:
        """Apply an :class:`~repro.delta.EdgeDelta` to ``gid`` *in place*.

        The streaming counterpart of :meth:`register`: one host-side
        patch (:func:`repro.delta.patch_host`) is shared by every cached
        engine of the gid — each backend/placement/tier gets its layout
        patched rather than rebuilt (single-device blocked layouts
        through :func:`repro.delta.patch_blocked_with`, sharded slabs
        through :func:`repro.delta.patch_sharded_with`; patched layouts
        are bitwise-identical to a from-scratch rebuild).  Cached tree
        states (:meth:`cache_result`) are incrementally repaired,
        bitwise-identical to from-scratch solves on the patched graph.

        Unlike :meth:`register`, the generation is **not** bumped and
        invalidation listeners do **not** fire: a router's placed
        replicas stay placed and receive the patched engines (one patch,
        N placements — no per-replica re-bucketing).  Engines are
        replaced as patched shallow copies, so an in-flight batch on the
        old object stays internally consistent.

        Perf artifacts follow ``config.delta_staleness_budget``
        (cumulative directed edits / m): the gid's ALT LandmarkSet
        survives increase/remove-only deltas within budget — marked
        ``stale``, which drops it to forward-difference bounds (old
        distances stay admissible lower bounds, see
        :class:`~repro.core.landmarks.LandmarkSet`) — and TunedStore
        overlays keep applying within budget.  Beyond the budget (or
        after any add/decrease) the LandmarkSet is dropped and rebuilds
        lazily.  Holds the registry lock for the patch; returns a report
        dict (``n_edits``/``engines_patched``/``results_repaired``/
        ``delta_frac``/``landmarks``/``host``/``applied``).
        """
        with self._lock:
            if gid not in self._specs:
                raise KeyError(f"graph {gid!r} is not registered "
                               f"(have: {sorted(self._specs)})")
            spec = self._specs[gid]
            if callable(spec):
                spec = spec()
            if isinstance(spec, DeviceGraph):
                spec = HostGraph(
                    n=int(spec.n), src=np.asarray(spec.src),
                    dst=np.asarray(spec.dst), w=np.asarray(spec.w),
                    row_ptr=np.asarray(spec.row_ptr),
                    deg=np.asarray(spec.deg), rtow=np.asarray(spec.rtow),
                    max_w=float(spec.max_w), directed=spec.directed)
            old_host = spec
            with profiling.annotate(f"repro:apply_delta:{gid}"):
                new_host, applied = patch_host(old_host, edits)
                self._specs[gid] = new_host
                # in-flight builds saw the old spec; the spec-identity
                # guard in engine() keeps their product out of the cache
                for key in [k for k in self._building if k[0] == gid]:
                    del self._building[key]
                frac = (self._delta_frac.get(gid, 0.0)
                        + applied.n_edits / max(old_host.m, 1))
                self._delta_frac[gid] = frac
                safe = self._delta_safe.get(gid, True) and applied.safe_stale
                self._delta_safe[gid] = safe
                keep_lm = safe and frac <= self.config.delta_staleness_budget
                lm = self._landmark_sets.get(gid)
                if lm is not None:
                    if keep_lm:
                        self._landmark_sets[gid] = dataclasses.replace(
                            lm, stale=True)
                        self._delta_counters["landmarks_kept"].inc()
                    else:
                        self._landmark_sets.pop(gid, None)
                        self._delta_counters["landmarks_dropped"].inc()
                n_patched = 0
                for key in [k for k in self._engines if k[0] == gid]:
                    eng = self._patch_engine(self._engines[key], old_host,
                                             new_host, applied, keep_lm)
                    self._engines[key] = eng    # same key: LRU position kept
                    n_patched += 1
                n_repaired = 0
                cache = self._result_cache.get(gid)
                if cache:
                    g_new = new_host.to_device()
                    for source in list(cache):
                        dist, parent = cache[source]
                        d_i, p_i, f0, st = repair_state(new_host, dist,
                                                        parent, applied)
                        d2, p2, _ = repair_relax(g_new, d_i, p_i, f0,
                                                 max_iters=self.max_iters)
                        cache[source] = (np.asarray(d2), np.asarray(p2))
                        self._delta_counters["reseeded"].inc(st.n_seeds)
                        n_repaired += 1
                    self._delta_counters["repaired"].inc(n_repaired)
                self._delta_counters["applied"].inc()
                self._delta_counters["edges"].inc(applied.n_edits)
                self._delta_counters["layout_patches"].inc(n_patched)
        return {"gid": gid, "n_edits": applied.n_edits,
                "engines_patched": n_patched,
                "results_repaired": n_repaired, "delta_frac": frac,
                "landmarks": ("stale" if lm is not None and keep_lm
                              else "dropped" if lm is not None else "none"),
                "host": new_host, "applied": applied}

    def _patch_engine(self, eng, old_host, new_host, applied, keep_lm):
        """Patched shallow copy of a cached engine (either tier).

        The copy shares the hint state (eccentricity estimates are
        scheduling heuristics; a small delta barely moves them) and gets
        new graph/layout buffers; the original object is left untouched
        for any batch already running on it.
        """
        eng = copy.copy(eng)
        eng.host = new_host
        eng.deg = np.asarray(new_host.deg)
        if eng.landmarks is not None:
            eng.landmarks = (dataclasses.replace(eng.landmarks, stale=True)
                             if keep_lm else None)
        if eng.tier == "sharded":
            sg = patch_sharded_with(eng.sg, new_host, applied)
            eng.sg = place_on_mesh(sg, eng.mesh, graph_specs("graph"))
            if eng.blocked is not None:
                # per-shard blocked slabs: full re-bucket for now (the
                # uniform-n_tiles stacked layout couples every shard's
                # tile budget; an in-place patcher is a follow-up)
                _, bmeta = eng.blocked
                arrays, bmeta = shard_blocked(
                    new_host, len(eng.devices), block_v=bmeta.block_v,
                    tile_e=bmeta.tile_e, use_kernel=bmeta.use_kernel,
                    interpret=bmeta.interpret)
                eng.blocked = (place_on_mesh(arrays, eng.mesh,
                                             blocked_specs("graph")), bmeta)
            return eng
        g = new_host.to_device()
        if eng.device is not None:
            g = jax.device_put(g, eng.device)
        eng.g = g
        if eng.backend.name == "blocked_pallas":
            layout = patch_blocked_with(eng.layout, old_host, new_host,
                                        applied)
            if eng.device is not None:
                layout = jax.device_put(layout, eng.device)
            eng.layout = layout
        else:
            eng.layout = eng.backend.prepare(eng.g)
        return eng

    # ------------------------------------------------------------------
    # warmup
    # ------------------------------------------------------------------

    def warmup(self, gids=None, *, backend: Optional[str] = None,
               device=None, kinds=("tree",), batch_sizes=(1,)):
        """Pre-pay engine builds and per-(graph, kind, batch-width) jit
        compiles before traffic arrives.

        Runs one dummy batch per (gid, kind, batch size) — the jit cache
        is keyed on the static goal kind and the batch width, so pass the
        scheduler's ``max_batch`` in ``batch_sizes`` for the compiles to
        be the ones traffic will hit.  Returns one row dict per dummy
        batch with ``build_s`` (engine build, attributed to its first
        row) and ``compile_s`` wall times — the serving benchmark reports
        these as the warmup cost.
        """
        if isinstance(gids, str):
            gids = [gids]
        gids = list(self.gids) if gids is None else list(gids)
        for kind in kinds:
            if kind not in GOALS:
                raise ValueError(f"unknown warmup kind {kind!r}; "
                                 f"expected one of {GOALS}")
        rows = []
        for gid in gids:
            t0 = time.perf_counter()
            eng = self.engine(gid, backend, device=device)
            build_s = time.perf_counter() - t0
            src = int(np.argmax(eng.deg))       # a vertex with edges
            for kind in kinds:
                for bs in batch_sizes:
                    bs = int(bs)
                    gp = {"tree": None, "p2p": [src] * bs,
                          "bounded": [0.0] * bs, "knear": [1] * bs}[kind]
                    t0 = time.perf_counter()
                    out = eng.run_batch([src] * bs, goal=kind,
                                        goal_params=gp)
                    jax.block_until_ready(out[0])
                    rows.append({"gid": gid, "tier": eng.tier, "kind": kind,
                                 "batch": bs, "build_s": build_s,
                                 "compile_s": time.perf_counter() - t0})
                    build_s = 0.0               # attribute the build once
        return rows

