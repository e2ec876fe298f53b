"""Declarative engine configuration — the one place backend/tier/placement
options live.

Four PRs of growth scattered a dozen loose keyword arguments
(``backend=``, ``shard_backend=``, ``shard_threshold_n=``,
``use_kernel=``, ...) across the single-device engine, the three
distributed engines, and the serving registry/router/service.  This
module replaces them with one frozen :class:`EngineConfig` value that
every layer accepts, plus an explicit, testable :meth:`EngineConfig.resolve`
step that turns the declarative config (which may say ``tier="auto"``)
into a concrete :class:`ResolvedEngine` — the engine tier, canonical
backend names, and device placement a solver session will actually use.

Resolution is deliberately separate from construction:

* ``EngineConfig(...)`` validates *context-free* invariants (known
  names, positive sizes) so a bad config fails where it is written;
* ``resolve(n=..., m=..., n_devices=...)`` validates *contextual*
  invariants (tier/backend conflicts, threshold-driven auto-tiering,
  device counts) and fails loudly **before** any tracing or layout
  build — a misconfigured solver never reaches ``jit``.

:class:`FacadeDeprecationWarning` marks the legacy ``sssp_*`` wrapper
entry points; tier-1 CI escalates it to an error so internal code cannot
quietly keep calling the shims (see ``pyproject.toml``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = ["ConfigError", "FacadeDeprecationWarning", "EngineConfig",
           "ResolvedEngine", "resolve_interpret", "check_kernel_geometry",
           "KERNEL_TILING", "TIERS",
           "SHARD_VERSIONS", "STEP_POLICIES",
           "P2P_MODES", "LANDMARK_STRATEGIES"]

TIERS = ("auto", "single", "sharded", "routed")
SHARD_VERSIONS = ("v1", "v2", "v3")
# stepping-policy names (kept in sync with repro.core.stepping.POLICIES)
STEP_POLICIES = ("static", "adaptive")
# p2p search directions (kept in sync with repro.core.sssp)
P2P_MODES = ("unidirectional", "bidirectional")
# landmark selection strategies (kept in sync with repro.core.landmarks)
LANDMARK_STRATEGIES = ("farthest", "max_degree")

# single-device relax-backend names whose sharded twin is the blocked
# per-shard layout (kept in sync with repro.core.distributed)
_BLOCKED_NAMES = ("blocked", "blocked_pallas")


class ConfigError(ValueError):
    """A contradictory or unresolvable :class:`EngineConfig`."""


class FacadeDeprecationWarning(DeprecationWarning):
    """Emitted by the legacy ``sssp_*`` wrapper shims.

    Kept as a dedicated category so the test suite can escalate exactly
    these to errors (internal code must use the :mod:`repro.api` facade)
    while parity tests exercise the shims under ``pytest.warns``.
    """


def _canonical_backend(name) -> str:
    """Resolve a relax-backend name/alias/object to its canonical name."""
    from . import relax
    try:
        return relax.get_backend(name).name
    except ValueError as exc:
        raise ConfigError(str(exc)) from None


def _canonical_shard_backend(name) -> str:
    """Resolve a backend name to the distributed engines' backend axis."""
    canon = _canonical_backend(name) if name not in ("segment_min",
                                                     "blocked") else name
    return "blocked" if canon in _BLOCKED_NAMES else canon


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode, decided once from the platform.

    ``None`` resolves to the interpreter on CPU and to compiled kernels
    on a TPU.  ``True`` on a TPU raises: it would time the Pallas
    interpreter and report it as the kernel.  ``False`` on CPU is kept,
    for compiling against a described TPU topology.
    """
    import jax
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ConfigError(
            "interpret=True on a TPU runs the Pallas interpreter in place "
            "of the compiled kernel; leave interpret unset (None)")
    return bool(interpret)


# Mosaic lays a 1-D kernel block out with the XLA tiling of its array,
# 1024 elements for the edge slabs and per-vertex outputs, and refuses
# smaller blocks at compile time
KERNEL_TILING = 1024


def check_kernel_geometry(block_v: int, tile_e: int, why: str = "") -> None:
    """Refuse a blocked geometry the compiled edge_relax kernels cannot
    take: ``block_v`` and ``tile_e`` must be multiples of
    :data:`KERNEL_TILING`."""
    if block_v % KERNEL_TILING or tile_e % KERNEL_TILING:
        raise ConfigError(
            f"compiled edge_relax kernels need block_v and tile_e in "
            f"multiples of {KERNEL_TILING} (the TPU tiling of 1-D arrays); "
            f"got block_v={block_v}, tile_e={tile_e}{why}; leave them "
            f"unset, or pass use_kernel=False for the jnp reference")


def resolve_devices(devices):
    """Concrete jax ``Device`` list for a config's ``devices`` field.

    Integer entries index ``jax.devices()`` (range-checked — a bad index
    raises :class:`ConfigError` here, not an ``IndexError`` mid-build);
    ``Device`` objects pass through; ``None`` stays ``None``.  The one
    conversion point for every config consumer (registry, router,
    service, solver)."""
    if devices is None:
        return None
    import jax
    pool = jax.devices()
    out = []
    for d in devices:
        if isinstance(d, int):
            if not 0 <= d < len(pool):
                raise ConfigError(f"device index {d} out of range for "
                                  f"{len(pool)} visible device(s)")
            out.append(pool[d])
        else:
            out.append(d)
    return out


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Declarative solver/serving configuration (frozen, hashable).

    One value of this type replaces the loose ``backend=`` /
    ``shard_backend=`` / ``shard_threshold_*`` / ``use_kernel=`` kwargs
    previously threaded through every layer.  Fields:

    * ``backend`` — single-device relaxation backend
      (:func:`repro.core.relax.available_backends`); aliases resolve.
    * ``tier`` — ``"single"`` (one device), ``"sharded"`` (whole-mesh
      ``shard_map`` engine), ``"routed"`` (multi-device serving plane),
      or ``"auto"`` (pick single vs sharded from the graph size against
      ``shard_threshold_n``/``shard_threshold_m``).
    * ``devices`` — explicit device placement (jax ``Device`` objects or
      integer indices); ``None`` uses every visible device for
      sharded/routed tiers and jax's default for single.
    * ``alpha``/``beta``/``max_iters`` — the stepping heuristic knobs.
    * ``policy`` — stepping policy: ``"static"`` (the paper's fixed
      Eq. 1-3 parameters) or ``"adaptive"`` (per-step feedback on
      ``alpha``/``beta`` and a window multiplier; see
      :mod:`repro.core.stepping`).  Scheduling-only: dist/parent match
      the static policy bitwise on graphs without exact float ties.
    * ``shard_backend`` — per-shard relaxation of the sharded tier
      (:data:`repro.core.distributed.DIST_BACKENDS`); ``None`` derives
      it from ``backend`` (``blocked_pallas`` -> ``blocked``).
    * ``shard_version``/``fused_rounds``/``compact_capacity`` — the
      distributed engine variant (v1/v2/v3, bucket-fusion waves, v3's
      compact-exchange capacity).
    * ``block_v``/``tile_e``/``use_kernel``/``interpret`` — blocked
      layout geometry (only meaningful with a blocked backend);
      ``interpret=None`` resolves from the platform
      (:func:`resolve_interpret`).
    * ``max_batch``/``registry_capacity``/``max_pending``/
      ``ecc_batching`` — serving-plane knobs (routed tier and the
      registry/scheduler stack).

    Construction validates context-free invariants; call
    :meth:`resolve` to validate tier/backend conflicts and obtain the
    concrete :class:`ResolvedEngine`.
    """

    backend: str = "segment_min"
    tier: str = "auto"
    devices: Optional[Tuple] = None
    alpha: float = 3.0
    beta: float = 0.9
    policy: str = "static"
    max_iters: int = 1_000_000
    # sharded tier
    shard_backend: Optional[str] = None
    shard_version: str = "v2"
    fused_rounds: int = 0
    compact_capacity: int = 0
    shard_threshold_n: Optional[int] = None
    shard_threshold_m: Optional[int] = None
    # blocked layout geometry
    block_v: Optional[int] = None
    tile_e: Optional[int] = None
    use_kernel: Optional[bool] = None
    interpret: Optional[bool] = None
    # serving plane
    max_batch: int = 8
    registry_capacity: int = 4
    max_pending: Optional[int] = None
    ecc_batching: bool = True
    # streaming deltas: cumulative directed-edit fraction (edits / m) a
    # graph may accumulate before its perf artifacts (ALT landmark sets,
    # tuned-config overlays) stop being reused; repairs stay bitwise
    # regardless — the budget only gates *heuristic* artifact reuse
    delta_staleness_budget: float = 0.05
    # observability: per-round solve traces (repro.obs.trace)
    trace: bool = False
    trace_capacity: int = 256
    # goal-directed p2p: ALT landmark lower bounds + search direction
    use_alt: bool = False
    n_landmarks: int = 8
    landmark_strategy: str = "farthest"
    p2p_mode: str = "unidirectional"

    def __post_init__(self):
        if self.tier not in TIERS:
            raise ConfigError(f"unknown tier {self.tier!r}; expected one "
                              f"of {TIERS}")
        if self.policy not in STEP_POLICIES:
            raise ConfigError(f"unknown policy {self.policy!r}; expected "
                              f"one of {STEP_POLICIES}")
        if self.shard_version not in SHARD_VERSIONS:
            raise ConfigError(f"unknown shard_version "
                              f"{self.shard_version!r}; expected one of "
                              f"{SHARD_VERSIONS}")
        _canonical_backend(self.backend)        # fail on unknown names now
        if self.shard_backend is not None:
            sb = _canonical_shard_backend(self.shard_backend)
            if sb not in ("segment_min", "blocked"):
                raise ConfigError(f"unknown shard_backend "
                                  f"{self.shard_backend!r}")
        if self.devices is not None:
            object.__setattr__(self, "devices", tuple(self.devices))
            if not self.devices:
                raise ConfigError("devices, when given, must be non-empty")
        for name in ("alpha", "beta"):
            if getattr(self, name) <= 0:
                raise ConfigError(f"{name} must be > 0")
        for name in ("max_iters", "max_batch", "registry_capacity"):
            if getattr(self, name) < 1:
                raise ConfigError(f"{name} must be >= 1")
        for name in ("fused_rounds", "compact_capacity"):
            if getattr(self, name) < 0:
                raise ConfigError(f"{name} must be >= 0")
        for name in ("shard_threshold_n", "shard_threshold_m", "block_v",
                     "tile_e", "max_pending"):
            v = getattr(self, name)
            if v is not None and v < 1:
                raise ConfigError(f"{name} must be >= 1 (or None)")
        if self.trace_capacity < 1:
            raise ConfigError("trace_capacity must be >= 1")
        if not 0.0 <= self.delta_staleness_budget <= 1.0:
            raise ConfigError("delta_staleness_budget must be in [0, 1]")
        if self.p2p_mode not in P2P_MODES:
            raise ConfigError(f"unknown p2p_mode {self.p2p_mode!r}; "
                              f"expected one of {P2P_MODES}")
        if self.landmark_strategy not in LANDMARK_STRATEGIES:
            raise ConfigError(
                f"unknown landmark_strategy {self.landmark_strategy!r}; "
                f"expected one of {LANDMARK_STRATEGIES}")
        if self.n_landmarks < 1:
            raise ConfigError("n_landmarks must be >= 1")
        if self.p2p_mode == "bidirectional":
            # the meet-in-the-middle search prunes both frontiers against
            # the shared meet bound, which only exists with ALT landmark
            # lower bounds; scheduling is forward-authoritative and fixed
            if not self.use_alt:
                raise ConfigError("p2p_mode='bidirectional' needs "
                                  "use_alt=True (the meet bound prunes "
                                  "through the ALT lower-bound machinery)")
            if self.policy != "static":
                raise ConfigError("p2p_mode='bidirectional' supports only "
                                  "policy='static'")
            if self.trace:
                raise ConfigError("p2p_mode='bidirectional' does not "
                                  "record per-round solve traces; drop "
                                  "trace=True")

    # ------------------------------------------------------------------
    # loose-kwarg adoption
    # ------------------------------------------------------------------

    @classmethod
    def from_loose(cls, config, what: str, *, defaults=None, **loose
                   ) -> "EngineConfig":
        """The one config-XOR-loose-kwargs gate for every entry point.

        Each engine/serving entry point accepts ``config=`` *or* its
        legacy loose kwargs, never both.  ``None`` is the unset sentinel
        for every loose kwarg (entry points default them all to None):

        * ``config`` given — every loose kwarg must still be unset, or
          this raises ``ConfigError("pass <what> options through
          config=, not alongside it")``; the config passes through.
        * ``config`` None — the set loose kwargs are layered over
          ``defaults`` (the entry point's historical defaults) and built
          into a fresh :class:`EngineConfig`; keys that are not config
          fields raise ``TypeError`` (unknown option), and values go
          through the constructor's usual validation.

        ``what`` names the entry point in the error message ("engine",
        "service", ...).  Relax-backend *objects* are accepted for
        ``backend`` and canonicalized to their registry name.
        """
        set_ = {k: v for k, v in loose.items() if v is not None}
        if config is not None:
            if set_:
                raise ConfigError(f"pass {what} options through config=, "
                                  f"not alongside it")
            return config
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(set_) - fields)
        if unknown:
            raise TypeError(f"unknown {what} options {unknown}")
        merged = dict(defaults or {})
        merged.update(set_)
        for key in ("backend", "shard_backend"):
            v = merged.get(key)
            if v is not None and not isinstance(v, str):
                merged[key] = _canonical_backend(v)
        return cls(**merged)

    # ------------------------------------------------------------------
    # resolution
    # ------------------------------------------------------------------

    @property
    def effective_shard_backend(self) -> str:
        """The sharded tier's backend: explicit, else derived from
        ``backend`` (``blocked_pallas`` maps to ``blocked``)."""
        if self.shard_backend is not None:
            return _canonical_shard_backend(self.shard_backend)
        return _canonical_shard_backend(self.backend)

    @property
    def has_thresholds(self) -> bool:
        return (self.shard_threshold_n is not None
                or self.shard_threshold_m is not None)

    def _auto_tier(self, n: Optional[int], m: Optional[int]) -> str:
        if not self.has_thresholds:
            return "single"
        if n is None and m is None:
            raise ConfigError(
                "tier='auto' with shard thresholds needs the graph size "
                "(resolve(n=..., m=...)) to pick single vs sharded")
        if (self.shard_threshold_n is not None and n is not None
                and n >= self.shard_threshold_n):
            return "sharded"
        if (self.shard_threshold_m is not None and m is not None
                and m >= self.shard_threshold_m):
            return "sharded"
        return "single"

    def validate_serving(self) -> "EngineConfig":
        """Contextual checks for the serving plane (registry / router /
        service), where per-graph tiering happens at ``register()`` time
        and per-lookup backends may override the defaults — so only
        combinations invalid under *every* possible lookup are rejected
        (blocked geometry without a blocked default stays legal: a
        per-lookup blocked backend consumes it)."""
        if self.compact_capacity and self.shard_version != "v3":
            raise ConfigError(
                "compact_capacity selects v3's compact exchange; set "
                "shard_version='v3' (or drop compact_capacity)")
        return self

    def _check_compiled_geometry(self, tier, shard_backend, n, n_shards):
        """The geometry the compiled kernels will run: the requested (or
        default) ``block_v``/``tile_e`` and, on the sharded blocked tier
        of a known graph, ``block_v`` as the layout snaps it to a divisor
        of the owner block (:func:`~repro.core.graph.shard_block_v`)."""
        from ..kernels.edge_relax.edge_relax import (DEFAULT_BLOCK_V,
                                                      DEFAULT_TILE_E)
        block_v = self.block_v or DEFAULT_BLOCK_V
        tile_e = self.tile_e or DEFAULT_TILE_E
        check_kernel_geometry(block_v, tile_e)
        if tier == "sharded" and shard_backend == "blocked" and n:
            from .graph import shard_block_v
            block = -(-int(n) // n_shards)
            snapped = shard_block_v(block, block_v)
            check_kernel_geometry(
                snapped, tile_e,
                f" (the sharded layout snaps block_v={block_v} to a divisor "
                f"of the {block}-vertex owner block of {n_shards} shards)")

    def resolve(self, *, n: Optional[int] = None, m: Optional[int] = None,
                n_devices: Optional[int] = None) -> "ResolvedEngine":
        """Resolve the declarative config against a graph/host context.

        ``n``/``m`` are the graph's vertex/edge counts (needed by
        ``tier="auto"`` thresholds); ``n_devices`` is the visible device
        count (defaults to ``len(jax.devices())``, or ``len(devices)``
        when the config pins devices).  Raises :class:`ConfigError` on
        any conflicting combination — **before** layouts are built or
        anything is traced.
        """
        backend = _canonical_backend(self.backend)
        shard_backend = self.effective_shard_backend

        tier = self.tier
        if tier == "auto":
            tier = self._auto_tier(n, m)
        elif self.has_thresholds and tier != "routed":
            # explicit single/sharded contradicts threshold auto-tiering;
            # routed keeps them (its registry tiers each graph)
            raise ConfigError(
                f"shard_threshold_n/_m only apply to tier='auto' or "
                f"'routed' (explicit tier {self.tier!r} already decided)")

        # sharded-only options are dead weight on a *necessarily* single
        # engine (explicit tier, or auto with no thresholds — which can
        # never resolve sharded).  Auto WITH thresholds legitimately
        # holds them for the graphs that cross the threshold, so a
        # deployment config must not fail data-dependently on small
        # graphs (the serving registry accepts it for the same reason).
        never_sharded = self.tier == "single" or not self.has_thresholds
        if tier == "single" and never_sharded:
            if self.shard_backend is not None:
                raise ConfigError(
                    "shard_backend is set but the engine can only "
                    "resolve to the single-device tier; drop it, set "
                    "tier='sharded', or add shard thresholds")
            if self.fused_rounds and backend not in _BLOCKED_NAMES:
                raise ConfigError(
                    "fused_rounds on the single-device tier needs a "
                    "blocked backend (the multi-round fused relaxation "
                    "megakernel); on segment_min it is a sharded-tier "
                    "option (bucket-fusion waves between exchanges)")
            if self.compact_capacity:
                raise ConfigError("compact_capacity is a sharded-tier "
                                  "option (v3's compact exchange)")
        if tier == "single" and never_sharded and self.devices is not None \
                and len(self.devices) > 1:
            # (with thresholds, a multi-device pin on a small graph just
            # places the single engine on devices[0])
            raise ConfigError(
                f"the single tier runs on one device; got "
                f"{len(self.devices)} (set tier='sharded' or 'routed')")
        self.validate_serving()
        if tier == "routed" and self.trace:
            raise ConfigError(
                "trace records per-round solve traces on the single/"
                "sharded tiers; the routed serving plane reports "
                "aggregate metrics through its MetricsRegistry instead "
                "(see repro.obs)")
        if tier == "sharded" and backend != "segment_min" \
                and self.shard_backend is not None \
                and shard_backend != _canonical_shard_backend(backend):
            raise ConfigError(
                f"backend={self.backend!r} and shard_backend="
                f"{self.shard_backend!r} disagree for tier='sharded'; "
                f"set one of them")
        if tier == "sharded" and self.p2p_mode == "bidirectional":
            raise ConfigError(
                "p2p_mode='bidirectional' runs on the single-device tier "
                "(the alternating forward/backward windows share one "
                "resident dist pair); use unidirectional ALT pruning on "
                "the sharded tier")
        blocked_anywhere = (backend in _BLOCKED_NAMES
                            or shard_backend == "blocked")
        if not blocked_anywhere:
            for name in ("block_v", "tile_e", "use_kernel"):
                if getattr(self, name) is not None:
                    raise ConfigError(
                        f"{name} is blocked-layout geometry but no blocked "
                        f"backend is selected (backend={backend!r}, "
                        f"shard_backend={shard_backend!r})")

        interpret = resolve_interpret(self.interpret)
        if (tier == "single" and self.fused_rounds and not interpret
                and backend in _BLOCKED_NAMES
                and self.use_kernel is not False):
            from ..kernels.edge_relax.edge_relax import FUSED_ON_TPU
            raise ConfigError(FUSED_ON_TPU)

        devices = self.devices
        if devices is not None:
            resolve_devices(devices)     # range-check int indices now
            if n_devices is not None and len(devices) != n_devices:
                raise ConfigError(
                    f"config pins {len(devices)} device(s) but the "
                    f"context provides {n_devices}")
            n_devices = len(devices)
        elif n_devices is None:
            import jax
            n_devices = len(jax.devices())
        if n_devices < 1:
            raise ConfigError("need at least one device")
        if blocked_anywhere and not interpret \
                and self.use_kernel is not False:
            self._check_compiled_geometry(tier, shard_backend, n, n_devices)

        return ResolvedEngine(
            tier=tier, backend=backend, shard_backend=shard_backend,
            devices=devices, n_shards=(len(devices) if devices is not None
                                       else n_devices),
            alpha=self.alpha, beta=self.beta, policy=self.policy,
            max_iters=self.max_iters,
            shard_version=self.shard_version,
            fused_rounds=self.fused_rounds,
            compact_capacity=self.compact_capacity,
            shard_threshold_n=self.shard_threshold_n,
            shard_threshold_m=self.shard_threshold_m,
            block_v=self.block_v, tile_e=self.tile_e,
            use_kernel=self.use_kernel, interpret=interpret,
            max_batch=self.max_batch,
            registry_capacity=self.registry_capacity,
            max_pending=self.max_pending, ecc_batching=self.ecc_batching,
            trace=self.trace, trace_capacity=self.trace_capacity,
            use_alt=self.use_alt, n_landmarks=self.n_landmarks,
            landmark_strategy=self.landmark_strategy,
            p2p_mode=self.p2p_mode,
            config=self)


@dataclasses.dataclass(frozen=True)
class ResolvedEngine:
    """The concrete engine an :class:`EngineConfig` resolved to.

    Every field is decided: ``tier`` is never ``"auto"``, backend names
    are canonical, ``n_shards`` is the mesh width the sharded tier would
    span.  Produced only by :meth:`EngineConfig.resolve`; carried by the
    :class:`repro.api.Solver` session and accepted (in place of loose
    kwargs) by the engine entry points.
    """

    tier: str
    backend: str
    shard_backend: str
    devices: Optional[Tuple]
    n_shards: int
    alpha: float
    beta: float
    policy: str
    max_iters: int
    shard_version: str
    fused_rounds: int
    compact_capacity: int
    shard_threshold_n: Optional[int]
    shard_threshold_m: Optional[int]
    block_v: Optional[int]
    tile_e: Optional[int]
    use_kernel: Optional[bool]
    interpret: bool
    max_batch: int
    registry_capacity: int
    max_pending: Optional[int]
    ecc_batching: bool
    trace: bool
    trace_capacity: int
    use_alt: bool
    n_landmarks: int
    landmark_strategy: str
    p2p_mode: str
    config: EngineConfig

    @property
    def trace_cap(self) -> int:
        """The engine-level trace knob: ring capacity, 0 = tracing off
        (the static jit key — 0 compiles the exact pre-trace program)."""
        return self.trace_capacity if self.trace else 0

    def require(self, *tiers: str) -> "ResolvedEngine":
        if self.tier not in tiers:
            raise ConfigError(f"engine resolved to tier {self.tier!r}; "
                              f"this entry point needs {tiers}")
        return self

    def resolve_devices(self):
        """Pinned devices as concrete jax ``Device`` objects (or None)."""
        return resolve_devices(self.devices)

    def layout_opts(self) -> dict:
        """Geometry kwargs for ``RelaxBackend.prepare`` /
        :func:`repro.core.graph.build_blocked` (only set fields)."""
        out = {}
        for name in ("block_v", "tile_e", "use_kernel"):
            v = getattr(self, name)
            if v is not None:
                out[name] = v
        if self.backend in _BLOCKED_NAMES:
            out["interpret"] = self.interpret
        return out

    def blocked_opts(self) -> dict:
        """Geometry kwargs for :func:`repro.core.distributed.shard_blocked`."""
        out = {}
        for name in ("block_v", "tile_e", "use_kernel"):
            v = getattr(self, name)
            if v is not None:
                out[name] = v
        out["interpret"] = self.interpret
        return out


def as_resolved(config, *, n=None, m=None, n_devices=None) -> ResolvedEngine:
    """Accept an :class:`EngineConfig` or an already-resolved engine."""
    if isinstance(config, ResolvedEngine):
        return config
    if isinstance(config, EngineConfig):
        return config.resolve(n=n, m=m, n_devices=n_devices)
    raise ConfigError(f"expected EngineConfig or ResolvedEngine, got "
                      f"{type(config)}")
