"""EngineConfig construction + resolve(): the error paths must fire
loudly, host-side, before any layout build or tracing."""
import numpy as np
import pytest

from repro.api import SolveSpec, Solver
from repro.core.config import ConfigError, EngineConfig, as_resolved
from repro.core.graph import build_blocked
from repro.data.generators import road_grid


def test_context_free_validation():
    with pytest.raises(ConfigError):
        EngineConfig(tier="bogus")
    with pytest.raises(ConfigError):
        EngineConfig(shard_version="v9")
    with pytest.raises(ConfigError):
        EngineConfig(backend="nope")
    with pytest.raises(ConfigError):
        EngineConfig(shard_backend="nope")
    with pytest.raises(ConfigError):
        EngineConfig(devices=())
    with pytest.raises(ConfigError):
        EngineConfig(max_batch=0)
    with pytest.raises(ConfigError):
        EngineConfig(alpha=0.0)
    with pytest.raises(ConfigError):
        EngineConfig(tile_e=0)


def test_auto_tier_resolution_by_thresholds():
    cfg = EngineConfig(shard_threshold_n=100)
    assert cfg.resolve(n=144, m=500, n_devices=2).tier == "sharded"
    assert cfg.resolve(n=64, m=500, n_devices=2).tier == "single"
    cfg_m = EngineConfig(shard_threshold_m=400)
    assert cfg_m.resolve(n=64, m=500, n_devices=2).tier == "sharded"
    # auto without thresholds: single, no graph size needed
    assert EngineConfig().resolve(n_devices=1).tier == "single"
    # auto *with* thresholds needs the size to decide
    with pytest.raises(ConfigError):
        cfg.resolve(n_devices=2)


def test_fused_rounds_resolution():
    # the blocked single-device backend carries fused_rounds through
    r = EngineConfig(backend="blocked_pallas", fused_rounds=4).resolve(
        n=10, m=10, n_devices=1)
    assert r.fused_rounds == 4 and r.tier == "single"
    # ... but segment_min has no megakernel to fuse into
    with pytest.raises(ConfigError):
        EngineConfig(backend="segment_min", fused_rounds=4).resolve(
            n=10, m=10, n_devices=1)
    # sharded tier: both backends accept it (waves vs grouped rounds)
    for sb in ("segment_min", "blocked"):
        r = EngineConfig(tier="sharded", shard_backend=sb,
                         fused_rounds=4).resolve(n=10, m=10, n_devices=2)
        assert r.fused_rounds == 4


def test_from_loose_gate():
    cfg = EngineConfig(backend="blocked_pallas")
    # config alone passes through untouched
    assert EngineConfig.from_loose(cfg, "engine", backend=None,
                                   alpha=None) is cfg
    # config + any set loose kwarg is ambiguous -> loud error
    with pytest.raises(ConfigError, match="through config="):
        EngineConfig.from_loose(cfg, "engine", backend="segment_min")
    # loose kwargs layer over the entry point's defaults
    c = EngineConfig.from_loose(None, "engine",
                                defaults={"shard_backend": "segment_min"},
                                alpha=2.0, backend=None)
    assert c.alpha == 2.0 and c.shard_backend == "segment_min"
    # a set loose kwarg overrides the default
    c = EngineConfig.from_loose(None, "engine",
                                defaults={"shard_backend": "segment_min"},
                                shard_backend="blocked")
    assert c.shard_backend == "blocked"
    # unknown loose options fail like a bad keyword argument
    with pytest.raises(TypeError, match="unknown engine options"):
        EngineConfig.from_loose(None, "engine", bogus=1)
    # relax-backend objects are canonicalized to their registry name
    from repro.core.relax import get_backend
    c = EngineConfig.from_loose(None, "engine",
                                backend=get_backend("blocked"))
    assert c.backend == "blocked_pallas"


def test_conflicting_backend_tier_combos():
    # shard options on a single-tier engine
    with pytest.raises(ConfigError):
        EngineConfig(shard_backend="blocked").resolve(n=10, m=10,
                                                      n_devices=1)
    with pytest.raises(ConfigError):
        EngineConfig(fused_rounds=4).resolve(n=10, m=10, n_devices=1)
    # blocked geometry without any blocked backend
    with pytest.raises(ConfigError):
        EngineConfig(block_v=64).resolve(n=10, m=10, n_devices=1)
    with pytest.raises(ConfigError):
        EngineConfig(tier="sharded", use_kernel=True).resolve(
            n=10, m=10, n_devices=1)
    # v3-only knob on another version
    with pytest.raises(ConfigError):
        EngineConfig(tier="sharded", compact_capacity=8).resolve(
            n=10, m=10, n_devices=1)
    # thresholds contradict an explicit single/sharded tier
    with pytest.raises(ConfigError):
        EngineConfig(tier="sharded", shard_threshold_n=5).resolve(
            n=10, m=10, n_devices=1)
    # backend and shard_backend that disagree on the sharded tier
    with pytest.raises(ConfigError):
        EngineConfig(tier="sharded", backend="blocked_pallas",
                     shard_backend="segment_min").resolve(n=10, m=10,
                                                          n_devices=1)
    # single tier cannot span several devices
    with pytest.raises(ConfigError):
        EngineConfig(tier="single", devices=(0, 1)).resolve(
            n=10, m=10, n_devices=2)
    # more pinned devices than visible
    with pytest.raises(ConfigError):
        EngineConfig(tier="sharded", devices=(0, 1, 2)).resolve(
            n=10, m=10, n_devices=2)


def test_resolve_canonicalizes_and_derives_shard_backend():
    r = EngineConfig(backend="blocked_pallas", tier="sharded",
                     block_v=64).resolve(n=10, m=10, n_devices=2)
    assert r.backend == "blocked_pallas"
    assert r.shard_backend == "blocked"      # derived, no explicit field
    assert r.n_shards == 2
    assert r.layout_opts()["block_v"] == 64
    # resolved engines pass through as_resolved unchanged
    assert as_resolved(r) is r
    with pytest.raises(ConfigError):
        as_resolved("segment_min")
    # require() guards entry points
    with pytest.raises(ConfigError):
        r.require("single")
    assert r.require("sharded", "routed") is r


def test_engine_entry_points_reject_config_plus_loose_kwargs():
    from repro.core.distributed import shard_graph, sssp_distributed
    from repro.core.sssp import sssp
    import jax
    g = road_grid(8, seed=0)
    cfg = EngineConfig().resolve(n=g.n, m=g.m, n_devices=1)
    with pytest.raises(ConfigError):
        sssp(g.to_device(), 0, config=cfg, backend="segment_min")
    with pytest.raises(ConfigError):
        sssp(g.to_device(), 0, config=cfg, alpha=2.0)
    mesh = jax.make_mesh((1,), ("graph",))
    sg = shard_graph(g, 1)
    shard_cfg = EngineConfig(tier="sharded")
    with pytest.raises(ConfigError):
        sssp_distributed(sg, 0, mesh, ("graph",), config=shard_cfg,
                         version="v1")


def test_layer_constructors_reject_config_plus_loose_kwargs():
    from repro.serve.registry import GraphRegistry
    from repro.serve.router import QueryRouter
    from repro.serve.sssp_service import SsspService
    g = road_grid(8, seed=0)
    cfg = EngineConfig(max_batch=4)
    with pytest.raises(ConfigError):
        GraphRegistry(config=cfg, backend="blocked_pallas")
    reg = GraphRegistry(config=cfg)
    with pytest.raises(ConfigError):
        QueryRouter(reg, config=cfg, max_batch=2)
    with pytest.raises(ConfigError):
        SsspService(g, config=cfg, max_batch=2)
    # the config path works and carries the batch width through
    svc = SsspService(g, config=cfg)
    assert svc.max_batch == 4


def test_blocked_backend_rejects_unpadded_or_foreign_layouts():
    g = road_grid(12, seed=2)
    cfg = EngineConfig(backend="blocked_pallas")
    # a flat-edge-list layout is not a blocked layout
    with pytest.raises(ConfigError):
        Solver.open(g, cfg, layout=g.to_device())
    # a blocked layout built for a *different* graph (wrong n / padding)
    other = build_blocked(road_grid(10, seed=1), block_v=64, tile_e=64)
    with pytest.raises(ConfigError):
        Solver.open(g, cfg, layout=other)
    # a shard slice (src_base != 0, partial source range) is rejected too
    from repro.core.graph import slice_for_shard
    slab = slice_for_shard(g, 1, 2, block_v=32, tile_e=32)
    with pytest.raises(ConfigError):
        Solver.open(g, cfg, layout=slab)
    # geometry disagreement between config and layout
    bl = build_blocked(g, block_v=64, tile_e=64)
    with pytest.raises(ConfigError):
        Solver.open(g, EngineConfig(backend="blocked_pallas", tile_e=128),
                    layout=bl)
    # and the segment_min backend cannot consume a BlockedGraph
    with pytest.raises(ConfigError):
        Solver.open(g, EngineConfig(), layout=bl)
    # the valid pairing still opens and solves
    s = Solver.open(g, EngineConfig(backend="blocked_pallas"), layout=bl)
    assert np.isfinite(s.solve(SolveSpec.p2p(0, 100)).distance())


def test_out_of_range_solvespec_sources_raise_before_tracing():
    g = road_grid(8, seed=0)
    s = Solver.open(g)
    with pytest.raises(ValueError, match="out of range"):
        s.solve(SolveSpec.tree(g.n + 5))
    with pytest.raises(ValueError, match="out of range"):
        s.solve(SolveSpec.tree([0, g.n]))
    with pytest.raises(ValueError, match="out of range"):
        s.solve(SolveSpec.p2p(0, g.n + 1))
    with pytest.raises(ValueError, match="out of range"):
        s.solve(SolveSpec.p2p([0, 1], [1, g.n]))


def test_device_indices_resolve_and_range_check():
    import jax
    from repro.core.config import resolve_devices
    assert resolve_devices(None) is None
    assert resolve_devices((0,)) == [jax.devices()[0]]
    with pytest.raises(ConfigError):
        resolve_devices((999,))
    # a bad index fails in resolve(), not as an IndexError mid-build
    with pytest.raises(ConfigError):
        EngineConfig(tier="sharded", devices=(999,)).resolve(n=10, m=10)
    # config-pinned integer devices drive the service's router path
    from repro.serve.sssp_service import SsspRequest, SsspService
    g = road_grid(8, seed=0)
    svc = SsspService(g, config=EngineConfig(devices=(0,), max_batch=2))
    req = svc.submit(SsspRequest(rid=0, source=1))
    svc.run()
    assert req.error is None and req.dist is not None


def test_engine_variant_knobs_ride_config_into_serving_engines():
    """Nothing a resolve()-accepted config declares is silently dropped:
    fused_rounds/compact_capacity/max_iters reach the built engines."""
    from repro.serve.registry import GraphRegistry
    g = road_grid(8, seed=0)
    reg = GraphRegistry(config=EngineConfig(
        shard_threshold_n=1, shard_version="v3", fused_rounds=2,
        compact_capacity=16, max_iters=777))
    reg.register("big", g)                       # 64 >= 1 -> sharded
    eng = reg.engine("big")
    assert eng.tier == "sharded"
    assert eng.fused_rounds == 2 and eng.capacity == 16
    assert eng.max_iters == 777
    reg2 = GraphRegistry(config=EngineConfig(max_iters=555))
    reg2.register("small", g)
    assert reg2.engine("small").max_iters == 555
    # and the symmetric single-tier rejection for the v3-only knob
    with pytest.raises(ConfigError):
        EngineConfig(shard_version="v3", compact_capacity=16).resolve(
            n=10, m=10, n_devices=1)


def test_segment_min_rejects_foreign_device_graph_layouts():
    g = road_grid(12, seed=2)
    # same n, different graph: the edge list IS the layout, so this
    # would silently answer over the wrong edges — reject host-side
    other = road_grid(12, seed=9).to_device()
    with pytest.raises(ConfigError):
        Solver.open(g, EngineConfig(), layout=other)
    with pytest.raises(ConfigError):
        Solver.open(g, EngineConfig(), layout="not a layout")
    # the graph's own device form is the valid layout
    s = Solver.open(g, EngineConfig(), layout=g.to_device())
    assert np.isfinite(s.solve(SolveSpec.p2p(0, 100)).distance())


def test_serving_config_rejects_capacity_off_v3():
    from repro.serve.registry import GraphRegistry
    with pytest.raises(ConfigError):
        GraphRegistry(config=EngineConfig(shard_version="v2",
                                          compact_capacity=64,
                                          shard_threshold_n=1))


def test_auto_tier_config_with_thresholds_holds_shard_options():
    """A deployment config (auto tier + thresholds + shard options) must
    not fail data-dependently on graphs below the threshold — the shard
    fields are held for the graphs that cross it."""
    g = road_grid(8, seed=0)                     # n=64, far below
    cfg = EngineConfig(shard_threshold_n=100_000,
                       shard_backend="blocked", block_v=64, tile_e=64)
    r = cfg.resolve(n=g.n, m=g.m, n_devices=1)
    assert r.tier == "single"
    s = Solver.open(g, cfg)
    assert np.isfinite(s.solve(SolveSpec.p2p(0, 30)).distance())
    # without thresholds the same shard options are dead weight -> loud
    with pytest.raises(ConfigError):
        EngineConfig(shard_backend="blocked").resolve(n=g.n, m=g.m,
                                                      n_devices=1)


def test_loose_blocked_backend_keeps_segment_min_sharded_tier():
    """Pre-facade behavior preserved: the loose-kwargs paths' default
    shard_backend='segment_min' is an explicit choice — a blocked
    single-device backend must not silently derive a blocked sharded
    tier through the synthesized config."""
    from repro.serve.registry import GraphRegistry
    from repro.serve.sssp_service import SsspService
    g = road_grid(8, seed=0)
    reg = GraphRegistry(backend="blocked_pallas", block_v=64, tile_e=64,
                        shard_threshold_n=1)
    reg.register("g", g)
    eng = reg.engine("g")
    assert eng.tier == "sharded" and eng.backend == "segment_min"
    assert reg.config.effective_shard_backend == "segment_min"
    import jax
    svc = SsspService(g, backend="blocked_pallas", block_v=64, tile_e=64,
                      shard_threshold_n=1, devices=jax.devices())
    assert svc.config.effective_shard_backend == "segment_min"
    # a user config that wants the blocked sharded tier says so
    assert EngineConfig(backend="blocked_pallas",
                        shard_threshold_n=1).effective_shard_backend \
        == "blocked"


def _on_tpu(monkeypatch):
    """Make the platform look like a TPU to the config resolution."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_interpret_resolves_from_the_platform(monkeypatch):
    from repro.core.config import resolve_interpret
    # CPU: the Pallas interpreter, unless a caller compiles for a TPU
    assert EngineConfig().resolve(n_devices=1).interpret is True
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    _on_tpu(monkeypatch)
    assert EngineConfig().resolve(n_devices=1).interpret is False
    assert resolve_interpret(None) is False
    with pytest.raises(ConfigError, match="interpreter"):
        EngineConfig(interpret=True).resolve(n_devices=1)


def test_fused_megakernel_refused_on_tpu(monkeypatch):
    cfg = EngineConfig(backend="blocked_pallas", fused_rounds=4)
    assert cfg.resolve(n_devices=1).fused_rounds == 4      # interpreted
    _on_tpu(monkeypatch)
    with pytest.raises(ConfigError, match="does not compile for TPU"):
        cfg.resolve(n_devices=1)
    # the jnp reference stays available by name; the sharded tier's
    # bucket-fusion waves run the gridded partials kernel
    assert EngineConfig(backend="blocked_pallas", fused_rounds=4,
                        use_kernel=False).resolve(n_devices=1)
    assert EngineConfig(tier="sharded", shard_backend="blocked",
                        fused_rounds=4).resolve(n_devices=2)


def test_compile_cache_follows_env_else_fixed_checkout_dir(monkeypatch):
    import jax
    from repro import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/somewhere/else")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == "/somewhere/else"
        # JAX reads the variable itself; nothing is set in code
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(compile_cache.ENV)
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path   # fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_kernel_geometry_refused_at_resolve_on_tpu(monkeypatch):
    """Compiled kernels take block_v/tile_e in multiples of 1024; the
    config layer refuses other sizes before any layout is built."""
    small = EngineConfig(backend="blocked_pallas", block_v=256, tile_e=512)
    assert small.resolve(n_devices=1).interpret is True     # interpreted
    _on_tpu(monkeypatch)
    with pytest.raises(ConfigError, match="multiples of 1024"):
        small.resolve(n_devices=1)
    with pytest.raises(ConfigError, match="multiples of 1024"):
        EngineConfig(tier="sharded", shard_backend="blocked",
                     tile_e=512).resolve(n_devices=4)
    assert EngineConfig(backend="blocked_pallas", block_v=256, tile_e=512,
                        use_kernel=False).resolve(n_devices=1)
    assert EngineConfig(backend="blocked_pallas").resolve(n_devices=1)
    # the sharded layout snaps block_v to a divisor of the owner block
    sharded = EngineConfig(tier="sharded", shard_backend="blocked")
    assert sharded.resolve(n=4 * 4096, n_devices=4)
    with pytest.raises(ConfigError, match="snaps block_v=1024"):
        sharded.resolve(n=4 * 3000, n_devices=4)
