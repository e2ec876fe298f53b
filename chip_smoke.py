#!/usr/bin/env python3
"""Smoke run of the EIC SSSP query engine on TPU chips.

    python chip_smoke.py               # one chip: real-size and kernel phases
    python chip_smoke.py --chips 4     # four chips: sharded tier and router
    JAX_PLATFORMS=cpu python chip_smoke.py --platform cpu --scale 10
                                       # rehearsal on CPU at a tiny size

Every graph is generated from ``--seed`` (``repro.data.generators``) and
every query goes through the user entry point, ``repro.api.Solver``; the
routed tier runs the registry, router and schedulers.  Each phase checks
its results against a plain reference (``bellman_ford`` and
``dijkstra_host`` from ``repro.core.baselines``, or the ``segment_min``
backend) and prints its timings, results and logical counters.  Any
failed check exits non-zero.  Without a TPU the script exits non-zero
unless ``--platform cpu`` asks for the rehearsal.  The last line of
standard output is one JSON object naming the device.

One chip (default):

* real size: ``kronecker(--scale=17, 16)``, the Graph500 / GAP-kron
  generator with its scale cut from 27 (see ``--scale``), on the default
  ``segment_min`` backend: batched tree solves from ``--sources``
  sources (``--batch`` per solve), then p2p, bounded and knear specs,
  then the same specs through ``tier="routed"``, bitwise equal to the
  single tier.  Trees match ``bellman_ford`` bitwise and one source
  matches ``dijkstra_host``; one tree on ``road_grid(--road-side)`` is
  checked against ``dijkstra_host``.
* kernel (run first): ``backend="blocked_pallas"`` with compiled Pallas
  kernels, on the largest Kronecker scale up to ``--kernel-max-scale``
  (and ``--scale``) whose padded blocked layout the layout code reckons
  under ``--kernel-gb``;
  dist/parent bitwise equal to ``segment_min``.  ``fused_rounds > 0``
  must be refused on a TPU.

Four chips (``--chips 4``, ``kronecker(--scale=13, 16)``): the sharded
tier (``shard_map`` v2 with the ``segment_min`` and ``blocked`` shard
backends) and the router over four replicas, each bitwise equal to the
single-device solve, with every shard's and replica's buffers on its own
device.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

LOGICAL = ("n_steps", "n_rounds", "n_extended", "n_trav", "n_pull_trav",
           "n_relax", "n_updates", "n_pruned")


def log(*a):
    print(*a, flush=True)


class Checks:
    """Named pass/fail records; the run fails if any check failed."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        log(f"  {'PASS' if ok else 'FAIL'} {name}"
            + (f" ({detail})" if detail else ""))
        if not ok:
            self.failed.append(name)
        return ok

    def phase(self, name: str, fn, *args):
        log(f"== phase {name}")
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        except Exception:
            traceback.print_exc()
            self(f"{name}: raised", False)
            out = None
        log(f"== phase {name} done in {time.perf_counter() - t0:.3f} s")
        return out


def counters(checks: Checks, label: str, metrics) -> None:
    """Print the logical counters (summed over slots); int32 counters that
    wrapped past 2^31 show up negative and fail the run."""
    ms = metrics if isinstance(metrics, list) else [metrics]
    # served queries carry the normalized dict, which keeps the integer
    # counters under the same names
    fields = [f for f in LOGICAL
              if all(f in m if isinstance(m, dict) else hasattr(m, f)
                     for m in ms)]
    get = lambda m, f: m[f] if isinstance(m, dict) else getattr(m, f)
    vals = {f: [int(x) for m in ms for x in np.ravel(get(m, f))]
            for f in fields}
    log(f"  counters {label}: "
        + " ".join(f"{f}={sum(v)}" for f, v in vals.items()))
    checks(f"{label}: counters non-negative",
           all(x >= 0 for v in vals.values() for x in v))


def same(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype \
        and a.tobytes() == b.tobytes()


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def dijkstra_close(dist, ref) -> bool:
    """f32 engine distances against the float64 host Dijkstra: the same
    reachable set and agreement to 1e-4 relative (examples/quickstart)."""
    dist = np.asarray(dist, np.float64)
    fin = np.isfinite(ref)
    return bool((np.isfinite(dist) == fin).all()
                and np.allclose(dist[fin], ref[fin], rtol=1e-4))


class HostReference(threading.Thread):
    """``dijkstra_host`` jobs on one host thread, overlapping the device
    phases (the chip waits on nothing the thread holds)."""

    def __init__(self, jobs):
        super().__init__(daemon=True)
        self.jobs = jobs
        self.out = {}

    def run(self):
        from repro.core.baselines import dijkstra_host
        for key, (g, s) in self.jobs.items():
            t0 = time.perf_counter()
            try:
                self.out[key] = dijkstra_host(g, s)[0]
            except Exception as e:               # reported by the caller
                self.out[key] = e
            log(f"  [host] dijkstra_host {key}: "
                f"{time.perf_counter() - t0:.3f} s")

    def get(self, key, timeout):
        self.join(timeout)
        out = self.out.get(key)
        if out is None or isinstance(out, Exception):
            raise RuntimeError(f"host reference {key} missing: {out!r}")
        return out


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def goal_specs(SolveSpec, srcs, tree_d, k: int):
    """p2p / bounded / knear specs from tree distances: a reachable target
    per source, the 40th-percentile distance as the bound."""
    rng = np.random.default_rng(len(srcs))
    targets, bounds = [], []
    for d in tree_d[:len(srcs)]:
        fin = np.flatnonzero(np.isfinite(d))
        targets.append(int(rng.choice(fin)))
        bounds.append(float(np.percentile(d[fin], 40)))
    srcs = tuple(int(s) for s in srcs)
    return {"p2p": SolveSpec.p2p(srcs, tuple(targets)),
            "bounded": SolveSpec.bounded(srcs, tuple(bounds)),
            "knear": SolveSpec.knear(srcs, k)}


def check_goal(checks, kind, spec, res_d, res_p, tree_d, tree_p, label):
    ok = True
    for i, s in enumerate(spec.sources):
        d, p, td, tp = res_d[i], res_p[i], tree_d[i], tree_p[i]
        if kind == "p2p":
            t = spec.target[i]
            ok &= same(d[t], td[t]) and int(p[t]) == int(tp[t])
        elif kind == "bounded":
            within = td <= spec.bound[i]
            ok &= same(d[within], td[within])
        else:
            k = spec.k
            ok &= same(np.sort(d)[:k + 1], np.sort(td)[:k + 1])
    checks(f"{label} {kind}: settled part equals the tree bitwise", ok)


def served_view(spec, res, deg):
    """The single-tier result as the serving plane reports it: each slot
    shaped by ``serve.queries.finalize``, which hides the entries an early
    exit left tentative."""
    from repro.serve.queries import Query, finalize
    dist, parent = np.asarray(res.dist), np.asarray(res.parent)
    params = spec.slot_params()
    out_d, out_p = [], []
    for i, s in enumerate(spec.sources):
        kw = {} if params is None else {
            {"p2p": "target", "bounded": "bound", "knear": "k"}[spec.kind]:
            params[i]}
        raw = type(res.metrics)(*(np.asarray(x)[i] for x in res.metrics))
        r = finalize(Query(gid="smoke", source=s, kind=spec.kind, **kw),
                     deg, dist[i], parent[i], raw)
        out_d.append(r.dist)
        out_p.append(r.parent)
    return np.stack(out_d), np.stack(out_p)


def phase_real(checks, args, jax):
    from repro.api import EngineConfig, SolveSpec, Solver
    from repro.core.baselines import bellman_ford
    from repro.data.generators import kronecker, road_grid

    g, t = timed(lambda: kronecker(args.scale, 16, seed=args.seed))
    log(f"  kronecker({args.scale}, 16): n={g.n} m={g.m} ({t:.3f} s host)")
    road, t = timed(lambda: road_grid(args.road_side, seed=args.seed))
    log(f"  road_grid({args.road_side}): n={road.n} m={road.m} "
        f"({t:.3f} s host)")
    rng = np.random.default_rng(args.seed)
    srcs = [int(s) for s in rng.choice(np.flatnonzero(g.deg > 0),
                                       args.sources, replace=False)]
    road_src = int(rng.integers(road.n))
    log(f"  sources {srcs}, road source {road_src}")
    ref = HostReference({"kron": (g, srcs[0]), "road": (road, road_src)})
    ref.start()

    solver, t = timed(lambda: Solver.open(g, EngineConfig(devices=(0,))))
    log(f"  Solver.open (single, segment_min): {t:.3f} s")
    tree_d, tree_p, metrics = [], [], []
    for lo in range(0, len(srcs), args.batch):
        spec = SolveSpec.tree(tuple(srcs[lo:lo + args.batch]))
        res, t = timed(lambda: solver.solve(spec).block_until_ready())
        log(f"  tree {spec.sources}: {t:.3f} s"
            + (" (compiles)" if lo == 0 else ""))
        tree_d += list(np.asarray(res.dist))
        tree_p += list(np.asarray(res.parent))
        metrics.append(res.metrics)
    counters(checks, "tree", metrics)

    dg = solver.device_graph
    ok = True
    for i, s in enumerate(srcs):
        (bd, _, _), t = timed(lambda: jax.block_until_ready(
            bellman_ford(dg, s)))
        log(f"  bellman_ford({s}): {t:.3f} s")
        ok &= same(np.asarray(bd), tree_d[i])
    checks("tree dist == bellman_ford dist, bitwise, every source", ok)

    specs = goal_specs(SolveSpec, srcs[:args.batch], tree_d, args.k)
    single = {}
    for kind, spec in specs.items():
        res, t = timed(lambda: solver.solve(spec).block_until_ready())
        log(f"  {kind}: {t:.3f} s (compiles)")
        single[kind] = served_view(spec, res, g.deg)
        check_goal(checks, kind, spec, np.asarray(res.dist),
                   np.asarray(res.parent), tree_d, tree_p, "single")
        counters(checks, kind, res.metrics)
    solver.close()
    del solver, dg, res
    gc.collect()

    # the serving plane on the same chip: registry -> router -> scheduler
    cfg = EngineConfig(tier="routed", max_batch=args.batch)
    routed, t = timed(lambda: Solver.open(g, cfg))
    log(f"  Solver.open (routed, max_batch={args.batch}): {t:.3f} s")
    futs = {}
    for lo in range(0, len(srcs), args.batch):
        futs[f"tree{lo}"] = routed.submit(
            SolveSpec.tree(tuple(srcs[lo:lo + args.batch])))
    for kind, spec in specs.items():
        futs[kind] = routed.submit(spec)
    t0 = time.perf_counter()
    served = set()
    for name, fut in futs.items():
        try:
            res = fut.result(timeout=args.timeout)
        except Exception as e:
            checks(f"routed {name}: future error {e!r}", False)
            continue
        served.update(str(x) for x in np.ravel(res.served_by))
        if name.startswith("tree"):
            lo = int(name[4:])
            want_d = np.stack(tree_d[lo:lo + args.batch])
            want_p = np.stack(tree_p[lo:lo + args.batch])
        else:
            want_d, want_p = single[name]
        checks(f"routed {name} == single tier, bitwise (dist, parent)",
               same(res.dist, want_d) and same(res.parent, want_p))
        counters(checks, f"routed {name}", res.metrics)
    log(f"  routed futures resolved in {time.perf_counter() - t0:.3f} s; "
        f"served by {sorted(served)}")
    routed.close()
    del routed
    gc.collect()

    dref = ref.get("kron", timeout=args.timeout)
    checks(f"tree from {srcs[0]} vs dijkstra_host",
           dijkstra_close(tree_d[0], dref))

    rs = Solver.open(road)
    res, t = timed(lambda: rs.solve(SolveSpec.tree(road_src))
                   .block_until_ready())
    log(f"  road tree from {road_src}: {t:.3f} s (compiles)")
    counters(checks, "road tree", res.metrics)
    rref = ref.get("road", timeout=args.timeout)
    checks("road tree vs dijkstra_host", dijkstra_close(res.dist, rref))
    rs.close()


def fitting_scale(args, bytes_of):
    """The largest Kronecker scale <= --kernel-max-scale (and --scale)
    whose layout, reckoned by ``bytes_of(g)`` before anything is
    allocated, fits --kernel-gb."""
    from repro.data.generators import kronecker
    limit = int(args.kernel_gb * 2**30)
    top = min(args.kernel_max_scale, args.scale)
    best = None
    for scale in range(min(args.kernel_min_scale, top), top + 1):
        g = kronecker(scale, 16, seed=args.seed)
        nbytes = bytes_of(g)
        log(f"  scale {scale}: padded layout {nbytes} bytes "
            f"({'fits' if nbytes <= limit else 'over'} {limit})")
        if nbytes > limit:
            break
        best = (scale, g)
    if best is None:
        raise RuntimeError("no Kronecker scale fits the layout budget")
    return best


def phase_kernel(checks, args, jax):
    from repro.api import EngineConfig, SolveSpec, Solver
    from repro.core.config import ConfigError
    from repro.core.graph import blocked_layout_bytes

    scale, g = fitting_scale(args, blocked_layout_bytes)
    log(f"  kernel graph: kronecker({scale}, 16) n={g.n} m={g.m}")
    rng = np.random.default_rng(args.seed + 1)
    srcs = [int(s) for s in rng.choice(np.flatnonzero(g.deg > 0), 2,
                                       replace=False)]
    ref = Solver.open(g, EngineConfig(devices=(0,)))
    blk, t = timed(lambda: Solver.open(
        g, EngineConfig(backend="blocked_pallas")))
    log(f"  Solver.open (blocked_pallas, interpret="
        f"{blk.resolved.interpret}): {t:.3f} s layout build")
    checks("blocked_pallas resolves to compiled kernels",
           blk.resolved.interpret is (jax.default_backend() != "tpu"))
    for s in srcs:
        want = ref.solve(SolveSpec.tree(s)).block_until_ready()
        got, t = timed(lambda: blk.solve(SolveSpec.tree(s))
                       .block_until_ready())
        log(f"  blocked tree from {s}: {t:.3f} s; "
            f"tiles scanned {float(got.metrics.n_tiles_scanned):.0f}")
        checks(f"blocked_pallas tree from {s} == segment_min, bitwise",
               same(got.dist, want.dist) and same(got.parent, want.parent))
        counters(checks, f"blocked tree {s}", got.metrics)
    if jax.default_backend() == "tpu":
        try:
            EngineConfig(backend="blocked_pallas", fused_rounds=4).resolve()
            checks("fused_rounds > 0 refused on TPU", False)
        except ConfigError as e:
            checks("fused_rounds > 0 refused on TPU", True, str(e)[:80])


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def on_distinct_devices(arrays, n: int) -> bool:
    """Every sharded array of ``arrays`` spans ``n`` distinct devices."""
    import jax
    return all(len({sh.device for sh in a.addressable_shards}) == n
               for a in jax.tree_util.tree_leaves(arrays) if a.ndim > 0)


def phase_sharded(checks, args, jax):
    from repro.api import EngineConfig, SolveSpec, Solver
    from repro.core.distributed import shard_layout_bytes
    from repro.data.generators import kronecker

    n_dev = len(jax.devices())
    g, t = timed(lambda: kronecker(args.scale, 16, seed=args.seed))
    log(f"  kronecker({args.scale}, 16): n={g.n} m={g.m} ({t:.3f} s host)")
    rng = np.random.default_rng(args.seed)
    srcs = tuple(int(s) for s in rng.choice(np.flatnonzero(g.deg > 0),
                                            args.batch, replace=False))
    single = Solver.open(g, EngineConfig(devices=(0,)))
    want = single.solve(SolveSpec.tree(srcs)).block_until_ready()
    tree_d = np.asarray(want.dist)
    specs = {"tree": SolveSpec.tree(srcs),
             **goal_specs(SolveSpec, srcs, tree_d, args.k)}
    wants = {k: single.solve(s).block_until_ready() for k, s in specs.items()}
    single.close()

    sh, t = timed(lambda: Solver.open(g, EngineConfig(tier="sharded")))
    log(f"  Solver.open (sharded v2, segment_min, {n_dev} shards): "
        f"{t:.3f} s")
    checks("sharded graph slabs on distinct devices",
           on_distinct_devices((sh._sg.src, sh._sg.dst, sh._sg.w), n_dev))
    for kind, spec in specs.items():
        got, t = timed(lambda: sh.solve(spec).block_until_ready())
        log(f"  sharded {kind}: {t:.3f} s (first call compiles)")
        checks(f"sharded {kind} == single device, bitwise",
               same(got.dist, wants[kind].dist)
               and same(got.parent, wants[kind].parent))
        counters(checks, f"sharded {kind}", got.metrics)
    sh.close()

    # the blocked shard backend at the largest scale its layout fits
    kscale, gk = fitting_scale(
        args, lambda hg: shard_layout_bytes(hg, n_dev))
    ksrcs = tuple(int(s) for s in np.random.default_rng(args.seed + 1)
                  .choice(np.flatnonzero(gk.deg > 0), args.batch,
                          replace=False))
    ref = Solver.open(gk, EngineConfig(devices=(0,)))
    want = ref.solve(SolveSpec.tree(ksrcs)).block_until_ready()
    ref.close()
    shb, t = timed(lambda: Solver.open(
        gk, EngineConfig(tier="sharded", shard_backend="blocked")))
    log(f"  Solver.open (sharded v2, blocked, kronecker({kscale}, 16)): "
        f"{t:.3f} s")
    checks("blocked shard slabs on distinct devices",
           on_distinct_devices(shb._blocked[0], n_dev))
    got, t = timed(lambda: shb.solve(SolveSpec.tree(ksrcs))
                   .block_until_ready())
    log(f"  sharded blocked tree: {t:.3f} s (compiles)")
    checks("sharded blocked tree == single device, bitwise",
           same(got.dist, want.dist) and same(got.parent, want.parent))
    counters(checks, "sharded blocked tree", got.metrics)
    shb.close()

    # the router with one replica of the graph on every device
    rt = Solver.open(g, EngineConfig(tier="routed", max_batch=args.batch))
    placed = rt.router.plan_placement({rt.gid: 1.0})
    log(f"  placement {placed}")
    rt.router.warmup()           # a tree replica compiled on every device
    engines = [rt.registry.peek(rt.gid, device=d) for d in jax.devices()]
    homes = [None if e is None else
             {d for a in jax.tree_util.tree_leaves(e.g) for d in a.devices()}
             for e in engines]
    checks(f"router: one replica per device ({n_dev})",
           all(h is not None and len(h) == 1 for h in homes)
           and len({next(iter(h)) for h in homes}) == n_dev,
           str(homes))
    futs = {k: rt.submit(s) for k, s in specs.items()}
    served = set()
    for kind, fut in futs.items():
        try:
            got = fut.result(timeout=args.timeout)
        except Exception as e:
            checks(f"routed {kind}: future error {e!r}", False)
            continue
        served.update(str(s) for s in np.ravel(got.served_by))
        want_d, want_p = served_view(specs[kind], wants[kind], g.deg)
        checks(f"routed {kind} (4 replicas) == single device, bitwise",
               same(got.dist, want_d) and same(got.parent, want_p))
    log(f"  served by {sorted(served)}")
    rt.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"),
                    help="cpu: rehearsal at a tiny --scale, no TPU needed")
    ap.add_argument("--scale", type=int, default=None,
                    help="Kronecker scale: 17 on one chip, 13 with --chips "
                         "4 (two 1024-vertex source blocks per shard, the "
                         "smallest scale that runs the blocked shard "
                         "kernel across several slabs).  Scale 22 "
                         "fits one chip's HBM but not the run's 1,200 s: on "
                         "a TPU v5e every gather and scatter over the edge "
                         "list runs at 110-150 M elements/s, so one batched "
                         "tree took 79 s at scale 18 and ran past 1,000 s "
                         "at scale 22")
    ap.add_argument("--road-side", type=int, default=256,
                    help="road_grid side (rounds grow with the side; "
                         "side 512 took 196 s on a TPU v5e)")
    ap.add_argument("--sources", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2,
                    help="sources per batched solve (a batch of 4 at "
                         "scale 22 exceeds one chip's 16 GB)")
    ap.add_argument("--k", type=int, default=64)
    ap.add_argument("--kernel-gb", type=float, default=2.0,
                    help="padded blocked-layout budget of the kernel phase")
    ap.add_argument("--kernel-min-scale", type=int, default=16)
    ap.add_argument("--kernel-max-scale", type=int, default=16,
                    help="largest kernel-phase scale: the blocked tree "
                         "takes 5.4 s at scale 16 on a TPU v5e and had not "
                         "finished in 300 s at scale 18 (one kernel call "
                         "per source block per round)")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--timeout", type=float, default=900.0)
    args = ap.parse_args(argv)
    if args.scale is None:
        args.scale = 17 if args.chips == 1 else 13

    import jax
    from repro.compile_cache import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    if args.platform == "tpu" and dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports {dev.platform}); "
              f"pass --platform cpu with a tiny --scale to rehearse",
              file=sys.stderr)
        return 2
    if args.platform == "cpu" and (dev.platform != "cpu" or args.scale > 12):
        print("chip_smoke: --platform cpu is a rehearsal on CPU devices "
              "with --scale <= 12", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devs)}", file=sys.stderr)
        return 2
    log(f"device {dev.platform} {dev.device_kind} x{len(devs)}; jax "
        f"{jax.__version__}; compile cache {enable_compile_cache()}")
    log(f"args {vars(args)}")

    checks = Checks()
    t0 = time.perf_counter()
    if args.chips == 1:
        checks.phase("kernel blocked_pallas", phase_kernel, checks, args,
                     jax)
        checks.phase("real-size segment_min + routed", phase_real, checks,
                      args, jax)
    else:
        checks.phase("four-chip sharded + router", phase_sharded, checks,
                     args, jax)
    log(f"total {time.perf_counter() - t0:.3f} s")
    if checks.failed:
        log(f"FAILED: {checks.failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
