"""Serving demo: multi-device router + per-device schedulers under Zipf
traffic.

    PYTHONPATH=src python examples/serving_demo.py [--scale 10] [--queries 32]

    # with a forced CPU device mesh:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/serving_demo.py

Registers a road grid and a Kronecker graph, plans placement from the
expected traffic shares, warms every replica engine, starts the
background workers (one per device), streams a Zipf-skewed mixed query
load (p2p / bounded / k-nearest / tree) through the router, and prints
per-kind samples plus placement and serving counters.

At exit it prints the serving plane's metrics snapshot (the one
registry/scheduler/router ``MetricsRegistry``), then solves one tree on
the hottest graph under a ``jax.profiler.trace()`` capture
(``--trace-dir``, default ``serving_demo_trace/``; open its
``perfetto_trace.json.gz`` at https://ui.perfetto.dev, or the directory
in TensorBoard).  The device ops carry the solve's named phases
(``sssp.round``, ``round.gather``, ..., ``transition.pull``) in their
HLO ``op_name``, and ``Solver.phase_table`` maps each op to its phase.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.api import EngineConfig  # noqa: E402
from repro.data.generators import kronecker, road_grid  # noqa: E402
from repro.data.traffic import make_traffic  # noqa: E402
from repro.serve.registry import GraphRegistry  # noqa: E402
from repro.serve.router import QueryRouter  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=10)
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--rate-qps", type=float, default=None,
                    help="open-loop arrival pacing (default: closed loop)")
    ap.add_argument("--trace-dir", default="serving_demo_trace",
                    help="write a profiled solve's capture here")
    args = ap.parse_args()

    n = 1 << args.scale
    graphs = {
        "social": kronecker(args.scale, 8, seed=2),      # hottest
        "road": road_grid(int(np.sqrt(n)), seed=5),
    }
    # one EngineConfig drives the registry and the router (multi-graph
    # serving keeps the registry/router stack; single-graph sessions can
    # use Solver.open(g, EngineConfig(tier="routed")) instead)
    cfg = EngineConfig(max_batch=args.max_batch,
                       registry_capacity=4 * len(graphs))
    registry = GraphRegistry(config=cfg)
    for gid, g in graphs.items():
        registry.register(gid, g)
        print(f"registered {gid!r}: |V|={g.n} |E|={g.m // 2}")

    router = QueryRouter(registry, config=cfg)
    print(f"router over {router.n_devices} device(s)")
    traffic = make_traffic(graphs, args.queries, seed=0,
                           rate_qps=args.rate_qps)
    shares = {}
    for item in traffic:
        shares[item.query.gid] = shares.get(item.query.gid, 0) + 1
    placement = router.plan_placement(shares)
    print(f"placement: {placement}")
    t0 = time.perf_counter()
    router.warmup(kinds=("p2p", "bounded", "knear", "tree"))
    print(f"warmup (builds + jit compiles): "
          f"{time.perf_counter() - t0:.1f}s")

    router.start()
    t0 = time.perf_counter()
    futs = []
    for item in traffic:
        if args.rate_qps is not None:       # open-loop pacing
            lag = item.arrival_s - (time.perf_counter() - t0)
            if lag > 0:
                time.sleep(lag)
        futs.append((item, router.submit(item.query,
                                         priority=item.priority)))
    results = [(item, fut.result(timeout=600)) for item, fut in futs]
    elapsed = time.perf_counter() - t0
    router.stop()

    shown = set()
    for item, res in results:
        q = item.query
        if q.kind in shown:
            continue
        shown.add(q.kind)
        where = f"@{res.served_by}"
        if q.kind == "p2p":
            hops = len(res.path) - 1 if res.path else None
            print(f"[{q.gid}{where}] p2p {q.source}->{q.target}: "
                  f"dist={res.distance:.4f} hops={hops} "
                  f"({res.latency_s * 1e3:.0f} ms)")
        elif q.kind == "bounded":
            print(f"[{q.gid}{where}] bounded src={q.source} "
                  f"D={q.bound:.2f}: "
                  f"{int(np.isfinite(res.dist).sum())} vertices in range")
        elif q.kind == "knear":
            v, d = res.nearest[-1]
            print(f"[{q.gid}{where}] knear src={q.source} k={q.k}: "
                  f"k-th neighbor {v} at {d:.4f}")
        else:
            print(f"[{q.gid}{where}] tree src={q.source}: "
                  f"{res.metrics['reachable']} reachable, "
                  f"nSync={res.metrics['nSync']:.2f}")

    lats = np.array([res.latency_s for _, res in results])
    stats = router.stats()
    print(f"\n{len(results)} queries in {elapsed:.2f}s "
          f"({len(results) / elapsed:.1f} q/s, warmed)")
    print(f"latency p50={np.percentile(lats, 50) * 1e3:.0f} ms "
          f"p99={np.percentile(lats, 99) * 1e3:.0f} ms; "
          f"occupancy={stats['occupancy']:.2f} over "
          f"{stats['n_batches']} batches on {stats['n_devices']} devices; "
          f"replications={stats['n_replications']}; "
          f"registry hit rate={stats['registry']['hit_rate']:.2f}")
    per_dev = {s["name"]: s["n_done"] for s in stats["schedulers"]
               if s["n_done"]}
    print(f"queries per scheduler: {per_dev}")

    # the same numbers, through the observability plane: one metrics
    # registry covers the engine registry, every scheduler, and the router
    print("\nmetrics snapshot (non-zero series):")
    for name, entry in sorted(registry.metrics.snapshot().items()):
        if entry["type"] == "histogram":
            if entry["count"]:
                print(f"  {name}: count={entry['count']} "
                      f"p50={entry['p50'] * 1e3:.1f}ms "
                      f"p99={entry['p99'] * 1e3:.1f}ms")
        elif entry["value"]:
            print(f"  {name}: {entry['value']}")

    # one tree on the hottest graph under a profiler capture: its device
    # ops are named by the solve's phases (solve -> round / transition)
    import collections  # noqa: E402

    import jax  # noqa: E402
    from repro.api import Solver, SolveSpec  # noqa: E402

    hot = max(shares, key=shares.get)
    spec = SolveSpec.tree(0)
    with Solver.open(graphs[hot]) as solver:
        solver.solve(spec).block_until_ready()    # compile outside it
        with jax.profiler.trace(args.trace_dir, create_perfetto_trace=True):
            res = solver.solve(spec).block_until_ready()
        table = solver.phase_table(spec)
    by_phase = collections.Counter(table.values())
    print(f"\nprofiled solve on {hot!r}: {int(res.metrics.n_rounds)} "
          f"rounds, {int(res.metrics.n_relax)} relaxations -> "
          f"{args.trace_dir}/")
    print("  compiled instructions by phase: " + ", ".join(
        f"{p}={by_phase[p]}" for p in sorted(by_phase)))


if __name__ == "__main__":
    main()
