"""Benchmark harness — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale 14] [--sources 4]
        [--backend segment_min|blocked_pallas] [--batch 4]
        [--full-variants]
        [--sections fig4,fig5,fig6,table3,backends,roofline,serving,p2p,
         delta,tuner]
        [--open-loop]

Prints ``name,us_per_call,derived`` CSV rows (one per graph x metric) and
writes benchmarks/artifacts/paper_metrics.json for EXPERIMENTS.md.

Sections:
  fig4     — nFrontier / nSync on the benchmark suite (paper Fig. 4a/4b)
             + the weight-variant suite (Fig. 4c/4d)
  fig5     — nTrav vs |E|/|V| and DD_skewness (paper Fig. 5)
  fig6     — wall time vs edge traversals (paper Fig. 6)
  table3   — EIC vs Bellman-Ford / Δ-stepping / host Dijkstra (paper
             Table 3 / Fig. 7): times, speedups, nFrontier, nSync
  backends — relaxation-backend head-to-head on the same graphs/sources:
             segment_min vs blocked_pallas (interpret mode on CPU) vs the
             distributed engine with both per-shard backends
             (segment_min / blocked), plus the fused multi-source
             sssp_batch at ``--batch`` sources per call.  Blocked rows
             report tiles_per_round / tile_reduction from the kernel's
             frontier-compaction metrics (the skipped-tile win) and run
             both unfused and ``fused_rounds=4`` (the multi-round
             megakernel), reporting invocations_per_solve /
             invocation_reduction / tile_regression
  roofline — fused-megakernel roofline smoke row: achieved vs peak
             bytes/FLOPs per invocation and rounds-per-invocation,
             measured from a real blocked solve (benchmarks/roofline.py
             hosts the model; ``--fused`` there runs it standalone)
  serving  — the multi-device serving plane under Zipf-skewed
             multi-graph traffic (router -> per-device schedulers ->
             registry tiers; mixed p2p/bounded/knear/tree queries):
             queries/s for the 1-device vs whole-mesh router configs and
             their scaling, p50/p99 latency, occupancy, warmup cost,
             bitwise p2p parity, a sharded-tier (shard_map) serving row,
             plus the p2p early-exit vs full-tree round comparison.
             Run under XLA_FLAGS=--xla_force_host_platform_device_count=8
             for a CPU device mesh.  With ``--open-loop``, submissions
             are paced by the traffic's Poisson ``arrival_s`` at several
             fractions of the measured closed-loop capacity and the
             section reports p50/p99 tail latency vs offered load; each
             load point also appends its shed/latency curve + the
             serving plane's metrics snapshot to
             benchmarks/artifacts/serving_open_loop.jsonl (the same
             JSONL snapshot stream the tuner writes).
  p2p      — goal-directed point-to-point ladder on the benchmark suite
             (incl. Road and the kron analogues): full tree vs early-exit
             p2p vs p2p + ALT landmark pruning vs bidirectional
             meet-in-the-middle, same (source, target) pairs.  Every rung
             is bitwise-exact (same d(s,t) + parent chain); rows report
             rounds / relaxations / pruned candidates per rung, the
             relax/round reduction ratios of the ALT rungs, and the
             one-off landmark build cost.  Committed as
             benchmarks/baselines/BENCH_p2p.json via --json
  delta    — streaming graph updates (repro.delta) on the benchmark
             suite: per edit-batch fraction (1% and 0.25% of undirected
             edges, mixed increase/decrease/remove), in-place
             patch_host + blocked-layout patch timings and incremental
             repair vs from-scratch recompute on the patched graph —
             relaxation counts, the relax_reduction headline, the
             invalidated/reseeded set sizes, and the bitwise dist+parent
             parity verdict (repair must be indistinguishable from a
             recompute).  Committed as
             benchmarks/baselines/BENCH_delta.json via --json; the
             acceptance floor is >= 3x relax_reduction at the 0.25%
             batch on Road and the kron analogue
  tuner    — the per-graph EngineConfig auto-tuner (repro.tune) on three
             graph families: default vs tuned trace objective, the
             reduction, bitwise dist/parent parity of the winner, and
             the evaluation counts.  Winners persist to
             benchmarks/artifacts/tuned.json; the search trajectory
             streams to benchmarks/artifacts/tuner.jsonl.

``--backend`` selects the relaxation backend used by the paper-metric
sections (fig4/5/6, table3); the ``backends`` section always sweeps all
of them head-to-head.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import common  # noqa: E402

ART = os.path.join(os.path.dirname(__file__), "artifacts")


def emit(rows, name, time_s, **derived):
    d = ";".join(f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                 for k, v in derived.items())
    print(f"{name},{time_s * 1e6:.1f},{d}")
    rows.append({"name": name, "us_per_call": time_s * 1e6, **derived})


def fig4_fig5_fig6(rows, scale, n_sources, full_variants, backend):
    print("# fig4/fig5/fig6: EIC metrics on benchmark + variant graphs")
    suites = [("bench", common.benchmark_graphs(scale))]
    suites.append(("variant", common.variant_graphs(max(scale - 1, 10),
                                                    full=full_variants)))
    for suite, graphs in suites:
        for name, make in graphs.items():
            g = make()
            srcs = common.pick_sources(g, n_sources)
            m = common.run_eic(g, srcs, backend=backend)
            e_over_v = g.m / 2 / g.n
            emit(rows, f"eic/{suite}/{name}", m["time_s"],
                 nFrontier=m["nFrontier"], nSync=m["nSync"],
                 nTrav=m["nTrav"], nTrav_push=m["nTrav_push"],
                 nTrav_pull=m["nTrav_pull"], steps=m["n_steps"],
                 E_over_V=e_over_v, dd_skew=common.dd_skewness(g),
                 trav_reduction=e_over_v - m["nTrav"])


def table3(rows, scale, n_sources, backend):
    print("# table3/fig7: comparison vs baselines")
    graphs = common.benchmark_graphs(scale)
    for name in ["Twitter", "Kron", "Web", "Urand", "Road",
                 f"gr{scale}_16"]:
        if name not in graphs:
            continue
        g = graphs[name]()
        srcs = common.pick_sources(g, n_sources)
        eic = common.run_eic(g, srcs, backend=backend)
        bf = common.run_baseline("bf", g, srcs)
        best_delta, best = None, None
        for delta in [0.1 * float(g.max_w), 0.5 * float(g.max_w),
                      float(g.max_w)]:
            d = common.run_baseline("delta", g, srcs, delta=delta)
            if best is None or d["time_s"] < best["time_s"]:
                best, best_delta = d, delta
        dj = common.run_dijkstra_host(g, srcs[:2])
        best_comp = min(bf["time_s"], best["time_s"])
        emit(rows, f"table3/{name}/eic", eic["time_s"],
             nFrontier=eic["nFrontier"], nSync=eic["nSync"],
             nTrav=eic["nTrav"],
             speedup_vs_best=best_comp / eic["time_s"])
        emit(rows, f"table3/{name}/bellman_ford", bf["time_s"],
             nFrontier=bf["nFrontier"], nSync=bf["nSync"],
             nTrav=bf["nTrav"])
        emit(rows, f"table3/{name}/delta_stepping", best["time_s"],
             nFrontier=best["nFrontier"], nSync=best["nSync"],
             nTrav=best["nTrav"], delta=best_delta)
        emit(rows, f"table3/{name}/dijkstra_host", dj["time_s"])


def backends(rows, scale, n_sources, batch):
    """Relaxation-backend head-to-head (see core/relax.py).

    Blocked rows report the kernel's own per-round tile metrics —
    ``tiles_per_round`` (active tiles the compacted schedule ran) and
    ``tile_reduction`` (the dense ``(n_dst_blocks, n_tiles)`` scan cost
    over it) — straight from ``SsspMetrics``, not recomputed host-side.
    """
    print("# backends: segment_min vs blocked_pallas (unfused/fused) vs"
          f" distributed (+ sssp_batch x{batch})")
    graphs = common.benchmark_graphs(scale)
    for name in [f"gr{scale}_8", "Road", "Urand"]:
        if name not in graphs:
            continue
        g = graphs[name]()
        srcs = common.pick_sources(g, max(n_sources, 2))
        base = None
        inv_unfused = tiles_unfused = None
        for be, fr in [("segment_min", 0), ("blocked_pallas", 0),
                       ("blocked_pallas", 4)]:
            m = common.run_eic(g, srcs, backend=be, fused_rounds=fr)
            if base is None:        # `or` would treat a 0.0 timing as unset
                base = m["time_s"]
            extra = {}
            if m["n_tiles_scanned"]:
                rounds = max(m["n_rounds"], 1)
                extra = {
                    "tiles_per_round": m["n_tiles_scanned"] / rounds,
                    "tile_reduction":
                        m["n_tiles_dense"] / max(m["n_tiles_scanned"], 1),
                }
            if m.get("n_invocations"):
                extra["invocations_per_solve"] = m["n_invocations"]
                if fr == 0:
                    inv_unfused = m["n_invocations"]
                    tiles_unfused = m["n_tiles_scanned"]
                elif inv_unfused:
                    # the fused-megakernel acceptance pair: launches drop,
                    # the compacted tile schedule does not grow
                    extra["invocation_reduction"] = (inv_unfused /
                                                     m["n_invocations"])
                    extra["tile_regression"] = (m["n_tiles_scanned"] /
                                                max(tiles_unfused, 1))
            label = f"{be}_fused{fr}" if fr else be
            emit(rows, f"backends/{name}/{label}", m["time_s"],
                 nTrav=m["nTrav"], nSync=m["nSync"],
                 rel_time=m["time_s"] / base, **extra)
        for dbe, fr in [("segment_min", 0), ("blocked", 0), ("blocked", 4)]:
            d = common.run_distributed(g, srcs, version="v2", backend=dbe,
                                       fused_rounds=fr)
            extra = {}
            if d["n_tiles_scanned"]:
                extra = {"tile_reduction": d["n_tiles_dense"] /
                         max(d["n_tiles_scanned"], 1)}
            if d.get("n_invocations"):
                extra["invocations_per_solve"] = d["n_invocations"]
            label = (f"distributed_v2_{dbe}_fused{fr}" if fr
                     else f"distributed_v2_{dbe}")
            emit(rows, f"backends/{name}/{label}", d["time_s"],
                 nTrav=d["nTrav"], nSync=d["nSync"],
                 n_devices=d["n_devices"], rel_time=d["time_s"] / base,
                 **extra)
        bsrcs = common.pick_sources(g, batch, seed=1)
        b = common.run_eic_batch(g, bsrcs)
        emit(rows, f"backends/{name}/sssp_batch", b["time_s"],
             batch=b["batch"], nTrav=b["nTrav"],
             rel_time=b["time_s"] / base)


def roofline(rows, scale):
    """Fused-megakernel roofline smoke row (see benchmarks/roofline.py).

    One real blocked-backend solve at ``fused_rounds=4``; emits achieved
    vs peak bytes/FLOPs per kernel invocation and rounds-per-invocation
    derived from the kernel's in-kernel counters.
    """
    from benchmarks import roofline as rl

    print("# roofline: fused relaxation megakernel, measured")
    r = rl.fused_relax_roofline(scale=min(scale, 10))
    emit(rows, "roofline/fused_relax", r["time_s"],
         rounds_per_invocation=r["rounds_per_invocation"],
         invocations_per_solve=r["invocations_per_solve"],
         bytes_per_invocation=r["bytes_per_invocation"],
         flops_per_invocation=r["flops_per_invocation"],
         peak_frac_bw=r["peak_frac_bw"],
         peak_frac_flops=r["peak_frac_flops"],
         dominant=r["dominant"])


def serving_open_loop(rows, graphs, base_qps, batch, n_queries, seed,
                      load_fracs=(0.3, 0.6, 0.9)):
    """Open-loop mode: Poisson arrivals at fractions of the measured
    closed-loop capacity; reports p50/p99 tail latency vs offered load.

    Each load point's shed/latency curve also lands in the JSONL
    snapshot stream (``serving_open_loop.jsonl``) together with the
    serving plane's full metrics snapshot, so the curves are queryable
    alongside the other exported telemetry instead of only living in
    the per-section BENCH json."""
    from repro.data.traffic import make_traffic

    jsonl = os.path.join(ART, "serving_open_loop.jsonl")
    for frac in load_fracs:
        rate = max(base_qps * frac, 0.5)
        traffic = make_traffic(graphs, n_queries, seed=seed, rate_qps=rate)
        # bounded per-device queues so overload sheds (QueueFull) instead
        # of stretching the tail unboundedly — open-loop needs real
        # admission control for the p99-vs-load curve to mean anything
        r = common.run_serving_traffic(graphs, traffic, max_batch=batch,
                                       open_loop=True,
                                       max_pending=8 * batch,
                                       jsonl_path=jsonl,
                                       jsonl_meta={
                                           "kind": "serving_open_loop",
                                           "load_frac": frac,
                                           "n_queries": n_queries,
                                       })
        emit(rows, f"serving/open_loop/{frac:g}x", r["time_s"],
             offered_qps=r["offered_qps"], achieved_qps=r["qps"],
             p50_ms=r["p50_ms"], p99_ms=r["p99_ms"], shed=r["shed"],
             occupancy=r["occupancy"], n_queries=n_queries)


def p2p(rows, scale, n_pairs=4, n_landmarks=8):
    """Goal-directed p2p ladder (tree / p2p / +ALT / bidirectional) —
    see :func:`benchmarks.common.run_p2p_alt`.  The ALT rungs must stay
    bitwise-exact while cutting relaxations (the issue's acceptance
    floor is >= 1.5x on Road and the kron analogue)."""
    print(f"# p2p: tree vs p2p vs p2p+ALT vs bidirectional, "
          f"{n_pairs} pairs, {n_landmarks} landmarks")
    graphs = common.benchmark_graphs(scale)
    for name in ["Road", f"gr{scale}_8", f"gr{scale}_16", "Urand",
                 "Kron"]:
        if name not in graphs:
            continue
        g = graphs[name]()
        srcs = common.pick_sources(g, n_pairs, seed=1)
        tgts = common.pick_sources(g, n_pairs, seed=2)
        m = common.run_p2p_alt(g, list(zip(srcs, tgts)),
                               n_landmarks=n_landmarks)
        emit(rows, f"p2p/{name}", m["time_s"],
             bitwise_equal=int(m["bitwise_equal"]),
             rounds_tree=m["rounds_tree"], rounds_p2p=m["rounds_p2p"],
             rounds_alt=m["rounds_alt"], rounds_bidi=m["rounds_bidi"],
             relax_p2p=m["relax_p2p"], relax_alt=m["relax_alt"],
             relax_bidi=m["relax_bidi"], pruned_alt=m["pruned_alt"],
             pruned_bidi=m["pruned_bidi"],
             relax_ratio_alt=m["relax_ratio_alt"],
             round_ratio_alt=m["round_ratio_alt"],
             relax_ratio_bidi=m["relax_ratio_bidi"],
             landmark_build_s=m["build_s"],
             time_s_p2p=m["time_s_p2p"], time_s_alt=m["time_s_alt"],
             time_s_bidi=m["time_s_bidi"])


def delta(rows, scale, fracs=(0.01, 0.0025), seed=0):
    """Streaming-update section (see
    :func:`benchmarks.common.run_delta_repair`): per benchmark graph and
    edit-batch fraction, in-place patch + incremental repair vs
    from-scratch recompute, with bitwise parity."""
    graphs = common.benchmark_graphs(scale)
    print(f"# delta: edit batches {[f'{f:.2%}' for f in fracs]} on "
          f"{len(graphs)} graphs, patch+repair vs recompute")
    for name, make in graphs.items():
        g = make()
        for r in common.run_delta_repair(g, fracs=fracs, seed=seed):
            emit(rows, f"delta/{name}/frac{r['frac']:g}", r["time_s"],
                 n_edits=r["n_edits"], n_invalid=r["n_invalid"],
                 n_seeds=r["n_seeds"], fast_path=int(r["fast_path"]),
                 patch_host_ms=r["patch_host_s"] * 1e3,
                 patch_layout_ms=r["patch_layout_s"] * 1e3,
                 time_s_full=r["time_s_full"],
                 relax_repair=r["relax_repair"],
                 relax_full=r["relax_full"],
                 relax_reduction=r["relax_reduction"],
                 rounds_repair=r["rounds_repair"],
                 rounds_full=r["rounds_full"],
                 bitwise_equal=int(r["bitwise_equal"]))


def tuner(rows, scale, budget=14, seed=0):
    """Per-graph EngineConfig auto-tuner (``repro.tune``) on three graph
    families: default vs tuned trace objective + reduction, winner's
    bitwise parity (the tuner accepts only parity-identical candidates,
    so rejects are also reported), and the evaluation budget spent.

    Winners persist to ``benchmarks/artifacts/tuned.json``; the full
    search trajectory streams to ``benchmarks/artifacts/tuner.jsonl``
    (one line per candidate + a final metrics snapshot per graph).
    """
    import time

    from repro.data.generators import kronecker, road_grid, uniform_random
    from repro.tune import TunedStore, tune

    sc = min(scale, 10)
    n = 1 << sc
    side = int(np.sqrt(n))
    graphs = {
        f"kron{sc}": kronecker(sc, 8, seed=2),
        "road": road_grid(side, seed=5),
        "urand": uniform_random(n, 8 * n, seed=6),
    }
    store = TunedStore(os.path.join(ART, "tuned.json"))
    jsonl = os.path.join(ART, "tuner.jsonl")
    print(f"# tuner: {len(graphs)} graphs, budget={budget} evals each, "
          f"seed={seed}")
    for gid, g in graphs.items():
        t0 = time.perf_counter()
        res = tune(g, gid=gid, budget=budget, seed=seed, store=store,
                   jsonl_path=jsonl)
        best = res.best_config
        emit(rows, f"tuner/{gid}", time.perf_counter() - t0,
             baseline_objective=res.baseline_objective,
             tuned_objective=res.best_objective,
             reduction=res.reduction, improved=int(res.improved),
             n_evals=res.n_evals, accepted=res.n_accepted,
             parity_rejects=res.n_parity_rejects, invalid=res.n_invalid,
             alpha=best.alpha, beta=best.beta, policy=best.policy,
             fused_rounds=best.fused_rounds)


def serving(rows, scale, batch, n_queries=None, seed=0, open_loop=False):
    """Serving plane under Zipf-skewed multi-graph traffic.

    Runs the same traffic twice — through a 1-device router and through a
    router over every local device — and reports the aggregate
    queries/s scaling (the multi-device acceptance check; run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to get a CPU
    mesh), with served p2p distances bitwise-checked against the
    single-device engine.  A final row serves a graph through the
    sharded (shard_map) engine tier via the same ``SsspService``/router
    API and checks dist/parent parity.
    """
    import time

    import jax

    from repro.core.sssp import sssp
    from repro.data.generators import kronecker, road_grid, uniform_random
    from repro.data.traffic import make_traffic
    from repro.serve.sssp_service import SsspRequest, SsspService

    n = 1 << scale
    side = int(np.sqrt(n))
    # heterogeneous shapes (skewed / road / random), enough graphs that
    # placement can spread over a mesh; insertion order = Zipf popularity
    graphs = {
        f"gr{scale}_8": kronecker(scale, 8, seed=2),   # hottest (rank 0)
        "Road": road_grid(side, seed=5),
        "Urand": uniform_random(n, 8 * n, seed=6),
        f"gr{scale}_4": kronecker(scale, 4, seed=11),
        "Web": kronecker(scale, 30, seed=7),
        "Twitter": kronecker(scale, 22, seed=8),
    }
    if n_queries is None:   # explicit 0 is 0, not the default
        n_queries = max(96, 16 * batch)
    n_dev = len(jax.devices())
    print(f"# serving: {len(graphs)} graphs, {n_queries} Zipf queries, "
          f"max_batch={batch}, devices={n_dev}")
    traffic = make_traffic(graphs, n_queries, seed=seed)

    one = common.run_serving_traffic(graphs, traffic,
                                     devices=jax.devices()[:1],
                                     max_batch=batch)
    emit(rows, "serving/1dev", one["time_s"], qps=one["qps"],
         p50_ms=one["p50_ms"], p99_ms=one["p99_ms"],
         occupancy=one["occupancy"], warmup_s=one["warmup_s"],
         n_batches=one["stats"]["n_batches"], n_graphs=len(graphs),
         n_queries=n_queries,
         registry_hit_rate=one["serving_hit_rate"])
    best = one
    if n_dev > 1:
        many = common.run_serving_traffic(graphs, traffic, max_batch=batch)
        parity, n_checked = common.check_p2p_parity(graphs,
                                                    many["results"],
                                                    sample=12)
        emit(rows, "serving/router", many["time_s"], qps=many["qps"],
             rebuilds=many["stats"]["n_rebuilds"],
             n_devices=n_dev, scaling=many["qps"] / one["qps"],
             p2p_bitwise_equal=int(parity), p2p_checked=n_checked,
             p50_ms=many["p50_ms"], p99_ms=many["p99_ms"],
             occupancy=many["occupancy"], warmup_s=many["warmup_s"],
             n_batches=many["stats"]["n_batches"],
             replications=many["stats"]["n_replications"],
             rejected=many["stats"]["rejected"],
             registry_hit_rate=many["serving_hit_rate"])
        best = many

    if open_loop:
        # tail latency vs offered load, paced by the traffic's Poisson
        # arrival offsets (instead of closed-loop drain throughput)
        serving_open_loop(rows, graphs, best["qps"], batch, n_queries, seed)

    lat_by_gid = {}
    for item, res in best["results"]:
        lat_by_gid.setdefault(item.query.gid, []).append(res.latency_s)
    for gid, lats in sorted(lat_by_gid.items()):
        lats = np.asarray(lats)
        emit(rows, f"serving/{gid}", float(lats.mean()),
             n=lats.size,
             p50_ms=float(np.percentile(lats, 50) * 1e3),
             p99_ms=float(np.percentile(lats, 99) * 1e3))

    # sharded-tier acceptance: a graph forced over the shard threshold is
    # served through the same SsspService/router API by the shard_map
    # engine spanning the mesh — once per relax backend (segment_min and
    # the sparsity-aware blocked layout) — with dist/parent parity vs
    # single-device
    big_name = f"gr{scale}_8"
    big = graphs[big_name]
    dg = big.to_device()
    srcs = common.pick_sources(big, min(batch, 4), seed=3)
    for sbe in ["segment_min", "blocked"]:
        svc = SsspService(big, devices=jax.devices(),
                          config=common.EngineConfig(
                              max_batch=min(batch, 4), shard_threshold_n=1,
                              shard_backend=sbe))
        t0 = time.perf_counter()
        reqs = [svc.submit(SsspRequest(rid=i, source=int(s)))
                for i, s in enumerate(srcs)]
        svc.run()
        elapsed = time.perf_counter() - t0
        parity = True
        for r in reqs:
            d_ref, p_ref, _ = sssp(dg, r.source)
            parity &= (np.array_equal(r.dist, np.asarray(d_ref))
                       and np.array_equal(r.parent, np.asarray(p_ref)))
        emit(rows, f"serving/{big_name}/sharded_tier_{sbe}",
             elapsed / len(reqs), n_devices=n_dev, parity=int(parity),
             n_sources=len(reqs))

    # acceptance check: p2p early exit saves rounds on the Road graph and
    # returns bitwise-identical target distances
    road = graphs["Road"]
    srcs = common.pick_sources(road, 6, seed=1)
    tgts = common.pick_sources(road, 6, seed=2)
    cmp_ = common.run_p2p_vs_tree(road, list(zip(srcs, tgts)))
    emit(rows, "serving/Road/p2p_vs_tree", cmp_["time_s"],
         rounds_tree=cmp_["rounds_tree"], rounds_p2p=cmp_["rounds_p2p"],
         round_ratio=cmp_["round_ratio"],
         bitwise_equal=int(cmp_["bitwise_equal"]),
         speedup_vs_tree=cmp_["time_s_tree"] / max(cmp_["time_s"], 1e-12))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=13)
    ap.add_argument("--sources", type=int, default=3)
    from repro.core.relax import available_backends
    ap.add_argument("--backend", default="segment_min",
                    choices=available_backends(),
                    help="relaxation backend for the paper-metric sections")
    ap.add_argument("--batch", type=int, default=4,
                    help="sources per fused sssp_batch call (backends "
                         "section)")
    ap.add_argument("--full-variants", action="store_true")
    ap.add_argument("--sections", default="fig4,table3,backends,serving")
    ap.add_argument("--queries", type=int, default=None,
                    help="query count for the serving section "
                         "(default: max(48, 8*batch))")
    ap.add_argument("--tune-budget", type=int, default=14,
                    help="tuner section: candidate evaluations per graph "
                         "(baseline included)")
    ap.add_argument("--open-loop", action="store_true",
                    help="serving section: pace submissions by the "
                         "traffic's Poisson arrival_s and report p50/p99 "
                         "tail latency vs offered load")
    ap.add_argument("--json", metavar="OUT", default=None, dest="json_out",
                    help="also write one BENCH_<section>.json per section "
                         "into this directory (per-row timings + derived "
                         "metrics, plus the run configuration)")
    args = ap.parse_args()
    if args.batch < 1:
        ap.error("--batch must be >= 1")
    if args.sources < 1:
        ap.error("--sources must be >= 1")
    if args.queries is not None and args.queries < 1:
        ap.error("--queries must be >= 1")
    if args.tune_budget < 1:
        ap.error("--tune-budget must be >= 1")

    from repro.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}")
    os.makedirs(ART, exist_ok=True)
    rows = []
    by_section = {}
    sections = set(args.sections.split(","))
    print("name,us_per_call,derived")

    def run_section(sec, fn, *a, **kw):
        # attribute each section's rows so --json can split them per file
        start = len(rows)
        fn(rows, *a, **kw)
        by_section[sec] = rows[start:]

    if sections & {"fig4", "fig5", "fig6"}:
        run_section("fig4", fig4_fig5_fig6, args.scale, args.sources,
                    args.full_variants, args.backend)
    if "table3" in sections:
        run_section("table3", table3, args.scale, args.sources, args.backend)
    if "backends" in sections:
        run_section("backends", backends, args.scale, args.sources,
                    args.batch)
    if "roofline" in sections:
        run_section("roofline", roofline, args.scale)
    if "serving" in sections:
        run_section("serving", serving, args.scale, args.batch,
                    n_queries=args.queries, open_loop=args.open_loop)
    if "p2p" in sections:
        run_section("p2p", p2p, args.scale)
    if "delta" in sections:
        run_section("delta", delta, args.scale)
    if "tuner" in sections:
        run_section("tuner", tuner, args.scale,
                    budget=args.tune_budget)
    with open(os.path.join(ART, "paper_metrics.json"), "w") as f:
        json.dump(rows, f, indent=1)
    print(f"# wrote {len(rows)} rows to benchmarks/artifacts/paper_metrics.json")
    if args.json_out:
        import jax
        os.makedirs(args.json_out, exist_ok=True)
        cfg = {"scale": args.scale, "sources": args.sources,
               "backend": args.backend, "batch": args.batch,
               "platform": jax.devices()[0].platform,
               "n_devices": len(jax.devices())}
        for sec, srows in by_section.items():
            path = os.path.join(args.json_out, f"BENCH_{sec}.json")
            with open(path, "w") as f:
                json.dump({"section": sec, "config": cfg,
                           "n_rows": len(srows), "rows": srows}, f, indent=1)
            print(f"# wrote {len(srows)} rows to {path}")


if __name__ == "__main__":
    main()
