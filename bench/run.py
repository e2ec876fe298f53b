"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell (``BENCHMARK.json``) names a
configuration, whose generator makes the graph from the seed, and a
traffic mix, which says how requests reach the program:

* closed loop (tree trials): ``repro.api.Solver.solve(SolveSpec.tree(s))``
  on the single tier, one tree in flight; the window ends when the
  first tree that finishes after ``--seconds`` returns;
* open loop (served queries): ``Solver.submit(...)`` on the routed
  tier, each request sent when it is due and timed from then to its
  result; the window closes after ``--seconds`` and waits up to a
  minute for what is outstanding.

Set-up (graph, solver, compiles, warm requests) is timed from process
start to the window.  After the window every answer is compared with
the plain reference (``compare.py``); the numbers compared and their
limits go to the last lines of standard error and under ``checks``,
the last key of the result line, which is the last line of standard
output.  ``--trace 1`` records a profiler trace of the window and
reports the per-layer metrics in place of the end-to-end ones; on the
single tier it also charges the window's device time to the solve's
named phases (``Solver.phase_table``, ``trace_phases.py``), reported
as ``extra.phases_s``.

A configuration with ``"undirected": false`` is directed: the program
gets its arcs unmirrored, and the reference judges by the arcs alone.

Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.  ``--rehearse`` runs the cell on the CPU at the
generator's tiny size, to check the harness; it refuses ``--trace 1``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import cell as cell_mod  # noqa: E402
from bench import compare, manifest  # noqa: E402

GRACE_S = 60.0
EXIT_NO_CHIP = 3
CACHE_DIR = ROOT / ".jax_cache"


class CompileCounter:
    """Counts programs compiled or loaded from the cache while armed."""

    def __init__(self):
        self.armed = False
        self.count = 0
        self._lock = threading.Lock()

    def _hit(self):
        if self.armed:
            with self._lock:
                self.count += 1

    def install(self, jax):
        jax.monitoring.register_event_duration_secs_listener(
            lambda ev, _d, **_kw: self._hit()
            if ev == "/jax/core/compile/backend_compile_duration" else None)
        jax.monitoring.register_event_listener(
            lambda ev, **_kw: self._hit()
            if ev == "/jax/compilation_cache/cache_hits" else None)


def _span(name):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def _scheduler_counters(solver) -> dict:
    snap = solver.router.metrics.snapshot()
    out = {"batches": 0, "queries_done": 0}
    for full, entry in snap.items():
        if full.startswith("sssp_scheduler_batches_total"):
            out["batches"] += entry["value"]
        elif full.startswith("sssp_scheduler_queries_done_total"):
            out["queries_done"] += entry["value"]
    return out


def _spec(req):
    from repro.api import SolveSpec
    if req.kind == "tree":
        return SolveSpec.tree(req.source)
    if req.kind == "knear":
        return SolveSpec.knear(req.source, int(req.param))
    return SolveSpec.bounded(req.source, float(req.param))


def configure_jax(rehearse: bool, trace: bool):
    """Before the first compile: the persistent compile cache in a fixed
    directory of the checkout (the path is part of the key); and, in a
    traced run, the programs' ``op_name`` metadata in the key, so that
    an entry written by a program without phases cannot serve one whose
    phase table is read.  Untraced runs keep their key."""
    import jax
    if trace:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    if not rehearse:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def host_graph(inp):
    """The program's CSR (``build_csr``) of the cell's edge list: each
    edge both ways, or each arc alone where the configuration is
    directed."""
    from repro.core.graph import build_csr
    return build_csr(inp.n, inp.u, inp.v, inp.w, symmetrize=not inp.directed)


def _warm_closed(solver, inp, engine_config):
    """Compile each kind's program without a whole solve: from a root
    with no (out-)edge the loop ends at once.  The cell's own solver
    serves where its graph has such a vertex; otherwise a graph of the
    same shapes, directed alike, whose root 0 has none."""
    import dataclasses
    from repro.api import Solver
    from repro.core.graph import build_csr
    first = {r.kind: r for r in reversed(inp.requests)}
    deg = cell_mod.degree(inp.n, inp.u, inp.v, inp.directed)
    isolated = np.flatnonzero(deg == 0)
    if isolated.size:
        root, warm = int(isolated[0]), solver
    else:
        m = inp.edges
        root, warm = 0, Solver.open(
            build_csr(inp.n, np.ones(m, np.int64), np.full(m, 2, np.int64),
                      np.ones(m), symmetrize=not inp.directed), engine_config)
    try:
        for req in first.values():
            warm.solve(_spec(dataclasses.replace(req, source=root))
                       ).block_until_ready()
    finally:
        if warm is not solver:
            warm.close()


def open_solver(inp, devices, grace_s: float = GRACE_S, phases=None):
    """The program's solver on the cell's graph and tier, warmed: every
    program the window runs is compiled and the serving plane's
    per-graph state is built.  A warm request that has not come back
    within ``grace_s`` is left behind: the window's own answers judge
    the program.  ``phases`` gets the end of each step on the clock of
    ``T_PROCESS``."""
    from repro.api import EngineConfig, Solver
    phases = {} if phases is None else phases
    mix = inp.mix
    host = host_graph(inp)
    phases["csr"] = time.perf_counter() - T_PROCESS
    if mix["tier"] == "routed":
        cfg = EngineConfig(tier="routed", devices=tuple(devices),
                           max_batch=int(mix["max_batch"]))
    else:
        cfg = EngineConfig(tier=mix["tier"])
    solver = Solver.open(host, cfg)
    phases["open"] = time.perf_counter() - T_PROCESS
    try:
        if mix["loop"] == "closed":
            _warm_closed(solver, inp, cfg)
        else:
            kinds = tuple(sorted({k for k, _ in mix["mix"]}))
            solver.warmup(kinds=kinds, batch_sizes=(int(mix["max_batch"]),))
            futs = [solver.submit(_spec(r)) for r in inp.warm]
            concurrent.futures.wait(futs, timeout=grace_s)
    except BaseException:
        solver.close()
        raise
    phases["warm"] = time.perf_counter() - T_PROCESS
    return solver


def closed_window(solver, requests, seconds: float) -> dict:
    """One tree in flight; ends with the first tree done after ``seconds``.
    Each tree's answer and every field of its ``SsspMetrics`` come back
    in one transfer."""
    import jax
    trees, answers, failed = [], [], 0
    t_start = t_end = time.perf_counter()
    for req in requests:
        try:
            with _span("bench:solve"):
                t0 = time.perf_counter()
                res = solver.solve(_spec(req))
                jax.block_until_ready((res.dist, res.parent, res.metrics))
                t1 = time.perf_counter()
            with _span("bench:fetch"):
                dist, parent, metrics = jax.device_get(
                    (res.dist, res.parent, res.metrics))
                answers.append((req, dist, parent))
                trees.append(dict(source=req.source, seconds=t1 - t0,
                                  **{f: getattr(metrics, f).item()
                                     for f in metrics._fields}))
        except Exception as exc:             # a failed tree is counted
            print(f"tree from {req.source} failed: {exc!r}", file=sys.stderr)
            failed += 1
            t1 = time.perf_counter()
        t_end = t1
        if t_end - t_start >= seconds:
            break
    return {"window_s": t_end - t_start, "trees": trees, "answers": answers,
            "attempted": len(trees) + failed, "failed": failed}


def open_window(solver, requests, seconds: float, grace_s: float) -> dict:
    """Send each request when due; time it from then to its result."""
    n = len(requests)
    done_at = [None] * n
    results = [None] * n
    errors = [None] * n
    late = np.zeros(n)
    lock = threading.Lock()
    all_done = threading.Event()
    left = [n]

    def on_done(i):
        def cb(fut):
            t = time.perf_counter()
            try:
                results[i] = fut.result()
                done_at[i] = t
            except Exception as exc:         # failed or cancelled
                errors[i] = repr(exc)
            with lock:
                left[0] -= 1
                if left[0] == 0:
                    all_done.set()
        return cb

    t_start = time.perf_counter()
    for i, req in enumerate(requests):
        due = t_start + req.due_s
        pause = due - time.perf_counter()
        if pause > 0:
            time.sleep(pause)
        with _span("bench:submit"):
            late[i] = time.perf_counter() - due
            try:
                fut = solver.submit(_spec(req))
            except Exception as exc:         # refused: counted as failed
                errors[i] = repr(exc)
                with lock:
                    left[0] -= 1
                    if left[0] == 0:
                        all_done.set()
                continue
        fut.add_done_callback(on_done(i))
    close = t_start + seconds
    with _span("bench:wait"):
        all_done.wait(timeout=max(0.0, close + grace_s - time.perf_counter()))
    t_end = time.perf_counter()
    queries = []
    for i, req in enumerate(requests):
        due = t_start + req.due_s
        ok = done_at[i] is not None
        queries.append({
            "kind": req.kind,
            # a missing answer counts as late as the wait gave it
            "latency_s": (done_at[i] if ok else t_end) - due,
            "ok": ok,
            "n_rounds": int(results[i].metrics["n_rounds"]) if ok else None})
    missing = sum(not q["ok"] for q in queries)
    return {"window_s": t_end - t_start, "queries": queries,
            "results": results, "errors": errors,
            "late_s_max": float(late.max()) if n else 0.0,
            "attempted": n, "failed": missing}


def check(inp, out) -> dict:
    """The numbers that decide ``correct`` (see ``compare.py``).  A
    root's reference tree is computed once, however often the window
    solved it."""
    from bench import reference
    adj = reference.adjacency(inp.n, inp.u, inp.v, inp.w,
                              directed=inp.directed)
    if inp.mix["loop"] == "closed":
        nums = {"dist_mismatch": 0, "parent_bad": 0}
        refs = {}
        for req, dist, parent in out["answers"]:
            if req.source not in refs:
                refs[req.source] = reference.dijkstra(adj, req.source)[0]
            for k, v in compare.tree_numbers(adj, req.source, dist, parent,
                                             refs[req.source]).items():
                nums[k] += v
        nums["answers_missing"] = out["failed"]
        return nums
    wrong = 0
    for req, res in zip(inp.requests, out["results"]):
        if res is None:
            continue
        answer = {"dist": res.dist, "parent": res.parent,
                  "nearest": res.nearest() if req.kind == "knear" else None}
        wrong += compare.query_wrong(adj, req.kind, req.source, req.param,
                                     answer)
    return {"answers_wrong": int(wrong), "answers_missing": out["failed"]}


def phase_table(solver, inp):
    """The phase table (``Solver.phase_table``) of the program that the
    cell's first request runs, or ``None`` off the single tier."""
    if inp.mix["tier"] != "single":
        return None
    return solver.phase_table(_spec(inp.requests[0]))


def read_trace(trace_dir, table=None, **where):
    """``(reduced, phased)`` of the window traced into ``trace_dir``:
    ``trace_reduce.reduce_trace``'s reduction, and with a phase table
    ``trace_phases.reduce_phases``'s device time by phase (else
    ``None``).  ``where`` names the planes and lines of a trace that is
    not a TPU's.  The trace is removed."""
    from bench import trace_phases, trace_reduce
    try:
        path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
        reduced = trace_reduce.reduce_trace(
            path, **{k: v for k, v in where.items() if k != "op_stat"})
        phased = None if table is None else \
            trace_phases.reduce_phases(path, table, **where)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return reduced, phased


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, grace_s: float = GRACE_S,
             man: dict = None):
    """Run one cell; returns ``(exit_code, result_dict_or_None)``."""
    man = manifest.load() if man is None else man
    wl = manifest.workload(man, workload)
    import jax
    phases = {"import": time.perf_counter() - T_PROCESS}
    configure_jax(rehearse, trace)
    devices = jax.devices()
    phases["jax"] = time.perf_counter() - T_PROCESS
    platform = devices[0].platform
    if rehearse:
        if trace:
            print("--trace 1 needs a TPU: a CPU run has no device metrics",
                  file=sys.stderr)
            return EXIT_NO_CHIP, None
    elif platform != "tpu" or len(devices) < int(wl["chips"]):
        print(f"cell {workload} needs {wl['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {platform} device(s)", file=sys.stderr)
        return EXIT_NO_CHIP, None
    peaks = None
    if trace:
        from bench.peaks import peaks as peaks_of
        peaks = peaks_of(devices[0].device_kind)
    compiles = CompileCounter()
    compiles.install(jax)

    inp = cell_mod.build(man, workload, seed, seconds, rehearse=rehearse)
    phases["inputs"] = time.perf_counter() - T_PROCESS
    mix = inp.mix
    used = devices[:inp.chips]
    solver = open_solver(inp, used, grace_s, phases)
    try:
        before = _scheduler_counters(solver) if mix["tier"] == "routed" \
            else None
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            jax.profiler.start_trace(trace_dir)
        setup_s = time.perf_counter() - T_PROCESS
        compiles.armed = True
        with _span("bench:window"):
            if mix["loop"] == "closed":
                out = closed_window(solver, inp.requests, seconds)
            else:
                out = open_window(solver, inp.requests, seconds, grace_s)
        compiles.armed = False
        table = None
        if trace:
            jax.profiler.stop_trace()
            table = phase_table(solver, inp)
        counters = None
        if before is not None:
            after = _scheduler_counters(solver)
            counters = {k: after[k] - before[k] for k in after}
        memory_peak = _memory_peak(used)
    finally:
        solver.close()
    del solver
    gc.collect()

    reduced = phased = None
    if trace:
        reduced, phased = read_trace(trace_dir, table)
    phases_s = phased["phases"] if phased else None

    rec = {"cell": workload, "loop": mix["loop"], "setup_s": setup_s,
           "window_s": out["window_s"], "edges": inp.edges,
           "trees": out.get("trees", []), "queries": out.get("queries", []),
           "counters": counters, "trace": reduced, "phases": phases_s,
           "peaks": peaks}
    metrics = {}
    for m in manifest.cell_metrics(man, workload,
                                   "per_layer" if trace else "end_to_end"):
        value = manifest.load_module(manifest.metric_path(m["name"])).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    numbers = check(inp, out)
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    result = {"correct": compare.verdict(numbers),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["extra"] = {"seed": seed, "window_s": out["window_s"],
                       "compiles_in_window": compiles.count,
                       "late_s_max": out.get("late_s_max"),
                       "setup_phases_s": phases}
    if trace:
        result["extra"]["phases_s"] = phases_s
    result["checks"] = {k: {"value": v, "limit": compare.LIMITS[k]}
                        for k, v in numbers.items()}
    return 0, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU run at the generator's tiny size")
    args = ap.parse_args(argv)
    rc, result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), rehearse=args.rehearse)
    if result is None:
        return rc
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
