"""Find an open-loop mix's knee: its window at several fixed rates.

    python3 bench/sweep.py --config <config> --traffic <mix> --seed <n> \
        --seconds 150 --rates 0.2,0.4

The configuration is one of ``BENCHMARK.json``; the mix is a file of
``bench/traffic/``, so a cell can be swept before it is added.

One process, one set-up; then each rate's window in turn, on the same
graph and the same warmed solver.  For each rate it prints the
latencies and whether the backlog grew: the requests still outstanding
when the window closed, and the median latency of the last quarter of
requests against the first quarter.  The knee is the highest rate
whose backlog stays flat; the cell's mix file then fixes its rate at
about four fifths of it.  Used when a cell is defined, not by runs.
``--rehearse`` runs it on the CPU at the generator's tiny size.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import cell as cell_mod  # noqa: E402
from bench import manifest, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax
    if not args.rehearse:
        jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
        if jax.devices()[0].platform != "tpu":
            print("the sweep measures a TPU; none found", file=sys.stderr)
            return run.EXIT_NO_CHIP
    man = manifest.load()
    man = dict(man, workloads=[{"name": "sweep", "config": args.config,
                                "traffic": args.traffic, "chips": 1}])
    rates = [float(r) for r in args.rates.split(",")]
    first = cell_mod.build(man, "sweep", args.seed, args.seconds,
                           rehearse=args.rehearse, rate_qps=rates[0])
    if first.mix["loop"] != "open":
        print("the sweep is for open-loop cells", file=sys.stderr)
        return 2
    solver = run.open_solver(first, jax.devices()[:first.chips])
    try:
        for rate in rates:
            inp = cell_mod.build(man, "sweep", args.seed, args.seconds,
                                 rehearse=args.rehearse, rate_qps=rate)
            out = run.open_window(solver, inp.requests, args.seconds,
                                  run.GRACE_S)
            lat = np.array([q["latency_s"] for q in out["queries"]])
            due = np.array([r.due_s for r in inp.requests])
            quarter = max(1, lat.size // 4)
            order = np.argsort(due)
            outstanding = int(np.sum(due + lat > args.seconds))
            print(json.dumps({
                "rate_qps": rate, "requests": int(lat.size),
                "failed": out["failed"],
                "p50_ms": 1e3 * float(np.percentile(lat, 50)),
                "p95_ms": 1e3 * float(np.percentile(lat, 95)),
                "first_quarter_p50_ms":
                    1e3 * float(np.median(lat[order[:quarter]])),
                "last_quarter_p50_ms":
                    1e3 * float(np.median(lat[order[-quarter:]])),
                "outstanding_at_close": outstanding,
                "drain_s": out["window_s"] - args.seconds,
                "late_s_max": out["late_s_max"]}), flush=True)
    finally:
        solver.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
