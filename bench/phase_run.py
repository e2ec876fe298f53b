"""Run a tree cell's window under a profiler trace; print device time by
the solve's named phases.

    python3 bench/phase_run.py --workload <tree cell> --seed <n> --seconds <s>

The window, the phase table (timed) and the trace's reduction are
``run.py``'s under ``--trace 1``, on the same inputs and solver, with
every op of the window kept and not the ten largest.  The last
line of standard output is a JSON object: ``phases_s`` (phase ->
``[seconds, runs]``), ``round_ms.tree`` and ``transition_ms.tree``
(``metrics/``), the traced window's ``teps`` and ``ms_per_round.tree``,
``busy_s``, ``device_ops_s`` (the leaf time of all of
``trace_reduce``'s ``device_ops``), ``leaf_s`` (the phases' sum) and
``phase_table_s``.  ``--out`` also writes it to a file.  ``--rehearse``
runs at the generator's tiny size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cell as cell_mod  # noqa: E402
from bench import manifest, trace_reduce  # noqa: E402
from bench import run as harness  # noqa: E402

READ = ("round_ms.tree", "transition_ms.tree", "teps", "ms_per_round.tree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU run at the generator's tiny size")
    args = ap.parse_args(argv)
    import jax
    harness.configure_jax(args.rehearse, trace=True)
    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        print("phase_run needs a TPU (or --rehearse)", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    where = (dict(device_plane="/host:CPU", op_line="tf_XLA",
                  op_stat="hlo_op") if args.rehearse else {})
    man = manifest.load()
    inp = cell_mod.build(man, args.workload, args.seed, args.seconds,
                         rehearse=args.rehearse)
    if inp.mix["loop"] != "closed":
        print("phase_run runs tree cells", file=sys.stderr)
        return 2
    solver = harness.open_solver(inp, devices[:inp.chips])
    trace_dir = tempfile.mkdtemp(prefix="phase-trace-")
    try:
        jax.profiler.start_trace(trace_dir)
        with harness._span("bench:window"):
            out = harness.closed_window(solver, inp.requests, args.seconds)
        jax.profiler.stop_trace()
        t0 = time.perf_counter()
        table = harness.phase_table(solver, inp)
        table_s = time.perf_counter() - t0
    finally:
        solver.close()
    trace_reduce.TOP = 1 << 30            # every op, not the ten largest
    reduced, phased = harness.read_trace(trace_dir, table, **where)
    rec = {"loop": "closed", "trace": reduced, "phases": phased["phases"],
           "trees": out["trees"], "edges": inp.edges,
           "window_s": out["window_s"]}
    result = {"cell": args.workload, "seed": args.seed,
              "device": devices[0].device_kind,
              "phases_s": phased["phases"], "leaf_s": phased["leaf_s"],
              "device_ops_s": sum(s for _, s in reduced["device_ops"]),
              "busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
              "trees": len(out["trees"]), "failed": out["failed"],
              "n_rounds": sum(t["n_rounds"] for t in out["trees"]),
              "n_steps": sum(t["n_steps"] for t in out["trees"]),
              "phase_table_s": table_s, "table_size": len(table)}
    for name in READ:
        result[name] = manifest.load_module(
            manifest.metric_path(name)).read(rec)
    line = json.dumps(result)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
