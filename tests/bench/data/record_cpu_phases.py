"""Record ``cpu_phases.xplane.pb`` and ``cpu_phases.json``, the small
trace and phase table that the phase reduction test reads.

    JAX_PLATFORMS=cpu python3 tests/bench/data/record_cpu_phases.py

A ``bench:window`` span holds one untraced tree solve on a small
Kronecker graph and the fetch of its answer, as a benchmark window
does.  The JSON holds the solve's phase table (``Solver.phase_table``)
and, from the same solve with the on-device ring on
(``EngineConfig(trace=True)``), the ring's records and the records that
ran a step transition.
"""
import glob
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from repro.api import EngineConfig, SolveSpec, Solver  # noqa: E402
from repro.data.generators import kronecker  # noqa: E402

SCALE, EDGE_FACTOR, GRAPH_SEED = 8, 4, 0


def main():
    g = kronecker(SCALE, EDGE_FACTOR, seed=GRAPH_SEED)
    spec = SolveSpec.tree(int(np.argmax(np.asarray(g.deg))))
    with Solver.open(g, EngineConfig(trace=True)) as solver:
        ring = solver.solve(spec).trace
    with Solver.open(g, EngineConfig()) as solver:
        solver.solve(spec).block_until_ready()
        tmp = tempfile.mkdtemp()
        # the op events alone: no Python calls, no HLO protos
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with TraceAnnotation("bench:window"):
            res = solver.solve(spec)
            jax.block_until_ready((res.dist, res.parent, res.metrics))
            np.asarray(res.dist)
            int(np.asarray(res.metrics.n_rounds))
        jax.profiler.stop_trace()
        table = solver.phase_table(spec)
    (src,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    shutil.copy(src, HERE / "cpu_phases.xplane.pb")
    shutil.rmtree(tmp)
    doc = {"graph": {"scale": SCALE, "edge_factor": EDGE_FACTOR,
                     "seed": GRAPH_SEED},
           "source": spec.sources,
           "ring": {"n_recorded": ring.n_records,
                    "stepped": int(np.sum(ring.columns["stepped"])),
                    "dropped": ring.dropped},
           "module": table.module, "looped": sorted(table.looped),
           "table": dict(sorted(table.items()))}
    (HERE / "cpu_phases.json").write_text(
        json.dumps(doc, separators=(",", ":")) + "\n")


if __name__ == "__main__":
    main()
