"""Per-round solve traces: parity, no-op, and export invariants.

The tentpole contract, tested per backend:

* **counter parity** — a traced solve's per-round counter deltas, summed
  over the trace and added to the engine's metric init (``n_extended``
  starts at 1 for the source pop), reproduce the final ``SsspMetrics``
  field bitwise;
* **bitwise no-op** — dist/parent/metrics of a traced solve are bitwise
  identical to the untraced solve (the ring only reads solver state);
* **ring overflow** — a small-capacity ring keeps the newest records and
  reports the drop, never corrupting retained records;
* **export invariants** — every ``metrics_dict`` field is present and
  finite for every backend x ``fused_rounds`` combination.

Distributed (v1/v2/v3 over 8 shards) parity lives in the multidevice
subprocess test at the bottom, mirroring test_distributed_sssp.py.
"""
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.api import EngineConfig, SolveSpec, Solver
from repro.core.config import ConfigError
from repro.core.sssp import (LOGICAL_METRIC_FIELDS, PHYSICAL_METRIC_FIELDS,
                             metrics_dict, sssp)
from repro.data.generators import kronecker
from repro.obs import (SolveTrace, TRACE_COLUMNS, TRACE_COUNTER_COLUMNS,
                       materialize_trace)

# (config kwargs, label) — every single-device engine variant
BACKENDS = [
    ({"backend": "segment_min"}, "segment_min"),
    ({"backend": "blocked_pallas", "interpret": True}, "blocked"),
    ({"backend": "blocked_pallas", "interpret": True, "fused_rounds": 4},
     "blocked_fused4"),
]


@pytest.fixture(scope="module")
def graph():
    return kronecker(8, 4, seed=0)


def assert_counter_parity(trace, metrics):
    """initial + summed per-round deltas == final, bitwise per field."""
    assert trace.dropped == 0, "parity needs the full record set"
    sums = trace.counter_sums()
    for f in LOGICAL_METRIC_FIELDS:
        init = 1 if f == "n_extended" else 0
        assert init + sums[f] == int(getattr(metrics, f)), f
    for f in PHYSICAL_METRIC_FIELDS:
        assert sums[f] == float(getattr(metrics, f)), f


@pytest.mark.parametrize("kw,label", BACKENDS, ids=[b[1] for b in BACKENDS])
def test_traced_solve_parity_and_noop(graph, kw, label):
    src = int(np.argmax(graph.deg))
    with Solver.open(graph, EngineConfig(**kw)) as plain:
        ref = plain.solve(SolveSpec.tree(src))
    assert ref.trace is None        # tracing is strictly opt-in
    with Solver.open(graph, EngineConfig(trace=True, **kw)) as traced:
        res = traced.solve(SolveSpec.tree(src))
    # bitwise no-op on the solver outputs
    np.testing.assert_array_equal(np.asarray(res.dist), np.asarray(ref.dist))
    np.testing.assert_array_equal(np.asarray(res.parent),
                                  np.asarray(ref.parent))
    for f in ref.metrics._fields:
        assert np.asarray(res.metrics._asdict()[f]) \
            == np.asarray(ref.metrics._asdict()[f]), f
    # counter parity + record shape
    trace = res.trace
    assert isinstance(trace, SolveTrace)
    assert trace.n_records > 1
    assert_counter_parity(trace, res.metrics)
    recs = trace.records()
    assert len(recs) == trace.n_records
    assert set(recs[0]) == set(TRACE_COLUMNS)
    iters = trace.columns["iter"]
    assert (np.diff(iters) == 1).all() and iters[0] == 0
    # the source starts alone on the frontier; every record saw >= 1 live
    # vertex (the loop exits rather than recording an empty iteration)
    assert trace.columns["frontier"][0] == 1
    assert (trace.columns["frontier"] >= 1).all()
    assert trace.summary()["n_records"] == trace.n_records


def test_trace_ring_overflow(graph):
    src = int(np.argmax(graph.deg))
    with Solver.open(graph, EngineConfig(trace=True)) as solver:
        full = solver.solve(SolveSpec.tree(src)).trace
    cap = 4
    assert full.n_records > cap     # the test needs a real overflow
    with Solver.open(graph,
                     EngineConfig(trace=True, trace_capacity=cap)) as solver:
        small = solver.solve(SolveSpec.tree(src)).trace
    assert small.capacity == cap
    assert small.n_records == cap
    assert small.n_recorded == full.n_records
    assert small.dropped == full.n_records - cap
    # the ring keeps the *newest* records, in order
    np.testing.assert_array_equal(small.columns["iter"],
                                  full.columns["iter"][-cap:])
    for c in TRACE_COLUMNS:
        np.testing.assert_array_equal(small.columns[c],
                                      full.columns[c][-cap:])


def test_traced_batch_per_slot(graph):
    srcs = [int(i) for i in np.argsort(-graph.deg)[:3]]
    with Solver.open(graph, EngineConfig(trace=True)) as solver:
        res = solver.solve(SolveSpec.tree(srcs))
    assert isinstance(res.trace, list) and len(res.trace) == len(srcs)
    for slot in range(len(srcs)):
        m = type(res.metrics)(*(np.asarray(v)[slot]
                                for v in res.metrics))
        assert_counter_parity(res.trace[slot], m)


def test_trace_direct_engine_entry(graph):
    # the engine entry point returns the raw device ring for callers that
    # bypass the facade
    g = graph.to_device()
    src = int(np.argmax(graph.deg))
    out = sssp(g, src, config=EngineConfig(trace=True))
    assert len(out) == 4
    trace = materialize_trace(out[3])
    assert_counter_parity(trace, out[2])


def test_trace_config_validation():
    with pytest.raises(ConfigError):
        EngineConfig(trace_capacity=0)
    # the routed serving tier reports aggregate metrics, not solve traces
    with pytest.raises(ConfigError):
        EngineConfig(tier="routed", trace=True).resolve()
    # non-routed tiers accept the knob
    assert EngineConfig(trace=True).resolve().trace_cap == 256
    assert EngineConfig().resolve().trace_cap == 0


@pytest.mark.parametrize("kw,label", BACKENDS, ids=[b[1] for b in BACKENDS])
def test_metrics_dict_export_invariants(graph, kw, label):
    """Satellite: every metrics field exports present + finite, typed."""
    src = int(np.argmax(graph.deg))
    with Solver.open(graph, EngineConfig(**kw)) as solver:
        res = solver.solve(SolveSpec.tree(src))
    d = metrics_dict(res.metrics)
    assert set(d) == set(res.metrics._fields)
    for f in LOGICAL_METRIC_FIELDS:
        assert isinstance(d[f], int), f
    for f in PHYSICAL_METRIC_FIELDS:
        assert isinstance(d[f], float) and math.isfinite(d[f]), f
    assert d["n_rounds"] > 0 and d["n_relax"] > 0
    if kw.get("fused_rounds"):
        assert d["n_invocations"] >= 1
        assert d["n_invocations"] < d["n_rounds"]   # fusion amortizes


# ----------------------------------------------------------------------
# distributed parity (8 forced host devices, subprocess)
# ----------------------------------------------------------------------

_DIST_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import numpy as np, jax
from repro.core.distributed import shard_blocked, shard_graph, sssp_distributed
from repro.core.sssp import LOGICAL_METRIC_FIELDS, PHYSICAL_METRIC_FIELDS
from repro.data.generators import kronecker
from repro.obs import materialize_trace

mesh = jax.make_mesh((8,), ("graph",))
g = kronecker(9, 8, seed=1)
sg = shard_graph(g, 8)
bl = shard_blocked(g, 8, block_v=128, tile_e=128)
src = int(np.argmax(g.deg))
failures = []
from repro.core.config import EngineConfig
for version, backend, fr in [("v1", "segment_min", 0),
                             ("v2", "segment_min", 0),
                             ("v3", "segment_min", 0),
                             ("v2", "blocked", 4)]:
    tag = f"{version}/{backend}/fused{fr}"
    kw = dict(version=version, backend=backend, fused_rounds=fr,
              blocked=bl if backend == "blocked" else None)
    ref = sssp_distributed(sg, src, mesh, ("graph",), **kw)
    out = sssp_distributed(sg, src, mesh, ("graph",),
                           config=EngineConfig(
                               tier="sharded", shard_version=version,
                               shard_backend=backend, fused_rounds=fr,
                               trace=True),
                           blocked=bl if backend == "blocked" else None)
    if len(out) != 4:
        failures.append(f"{tag}: no trace returned"); continue
    d0, p0, m0 = ref[0], ref[1], ref[2]
    d1, p1, m1 = out[0], out[1], out[2]
    if not np.array_equal(np.asarray(d0), np.asarray(d1)):
        failures.append(f"{tag}: dist changed under tracing")
    if not np.array_equal(np.asarray(p0), np.asarray(p1)):
        failures.append(f"{tag}: parent changed under tracing")
    for f in m0._fields:
        if np.asarray(getattr(m0, f)) != np.asarray(getattr(m1, f)):
            failures.append(f"{tag}: metric {f} changed under tracing")
    tr = materialize_trace(out[3])
    if tr.dropped:
        failures.append(f"{tag}: unexpected ring overflow")
    sums = tr.counter_sums()
    for f in LOGICAL_METRIC_FIELDS:
        init = 1 if f == "n_extended" else 0
        if init + sums[f] != int(getattr(m1, f)):
            failures.append(
                f"{tag}: {f} parity {init + sums[f]} != "
                f"{int(getattr(m1, f))}")
    for f in PHYSICAL_METRIC_FIELDS:
        if sums[f] != float(getattr(m1, f)):
            failures.append(f"{tag}: {f} physical parity broke")
    print(f"OK {tag}: {tr.n_records} records")
if failures:
    print("FAILURES:\n" + "\n".join(failures)); sys.exit(1)
print("ALL_OK")
"""


@pytest.mark.multidevice
@pytest.mark.slow
def test_distributed_trace_parity_8dev():
    src_dir = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _DIST_SCRIPT, src_dir],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "ALL_OK" in proc.stdout
