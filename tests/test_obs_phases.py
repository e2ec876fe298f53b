"""Named phases of the jitted solve, the op -> phase table, and the
serving scheduler's host spans and queue-wait counter.

* every fusion the solve loop runs carries a phase of
  :data:`repro.obs.profiling.PHASES`, or is the loop's own control,
  the compacted round's search loop and dense fallback included;
* the phases are metadata: with them stubbed out the compiled program
  is the same, instruction for instruction;
* a profiler capture of a solve, joined through ``Solver.phase_table``,
  counts one ``sssp.round`` per on-device ring record and one
  ``sssp.transition`` per record that stepped.
"""
import contextlib
import glob
import re

import jax
import numpy as np
import pytest

from repro.api import EngineConfig, SolveSpec, Solver
from repro.core import sssp as sssp_mod
from repro.data.generators import kronecker
from repro.obs import profiling
from repro.serve.queries import Query
from repro.serve.registry import GraphRegistry
from repro.serve.scheduler import QueryScheduler

SPECS = {
    "tree": (SolveSpec.tree(0), {}),
    "tree_batch2": (SolveSpec.tree([0, 1]), {}),
    "tree_adaptive": (SolveSpec.tree(0), {"policy": "adaptive"}),
}


@pytest.fixture(scope="module")
def graph():
    return kronecker(8, 4, seed=0)


def _text(graph, spec, **cfg):
    with Solver.open(graph, EngineConfig(**cfg)) as solver:
        srcs = list(spec.sources) if spec.batched else spec.sources
        return sssp_mod.compiled_text(
            solver._dg, srcs, batched=spec.batched, config=solver.resolved,
            layout=solver._layout, **solver._goal_args(spec))


def _computations(text):
    comps, lines, comp = {}, {}, None
    for line in text.splitlines():
        m = profiling._COMPUTATION.match(line)
        if m and " = " not in line:
            comp = m.group(1)
            comps[comp] = []
            continue
        m = profiling._INSTRUCTION.match(line)
        if m and comp is not None:
            comps[comp].append(m.group(1))
            lines[m.group(1)] = line
    return comps, lines


def _loop_computations(text):
    """The solve loop's body and condition, with every computation they
    run through control flow (fusions' own computations left out)."""
    comps, lines = _computations(text)
    entry = re.search(r"^ENTRY %?([\w.\-]+)", text, re.M).group(1)
    (loop,) = [n for n in comps[entry] if " while(" in lines[n]]
    todo = re.findall(r"(?:body|condition)=%?([\w.\-]+)", lines[loop])
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for n in comps[c]:
            if " fusion(" in lines[n]:
                continue
            todo += [r for r in re.findall(r"%([\w.\-]+)",
                                           lines[n].split(" = ", 1)[1])
                     if r in comps]
    return {n: lines[n] for c in seen for n in comps[c]}


@pytest.mark.parametrize("kind", sorted(SPECS))
def test_every_loop_fusion_has_a_phase_or_is_loop_control(graph, kind):
    spec, cfg = SPECS[kind]
    text = _text(graph, spec, **cfg)
    table = profiling.phase_table(text)
    assert table.module == "jit__sssp_batch_jit" if spec.batched \
        else table.module == "jit__sssp_jit"
    control, phased = [], []
    for name, line in _loop_computations(text).items():
        if " fusion(" not in line:
            continue
        if name in table:
            assert table[name] in profiling.PHASES
            phased.append(name)
            continue
        # outside every phase by its source: the loop's condition, the
        # frontier test that picks the transition, the iteration count,
        # a batch's per-slot masks
        op = re.search(r'op_name="([^"]*)"', line)
        assert op and re.search(r"/while(/(body|cond)/[^/]+)?$",
                                op.group(1)), (name, line[:200])
        control.append(name)
    assert len(control) < len(phased) / 4
    assert {"sssp.round", "round.gather", "round.reduce", "round.apply",
            "round.count", "sssp.bootstrap", "sssp.transition",
            "transition.pending", "transition.window",
            "transition.pull"} <= set(table.values())
    # an unbatched solve compacts its rounds; a batch keeps the dense one
    assert ("round.compact" in table.values()) == (not spec.batched)


def test_phases_add_metadata_only(graph, monkeypatch):
    """With every phase a no-op the compiled solve is the same program:
    the same instructions under the same names."""
    def strip(text):
        return [re.sub(r", metadata=\{[^}]*\}", "", line)
                for line in text.splitlines()
                if profiling._INSTRUCTION.match(line)]

    spec, cfg = SPECS["tree"]
    jax.clear_caches()
    phased = _text(graph, spec, **cfg)
    monkeypatch.setattr(profiling, "phase",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    plain = _text(graph, spec, **cfg)
    monkeypatch.undo()
    jax.clear_caches()
    assert "sssp.round" in phased and "sssp.round" not in plain
    assert strip(phased) == strip(plain)


def test_phase_names_are_dotted_and_known():
    assert len(set(profiling.PHASES)) == len(profiling.PHASES)
    for name in profiling.PHASES:
        outer, _, leaf = name.partition(".")
        assert outer and leaf and "/" not in name
        if outer != "sssp":             # a sub-phase names its outer one
            assert f"sssp.{outer}" in profiling.PHASES
    with pytest.raises(ValueError, match="unknown phase"):
        profiling.phase("gather")


HLO = """HloModule jit_demo, entry_computation_layout={()->f32[]}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/while/body/sssp.round/round.apply/add"}
}

%inner_body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %copy.1 = f32[8]{0} copy(%p)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%p, %copy.1)
}

%inner_cond (q: (s32[], f32[8])) -> pred[] {
  %q = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%q, %q), direction=LT
}

%body (b: f32[8]) -> f32[8] {
  %b = f32[8]{0} parameter(0)
  %gather.1 = f32[8]{0} gather(%b), metadata={op_name="jit(f)/while/body/sssp.round/round.gather/gather"}
  %reduce-window.1 = f32[8]{0} reduce-window(%gather.1)
  %max.1 = f32[8]{0} maximum(%reduce-window.1, %gather.1), metadata={op_name="jit(f)/while/body/sssp.round/round.gather/max"}
  %fusion.1 = f32[8]{0} fusion(%max.1), kind=kLoop, calls=%fused_computation
  %while.2 = (s32[], f32[8]{0}) while(%fusion.1), condition=%inner_cond, body=%inner_body, metadata={op_name="jit(f)/while/body/sssp.transition/transition.window/while"}
  %neg.1 = f32[8]{0} negate(%b), metadata={op_name="jit(f)/while/body/neg"}
  ROOT %sub.1 = f32[8]{0} subtract(%fusion.1, %neg.1), metadata={op_name="jit(f)/while/body/sssp.round/sub"}
}
"""


def test_phase_table_reads_scopes_and_fills_compiler_made_ops():
    t = profiling.phase_table(HLO)
    assert t.module == "jit_demo"
    assert t["gather.1"] == "round.gather"       # innermost scope
    assert t["sub.1"] == "sssp.round"
    assert t["fusion.1"] == "round.apply"        # from its fused ops
    assert t["copy.1"] == "transition.window"    # from the loop that runs it
    assert t["reduce-window.1"] == "round.gather"  # operands and users
    assert "neg.1" not in t                      # placed outside every phase
    assert t.looped >= {"copy.1", "tuple.1", "lt.1"}
    assert not t.looped & {"gather.1", "fusion.1", "while.2"}


def test_solver_phase_table_matches_the_solve_it_runs(graph):
    with Solver.open(graph, EngineConfig()) as solver:
        table = solver.phase_table(SolveSpec.tree(3))
        assert table == profiling.phase_table(
            _text(graph, SolveSpec.tree(3)))
    with pytest.raises(RuntimeError, match="closed"):
        solver.phase_table(SolveSpec.tree(3))


def test_capture_counts_rounds_and_transitions_as_the_ring(graph, tmp_path):
    """A real profiler capture of an untraced solve, joined to its
    phases, counts what the on-device ring records for the same solve."""
    from bench.trace_phases import group, reduce_phases
    src = int(np.argmax(np.asarray(graph.deg)))
    spec = SolveSpec.tree(src)
    with Solver.open(graph, EngineConfig(trace=True)) as solver:
        ring = solver.solve(spec).trace
    with Solver.open(graph, EngineConfig()) as solver:
        solver.solve(spec).block_until_ready()
        jax.profiler.start_trace(str(tmp_path))
        with profiling.annotate("bench:window"):
            jax.block_until_ready(solver.solve(spec).dist)
        jax.profiler.stop_trace()
        table = solver.phase_table(spec)
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    phases = reduce_phases(path, table, device_plane="/host:CPU",
                           op_line="tf_XLA", op_stat="hlo_op")["phases"]
    assert ring.dropped == 0
    assert group(phases, "sssp.round")[1] == ring.n_records
    assert group(phases, "sssp.transition")[1] == \
        int(np.sum(ring.columns["stepped"]))


class FakeClock:
    def __init__(self, start: float = 100.0):
        self.now = start

    def __call__(self) -> float:
        return self.now


def test_scheduler_queue_wait_is_submit_to_dispatch(graph):
    reg = GraphRegistry(capacity=2)
    reg.register("g", graph)
    clock = FakeClock()
    sch = QueryScheduler(reg, max_batch=4, ecc_batching=False, clock=clock)
    submitted = []
    for i in range(6):
        sch.submit(Query(gid="g", source=i))
        submitted.append(clock.now)
        clock.now += 0.25
    clock.now = 110.0
    assert sch.step()            # four queries, dispatched at 110
    clock.now = 113.5
    assert sch.step()            # the other two, at 113.5
    waits = [110.0 - t for t in submitted[:4]] + \
        [113.5 - t for t in submitted[4:]]
    snap = sch.metrics.snapshot()
    key = 'sssp_scheduler_queue_wait_seconds_total{scheduler="default"}'
    assert snap[key]["value"] == pytest.approx(sum(waits))
    assert sch.n_done == len(waits)


def test_scheduler_names_dispatch_and_finalize(graph, monkeypatch):
    import repro.serve.scheduler as sched
    opened = []

    def record(name):
        opened.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(sched.profiling, "annotate", record)
    reg = GraphRegistry(capacity=2)
    reg.register("g", graph)
    sch = QueryScheduler(reg, max_batch=2, ecc_batching=False)
    fut = sch.submit(Query(gid="g", source=0))
    assert sch.step() and fut.result(timeout=60).dist is not None
    # the engine's own dispatch span opens inside the scheduler's
    assert opened[0] == "repro:sched_dispatch"
    assert "repro:sssp_batch_dispatch" in opened
    assert opened[-1] == "repro:sched_finalize"
