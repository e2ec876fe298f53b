"""Device time by the solve's named phases, on a trace recorded on the CPU.

``data/cpu_phases.xplane.pb`` and ``data/cpu_phases.json`` (see
``data/record_cpu_phases.py``) hold one untraced tree solve in a
``bench:window`` span, the solve's phase table, and the on-device
ring's counts for the same solve run with ``trace=True``.  On the CPU
XLA's ops run on host threads, one line each, beside the thread pool's
own events, so the reduction reads the events that carry ``hlo_op``.
"""
import json
import shutil
from pathlib import Path

import pytest

from bench import manifest, run, trace_phases
from bench.trace_reduce import _leaves, _op_name
from repro.obs.profiling import PHASES, PhaseTable

DATA = Path(__file__).with_name("data")
TRACE = DATA / "cpu_phases.xplane.pb"
CPU = dict(device_plane="/host:CPU", op_line="tf_XLA", op_stat="hlo_op")
READERS = ("round_ms.tree", "transition_ms.tree")


@pytest.fixture(scope="module")
def doc():
    return json.loads((DATA / "cpu_phases.json").read_text())


@pytest.fixture(scope="module")
def table(doc):
    return PhaseTable(doc["table"], module=doc["module"],
                      looped=doc["looped"])


@pytest.fixture(scope="module")
def reduced(table):
    return trace_phases.reduce_phases(TRACE, table, **CPU)


def _window_leaf_s():
    """The window's leaf op time, recomputed from the raw events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(TRACE))
    lo = hi = None
    lines = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "bench:window":
                    lo, hi = int(ev.start_ns), int(ev.start_ns +
                                                    ev.duration_ns)
            if line.name.startswith("tf_XLA"):
                lines.append([(int(e.start_ns),
                               int(e.start_ns + e.duration_ns), e.name)
                              for e in line.events
                              if "hlo_op" in trace_phases._stats(e)])
    return sum(min(b, hi) - max(a, lo) for ops in lines
               for a, b, _ in _leaves(ops) if b > lo and a < hi) / 1e9


def test_phase_seconds_sum_to_the_window_leaf_time(reduced):
    secs = [s for s, _ in reduced["phases"].values()]
    assert sum(secs) == pytest.approx(reduced["leaf_s"], rel=1e-9)
    assert reduced["leaf_s"] == pytest.approx(_window_leaf_s(), rel=1e-9)
    assert all(s > 0 for s in secs)


def test_every_phase_of_the_solve_shows(reduced):
    got = set(reduced["phases"])
    assert got <= set(PHASES) | {trace_phases.UNPHASED}
    assert {"sssp.round", "round.gather", "round.reduce", "round.apply",
            "sssp.transition", "transition.pending",
            "transition.window"} <= got
    # loop control and copies only
    unphased = reduced["phases"][trace_phases.UNPHASED][0]
    assert unphased < 0.25 * reduced["leaf_s"]


def test_round_runs_are_the_ring_records(reduced, doc):
    """One relaxation round per loop iteration: the ring holds one
    record per iteration of the same solve."""
    assert doc["ring"]["dropped"] == 0
    _, runs = trace_phases.group(reduced["phases"], "sssp.round")
    assert runs == doc["ring"]["n_recorded"]


def test_transition_runs_are_the_stepped_records(reduced, doc):
    """The transition runs in exactly the iterations the ring marks
    ``stepped``; the binary search inside it loops, and is not counted."""
    _, runs = trace_phases.group(reduced["phases"], "sssp.transition")
    assert runs == doc["ring"]["stepped"] > 0
    assert reduced["phases"]["transition.window"][1] == runs


def test_another_modules_op_of_the_same_name_is_unphased(doc):
    """An op of another program (the fetch's converts) is never charged
    to a phase, whatever its instruction's name."""
    other = PhaseTable(doc["table"], module="jit_something_else",
                       looped=doc["looped"])
    red = trace_phases.reduce_phases(TRACE, other, **CPU)
    assert set(red["phases"]) == {trace_phases.UNPHASED}


def test_group_adds_the_sub_phases():
    phases = {"sssp.round": [1.0, 4], "round.gather": [2.0, 4],
              "round.reduce": [3.0, 3], "transition.pull": [5.0, 2],
              trace_phases.UNPHASED: [7.0, 9]}
    assert trace_phases.group(phases, "sssp.round") == (6.0, 4)
    assert trace_phases.group(phases, "sssp.transition") == (5.0, 2)
    assert trace_phases.group(phases, "sssp.bootstrap") is None


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("missing", ["trace", "phases"])
def test_readers_need_a_trace_and_a_table(name, missing):
    rec = {"trace": {"busy_s": 1.0}, "phases": {"sssp.round": [1.0, 2],
                                                "sssp.transition": [1.0, 2]}}
    rec[missing] = None
    mod = manifest.load_module(manifest.metric_path(name))
    assert mod.read(rec) is None
    del rec[missing]
    assert mod.read(rec) is None


def test_readers_read_time_per_run(reduced):
    rec = {"trace": {"busy_s": 1.0}, "phases": reduced["phases"]}
    for name, outer in zip(READERS, ("sssp.round", "sssp.transition")):
        secs, runs = trace_phases.group(reduced["phases"], outer)
        mod = manifest.load_module(manifest.metric_path(name))
        assert mod.read(rec) == pytest.approx(1e3 * secs / runs)


def test_a_traced_run_reads_the_phases_that_feed_the_readers(
        table, reduced, tmp_path):
    """``run.read_trace``, as a ``--trace 1`` run calls it after its
    window: its phases, which become ``rec["phases"]``, give both
    readers their values; the trace is removed."""
    shutil.copy(TRACE, tmp_path / TRACE.name)
    trace, phased = run.read_trace(tmp_path, table, **CPU)
    assert not tmp_path.exists()
    assert phased == reduced and trace["busy_s"] > 0
    rec = {"trace": trace, "phases": phased["phases"]}
    for name, outer in zip(READERS, ("sssp.round", "sssp.transition")):
        secs, runs = trace_phases.group(phased["phases"], outer)
        got = manifest.load_module(manifest.metric_path(name)).read(rec)
        assert got == pytest.approx(1e3 * secs / runs) and got > 0


def test_op_names_match_the_table(reduced, table):
    """The trace's op names are the table's instruction names: every
    phased second went through a lookup."""
    assert _op_name("%fusion.4 = f32[] fusion(...)") == "fusion.4"
    phased = sum(s for p, (s, _) in reduced["phases"].items()
                 if p != trace_phases.UNPHASED)
    assert phased > 0.5 * reduced["leaf_s"]
    assert set(table.values()) <= set(PHASES)


def test_a_tpu_op_belongs_to_the_program_run_that_holds_it():
    """A TPU op event names only its instruction; the plane's
    ``XLA Modules`` line says which program was running."""
    from types import SimpleNamespace as NS

    def ev(name, start, dur):
        return NS(name=name, start_ns=start, duration_ns=dur, stats=[])

    plane = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit__sssp_jit(123)", 0, 100),
                                       ev("jit_convert(9)", 200, 10)]),
        NS(name="XLA Ops", events=[ev("%fusion.4 = f32[] fusion()", 10, 5),
                                   ev("%fusion.4 = f32[] fusion()", 202, 3),
                                   ev("%copy.1 = f32[] copy()", 150, 2)]),
        NS(name="Async XLA Ops", events=[ev("%copy-start = f32[]", 20, 5)])])
    (ops,) = trace_phases._op_lines(plane, "XLA Ops", None, "XLA Modules")
    assert [(a, mod) for a, _, (_, mod) in ops] == [
        (10, "jit__sssp_jit"), (202, "jit_convert"), (150, None)]
