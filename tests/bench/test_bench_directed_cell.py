"""The directed cell ``kron17d-tree`` and the pull phase's two readers.

The program now solves directed graphs, so the harness's directed
fixture (``data/kron-directed.json``, GAP kron at scale 8 kept as arcs)
comes out correct through ``run.run_cell``, the code behind ``run.py
--rehearse``.  ``gap-kron17-directed`` draws the very edge list of
``gap-kron17``: only the arcs' reading differs.  ``pull_ms.tree`` reads
the device time per pull from the pull's sub-phases, and
``pull_requests_per_tree`` the solver's ``n_pull_relax``; on a program
without either they report nothing.
"""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import cell, manifest, run, trace_phases
from repro.obs.profiling import PhaseTable

BENCH = manifest.load()
DATA = Path(__file__).with_name("data")
FIXTURE, CELL = "kron-directed", "kron-directed-tree"
MAN = dict(
    BENCH,
    configs=BENCH["configs"] + [{
        "name": FIXTURE, "source": "https://arxiv.org/abs/1508.03619",
        "file": "tests/bench/data/kron-directed.json", "reduced": ["scale"],
        "why": "test fixture: GAP kron's edge list kept as arcs"}],
    workloads=BENCH["workloads"] + [{
        "name": CELL, "config": FIXTURE, "traffic": "tree-trials",
        "chips": 1, "why": "test fixture: trees over out-arcs"}])


def _reader(name):
    return manifest.load_module(manifest.metric_path(name)).read


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 7])
def test_the_directed_fixture_comes_out_correct(seed):
    rc, line = run.run_cell(CELL, seed, 1.0, trace=False, rehearse=True,
                            man=MAN)
    assert rc == 0 and line["correct"] is True
    assert {k: c["value"] for k, c in line["checks"].items()} == {
        "dist_mismatch": 0, "parent_bad": 0, "answers_missing": 0}
    assert line["attempted"] >= 1 and line["failed"] == 0


def test_the_directed_configuration_keeps_kron17s_edge_list():
    cfg = manifest.config(BENCH, "gap-kron17-directed")
    base = manifest.config(BENCH, "gap-kron17")
    differ = {k for k in set(cfg) | set(base) if cfg.get(k) != base.get(k)}
    assert differ == {"name", "source", "source_detail", "deployment",
                      "undirected", "guarantees", "reduced", "assumed"}
    assert cfg["undirected"] is False
    for tiny in (True, False):
        a = cell.build(BENCH, "kron17d-tree", 4294967311, 51.0,
                       rehearse=tiny)
        b = cell.build(BENCH, "kron17-tree", 4294967311, 51.0,
                       rehearse=tiny)
        assert a.directed and not b.directed
        for x, y in ((a.u, b.u), (a.v, b.v), (a.w, b.w)):
            np.testing.assert_array_equal(x, y)
        assert a.edges == b.edges == 16 << (8 if tiny else 17)


def test_pull_ms_reads_the_pull_and_its_sub_phases():
    """Per pull that ran: ``transition.pull`` itself also runs, as the
    branch that skips the pull, in the transitions without one."""
    read = _reader("pull_ms.tree")
    phases = {"transition.pull": [0.001, 9],
              "transition.pull.scan": [0.004, 4],
              "transition.pull.reduce": [0.002, 3],
              "transition.pull.apply": [0.001, 4],
              "transition.pending": [0.5, 13], "sssp.round": [0.7, 30]}
    rec = {"trace": {"busy_s": 1.0}, "phases": phases}
    assert read(rec) == pytest.approx(1e3 * 0.008 / 4)
    # the sub-phases are transition.* too: the transition keeps them
    got = trace_phases.group(phases, "sssp.transition")
    assert got == (pytest.approx(0.508), 13)
    assert read(dict(rec, phases={"sssp.round": [0.7, 30]})) is None
    assert read(dict(rec, trace=None)) is None


def test_pull_ms_reads_nothing_of_a_program_without_sub_phases():
    """A trace of the program before the pull's sub-phases had names
    holds ``transition.pull`` alone, which counts no pulls."""
    doc = json.loads((DATA / "cpu_phases.json").read_text())
    table = PhaseTable(doc["table"], module=doc["module"],
                       looped=doc["looped"])
    phased = trace_phases.reduce_phases(
        DATA / "cpu_phases.xplane.pb", table, device_plane="/host:CPU",
        op_line="tf_XLA", op_stat="hlo_op")["phases"]
    assert phased["transition.pull"][0] > 0
    assert not any(p.startswith("transition.pull.") for p in phased)
    assert _reader("pull_ms.tree")({"trace": {}, "phases": phased}) is None


def test_pull_requests_per_tree_is_the_mean_counter():
    read = _reader("pull_requests_per_tree")
    trees = [{"n_pull_relax": 10, "n_relax": 50},
             {"n_pull_relax": 4, "n_relax": 20}]
    assert read({"trees": trees}) == 7.0
    assert read({"trees": []}) is None
    # a program without the counter
    assert read({"trees": [{"n_relax": 50}]}) is None
