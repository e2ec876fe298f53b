"""Every cell of ``BENCHMARK.json`` runs end to end through ``bench/run.py``
on the CPU at its generator's tiny size (``--rehearse``) and prints one
contract line with ``correct`` true.  Without ``--rehearse`` a CPU-only
machine gets no result, and a rehearsal that asks for device metrics
(``--trace 1``) fails rather than reporting them.  The held-back
serving cell (``held_back.py``) rehearses in process through the same
``run_cell``; ``bench/phase_run.py`` rehearses its traced window.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import held_back
from bench import cell as cell_mod
from bench import manifest, run

MAN = manifest.load()
CELLS = [w["name"] for w in MAN["workloads"]]
RUN = [sys.executable, str(manifest.HERE / "run.py")]


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(RUN + list(args), cwd=manifest.ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


def _json_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_with_one_correct_contract_line(cell):
    p = _run("--workload", cell, "--seed", "4294967311", "--seconds", "1",
             "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert len(_json_lines(p.stdout)) == 1
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "checks"
    for name, c in line["checks"].items():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    want = {m["name"]: m["unit"]
            for m in manifest.cell_metrics(MAN, cell, "end_to_end")}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert line["extra"]["compiles_in_window"] == 0
    tail = p.stderr.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and "limit" in t for t in tail)


def test_rehearsal_refuses_device_metrics():
    p = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
             "--trace", "1", "--rehearse")
    assert p.returncode != 0
    assert not _json_lines(p.stdout)


def test_no_accelerator_gives_no_result():
    p = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert not _json_lines(p.stdout)


def test_window_fetches_every_counter_in_one_transfer_per_tree(monkeypatch):
    """Each tree's record holds every ``SsspMetrics`` field as a plain
    number, from one ``jax.device_get`` with its answer; ``n_rounds``
    is the solve's own."""
    import jax
    from repro.api import SolveSpec
    from repro.core.sssp import SsspMetrics
    inp = cell_mod.build(MAN, CELLS[-1], 7, 1.0, rehearse=True)
    solver = run.open_solver(inp, jax.devices()[:1])
    real, calls = jax.device_get, []

    def counted(x):
        calls.append(x)
        return real(x)

    try:
        monkeypatch.setattr(jax, "device_get", counted)
        out = run.closed_window(solver, inp.requests[:3], 1e9)
        monkeypatch.undo()
        direct = solver.solve(SolveSpec.tree(inp.requests[0].source))
    finally:
        solver.close()
    assert len(out["trees"]) == len(calls) == 3 and out["failed"] == 0
    for t in out["trees"]:
        assert set(t) == {"source", "seconds", *SsspMetrics._fields}
        assert all(type(t[f]) in (int, float) for f in SsspMetrics._fields)
    assert out["trees"][0]["n_rounds"] == int(np.asarray(
        direct.metrics.n_rounds)) > 0
    assert out["trees"][0]["n_compact_rounds"] == float(np.asarray(
        direct.metrics.n_compact_rounds))


def test_phase_run_rehearses_and_keeps_its_keys():
    p = subprocess.run(
        [sys.executable, str(manifest.HERE / "phase_run.py"), "--workload",
         CELLS[-1], "--seed", "4294967311", "--seconds", "1", "--rehearse"],
        cwd=manifest.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) == {
        "cell", "seed", "device", "phases_s", "leaf_s", "device_ops_s",
        "busy_s", "window_s", "trees", "failed", "n_rounds", "n_steps",
        "phase_table_s", "table_size", "round_ms.tree",
        "transition_ms.tree", "teps", "ms_per_round.tree"}
    assert line["failed"] == 0 and line["trees"] >= 1
    assert line["round_ms.tree"] > 0 and line["transition_ms.tree"] > 0
    assert sum(s for s, _ in line["phases_s"].values()) == \
        pytest.approx(line["leaf_s"])


@pytest.mark.parametrize("cell", held_back.CELLS)
def test_held_back_cell_rehearses_correct(cell):
    rc, line = run.run_cell(cell, 4294967311, 1.0, False, rehearse=True,
                            grace_s=30.0, man=held_back.MAN)
    assert rc == 0 and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert list(line)[-1] == "checks"
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]
    want = {m["name"]: m["unit"]
            for m in manifest.cell_metrics(held_back.MAN, cell, "end_to_end")}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["extra"]["compiles_in_window"] == 0


def test_sweep_rehearses_a_held_back_mix(capsys):
    from bench import sweep
    cell = held_back.HELD["workloads"][0]
    assert sweep.main(["--config", cell["config"], "--traffic",
                       cell["traffic"], "--seed", "9", "--seconds", "3",
                       "--rates", "1,2", "--rehearse"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["rate_qps"] for x in lines] == [1.0, 2.0]
    assert all(x["failed"] == 0 and x["requests"] == round(3 * x["rate_qps"])
               for x in lines)
