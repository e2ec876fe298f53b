"""A run whose timed path is broken underneath comes out not correct.

Each test drives the whole of ``run_cell`` (CPU, tiny size) with one
fault planted in the program where it produces its answer:

* a solve that returns its state unchanged (the initial distances);
* an answer altered by one unit in the last place where it is made;
* half of each served batch left out (those answers never come).

The exchange between chips does not exist in these one-chip cells.
The served faults run on the held-back serving cell (``held_back.py``).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from held_back import MAN
TREE_CELLS = [w["name"] for w in MAN["workloads"]
              if w["traffic"] == "tree-trials"]
OPEN_CELLS = [w["name"] for w in MAN["workloads"]
              if w["traffic"] == "local-open"]


def _run(cell):
    rc, result = run.run_cell(cell, 5, 1.0, False, rehearse=True,
                              grace_s=2.0, man=MAN)
    assert rc == 0
    return result


@pytest.mark.parametrize("cell", TREE_CELLS)
def test_unchanged_state_is_caught(cell, monkeypatch):
    import repro.api as api
    real = api.sssp

    def unchanged(g, source, **kw):
        _, _, metrics = real(g, source, **kw)[:3]
        n = g.n
        dist = jnp.full((n,), jnp.inf, jnp.float32).at[source].set(0.0)
        parent = jnp.full((n,), -1, jnp.int32).at[source].set(source)
        return dist, parent, metrics

    monkeypatch.setattr(api, "sssp", unchanged)
    result = _run(cell)
    assert result["correct"] is False
    assert result["checks"]["dist_mismatch"]["value"] > 0


@pytest.mark.parametrize("cell", TREE_CELLS)
def test_altered_tree_answer_is_caught(cell, monkeypatch):
    import repro.api as api
    real = api.sssp

    def altered(g, source, **kw):
        dist, parent, metrics = real(g, source, **kw)[:3]
        v = jnp.argmax(jnp.where(jnp.isfinite(dist), dist, -1.0))
        return dist.at[v].set(jnp.nextafter(dist[v], jnp.inf)), parent, \
            metrics

    monkeypatch.setattr(api, "sssp", altered)
    result = _run(cell)
    assert result["correct"] is False
    assert result["checks"]["dist_mismatch"]["value"] > 0


@pytest.mark.parametrize("cell", OPEN_CELLS)
def test_altered_served_answer_is_caught(cell, monkeypatch):
    import repro.serve.scheduler as sched
    real = sched.finalize

    def altered(q, deg, dist, parent, raw):
        res = real(q, deg, dist, parent, raw)
        d = res.dist.copy()
        v = int(np.argmax(np.where(np.isfinite(d), d, -1.0)))
        d[v] = np.nextafter(d[v], np.float32(np.inf))
        res.dist = d
        return res

    monkeypatch.setattr(sched, "finalize", altered)
    result = _run(cell)
    assert result["correct"] is False
    assert result["checks"]["answers_wrong"]["value"] > 0


@pytest.mark.parametrize("cell", OPEN_CELLS)
def test_half_of_each_batch_left_out_is_caught(cell, monkeypatch):
    import repro.serve.scheduler as sched
    real = sched.QueryScheduler._finalize

    def half(self, inflight):
        keep = inflight.batch[:len(inflight.batch) // 2]
        real(self, dataclasses.replace(inflight, batch=keep))

    monkeypatch.setattr(sched.QueryScheduler, "_finalize", half)
    result = _run(cell)
    assert result["correct"] is False
    assert result["checks"]["answers_missing"]["value"] > 0
