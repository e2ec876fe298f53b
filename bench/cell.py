"""A cell's inputs, made from ``--seed``: the graph and the requests.

Three independent streams come from the seed: the graph, the window's
requests and the set-up's warm requests, so that the same seed gives
the same inputs in every run and tool (``run.py``, ``control.py``,
``sweep.py``).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from bench import loadgen, manifest

GRAPH, TRAFFIC, WARM = 0, 1, 2
CLOSED_LOOP_REQUESTS = 256     # more trees than any window finishes


def is_directed(cfg: dict) -> bool:
    """Whether a configuration's graph is directed (``"undirected":
    false``; undirected by default): ``(u[i], v[i])`` is then an arc
    ``u -> v``, never mirrored."""
    return not cfg.get("undirected", True)


def degree(n: int, u, v, directed: bool) -> np.ndarray:
    """Per-vertex degree; the out-degree where the graph is directed."""
    deg = np.bincount(u, minlength=n)
    return deg if directed else deg + np.bincount(v, minlength=n)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % 2 ** 64, stream]))


@dataclasses.dataclass
class Inputs:
    cell: str
    chips: int
    config: dict
    mix: dict
    n: int
    u: np.ndarray
    v: np.ndarray
    w: np.ndarray
    requests: List[loadgen.Request]
    warm: List[loadgen.Request]

    @property
    def directed(self) -> bool:
        return is_directed(self.config)

    @property
    def edges(self) -> int:
        """Input edges, each counted once, as Graph500 and GAP count them:
        the undirected edges, or the arcs of a directed configuration."""
        return int(self.u.shape[0])


def build(man: dict, name: str, seed: int, seconds: float,
          rehearse: bool = False, rate_qps: Optional[float] = None
          ) -> Inputs:
    """The inputs of cell ``name``; ``rehearse`` cuts the graph to the
    generator's tiny size; ``rate_qps`` overrides an open loop's rate
    (the knee sweep)."""
    wl = manifest.workload(man, name)
    cfg = manifest.config(man, wl["config"])
    gen = manifest.load_module(manifest.generator_path(cfg["generator"]))
    if rehearse:
        cfg = gen.tiny(cfg)
    mix = loadgen.load(manifest.traffic_path(wl["traffic"]))
    if rate_qps is not None:
        mix = dict(mix, rate_qps=rate_qps)
    directed = is_directed(cfg)
    n, u, v, w = gen.generate(cfg, rng(seed, GRAPH))
    graph = {"n": n, "deg": degree(n, u, v, directed),
             "max_w": float(np.max(w.astype(np.float32)))}

    def trial(r, count):
        return gen.trial_sources(n, u, v, r, count, directed=directed)

    if mix["loop"] == "open":
        count = loadgen.open_count(mix, seconds)
        warm_count = len(mix["mix"]) * int(mix.get("max_batch", 1))
    else:
        count, warm_count = CLOSED_LOOP_REQUESTS, 0
    reqs = loadgen.requests(mix, graph, rng(seed, TRAFFIC), count,
                            trial_sources=trial, seconds=seconds)
    warm = loadgen.requests(mix, graph, rng(seed, WARM), warm_count,
                            trial_sources=trial, seconds=seconds) \
        if warm_count else []
    return Inputs(cell=name, chips=int(wl["chips"]), config=cfg, mix=mix,
                  n=n, u=u, v=v, w=w, requests=reqs, warm=warm)
