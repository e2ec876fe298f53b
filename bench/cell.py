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
    def edges(self) -> int:
        """Input (undirected) edges, each counted once, as Graph500 does."""
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
    n, u, v, w = gen.generate(cfg, rng(seed, GRAPH))
    graph = {"n": n,
             "deg": np.bincount(u, minlength=n) + np.bincount(v, minlength=n),
             "max_w": float(np.max(w.astype(np.float32)))}

    def trial(r, count):
        return gen.trial_sources(n, u, v, r, count)

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
