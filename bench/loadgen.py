"""The one traffic generator: reads a mix file of ``bench/traffic/``.

A mix file is JSON with these keys:

* ``loop`` — ``"closed"`` (one request in flight, the next sent when
  the last returns) or ``"open"`` (requests sent when they are due,
  whatever is outstanding);
* ``tier`` — the solver tier the requests go through (``"single"`` or
  ``"routed"``); ``max_batch`` for the routed tier's scheduler;
* ``mix`` — ``[[kind, share], ...]`` over ``tree``, ``knear`` and
  ``bounded``;
* ``sources`` — ``"trial"`` (the configuration's generator picks tree
  roots, the GAP trial protocol) or ``"zipf_degree"`` (Zipf by degree
  rank with exponent ``zipf_a``, hubs hottest);
* ``k_range`` — k-nearest sizes, log-uniform; ``bound_w_scale`` —
  bounded radii, uniform, in units of the graph's largest weight;
* ``rate_qps`` — the open loop's mean arrival rate.

The distributions are those of the program's ``data/traffic.py``
``make_traffic``.  To keep the work of a run the same from seed to seed,
open-loop kinds, sizes and gaps are stratified: each run draws the
same multiset (the quantiles at ``(i + 0.5) / count``) and the seed
shuffles it, so only the order and the sources change.  The gaps are
exponential quantiles scaled so that exactly ``open_count`` requests
fill the window: not a Poisson process, which would also draw the
count and its bursts from the seed.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List, Optional

import numpy as np

KINDS = ("tree", "knear", "bounded")


@dataclasses.dataclass(frozen=True)
class Request:
    kind: str
    source: int
    param: Optional[float] = None     # k for knear, radius for bounded
    due_s: Optional[float] = None     # open loop: offset from window start


def load(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    for key in ("loop", "tier", "mix", "sources"):
        if key not in mix:
            raise ValueError(f"{path}: traffic mix lacks {key!r}")
    if mix["loop"] not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    for kind, _ in mix["mix"]:
        if kind not in KINDS:
            raise ValueError(f"{path}: unknown request kind {kind!r}")
    return mix


def zipf_probs(n_ranks: int, a: float) -> np.ndarray:
    """Normalized ``P(r) ~ 1/(r+1)^a`` over ranks ``[0, n_ranks)``."""
    p = 1.0 / np.arange(1, n_ranks + 1, dtype=np.float64) ** a
    return p / p.sum()


def _zipf_degree_sources(deg, rng, count: int, a: float) -> np.ndarray:
    order = np.argsort(-deg, kind="stable")
    ranked = order[deg[order] > 0]                 # hubs first, no isolates
    return ranked[rng.choice(ranked.size, size=count,
                             p=zipf_probs(ranked.size, a))]


def _strata(count: int) -> np.ndarray:
    return (np.arange(count) + 0.5) / count


def requests(mix: dict, graph: dict, rng: np.random.Generator, count: int,
             trial_sources=None, seconds: Optional[float] = None
             ) -> List[Request]:
    """``count`` requests of ``mix`` over ``graph``.

    ``graph`` holds ``n``, ``deg`` (per-vertex degree) and ``max_w``;
    ``trial_sources(rng, count)`` is the configuration's root picker.
    An open loop spreads its requests over ``seconds``.
    """
    kinds_, shares = zip(*mix["mix"])
    shares = np.asarray(shares, np.float64) / np.sum(shares)
    counts = np.floor(shares * count).astype(int)
    counts[: count - counts.sum()] += 1
    kinds = np.repeat(np.asarray(kinds_), counts)
    if mix["sources"] == "trial":
        sources = np.asarray(trial_sources(rng, count), np.int64)
    elif mix["sources"] == "zipf_degree":
        sources = _zipf_degree_sources(np.asarray(graph["deg"]), rng, count,
                                       float(mix.get("zipf_a", 1.1)))
    else:
        raise ValueError(f"unknown source rule {mix['sources']!r}")
    params = np.full(count, np.nan)
    for kind in ("knear", "bounded"):
        at = np.flatnonzero(kinds == kind)
        if not at.size:
            continue
        q = rng.permutation(_strata(at.size))
        if kind == "knear":
            lo, hi = mix["k_range"]
            params[at] = np.floor(np.exp(np.log(lo) + q *
                                         (np.log(hi + 1) - np.log(lo))))
        else:
            lo, hi = mix["bound_w_scale"]
            # float32-exact radii: the served set and the reference's
            # then compare distances against the same number
            params[at] = np.float32((lo + q * (hi - lo)) * graph["max_w"])
    order = rng.permutation(count) if mix["loop"] == "open" else \
        np.arange(count)
    kinds = kinds[order]
    params = params[order]
    due = [None] * count
    if mix["loop"] == "open":
        # the gap after each request; the last one runs to the close
        gaps = rng.permutation(-np.log(1.0 - _strata(count)))
        due = ((np.cumsum(gaps) - gaps) * (seconds / gaps.sum())).tolist()
    return [Request(kind=str(k), source=int(s),
                    param=None if k == "tree" else
                    (int(p) if k == "knear" else float(p)), due_s=d)
            for k, s, p, d in zip(kinds, sources, params, due)]


def open_count(mix: dict, seconds: float) -> int:
    """Requests an open loop sends in a window of ``seconds``."""
    return max(1, int(round(float(mix["rate_qps"]) * seconds)))
