"""The lower-precision control: the reference in bfloat16 in the program's place.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--trees 4]

Builds the cell's inputs from each seed exactly as ``run.py`` does,
answers the window's requests with the plain reference computed in
bfloat16 (the precision below the configuration's float32), and
judges those answers with the same comparison as a run.  It must come
out not correct; its readings are the upper ends from which the
limits in ``compare.py`` were set.  Tree cells answer the first
``--trees`` roots (about one window's worth); open-loop cells every
request of a window of ``--seconds``.  It runs on the host alone.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from bench import cell as cell_mod  # noqa: E402
from bench import compare, manifest, reference  # noqa: E402

LOWER = ml_dtypes.bfloat16


def _answer(adj_low, req) -> dict:
    """What a bfloat16 program would serve for one request."""
    if req.kind == "knear":
        d, p, settled = reference.dijkstra(adj_low, req.source, k=req.param)
        d = d.astype(np.float32)
        near = reference.nearest(d, settled, req.source, req.param)
        return {"nearest": near, "parent": p, "dist": d}
    d, p, settled = reference.dijkstra(adj_low, req.source, bound=req.param)
    d = d.astype(np.float32)
    keep = settled & (d <= np.float32(req.param))
    return {"dist": np.where(keep, d, np.inf), "parent": np.where(keep, p, -1),
            "nearest": None}


def readings(man: dict, workload: str, seed: int, seconds: float,
             trees: int = 4, rehearse: bool = False) -> dict:
    """The control's numbers for one seed, as ``compare`` counts them."""
    inp = cell_mod.build(man, workload, seed, seconds, rehearse=rehearse)
    adj = reference.adjacency(inp.n, inp.u, inp.v, inp.w,
                              directed=inp.directed)
    low = adj.astype(LOWER)
    if inp.mix["loop"] == "closed":
        nums = {"dist_mismatch": 0, "parent_bad": 0, "answers_missing": 0}
        for req in inp.requests[:trees]:
            ref, _, _ = reference.dijkstra(adj, req.source)
            d, p, _ = reference.dijkstra(low, req.source)
            for k, v in compare.tree_numbers(
                    adj, req.source, d.astype(np.float32), p, ref).items():
                nums[k] += v
        return nums
    wrong = sum(compare.query_wrong(adj, r.kind, r.source, r.param,
                                    _answer(low, r))
                for r in inp.requests)
    return {"answers_wrong": int(wrong), "answers_missing": 0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trees", type=int, default=4)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    man = manifest.load()
    seconds = args.seconds or man["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        nums = readings(man, args.workload, seed, seconds, args.trees,
                        args.rehearse)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": compare.verdict(nums), **nums}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
