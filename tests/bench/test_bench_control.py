"""The lower-precision control comes out not correct, in every cell.

``bench/control.py`` answers a cell's requests with the plain reference
computed in bfloat16, the precision below the configuration's float32,
and judges them as a run is judged.  Here at the generators' tiny
sizes; on the chip at the cells' own sizes (PERF.md gives the
readings).  The float32 reference in the same place comes out correct.  The
held-back serving cell (``held_back.py``) is checked alike.
"""
import numpy as np
import pytest

from bench import cell as cell_mod
from bench import compare, control, reference
from held_back import MAN

CELLS = [w["name"] for w in MAN["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [11, 2 ** 31 + 5])
def test_control_is_not_correct(cell, seed):
    nums = control.readings(MAN, cell, seed, 4.0, trees=2, rehearse=True)
    assert not compare.verdict(nums), nums


@pytest.mark.parametrize("cell", CELLS)
def test_float32_reference_in_the_same_place_is_correct(cell):
    inp = cell_mod.build(MAN, cell, 3, 4.0, rehearse=True)
    adj = reference.adjacency(inp.n, inp.u, inp.v, inp.w)
    if inp.mix["loop"] == "closed":
        for req in inp.requests[:2]:
            d, p, _ = reference.dijkstra(adj, req.source)
            nums = compare.tree_numbers(adj, req.source, d, p, d)
            assert compare.verdict(nums), nums
        return
    for req in inp.requests:
        if req.kind == "knear":
            d, p, settled = reference.dijkstra(adj, req.source, k=req.param)
            ans = {"nearest": reference.nearest(d, settled, req.source,
                                                req.param),
                   "parent": p, "dist": d}
        else:
            d, p, settled = reference.dijkstra(adj, req.source,
                                               bound=req.param)
            keep = settled & (d <= np.float32(req.param))
            ans = {"dist": np.where(keep, d, np.inf),
                   "parent": np.where(keep, p, -1), "nearest": None}
        assert not compare.query_wrong(adj, req.kind, req.source, req.param,
                                       ans)


def test_control_cli_prints_one_line_per_seed(capsys):
    assert control.main(["--workload", CELLS[0], "--seeds", "1,2",
                         "--seconds", "2", "--trees", "1",
                         "--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 2 and all('"correct": false' in x for x in lines)
