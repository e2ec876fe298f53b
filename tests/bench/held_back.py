"""Cells held back from ``BENCHMARK.json`` whose harness path is kept.

``data/held_back_cells.json`` holds their entries, in the manifest's
own form.  ``MAN`` is ``BENCHMARK.json`` with them added, so that the
tests rehearse, control and fault-check the open-loop serving path as
they do the cells the benchmark holds.
"""
import json
from pathlib import Path

from bench import manifest

HELD = json.loads((Path(__file__).parent / "data" /
                   "held_back_cells.json").read_text())
BENCH = manifest.load()
MAN = {k: (v + HELD[k] if k in HELD else v) for k, v in BENCH.items()}
CELLS = [w["name"] for w in HELD["workloads"]]
