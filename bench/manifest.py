"""``BENCHMARK.json`` and the files it names, found by name.

* a configuration's file is its entry's ``file``; its generator is
  ``bench/graphs/<generator>.py``;
* a traffic mix is ``bench/traffic/<traffic>.json``;
* a metric, end-to-end or per-layer, is read by
  ``bench/metrics/<name>.py``, whose ``read(rec)`` returns a number or
  ``None`` where the run holds nothing to read.

So a later cell, mix, configuration or metric is new files and new
entries, and no edit to a file that is already here.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "bench"
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def workload(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(man: dict, name: str) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def config(man: dict, name: str) -> dict:
    return json.loads((ROOT / config_entry(man, name)["file"]).read_text())


def traffic_path(name: str) -> Path:
    return HERE / "traffic" / f"{name}.json"


def metric_path(name: str) -> Path:
    return HERE / "metrics" / f"{name}.py"


def generator_path(name: str) -> Path:
    return HERE / "graphs" / f"{name}.py"


def load_module(path: Path):
    """Import a file by path (names may hold dots, such as metric names)."""
    path = Path(path)
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = "bench_file_" + re.sub(r"\W", "_",
                                      str(path.relative_to(HERE)))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(man: dict, cell: str, section: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports."""
    e2e = [m for m in man["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if section == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in man["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]
