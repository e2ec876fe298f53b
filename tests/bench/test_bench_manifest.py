"""``BENCHMARK.json`` keeps the contract, and every name finds its file.

A later PR adds a configuration, a traffic mix or a metric as new
files plus new entries; these checks hold for whatever it adds.
"""
import json
import re

import pytest

from bench import loadgen, manifest

MAN = manifest.load()
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|projection|_dim$|"
                   r"_rank$|head|expansion|per_tok)")
LINE = re.compile(r"^[^\t\n]{1,200}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def test_top_level_and_entry_keys():
    assert set(MAN) == TOP_KEYS
    for section, keys in ENTRY_KEYS.items():
        assert 1 <= len(MAN[section])
        for entry in MAN[section]:
            extra = {"workloads"} if section in ("end_to_end",
                                                 "per_layer") else set()
            assert keys <= set(entry) <= keys | extra, entry


def test_command_and_paths():
    cmd, paths = MAN["command"], MAN["paths"]
    assert 1 <= len(cmd) <= 32 and all(LINE.match(w) for w in cmd)
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (manifest.ROOT / p).is_dir()
    for word in cmd[1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in paths), word


def test_names_units_and_lines():
    names = []
    for section in ENTRY_KEYS:
        for e in MAN[section]:
            assert manifest.NAME_RE.match(e["name"]), e["name"]
            names.append((section, e["name"]))
            for key in ("why", "layer", "source"):
                if key in e:
                    assert LINE.match(e[key]), (e["name"], key)
            if "unit" in e:
                assert manifest.UNIT_RE.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    for section in ENTRY_KEYS:
        got = [n for s, n in names if s == section]
        assert len(got) == len(set(got)), section
    metric_names = [n for s, n in names if s in ("end_to_end", "per_layer")]
    assert len(metric_names) == len(set(metric_names))
    assert len(json.dumps(MAN)) < 64 * 1024


def test_configs_resolve_and_state_their_cuts():
    for c in MAN["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in MAN["paths"]))
        cfg = manifest.config(MAN, c["name"])
        assert cfg["name"] == c["name"]
        assert sorted(cfg.get("reduced", {})) == sorted(c["reduced"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert manifest.NAME_RE.match(key) and not WIDTH.search(key)
        gen = manifest.load_module(manifest.generator_path(cfg["generator"]))
        for fn in ("generate", "trial_sources", "tiny"):
            assert callable(getattr(gen, fn))
    files = [c["file"] for c in MAN["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}


def test_workloads_resolve():
    pairs = set()
    for w in MAN["workloads"]:
        assert w["chips"] in (1, 4)
        manifest.config_entry(MAN, w["config"])
        assert manifest.NAME_RE.match(w["traffic"])
        loadgen.load(manifest.traffic_path(w["traffic"]))
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(MAN["workloads"]) // 2)
    assert 1 <= len(MAN["workloads"]) <= 24


@pytest.mark.parametrize("section", ["end_to_end", "per_layer"])
def test_every_metric_has_a_reader(section):
    for m in MAN[section]:
        mod = manifest.load_module(manifest.metric_path(m["name"]))
        assert callable(mod.read), m["name"]
        for cell in m.get("workloads", []):
            manifest.workload(MAN, cell)


def test_end_to_end_bounds_and_sources():
    names = {m["name"] for m in MAN["end_to_end"]}
    assert "setup_s" in names
    for m in MAN["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in MAN["workloads"]:
        e2e = {m["name"] for m in manifest.cell_metrics(MAN, w["name"],
                                                         "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert manifest.cell_metrics(MAN, w["name"], "per_layer"), w["name"]


def test_moves_names_a_metric_each_listed_cell_reports():
    for m in MAN["per_layer"]:
        cells = m.get("workloads", [w["name"] for w in MAN["workloads"]])
        for cell in cells:
            e2e = {x["name"] for x in manifest.cell_metrics(MAN, cell,
                                                             "end_to_end")}
            assert m["moves"] in e2e, (m["name"], cell)


def test_layers_are_named_alike():
    layers = {m["layer"] for m in MAN["per_layer"]}
    for layer in layers:
        assert layer == layer.strip() and "\n" not in layer


def test_roofline_shares_are_percent():
    for m in MAN["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"


def test_run_length_fits_a_full_check_of_24_cells():
    rs = MAN["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_files_under_paths_are_named_from_name_characters():
    for p in MAN["paths"]:
        for f in (manifest.ROOT / p).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            rel = f.relative_to(manifest.ROOT).as_posix()
            assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


def test_held_back_cells_resolve_and_report():
    import held_back
    man = held_back.MAN
    for w in held_back.HELD["workloads"]:
        assert manifest.NAME_RE.match(w["name"]) and LINE.match(w["why"])
        manifest.config_entry(man, w["config"])
        loadgen.load(manifest.traffic_path(w["traffic"]))
        e2e = {m["name"] for m in manifest.cell_metrics(man, w["name"],
                                                         "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = manifest.cell_metrics(man, w["name"], "per_layer")
        assert layers and all(m["moves"] in e2e for m in layers)
    for section in ("end_to_end", "per_layer"):
        for m in held_back.HELD[section]:
            assert callable(manifest.load_module(
                manifest.metric_path(m["name"])).read)
            assert m["name"] not in {x["name"] for x in MAN[section]}
