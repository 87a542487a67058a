"""BENCHMARK.json and every file it names: shape, names, and loading by
name."""
import json
import os
import re

import pytest

from bench import harness

BM = harness.load_json(harness.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                   r"projection|head|expansion|experts_per_tok")


def test_top_level_keys_and_size():
    assert set(BM) == KEYS
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    assert 1 <= BM["run_seconds"] <= 51
    assert isinstance(BM["run_seconds"], int)


def test_command_and_paths():
    cmd, paths = BM["command"], BM["paths"]
    assert 1 <= len(cmd) <= 32 and 1 <= len(paths) <= 16
    for p in paths:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(harness.ROOT, p))
    for word in cmd:
        assert 1 <= len(word) <= 200 and "\n" not in word and "\t" not in word
        assert not word.startswith("/") and ".." not in word
        if os.sep in word:
            assert any(word.startswith(p + "/") for p in paths)


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_entry_keys(section):
    for e in BM[section]:
        extra = set(e) - ENTRY_KEYS[section]
        assert extra <= {"workloads"}, (e["name"], extra)
        assert ENTRY_KEYS[section] <= set(e), e["name"]


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_names_and_units(section):
    names = [e["name"] for e in BM[section]]
    assert len(names) == len(set(names))
    for e in BM[section]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer"):
            if key in e:
                assert 1 <= len(e[key]) <= 200
                assert "\n" not in e[key] and "\t" not in e[key]
        if section == "configs":
            assert 1 <= len(e["source"]) <= 200
        for key in ("config", "traffic"):
            if key in e:
                assert NAME.match(e[key]), e[key]


def test_metric_sources_and_bounds():
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in BM["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e


@pytest.mark.parametrize("cfg", BM["configs"], ids=lambda c: c["name"])
def test_config_file(cfg):
    data = harness.load_json(harness.ROOT, cfg["file"])
    assert cfg["file"].startswith("bench/configs/")
    assert data["name"] == cfg["name"]
    assert data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"] and len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
        assert key in data
    assert data["assumed"] and data["precision"] == "float32"
    # its plain reference loads by name
    ref = harness.load_module("references", data["reference"])
    assert callable(ref.simulate)
    used = [w for w in BM["workloads"] if w["config"] == cfg["name"]]
    assert used, f"{cfg['name']} is used by no cell"


@pytest.mark.parametrize("cell", BM["workloads"], ids=lambda w: w["name"])
def test_cell_files(cell):
    spec = harness.cell_spec(cell["name"], BM)
    t = spec["traffic"]
    assert t["name"] == cell["traffic"] and t["chips"] == cell["chips"]
    assert t["why"] == cell["why"] and len(cell["why"]) <= 200
    assert cell["chips"] in (1, 4)
    assert {"source", "reduced", "assumed", "limits"} <= set(t)
    labels = {p["label"] for p in spec["config"]["points"]}
    assert set(t["points"]) <= labels
    assert len(harness.call_grid(spec, 1, 0)) == \
        len(t["points"]) * len(t["clients"]) * t["seeds_per_call"]
    # every metric the cell reports has a reader, found by name
    for m in spec["per_layer"]:
        mod = harness.load_module("metrics", m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"],
                                                     m["moves"])
    assert {m["name"] for m in spec["end_to_end"]} >= {"setup_s",
                                                        "sim_req_per_s"}


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(harness.BENCH_DIR, "workloads"))))
def test_every_traffic_file_loads(name):
    t = harness.load_json(harness.BENCH_DIR, "workloads", name + ".json")
    assert t["name"] == name and NAME.match(name)
    cfg = harness.load_json(harness.BENCH_DIR, "configs",
                            t["config"] + ".json")
    assert set(t["points"]) <= {p["label"] for p in cfg["points"]}
    from repro.core import vectorsim

    assert callable(getattr(vectorsim, t["entry"]))
    assert {"why", "source", "reduced", "assumed", "limits", "chips"} <= set(t)


def test_each_cell_reports_a_per_layer_metric():
    for w in BM["workloads"]:
        assert harness.cell_spec(w["name"], BM)["per_layer"], w["name"]


def test_four_chip_cells_and_budget():
    cells = BM["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    # a full check of 24 cells fits its 43200 s budget
    rs = BM["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_per_layer_workloads_name_cells():
    cells = {w["name"] for w in BM["workloads"]}
    for m in BM["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells


def test_call_seeds_differ_and_repeat():
    a = harness.call_seeds(2 ** 31 + 5, 0, 32)
    assert a == harness.call_seeds(2 ** 31 + 5, 0, 32)
    assert len(set(a)) == 32
    assert not set(a) & set(harness.call_seeds(2 ** 31 + 5, 1, 32))
    assert not set(a) & set(harness.call_seeds(2 ** 31 + 5, -1, 32))
    assert all(0 <= s < 2 ** 31 for s in a)


def test_file_is_json_with_sorted_cells():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        assert json.load(f) == BM
    names = [w["name"] for w in BM["workloads"]]
    assert names[0] == "fig8-grid"
