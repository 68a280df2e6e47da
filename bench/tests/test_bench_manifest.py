"""BENCHMARK.json against the rules the harness and its checkers rely on."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def _metrics(m):
    return m["end_to_end"] + m["per_layer"]


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["bench"]
    assert manifest["command"][1] == "bench/run.py"
    assert all(not w.startswith("/") and ".." not in w
               for w in manifest["command"])


def test_names_and_units(manifest):
    names = ([c["name"] for c in manifest["configs"]]
             + [w["name"] for w in manifest["workloads"]]
             + [m["name"] for m in _metrics(manifest)])
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for c in manifest["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
        for text in (c["why"], c["source"]):
            assert 0 < len(text) <= 200 and not set(text) & {"\n", "\t"}
    for m in _metrics(manifest):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


def test_bounds_and_window_fit_the_check(manifest):
    rs = manifest["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])


def test_every_cell_reports_what_its_layers_move(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in manifest["end_to_end"]}
    for cell in cells:
        assert cell in e2e["setup_s"]
        assert sum(cell in ws for ws in e2e.values()) >= 2, cell
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"]), cell
    layers = {}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells and cell in e2e[m["moves"]], (m, cell)
        layers.setdefault(m["layer"], m["layer"])
        assert "\n" not in m["layer"] and 0 < len(m["layer"]) <= 200


def test_every_config_has_a_cell(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}


def test_every_file_found_by_name_exists(manifest):
    for c in manifest["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert (ROOT / c["file"]).is_file()
        assert (BENCH / "configs" / f"{c['name']}.py").is_file()
    for w in manifest["workloads"]:
        mix = BENCH / "traffic" / f"{w['traffic']}.json"
        with open(mix) as f:
            request = json.load(f)["request"]
        steps = [s for step in request
                 for s in ([x for alt in step["choose"] for x in alt]
                           if "choose" in step else [step])]
        for s in steps:
            assert (BENCH / "ops" / f"{s['op']}.py").is_file(), s
    for m in _metrics(manifest):
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m


def test_peaks_table_names_its_source():
    with open(BENCH / "peaks.json") as f:
        peaks = json.load(f)
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["kinds"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
