"""BENCHMARK.json keeps to the benchmark's contract: names, units, keys,
and every name it gives is found as a file."""
import json
import re

import pytest

from bench.tests.tiny import REPO

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][:1] == ["python3"]
    assert all(p == "bench" for p in SPEC["paths"])


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_their_keys_and_names(section):
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))
    for e in SPEC[section]:
        extra = set(e) - KEYS[section]
        assert extra <= {"workloads"}, extra
        assert KEYS[section] <= set(e)
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200
                assert "\n" not in e[key] and "\t" not in e[key]


def test_names_refer_to_files_and_entries():
    configs = {c["name"] for c in SPEC["configs"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for c in SPEC["configs"]:
        assert (REPO / c["file"]).is_file()
        assert (REPO / c["file"]).with_suffix(".py").is_file()
        assert all(NAME.match(k) for k in c["reduced"])
        cfg = json.loads((REPO / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert {"source", "reduced", "assumed"} <= set(cfg)
    for w in SPEC["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        assert (REPO / "bench/traffic" / f"{w['traffic']}.json").is_file()
    for m in SPEC["per_layer"]:
        assert (REPO / "bench/metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in SPEC["workloads"]:
        def has(m):
            return w["name"] in m.get("workloads", [w["name"]])
        e2e = [m["name"] for m in SPEC["end_to_end"] if has(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(has(m) for m in SPEC["per_layer"])


def test_full_check_fits_its_time():
    rs = SPEC["run_seconds"]
    assert 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
