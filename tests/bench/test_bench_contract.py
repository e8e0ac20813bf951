"""``BENCHMARK.json`` keeps to the form the benchmark's checker reads."""
import json
import re

from benchkit import REPO

DOC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_command():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["command"] == ["python3", "bench/run.py"]
    for p in DOC["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
        assert (REPO / p).is_dir()
    assert 1 <= DOC["run_seconds"] <= 51
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_the_full_check_fits_its_budget_at_24_cells():
    runs = 2 + 14 * 24
    assert runs * (DOC["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_entries_have_exactly_their_keys():
    for c in DOC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (REPO / c["file"]).is_file()
        assert TEXT.match(c["source"]) and TEXT.match(c["why"])
        assert len(c["reduced"]) <= 16
    for w in DOC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and TEXT.match(w["why"])
    for m in DOC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in DOC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and TEXT.match(m["layer"])


def test_names_units_and_references():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in DOC[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in DOC[k]}) == len(DOC[k])
    metrics = [m["name"] for m in DOC["end_to_end"] + DOC["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    configs = {c["name"] for c in DOC["configs"]}
    cells = {w["name"] for w in DOC["workloads"]}
    assert {w["config"] for w in DOC["workloads"]} == configs
    assert len({(w["config"], w["traffic"]) for w in DOC["workloads"]}) == \
        len(DOC["workloads"])
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    e2e = {m["name"] for m in DOC["end_to_end"]}
    assert "setup_s" in e2e
    for m in DOC["per_layer"]:
        assert m["moves"] in e2e


def test_every_cell_reports_enough():
    for w in DOC["workloads"]:
        e2e = [m["name"] for m in DOC["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2
        per = [m for m in DOC["per_layer"]
               if (w["name"] in m["workloads"] if "workloads" in m
                   else m["moves"] in e2e)]
        assert per
        for m in per:
            assert m["moves"] in e2e
    four = sum(w["chips"] == 4 for w in DOC["workloads"])
    assert four <= max(1, len(DOC["workloads"]) // 2)
