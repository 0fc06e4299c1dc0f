"""BENCHMARK.json against the literal rules of the manifest check, and
every file a cell names."""

import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(s):
    return (isinstance(s, str) and 1 <= len(s) <= 200 and s.isascii()
            and s.isprintable())


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert 1 <= len(MANIFEST["command"]) <= 32
    assert all(line(w) and not w.startswith("/") and ".." not in w
               for w in MANIFEST["command"])
    assert 1 <= len(MANIFEST["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in MANIFEST["paths"])
    for p in MANIFEST["paths"]:
        assert (ROOT / p).is_dir()


def test_every_string_is_plain_ascii_and_within_its_length():
    cfgs, cells = MANIFEST["configs"], MANIFEST["workloads"]
    assert 1 <= len(cfgs) <= 24 and 1 <= len(cells) <= 24
    for c in cfgs:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        assert PATH.match(c["file"])
    assert len({c["name"] for c in cfgs}) == len(cfgs)
    assert len({c["file"] for c in cfgs}) == len(cfgs)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and line(w["why"])
        assert w["config"] in {c["name"] for c in cfgs}
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert {w["config"] for w in cells} == {c["name"] for c in cfgs}
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


def test_metrics_follow_the_contract():
    cells = {w["name"] for w in MANIFEST["workloads"]}
    e2e, layer = MANIFEST["end_to_end"], MANIFEST["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    reports = {}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        reports[m["name"]] = set(m.get("workloads", cells))
    assert "setup_s" in reports and reports["setup_s"] == cells
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert m["source"] in SOURCES and line(m["layer"])
        assert m["moves"] in reports
        assert set(m.get("workloads", reports[m["moves"]])) <= \
            reports[m["moves"]]
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for cell in cells:
        assert any(cell in r for n, r in reports.items() if n != "setup_s")
        assert any(cell in m.get("workloads", reports[m["moves"]])
                   for m in layer)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_file_a_cell_names_exists(cell):
    from benchmark import harness
    mf = harness.Manifest()
    w = mf.cell(cell)
    config, traffic = mf.config(w["config"]), mf.traffic(w["traffic"])
    assert mf.find("drivers", traffic["driver"]).is_file()
    assert set(next(c["reduced"] for c in MANIFEST["configs"]
                    if c["name"] == w["config"])) <= set(config["reduced"])
    assert "guarantees" in config and "limits" in config
    for m in mf.metrics_of(cell, "per_layer"):
        spec = json.loads(mf.find("metrics", m["name"]).read_text())
        assert hasattr(mf.load("readers", spec["reader"]), "read")
    for p in MANIFEST["paths"]:
        for f in (ROOT / p).rglob("*"):
            if f.is_file() and "__pycache__" not in f.parts:
                assert PATH.match(str(f.relative_to(ROOT))), f
