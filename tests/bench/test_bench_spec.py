"""BENCHMARK.json against the benchmark contract, and every cell found as
files by name."""
from __future__ import annotations

import importlib.util
import json
import re
import sys

import pytest

from _bench_cells import REPO, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


def test_top_level_keys_and_command():
    s = spec()
    assert set(s) == TOP
    assert s["command"] == ["python3", "bench/run.py"]
    assert s["paths"] == ["bench", "tests/bench"]
    assert 1 <= s["run_seconds"] <= 51


@pytest.mark.parametrize("kind,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ("end_to_end", {"name", "unit", "better", "bound", "source"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
])
def test_entries_have_the_contract_keys(kind, keys):
    for e in spec()[kind]:
        assert set(e) - {"workloads"} == keys, e["name"]


def test_names_and_units_use_allowed_characters():
    s = spec()
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in s[kind]:
            assert NAME.match(e["name"]), e["name"]
            names.append(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    for w in s["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in s["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    assert len(names) == len(set(names))


def test_free_text_fits_one_line():
    s = spec()
    texts = [e["why"] for k in ("configs", "workloads") for e in s[k]]
    texts += [c["source"] for c in s["configs"]]
    texts += [m["layer"] for m in s["per_layer"]] + s["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t
    assert len(json.dumps(s, allow_nan=False)) <= 64 * 1024


def test_bounds_and_sources():
    s = spec()
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in s["end_to_end"])
    for m in s["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in s["end_to_end"]}
    for m in s["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("cell", [w["name"] for w in spec()["workloads"]])
def test_cell_resolves_to_its_files(cell):
    s = spec()
    w = next(x for x in s["workloads"] if x["name"] == cell)
    cf = json.loads((REPO / "bench" / "cells" / f"{cell}.json").read_text())
    assert (cf["config"], cf["traffic"]) == (w["config"], w["traffic"])
    assert cf["limits"]
    conf = next(c for c in s["configs"] if c["name"] == w["config"])
    assert (REPO / conf["file"]).is_file()
    assert conf["file"] == f"bench/configs/{w['config']}.json"
    tr = json.loads((REPO / "bench" / "traffic"
                     / f"{w['traffic']}.json").read_text())
    assert (REPO / "bench" / "drivers" / f"{tr['driver']}.py").is_file()
    reported = [m for m in s["end_to_end"]
                if cell in m.get("workloads", [cell])]
    assert "setup_s" in {m["name"] for m in reported} and len(reported) >= 2
    layer = [m for m in s["per_layer"] if cell in m.get("workloads", [])]
    assert layer
    sys.path.insert(0, str(REPO))
    from bench import run as harness
    for m in layer + reported:
        path = harness.reader_path(m["name"])
        assert path.parent == REPO / "bench" / "metrics"
        sp = importlib.util.spec_from_file_location("m", path)
        mod = importlib.util.module_from_spec(sp)
        sp.loader.exec_module(mod)
        assert callable(mod.read)


def test_split_metric_shares_its_reader():
    sys.path.insert(0, str(REPO))
    from bench import run as harness
    assert (harness.reader_path("device_idle_share.mc")
            == harness.reader_path("device_idle_share.fl")
            == REPO / "bench" / "metrics" / "device_idle_share.py")


def test_every_config_is_used_and_has_its_own_file():
    s = spec()
    used = {w["config"] for w in s["workloads"]}
    assert used == {c["name"] for c in s["configs"]}
    files = [c["file"] for c in s["configs"]]
    assert len(files) == len(set(files))
