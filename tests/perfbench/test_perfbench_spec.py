"""``BENCHMARK.json`` is well-formed by the contract's own rules, and every
name in it leads to its file: in the repository, and in a copy of it into
which a family that is no U-Net was laid by new files and entries alone
(``conftest.toy_root``), so every test here counts once for each."""

import json
import math
import re
from pathlib import Path

import pytest

from perfbench.lib import spec

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module", params=["repository", "toy-family"])
def bench(request):
    if request.param == "repository":
        return spec.Bench(ROOT)
    return spec.Bench(request.getfixturevalue("toy_root"))


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes(bench):
    doc = bench.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert (bench.root / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    # a full check of the full 24 cells has to fit into 43200 s
    runs = 2 + 14 * 24
    assert runs * (doc["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert 1 <= len(doc["paths"]) <= 16
    for p in doc["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (bench.root / p).is_dir()
    assert 1 <= len(doc["command"]) <= 32
    for word in doc["command"]:
        assert _line(word) and not word.startswith("/") and ".." not in word
    script = doc["command"][1]
    assert any(script.startswith(p + "/") for p in doc["paths"])


def test_files_under_paths_are_named_from_name_characters(bench):
    for p in bench.doc["paths"]:
        for f in (bench.root / p).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]+",
                                str(f.relative_to(bench.root))), f


def test_configs(bench):
    configs = bench.doc["configs"]
    assert 1 <= len(configs) <= 24
    names = [c["name"] for c in configs]
    assert len(set(names)) == len(names)
    assert len({c["file"] for c in configs}) == len(configs)
    used = {w["config"] for w in bench.doc["workloads"]}
    for c in configs:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench.doc["paths"])
        body = json.loads((bench.root / c["file"]).read_text())
        assert len(c["reduced"]) <= 16 and body["reduced"] == c["reduced"]
        assert {"family", "parameters", "source", "model", "train",
                "assumed"} <= set(body)
        assert NAME.match(body["family"])
        # the plain reference sits beside the configuration, by its name,
        # and has the parameters that the configuration's own file states
        assert (bench.home / "reference" / f"{c['name']}.py").is_file()
        shapes = bench.reference(c["name"]).param_shapes(body["model"])
        assert sum(math.prod(v) for v in shapes.values()) == \
            body["parameters"]


def test_workloads(bench):
    cells = bench.doc["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        bench.config(w["config"])
        traffic = bench.traffic(w["traffic"])
        driver = bench.driver(traffic["driver"])
        for fn in ("setup", "window", "end_to_end", "counters", "check",
                   "follow", "readings", "controls", "abstract_step"):
            assert callable(getattr(driver, fn)), fn
        limits = bench.limits(w["name"])
        assert limits and all(v >= 0 for v in limits.values())


def test_metrics(bench):
    doc = bench.doc
    cells = [w["name"] for w in doc["workloads"]]
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and "setup_s" in e2e
    assert "workloads" not in e2e["setup_s"]
    names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    assert len(set(names)) == len(names)
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert 1 <= len(doc["per_layer"]) <= 128
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert (bench.home / "layer_metrics" / f"{m['name']}.py").is_file()
        assert callable(bench.reader(m["name"]).read)
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells and bench.reports(moved, cell)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for cell in cells:
        reported = [m for m in doc["end_to_end"] if bench.reports(m, cell)]
        assert len(reported) >= 2
        assert any(bench.reports(m, cell) for m in doc["per_layer"])


def test_peaks_table_rejects_an_unknown_device(bench):
    assert bench.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert bench.peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        bench.peaks("cpu")
