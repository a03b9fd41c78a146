"""BENCHMARK.json against the contract it was written to, and every name
in it against the files that the harness finds by that name."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16
    for word in manifest["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/")
        assert ".." not in word
    assert any(manifest["command"][1].startswith(p + "/")
               for p in manifest["paths"])
    for p in manifest["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
        for _, _, files in os.walk(os.path.join(ROOT, p)):
            for f in files:
                if f.endswith(".pyc"):
                    continue
                assert re.fullmatch(r"[A-Za-z0-9_.\-]+", f), f


def test_configs(manifest):
    names = [c["name"] for c in manifest["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in manifest["workloads"]}
    files = set()
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["source"].startswith("https://")
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["name"] == c["name"] and held["source"] == c["source"]
        # what the manifest says was cut is what the file says was cut
        assert sorted(c["reduced"]) == sorted(held["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate)", key)
            assert held[key] == held["reduced"][key]["here"]
        # the program's keys are the published ones under other names
        for ours, theirs in held["keys_from"].items():
            assert held["keys"][ours] == held[theirs], (ours, theirs)


def test_workloads_resolve_to_files(manifest):
    configs = {c["name"] for c in manifest["configs"]}
    seen = set()
    names = set()
    four = 0
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["name"] not in names
        names.add(w["name"])
        assert w["config"] in configs
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        path = os.path.join(ROOT, "benchmark", "traffic",
                            w["traffic"] + ".json")
        with open(path) as f:
            traffic = json.load(f)
        for group, key in (("runners", "runner"), ("generators", "generator")):
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", group, traffic[key] + ".py")), traffic[key]
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_metrics(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) == len(manifest["end_to_end"])
    layer = {m["name"] for m in manifest["per_layer"]}
    assert len(layer) == len(manifest["per_layer"]) and not layer & set(e2e)

    def cells_of(m):
        assert set(m.get("workloads", cells)) <= cells
        return set(m.get("workloads", cells))

    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert 1 <= len(m["layer"]) <= 200
        # the end-to-end metric it moves is reported wherever it is
        assert cells_of(m) <= cells_of(e2e[m["moves"]]), m["name"]
        # a reader of its own, or its quantity's (the name before the dot)
        folder = os.path.join(ROOT, "benchmark", "layer_metrics")
        paths = [os.path.join(folder, n + ".py")
                 for n in (m["name"], m["name"].rsplit(".", 1)[0])]
        path = next((p for p in paths if os.path.isfile(p)), None)
        assert path, paths
        with open(path) as f:
            assert "def read(ctx)" in f.read()
    for cell in cells:
        mine = [m for m in manifest["end_to_end"] if cell in cells_of(m)]
        assert len(mine) >= 2, cell
        assert any(cell in cells_of(m) for m in manifest["per_layer"]), cell


def test_peaks_table():
    import sys

    sys.path.insert(0, ROOT)
    from benchmark import common

    v5e = common.peaks_for("TPU v5 lite")
    assert (v5e["bf16_tflops"], v5e["int8_tops"], v5e["hbm_gb"],
            v5e["hbm_gb_per_s"]) == (197.0, 393.0, 16.0, 819.0)
    with pytest.raises(SystemExit):
        common.peaks_for("cpu")
