"""BENCHMARK.json against the rules a manifest must keep, and every file
that it names present."""
import json
import os
import re

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def text_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_keys_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in manifest[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and text_ok(w["why"])
    for c in manifest["configs"]:
        assert text_ok(c["why"]) and text_ok(c["source"])
        assert all(NAME.match(k) for k in c["reduced"])


def test_files_are_under_paths(manifest):
    paths = manifest["paths"]
    for c in manifest["configs"]:
        assert any(c["file"].startswith(p + "/") for p in paths)
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    assert os.path.exists(os.path.join(ROOT, manifest["command"][1]))


def test_every_cell_has_its_files(manifest):
    configs = {c["name"] for c in manifest["configs"]}
    used = set()
    for w in manifest["workloads"]:
        assert w["config"] in configs
        used.add(w["config"])
        with open(os.path.join(ROOT, "bench", "mixes",
                               w["traffic"] + ".json")) as f:
            driver = json.load(f)["driver"]
        assert os.path.exists(os.path.join(ROOT, "bench", "drivers",
                                           driver + ".py"))
        assert os.path.exists(os.path.join(ROOT, "bench", "limits",
                                           w["name"] + ".json"))
    assert used == configs


def test_metrics(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in manifest["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in manifest["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert text_ok(m["layer"])
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"], set()).add(m["name"])
    for cell in cells:
        reported = [m for m in manifest["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"])


def test_size(manifest):
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
