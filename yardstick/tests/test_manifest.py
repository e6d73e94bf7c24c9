"""BENCHMARK.json against the contract's limits, and every name in it
against the file it stands for."""

import json
import os
import re

import pytest

from yardstick import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["yardstick"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert len(json.dumps(manifest)) < 64 * 1024
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert word.startswith("yardstick/")


def test_names_units_and_lines(manifest):
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for e in manifest["configs"] + manifest["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for c in manifest["configs"]:
        assert 1 <= len(c["source"]) <= 200
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)


def test_cells_find_their_files(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    assert 2 <= len(cells) <= 24
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = set()
    for name in cells:
        cell = harness.Cell(manifest, name)
        used.add(cell.entry["config"])
        assert cell.config["kind"] == cell.traffic["kind"]
        assert cell.config["chips"] == cell.chips
        assert os.path.isfile(os.path.join(
            harness.HERE, "generators", cell.config["kind"] + ".py"))
        assert hasattr(cell.generator(), "run")
        assert cell.reference() is not None
        for spec, reader in cell.readers():
            assert callable(reader.read)
            assert reader.__file__.endswith(os.path.join(
                "layer_metrics", spec["name"].split(".")[0] + ".py"))
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, name
        assert cell.per_layer, name
        for m in cell.per_layer:        # reported only where its target is
            assert m["moves"] in e2e, (name, m["name"])
    assert used == {c["name"] for c in manifest["configs"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    for c in manifest["configs"]:
        assert c["file"].startswith("yardstick/")
        conf = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert conf["reduced"] == c["reduced"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        for w in m.get("workloads", []):
            assert w in cells, (m["name"], w)
    # a kernel's share of its roofline is `<kernel>_roofline`, in %
    for m in manifest["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].split(".")[0].endswith("_roofline")
            assert m["unit"] == "%"


def test_peaks_table_names_its_source():
    peaks = harness.load_json(os.path.join(harness.HERE, "peaks.json"))
    assert "TPU v5 lite" in peaks and peaks["_source"]
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
