"""`sel_scan_kernel_share` (PR 42): the counter's reader and what it says of
a program without the counter, the manifest's entry (asserted BY NAME AND BY
CONTENT and never by its place in `per_layer`, so the next PR's append
falsifies nothing here), the phi cell's list of metrics, and a CPU rehearsal
of the cell (the CPU backend leaves every selective scan to the plain path:
0, printed without a value)."""

import json
import os
import types

import pytest

from yardstick import harness
from test_generators import rehearse, run_py
from test_lm_sambay_train_step import CELL, NEW, SHARED

KEY = "sel_scan_kernel_lowerings"
NAME = "sel_scan_kernel_share"
READER = harness.load_module(
    os.path.join(harness.HERE, "layer_metrics", NAME + ".py"),
    "ys_layer_" + NAME)


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def fake(begin):
    return types.SimpleNamespace(counters={"begin": begin, "end": begin})


@pytest.mark.parametrize("kernel, plain, share", [
    (2, 0, 100.0), (0, 2, 0.0), (1, 3, 25.0)])
def test_share_is_kernel_over_all_scans_of_the_begin_snapshot(
        kernel, plain, share):
    assert READER.read(fake({KEY: {"kernel": kernel, "plain": plain}})) \
        == share


@pytest.mark.parametrize("counters", [
    {"begin": {"sel_scan_lowerings": {"chunked": 2, "padded": 0},   # the
               "scan_kernel_lowerings": {"kernel": 0, "plain": 0}}},  # parent
    {"begin": {KEY: {"kernel": 0, "plain": 0}}},    # no mamba layer
    {"begin": {}}, {}])
def test_a_program_without_the_counter_or_without_a_scan_leaves_it_out(
        counters):
    assert READER.read(types.SimpleNamespace(counters=counters)) is None


def test_the_entry_by_name(manifest):
    (spec,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert spec == {"name": NAME, "unit": "%", "better": "higher",
                    "source": "program_counter", "layer": "train step",
                    "moves": "train_tokens_per_s", "workloads": [CELL]}
    names = [m["name"] for m in manifest["per_layer"]]
    assert len(set(names)) == len(names)
    assert CELL in next(m for m in manifest["end_to_end"]
                        if m["name"] == "train_tokens_per_s")["workloads"]


def test_the_phi_cell_reports_it_and_no_other_cell_does(manifest):
    """What `test_lm_sambay_train_step.py` asserted of the cell's list, with
    this metric in it: every name the cell had, each read by a reader."""
    cell = harness.Cell(manifest, CELL)
    names = [m["name"] for m in cell.per_layer]
    had = ["compiles_in_window", "backend_start_s"] + NEW + SHARED
    assert set(names) >= set(had) | {NAME} and len(set(names)) == len(names)
    by_name = {m["name"]: m for m in cell.per_layer}
    for name in NEW + SHARED:
        assert CELL in by_name[name]["workloads"]
    for _spec, mod in cell.readers():
        assert hasattr(mod, "read")
    for w in manifest["workloads"]:
        if w["name"] != CELL:
            assert NAME not in {
                m["name"] for m in harness.Cell(manifest, w["name"]).per_layer}


def test_a_rehearsal_counts_the_scans_of_the_cell():
    """The cell at its rehearse size on this CPU: every selective scan is
    left to the plain path, and the counter says so."""
    run = rehearse(CELL, trace=True)
    assert run.values[NAME] == 0.0
    built = run.counters["begin"][KEY]
    assert built["kernel"] == 0 and built["plain"] > 0
    forms = run.counters["begin"]["sel_scan_lowerings"]
    assert built["plain"] == forms["chunked"] + forms["padded"]
    assert run.values["sel_scan_device_ms"] is None   # no device trace here


def test_a_cpu_rehearsal_prints_the_metric_without_a_value():
    p = run_py("--workload", CELL, "--seed", "5", "--seconds", "0.5",
               "--trace", "1", "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.splitlines()
    assert NAME + ": not measured" in lines
    assert json.loads(lines[-1])["correct"]
