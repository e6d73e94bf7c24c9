"""`blocked_head_share` (PR 43): the counter's reader and what it says of a
program without the counter (the parent), the manifest's entry (asserted BY
NAME AND BY CONTENT and never by its place in `per_layer`, so the next PR's
append falsifies nothing here), that the six train cells report it and the
three OSU cells do not, and a CPU rehearsal of two cells (which function the
step calls does not depend on the backend: 100 here too, printed without a
value as every share of lowerings is).

Its cells are asserted as a subset: a later train cell joins the list."""

import json
import os
import types

import pytest

from yardstick import harness
from test_generators import rehearse, run_py

KEY = "head_loss_lowerings"
NAME = "blocked_head_share"
READER = harness.load_module(
    os.path.join(harness.HERE, "layer_metrics", NAME + ".py"),
    "ys_layer_" + NAME)
TRAIN_CELLS = [
    "flagship-d1024-1c.step-b8s1024",
    "olmoe-1b-7b-1c.lm-step-b2s4096",
    "k-exaone-236b-a23b-1c.lm-step-b1s8192",
    "openpangu-ultra-moe-718b-1c.lm-step-b1s4096",
    "granite-4.0-h-micro-1c.ssm-step-b1s8192",
    "phi-4-mini-flash-reasoning-1c.sambay-step-b1s8192"]


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def fake(begin):
    return types.SimpleNamespace(counters={"begin": begin, "end": begin})


@pytest.mark.parametrize("blocked, whole, share", [
    (2, 0, 100.0), (0, 2, 0.0), (1, 3, 25.0)])
def test_share_is_blocked_over_all_losses_of_the_begin_snapshot(
        blocked, whole, share):
    assert READER.read(fake({KEY: {"blocked": blocked, "whole": whole},
                             "head_loss_blocks": {"4": blocked}})) == share


@pytest.mark.parametrize("counters", [
    {"begin": {"sel_scan_kernel_lowerings": {"kernel": 5, "plain": 0},  # the
               "attn_lowerings": {"fused": 8, "plain": 0}}},           # parent
    {"begin": {KEY: {"blocked": 0, "whole": 0}}},   # no step was traced
    {"begin": {}}, {}])
def test_a_program_without_the_counter_or_without_a_loss_leaves_it_out(
        counters):
    assert READER.read(types.SimpleNamespace(counters=counters)) is None


def test_the_entry_by_name(manifest):
    (found,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    spec = dict(found)
    assert set(TRAIN_CELLS) <= set(spec.pop("workloads"))
    assert spec == {"name": NAME, "unit": "%", "better": "higher",
                    "source": "program_counter", "layer": "train step",
                    "moves": "train_tokens_per_s"}
    names = [m["name"] for m in manifest["per_layer"]]
    assert len(set(names)) == len(names)
    assert set(TRAIN_CELLS) <= set(next(
        m for m in manifest["end_to_end"]
        if m["name"] == "train_tokens_per_s")["workloads"])


def test_the_six_train_cells_report_it_and_no_other_cell_does(manifest):
    """The six it was written for, every train cell since, and no OSU cell:
    it is reported where `train_tokens_per_s` is."""
    rate = next(m for m in manifest["end_to_end"]
                if m["name"] == "train_tokens_per_s")["workloads"]
    for w in manifest["workloads"]:
        cell = harness.Cell(manifest, w["name"])
        names = [m["name"] for m in cell.per_layer]
        assert (NAME in names) == (w["name"] in rate), w["name"]
        assert len(set(names)) == len(names)
        for _spec, mod in cell.readers():
            assert hasattr(mod, "read")
    assert set(TRAIN_CELLS) <= set(rate)
    assert not any(c.startswith("osu-") for c in rate)


@pytest.mark.parametrize("cell", [TRAIN_CELLS[0], TRAIN_CELLS[4]])
def test_a_rehearsal_counts_the_losses_of_the_cell(cell):
    """The cell at its rehearse size on this CPU: every traced step's loss
    is the blocked one, over one block at this size."""
    run = rehearse(cell, trace=True)
    assert run.values[NAME] == 100.0
    begin = run.counters["begin"]
    assert begin[KEY]["blocked"] >= 1 and begin[KEY]["whole"] == 0
    assert sum(begin["head_loss_blocks"].values()) == begin[KEY]["blocked"]
    assert run.results["correct"]


def test_a_cpu_rehearsal_prints_the_metric_without_a_value():
    p = run_py("--workload", TRAIN_CELLS[1], "--seed", "5", "--seconds", "0.5",
               "--trace", "1", "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.splitlines()
    assert NAME + ": not measured" in lines
    assert json.loads(lines[-1])["correct"]
