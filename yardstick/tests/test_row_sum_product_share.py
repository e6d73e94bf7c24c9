"""`row_sum_product_share`, `embed_device_ms` and the openPangu cell's
`held_dispatch_device_ms` (PR 33; PR 50 folded its tag `.pgu` into the
reader's one entry): the counter's reader and what it says of a program
without the counter, the manifest's entries by name, the two held cells
among their cells, and a
CPU rehearsal of the cell that reports all three (the CPU backend selects
XLA's scatter-add: 0, printed without a value)."""

import json
import os
import types

import pytest

from yardstick import harness
from test_generators import rehearse, run_py
from test_lm_latent_train_step import MINE as LATENT

KEX = "k-exaone-236b-a23b-1c.lm-step-b1s8192"
PGU = "openpangu-ultra-moe-718b-1c.lm-step-b1s4096"
KEY = "row_sum_lowerings"
MINE = [("held_dispatch_device_ms", "ms", "lower", "device_trace", [PGU]),
        ("row_sum_product_share", "%", "higher", "program_counter",
         [KEX, PGU]),
        ("embed_device_ms", "ms", "lower", "device_trace", [KEX, PGU])]
READER = harness.load_module(
    os.path.join(harness.HERE, "layer_metrics", "row_sum_product_share.py"),
    "ys_layer_row_sum_product_share")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def fake(begin):
    return types.SimpleNamespace(counters={"begin": begin, "end": begin})


def test_share_is_product_over_all_sums_of_the_begin_snapshot():
    assert READER.read(fake({KEY: {"product": 5, "scatter": 0}})) == 100.0
    assert READER.read(fake({KEY: {"product": 0, "scatter": 5}})) == 0.0
    assert READER.read(fake({KEY: {"product": 1, "scatter": 3}})) == 25.0


def test_a_program_without_the_counter_or_without_a_sum_leaves_it_out():
    assert READER.read(fake({"gmm_lowerings": {"kernel": 6}})) is None  # the parent's
    assert READER.read(fake({KEY: {"product": 0, "scatter": 0}})) is None
    assert READER.read(types.SimpleNamespace(counters={})) is None


def test_the_entries_follow_what_the_benchmark_had(manifest):
    """Each by name, with its unit and source, `moves` the train cells' one
    end-to-end metric, the cells it was written for among its cells; PR
    32's fifteen stand beside them."""
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, unit, better, source, cells in MINE:
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"]) == (unit, better, source)
        assert set(cells) <= set(m["workloads"]), name
        assert m["layer"] == "train step"
        assert m["moves"] == "train_tokens_per_s"
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
    assert set(LATENT) <= set(by_name)
    assert len(by_name) == len(manifest["per_layer"])
    assert len(manifest["workloads"]) >= 7 and len(manifest["configs"]) >= 6


def test_the_held_cells_report_them(manifest):
    pgu = {m["name"] for m in harness.Cell(manifest, PGU).per_layer}
    assert pgu >= {"compiles_in_window", "backend_start_s"} | set(LATENT) | {
        name for name, *_ in MINE}
    kex = {m["name"] for m in harness.Cell(manifest, KEX).per_layer}
    assert kex >= {"row_sum_product_share", "embed_device_ms",
                   "held_dispatch_device_ms"}
    for cell in (PGU, KEX):
        for _spec, mod in harness.Cell(manifest, cell).readers():
            assert hasattr(mod, "read")
    for cell in ("flagship-d1024-1c.step-b8s1024",
                 "olmoe-1b-7b-1c.lm-step-b2s4096"):
        names = {m["name"] for m in harness.Cell(manifest, cell).per_layer}
        assert not names & {name for name, *_ in MINE}


def test_a_rehearsal_counts_the_sums_of_a_held_program():
    """The held cell at its rehearse size on this CPU: every sum is left to
    the scatter-add, and the counter says so."""
    run = rehearse(PGU, trace=True)
    assert run.values["row_sum_product_share"] == 0.0
    built = run.counters["begin"][KEY]
    assert built["product"] == 0 and built["scatter"] > 0
    assert run.values["embed_device_ms"] is None    # no device trace here
    assert run.values["held_dispatch_device_ms"] is None


def test_a_cpu_rehearsal_prints_the_metrics_without_a_value():
    p = run_py("--workload", KEX, "--seed", "5", "--seconds", "0.5",
               "--trace", "1", "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.splitlines()
    assert "row_sum_product_share: not measured" in lines
    assert "embed_device_ms: not measured" in lines
    assert json.loads(lines[-1])["correct"]
