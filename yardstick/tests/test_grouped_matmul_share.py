"""`grouped_matmul_share`: the reader's arithmetic, what it says of a program
without the counter, and a CPU rehearsal of the cell that reports it (`lax.
ragged_dot` is what the CPU backend and the rehearse size select: 0, printed
without a value)."""

import json
import os
import types

from yardstick import harness
from yardstick.tests.test_generators import run_py

READER = harness.load_module(
    os.path.join(harness.HERE, "layer_metrics", "grouped_matmul_share.py"),
    "ys_layer_grouped_matmul_share")
KEY = "gmm_lowerings"


def fake(begin):
    return types.SimpleNamespace(counters={"begin": begin, "end": begin})


def test_share_is_kernel_over_all_products_of_the_begin_snapshot():
    assert READER.read(fake({KEY: {"kernel": 6, "ragged_dot": 0}})) == 100.0
    assert READER.read(fake({KEY: {"kernel": 0, "ragged_dot": 6}})) == 0.0
    assert READER.read(fake({KEY: {"kernel": 3, "ragged_dot": 9}})) == 25.0


def test_a_program_without_the_counter_or_without_a_product_leaves_it_out():
    assert READER.read(fake({"attn_lowerings": {"fused": 8}})) is None  # the parent's
    assert READER.read(fake({KEY: {"kernel": 0, "ragged_dot": 0}})) is None
    assert READER.read(types.SimpleNamespace(counters={})) is None


def test_a_cpu_rehearsal_prints_the_metric_without_a_value():
    p = run_py("--workload", "olmoe-1b-7b-1c.lm-step-b2s4096", "--seed", "5",
               "--seconds", "0.5", "--trace", "1", "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.splitlines()
    assert "grouped_matmul_share: not measured" in lines
    assert json.loads(lines[-1])["correct"]


def test_the_flagship_reports_no_such_metric():
    """A model without experts builds no grouped product: the cell is not
    in the metric's `workloads` and its line does not carry it."""
    manifest = json.load(open(os.path.join(os.path.dirname(harness.HERE),
                                           "BENCHMARK.json")))
    (found,) = [m for m in manifest["per_layer"]
                if m["name"] == "grouped_matmul_share"]
    entry = dict(found)
    cells = entry.pop("workloads")
    assert entry == {
        "name": "grouped_matmul_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tokens_per_s"}
    assert "olmoe-1b-7b-1c.lm-step-b2s4096" in cells
    assert "flagship-d1024-1c.step-b8s1024" not in cells
