"""`fused_attn_share`: the reader's arithmetic, what it says of a program
without the counter, and a CPU rehearsal of both train cells (the plain path
is what the CPU backend selects: 0, printed without a value)."""

import json
import os
import types

import pytest

from yardstick import harness
from yardstick.tests.test_generators import run_py

READER = harness.load_module(
    os.path.join(harness.HERE, "layer_metrics", "fused_attn_share.py"),
    "ys_layer_fused_attn_share")


def fake(begin):
    return types.SimpleNamespace(counters={"begin": begin, "end": begin})


def test_share_is_fused_over_all_calls_of_the_begin_snapshot():
    assert READER.read(fake({"attn_lowerings": {"fused": 8, "plain": 0}})) == 100.0
    assert READER.read(fake({"attn_lowerings": {"fused": 0, "plain": 12}})) == 0.0
    assert READER.read(fake({"attn_lowerings": {"fused": 4, "plain": 12}})) == 25.0


def test_a_program_without_the_counter_or_without_a_call_leaves_it_out():
    assert READER.read(fake({"arming_s": 0.0})) is None     # the parent's
    assert READER.read(fake({"attn_lowerings": {"fused": 0, "plain": 0}})) is None
    assert READER.read(types.SimpleNamespace(counters={})) is None


@pytest.mark.parametrize("cell, metric", [
    ("flagship-d1024-1c.step-b8s1024", "fused_attn_share"),
    ("olmoe-1b-7b-1c.lm-step-b2s4096", "fused_attn_share")])
def test_a_cpu_rehearsal_prints_the_metric_without_a_value(cell, metric):
    p = run_py("--workload", cell, "--seed", "5", "--seconds", "0.5",
               "--trace", "1", "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.splitlines()
    assert f"{metric}: not measured" in lines
    assert json.loads(lines[-1])["correct"]
