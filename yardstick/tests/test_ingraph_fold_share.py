"""`ingraph_fold_share`: the reader's arithmetic, what it says of a program
without the counter, and the two placements at tiny sizes on virtual CPU
devices: 100 where rank i sits on device i, 0 where four ranks share one."""

import json
import os
import types

from yardstick import harness
from yardstick.tests.test_generators import CELLS, rehearse, run_py

READER = harness.load_module(
    os.path.join(harness.HERE, "layer_metrics", "ingraph_fold_share.py"),
    "ys_layer_ingraph_fold_share")


def fake(begin, end, ops):
    snap = lambda folds: {"comms": [dict(cid=cid, rank=rank, **(
        {} if n is None else {"ingraph_folds": n}))
        for (cid, rank), n in folds.items()]}
    return types.SimpleNamespace(
        facts={"ops": ops}, counters={"begin": snap(begin), "end": snap(end)})


def test_share_is_the_delta_of_the_counter_over_the_windows_ops():
    begin = {(0, 0): 1, (0, 1): 0, (0, 2): 2, (0, 3): 0}
    end = {(0, 0): 11, (0, 1): 10, (0, 2): 12, (0, 3): 7, (5, 0): 0}
    assert READER.read(fake(begin, end, ops=40)) == 92.5
    assert READER.read(fake(begin, begin, ops=40)) == 0.0
    assert READER.read(fake(begin, end, ops=0)) is None


def test_a_program_without_the_counter_leaves_the_metric_out():
    old = {(0, r): None for r in range(4)}      # the parent's snapshot
    assert READER.read(fake(old, old, ops=40)) is None
    assert READER.read(fake({}, {}, ops=40)) is None    # no communicator


def test_rank_i_on_device_i_folds_every_round_over_the_devices():
    run = rehearse(CELLS["4c"], {"counts": [300], "block_ops": 5})
    assert run.results["correct"] and run.results["failed"] == 0
    assert run.values["armed_share.large"] == 100.0
    assert run.values["ingraph_fold_share"] == 100.0
    assert run.values["compiles_in_window"] == 0
    # the bytes that cross devices are the star's, now inside the fold
    assert run.values["xchip_bytes_per_op"] == 2 * 3 * 300 * 4


def test_four_ranks_on_one_device_keep_the_star(monkeypatch):
    import jax
    from tpu_mpi import SpmdContext
    # a one-chip host: every rank's device is the same one
    monkeypatch.setattr(SpmdContext, "device_for",
                        lambda self, rank: jax.devices()[0])
    run = rehearse("osu-allreduce-4r1c.large-reuse",
                   {"counts": [300], "block_ops": 5})
    assert run.results["correct"] and run.results["failed"] == 0
    assert run.values["armed_share.large"] == 100.0
    assert run.values["ingraph_fold_share"] == 0.0


def test_a_cpu_rehearsal_prints_the_metric_without_a_value():
    p = run_py("--workload", "osu-allreduce-4r1c.large-reuse", "--seed", "5",
               "--seconds", "0.5", "--trace", "1", "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.splitlines()
    assert "ingraph_fold_share: not measured" in lines
    assert json.loads(lines[-1])["correct"]
