"""The generators and the plain references against the system, at tiny
sizes on four virtual CPU devices: every `op`, `buffers` and `sync` value
the collective generator accepts, so that a later cell that is only a data
file does not meet untried code. Correctness only; nothing is measured."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from yardstick import harness

# in-process rehearsals run the four-chip cell: the test process has four
# virtual devices and the program gives rank i device i; the one-chip cells
# are rehearsed through run.py, which asks for one device
CELLS = {"4c": "osu-allreduce-4r4c.large-reuse"}


def rehearse(cell_name, traffic=None, seconds=0.4, trace=False, seed=3):
    """One in-process rehearsal of a cell, its traffic replaced by `traffic`
    (what a later PR would add as a new data file)."""
    import jax
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.Cell(manifest, cell_name, rehearse=True)
    if traffic is not None:
        cell.traffic = {**cell.traffic, **traffic}
    run = harness.Run(cell, seed, seconds, trace, True, 0.0)
    run.devices = list(jax.devices()[:cell.chips])
    readers = cell.readers()
    if trace:
        for _spec, mod in readers:
            if hasattr(mod, "prepare"):
                mod.prepare(run)
    cell.generator().run(run)
    run.values = {spec["name"]: mod.read(run) for spec, mod in readers}
    return run


OPS = ["allreduce", "allgather", "alltoall", "bcast", "reduce_scatter"]


@pytest.mark.parametrize("sync", ["per-block", "per-op"])
@pytest.mark.parametrize("buffers", ["reuse", "fresh"])
@pytest.mark.parametrize("op", OPS)
def test_every_op_buffers_and_sync_on_four_devices(op, buffers, sync):
    run = rehearse(CELLS["4c"], {"op": op, "buffers": buffers, "sync": sync,
                                 "chain": False, "counts": [64],
                                 "block_ops": 3})
    r = run.results
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 3
    assert r["attempted"] % 3 == 0
    # a rung synced per op yields latencies, one closed per block bandwidth
    assert set(r["metrics"]) == ({"coll_latency_p50"} if sync == "per-op"
                                 else {"coll_algbw"})
    assert all(v > 0 for v in r["metrics"].values())
    assert run.compiles_in_window is not None


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("buffers", ["reuse", "fresh"])
@pytest.mark.parametrize("sync", ["per-block", "per-op"])
def test_chained_allreduce_and_the_armed_lane(cell, buffers, sync):
    run = rehearse(CELLS[cell], {"buffers": buffers, "sync": sync,
                                 "counts": [300], "block_ops": 5})
    assert run.results["correct"] and run.results["failed"] == 0
    # the same buffers every call arm; a rotating pool never does
    assert run.values["armed_share.large"] == \
        (100.0 if buffers == "reuse" else 0.0)
    assert run.values["compiles_in_window"] == 0
    # the pvar phase span advances on the armed lane too
    assert run.values["rendezvous_wait_share.large"] > 0


def test_a_ladder_cycles_through_its_rungs_and_stays_armed():
    run = rehearse(CELLS["4c"], {"counts": [8, 4096], "block_ops": 2},
                   seconds=0.6)
    assert run.results["correct"] and run.results["attempted"] >= 4
    assert run.results["attempted"] % 4 == 0    # whole iterations only
    # a communicator per rung: neither demotes the other, nothing registers
    # (compiles) inside the window
    assert run.values["armed_share.large"] == 100.0
    assert run.values["compiles_in_window"] == 0


def test_an_iteration_mixes_rungs_closed_per_block_and_per_op():
    # what traffic/small-reuse.json is at full size: a chained field
    # reduction closed by a readback, then small ops synced one by one
    run = rehearse(CELLS["4c"], {
        "counts": [4096, 2], "sync": ["per-block", "per-op"],
        "chain": [True, False], "block_ops": [1, 7]}, seconds=0.6)
    r = run.results
    assert r["correct"] and r["failed"] == 0
    assert r["attempted"] >= 8 and r["attempted"] % 8 == 0
    assert set(r["metrics"]) == {"coll_algbw", "coll_latency_p50"}
    assert run.values["armed_share.large"] == 100.0
    assert run.values["compiles_in_window"] == 0
    assert run.facts["payload_bytes"] is None   # no one fold size


def test_per_rung_lists_must_match_counts():
    with pytest.raises(ValueError, match="counts has 2 rungs"):
        rehearse(CELLS["4c"], {"counts": [8, 16], "block_ops": [1, 2, 3]})


def test_reduce_max_min_and_other_dtypes():
    for reduce, dtype in (("max", "int32"), ("min", "float32"),
                          ("sum", "bfloat16")):
        run = rehearse(CELLS["4c"], {"reduce": reduce, "dtype": dtype,
                                     "chain": False, "counts": [128],
                                     "block_ops": 2})
        assert run.results["correct"], (reduce, dtype)


def test_a_short_chain_restarts_and_stays_exact():
    # bfloat16 holds integers to 256: a chain of 0/1 sums over 4 ranks must
    # restart every 85 ops, and every block's closed form must still hold
    run = rehearse(CELLS["4c"], {"dtype": "bfloat16", "counts": [64],
                                 "block_ops": 40}, seconds=0.5)
    assert run.results["correct"] and run.results["failed"] == 0
    assert run.results["attempted"] >= 120


def test_the_ingraph_reader_prepares_on_the_cells_devices():
    run = rehearse(CELLS["4c"], {"counts": [256], "block_ops": 2}, trace=True)
    assert run.prepared["ingraph_psum_algbw"] > 0
    assert run.trace is None            # a CPU rehearsal takes no profile
    assert run.values["backend_start_s"] is None    # no chip, no number


def test_a_wrong_result_is_caught_before_the_window(monkeypatch):
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    ref = harness.Cell(manifest, CELLS["4c"]).reference()
    honest = ref.expected
    monkeypatch.setattr(ref, "expected", lambda op, red, xs: [
        w + 1 for w in honest(op, red, xs)])
    with pytest.raises(RuntimeError, match="wrong before the window"):
        rehearse(CELLS["4c"], {"chain": False, "counts": [32],
                               "block_ops": 2})


def test_bad_traffic_is_refused():
    with pytest.raises(ValueError, match="chain is defined"):
        rehearse(CELLS["4c"], {"op": "bcast"})
    with pytest.raises(ValueError, match="operands"):
        rehearse(CELLS["4c"], {"operands": "host"})


def test_collective_reference_against_numpy():
    import jax.numpy as jnp
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    ref = harness.Cell(manifest, CELLS["4c"]).reference()
    rng = np.random.default_rng(0)
    xs = [rng.integers(0, 2, 8).astype(np.float32) for _ in range(4)]
    jx = [jnp.asarray(x) for x in xs]
    np.testing.assert_array_equal(ref.expected("allreduce", "sum", jx)[2],
                                  sum(xs))
    np.testing.assert_array_equal(ref.expected("allgather", "sum", jx)[1],
                                  np.concatenate(xs))
    np.testing.assert_array_equal(ref.expected("bcast", "sum", jx)[3], xs[0])
    a2a = ref.expected("alltoall", "sum", jx)
    np.testing.assert_array_equal(
        a2a[1], np.concatenate([x[2:4] for x in xs]))
    rs = ref.expected("reduce_scatter", "max", jx)
    np.testing.assert_array_equal(rs[3], np.maximum.reduce(xs)[6:8])
    np.testing.assert_array_equal(
        ref.chained_allreduce(jx[0], ref.fold("sum", jx[1:]), 5),
        xs[0] + 5 * (xs[1] + xs[2] + xs[3]))


def test_train_step_matches_its_plain_reference():
    run = rehearse("flagship-d1024-1c.step-b8s1024", seconds=0.5)
    r = run.results
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 3
    assert run.values["compiles_in_window"] == 0
    assert run.facts["flops_per_step"] > 0


def run_py(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), *args],
        capture_output=True, text=True, env=env, cwd=harness.ROOT, timeout=300)


def test_without_a_chip_the_run_fails_and_prints_no_result():
    p = run_py("--workload", CELLS["4c"], "--seed", "1", "--seconds", "1",
               "--trace", "0")
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_cpu_rehearsal_says_so_and_measures_nothing():
    p = run_py("--workload", "osu-allreduce-4r1c.small-reuse", "--seed", "2",
               "--seconds", "0.5", "--trace", "1", "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.splitlines()
    assert any("platform: cpu" in ln for ln in lines)
    assert any(ln == "host_overhead_us: not measured" for ln in lines)
    last = json.loads(lines[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert last["device"]["platform"] == "cpu" and last["correct"]
    # only exact counts carry a value off the chip
    assert set(last["metrics"]) <= {"compiles_in_window", "armed_share"}
    assert last["metrics"]["compiles_in_window"]["value"] == 0
