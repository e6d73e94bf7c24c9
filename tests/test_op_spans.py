"""The span tree of a host-path collective (PR 23, docs/observability.md
"Op spans"): with ``trace_sample > 0`` every Allreduce of an SPMD rank
thread publishes one ``op`` span with named children into tracectx's
buffer; with it off the path keeps sums only. On the CPU-sim mesh; nothing
here is a timing."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_mpi as MPI
from tpu_mpi import config, perfvars, tracectx
from tpu_mpi.testing import run_spmd

N, CALLS, COUNT = 4, 12, 1024
NBYTES = COUNT * 4
RDV = ("rdv_skew", "rdv_fold", "rdv_wake")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("TPU_MPI_PVARS", raising=False)
    monkeypatch.delenv("TPU_MPI_TRACE_SAMPLE", raising=False)
    config.load(refresh=True)
    perfvars.pcontrol(1)
    perfvars.reset()
    tracectx.reset()
    yield
    monkeypatch.delenv("TPU_MPI_TRACE_SAMPLE", raising=False)
    config.load(refresh=True)
    perfvars.reset()
    tracectx.reset()


def _sample(monkeypatch, rate):
    monkeypatch.setenv("TPU_MPI_TRACE_SAMPLE", str(rate))
    config.load(refresh=True)


def _job(calls=CALLS, own_chip=True, dup=False):
    """`calls` Allreduces of one DeviceBuffer pair on 4 ranks. Returns
    (results by rank, pvar snapshot, per-signature auto-arm stats)."""
    out = {}

    def body():
        comm = MPI.COMM_WORLD
        if dup:
            comm = MPI.Comm_dup(comm)
        r = comm.rank()
        dev = comm.device if own_chip else jax.devices()[0]
        send = MPI.DeviceBuffer(
            jnp.arange(COUNT, dtype=jnp.float32, device=dev) * (r + 1),
            device=dev)
        recv = MPI.DeviceBuffer(jnp.zeros(COUNT, jnp.float32, device=dev),
                                device=dev)
        for _ in range(calls):
            MPI.Allreduce(send, recv, MPI.SUM, comm)
        out[r] = np.asarray(recv.value)

    before = _auto_stats()
    run_spmd(body, N)
    _settle()
    after = _auto_stats()
    # the plan cache's statistics outlive a job: what this job added
    added = {k: (v[0] - before.get(k, (0, 0))[0],
                 v[1] - before.get(k, (0, 0))[1]) for k, v in after.items()}
    return out, perfvars.snapshot(), {k: v for k, v in added.items() if v[0]}


def _auto_stats():
    from tpu_mpi.overlap import plans
    return {k: (s["calls"], s["hits"])
            for k, s in plans.stats()["auto"]["signatures"].items()}


def _settle():
    """Let the watcher thread publish what it was handed."""
    deadline = time.monotonic() + 5.0
    q = perfvars._watch_q
    while q is not None and not q.empty() and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.05)


def _rounds(spans):
    """{(cid, round): {rank: (op span, [descendants])}} of allreduce ops."""
    by_id = {s["span"]: s for s in spans}
    out = {}
    for s in spans:
        if s["name"] == "op" and s["coll"] == "allreduce":
            out.setdefault((s["cid"], s["round"]), {})[s["rank"]] = (s, [])
    for s in spans:
        top = s
        while top["parent"] in by_id:
            top = by_id[top["parent"]]
        if top is not s and top["name"] == "op" \
                and top.get("coll") == "allreduce":
            out[top["cid"], top["round"]][top["rank"]][1].append(s)
    return out


def test_armed_rounds_have_one_tree_per_rank(monkeypatch):
    _sample(monkeypatch, 1)
    _job()
    spans = tracectx.drain()
    by_id = {s["span"]: s for s in spans}
    rounds = _rounds(spans)
    armed = {k: v for k, v in rounds.items()
             if all(op["lane"] == "armed" for op, _ in v.values())}
    assert len(armed) >= CALLS - 4, sorted(rounds)
    for (cid, rnd), ranks in armed.items():
        assert sorted(ranks) == list(range(N)), (rnd, sorted(ranks))
        assert all(op["nbytes"] == NBYTES for op, _ in ranks.values())
        last = [r for r, (op, _) in ranks.items() if op["last"]]
        assert len(last) == 1, (rnd, last)
        for r, (op, kids) in ranks.items():
            names = [k["name"] for k in kids]
            assert names.count("front_door") == 1 and "lock" in names
            # the annotation opened at the channel's door, between two reads
            (door,) = [k for k in kids if k["name"] == "front_door"]
            assert op["t0"] < op["t_ann"] <= door["t1"]
            assert "copyout" in names
            if r == last[0]:
                # one launch over the ranks' chips: nothing is copied
                assert names.count("fold_dispatch") == 1
                assert "colocate" not in names and "rendezvous" not in names
            else:
                assert "fold_dispatch" not in names
                (wait,) = [k for k in kids if k["name"] == "rendezvous"]
                parts = [k for k in kids if k["name"] in RDV]
                assert [p["name"] for p in parts] == list(RDV)
                assert all(p["parent"] == wait["span"] for p in parts)
                tiled = sum(p["t1"] - p["t0"] for p in parts)
                assert abs(tiled - (wait["t1"] - wait["t0"])) < 50e-6
            for k in kids:          # the watcher's spans end when the
                if k["name"].endswith(".done"):     # device did, later
                    continue
                parent = by_id[k["parent"]]
                assert parent["t0"] <= k["t0"] <= k["t1"] <= parent["t1"], \
                    (k["name"], parent["name"])
    # the first calls ran the legacy lane, under the same tree
    legacy = [op for v in rounds.values() for op, _ in v.values()
              if op["lane"] == "legacy"]
    assert legacy and all(op["coll"] == "allreduce" for op in legacy)


def test_spans_are_a_pure_observer(monkeypatch):
    off, snap_off, sigs_off = _job()
    assert tracectx.drain() == [] and tracectx.dropped() == 0
    waited = sum(c["phase_s"]["rendezvous"] for c in snap_off["comms"])
    parts = sum(c["phase_s"][p] for c in snap_off["comms"] for p in RDV)
    assert waited > 0 and abs(waited - parts) < 1e-6
    assert all(c["phase_s"]["front_door"] > 0 and c["phase_s"]["lock"] > 0
               for c in snap_off["comms"] if c["cid"] == 0)
    perfvars.reset()
    _sample(monkeypatch, 1)
    on, _snap, sigs_on = _job()
    assert tracectx.drain()
    for r in range(N):
        assert on[r].tobytes() == off[r].tobytes()
    assert sigs_on == sigs_off and len(sigs_on) == N
    assert all(calls == CALLS and hits >= CALLS - 4
               for calls, hits in sigs_on.values())


def test_sampling_keeps_the_same_rounds_on_every_rank(monkeypatch):
    _sample(monkeypatch, 0.5)
    _job(calls=40)
    rounds = _rounds(tracectx.drain())
    assert 0 < len(rounds) < 40
    assert all(sorted(v) == list(range(N)) for v in rounds.values())


def test_store_keeps_the_first_spans_and_counts_the_rest(monkeypatch):
    monkeypatch.setattr(tracectx, "_OP_SPAN_CAP", 64)
    _sample(monkeypatch, 1)
    _job()
    spans = tracectx.drain()
    refused = tracectx.dropped()
    assert 0 < len(spans) <= 64 and refused > 0
    first = min(s["round"] for s in spans if s["name"] == "op")
    kept = {s["round"] for s in spans if s["name"] == "op"}
    assert kept == set(range(first, first + len(kept)))     # no hole: oldest
    # request spans are a ring of their own: a serve process that has run
    # for long still records its latest requests
    monkeypatch.setattr(tracectx, "_SPAN_CAP", 8)
    ctx = tracectx.TraceCtx.mint()
    for i in range(20):
        tracectx.emit_span(ctx, f"req{i}", "test", 0.0, 1.0)
    names = [s["name"] for s in tracectx.drain() if s["who"] == "test"]
    assert names[-1] == "req19" and "req0" not in names and len(names) <= 8
    assert tracectx.dropped() > refused
    assert len([s for s in tracectx.drain() if s["name"] == "op"]) \
        == len([s for s in spans if s["name"] == "op"])


@pytest.mark.parametrize("own_chip", [False, True],
                         ids=["one-device", "four-devices"])
def test_xchip_bytes_count_copies_between_devices(monkeypatch, own_chip):
    _job(own_chip=own_chip)
    comms = [c for c in perfvars.snapshot()["comms"] if c["cid"] == 0]
    moved = sum(c["xchip_bytes"] for c in comms)
    copies = sum(c["xchip_copies"] for c in comms)
    folds = sum(c["ingraph_folds"] for c in comms)
    if own_chip:
        # 2 (n-1) payloads cross chips a round by either route: the star's
        # copies (n-1 operands in, n-1 results out) until the streak arms,
        # then inside the one executable over the chips, which copies nothing
        assert moved == CALLS * (N - 1) * 2 * NBYTES
        assert CALLS - 4 <= folds < CALLS
        assert copies == (CALLS - folds) * (N - 1) * 2
    else:       # buffers on one device: the star, and nothing to move
        assert moved == 0 and copies == 0 and folds == 0


def test_watcher_stamps_the_device_end_across_devices(monkeypatch):
    _sample(monkeypatch, 1)
    _job()
    _settle()
    spans = tracectx.drain()
    by_id = {s["span"]: s for s in spans}
    for name in ("copy_in.done", "fold.done", "copy_out.done"):
        got = [s for s in spans if s["name"] == name]
        assert got, name
        for s in got:
            op = by_id[s["parent"]]
            assert op["name"] == "op" and op["round"] == s["round"]
            assert op["t0"] <= s["t0"] <= s["t1"]
    # the star's copies belong to the calls before the streak armed; an
    # armed round has none, one ``fold.done`` and every rank's result home
    moved = [s for s in spans if s["name"] == "colocate"]
    assert moved and all(by_id[s["trace"]]["lane"] == "legacy"
                         and s["bytes_moved"] == (N - 1) * NBYTES
                         and s["copies"] == N - 1 for s in moved)
    armed = {(op["cid"], op["round"]) for op in spans
             if op["name"] == "op" and op["lane"] == "armed"}
    assert len(armed) >= CALLS - 4
    for name, per_round in (("fold.done", 1), ("copy_out.done", N)):
        got = [(s["cid"], s["round"]) for s in spans if s["name"] == name]
        assert all(got.count(r) == per_round for r in armed), name
    folds = sum(c["ingraph_folds"] for c in perfvars.snapshot()["comms"])
    assert folds == len(armed)


def test_plan_register_once_per_signature(monkeypatch):
    from tpu_mpi import collective
    monkeypatch.setattr(collective, "_exchange_compiled", type(
        collective._exchange_compiled)())   # as in a process's first job
    _sample(monkeypatch, 1)
    _job(dup=True)
    setup = [s for s in tracectx.drain() if s["trace"].startswith("setup:")]
    regs = [s for s in setup if s["name"] == "plan.register"]
    assert sorted(s["who"] for s in regs) == [f"rank {r}" for r in range(N)]
    by_id = {s["span"]: s for s in setup}
    # four ranks register, one of them compiles the fold over their chips
    (compiled,) = [s for s in setup if s["name"] == "fold.compile"]
    assert by_id[compiled["parent"]]["name"] == "plan.register"
    assert compiled["function"] == "exchange_fold"
    arming = perfvars.snapshot()["arming_s"]
    assert 0 < arming <= sum(s["t1"] - s["t0"] for s in setup
                             if s["parent"] is None) + 1e-9


def test_arming_is_summed_with_spans_off():
    _job()
    assert tracectx.drain() == []
    assert perfvars.snapshot()["arming_s"] > 0


def test_span_dump_and_chrome_export_take_op_trees(monkeypatch, tmp_path):
    from tpu_mpi.analyze import timeline
    _sample(monkeypatch, 1)
    _job(calls=4)
    path = tracectx.dump_spans(str(tmp_path / "spans.json"))
    loaded = tracectx.load_spans(path)
    assert {s["name"] for s in loaded} >= {"op", "front_door", "copyout"}
    chrome = timeline.spans_to_chrome(loaded)
    names = {e.get("name") for e in chrome["traceEvents"]}
    assert {"op", "front_door", "lock"} <= names
