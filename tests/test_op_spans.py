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


def _job(calls=CALLS, own_chip=True, dup=False, persistent=False):
    """`calls` Allreduces of one DeviceBuffer pair on 4 ranks (`persistent`:
    rounds of one `Allreduce_init`, the lane that donates its accumulator).
    Returns (results by rank, pvar snapshot, per-signature auto-arm
    stats)."""
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
        req = MPI.Allreduce_init(send, recv, MPI.SUM, comm) \
            if persistent else None
        for _ in range(calls):
            if persistent:
                MPI.Start(req)
                MPI.Wait(req)
            else:
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


def _one_device(monkeypatch):
    """A one-chip host: every rank owns the same device."""
    from tpu_mpi._runtime import SpmdContext
    monkeypatch.setattr(SpmdContext, "device_for",
                        lambda self, rank: jax.devices()[0])


def _watches(monkeypatch):
    """Every hand-over to the watcher, as (cid, round, rank, stages)."""
    seen, watch = [], perfvars.watch

    def recording(sc, t0, *stages):
        seen.append((sc.cid, sc.round, sc.rank, stages))
        watch(sc, t0, *stages)
    monkeypatch.setattr(perfvars, "watch", recording)
    return seen


@pytest.mark.parametrize("persistent", [False, True],
                         ids=["plain_fold", "donated-chain"])
def test_one_device_fold_is_stamped_under_the_last_arriver(monkeypatch,
                                                           persistent):
    """Four rank threads on ONE device: a sampled armed round hands its
    fold's output, and nothing else, to the watcher, which stamps
    ``fold.done`` under the last arriver's ``op``, after its dispatch."""
    _one_device(monkeypatch)
    _sample(monkeypatch, 1)
    seen = _watches(monkeypatch)
    got, _snap, _sigs = _job(persistent=persistent)
    want = np.arange(COUNT, dtype=np.float32) * sum(range(1, N + 1))
    assert all(np.array_equal(got[r], want) for r in range(N))
    spans = tracectx.drain()
    rounds = _rounds(spans)
    armed = {k: v for k, v in rounds.items()
             if all(op["lane"] == "armed" for op, _ in v.values())}
    assert len(armed) >= CALLS - 4
    assert not [s for s in spans
                if s["name"] in ("copy_in.done", "copy_out.done")]
    for (cid, rnd), ranks in armed.items():
        (last,) = [r for r, (op, _) in ranks.items() if op["last"]]
        for r, (op, kids) in ranks.items():
            done = [k for k in kids if k["name"] == "fold.done"]
            assert len(done) == (r == last), (rnd, r)
            if r != last:
                continue
            (fold,) = [k for k in kids if k["name"] == "fold_dispatch"]
            assert done[0]["parent"] == op["span"]
            # the span begins where the combine began. Its end is the
            # device's: a CPU device can be done before the launching
            # thread is back from its combine (microseconds before
            # ``fold_dispatch`` ends; on the chip, 0.7 ms after)
            assert fold["t0"] <= done[0]["t0"] <= fold["t1"]
            assert done[0]["t0"] <= done[0]["t1"]
            assert done[0]["t1"] >= fold["t1"] - 1e-3
        # one watch a round, of the output alone: never the operands
        (stages,) = [st for c, rd, _r, st in seen if (c, rd) == (cid, rnd)]
        ((name, out),) = stages
        assert name == "fold.done" and out.shape == (COUNT,)
    compiled = {s["function"] for s in spans if s["name"] == "fold.compile"}
    assert compiled == ({"plain_fold", "chain"} if persistent
                        else {"plain_fold"})


def test_a_slot_donated_before_the_watcher_reaches_it_goes_unstamped(
        monkeypatch):
    """The donated lane's accumulator slots alternate, a pair a rank: the
    output of a rank's fold is donated again by the next fold but one that
    the same rank dispatches. A watcher that comes too late finds it gone:
    nothing is raised, that round has no ``fold.done``, and every round
    whose output is still alive has its own."""
    import threading
    _one_device(monkeypatch)
    _sample(monkeypatch, 1)

    class Gate:                     # holds the watcher until the job is over
        open = threading.Event()

        def block_until_ready(self):
            assert self.open.wait(60)

    first = perfvars._OpScope()
    first.cid, first.round, first.rank = "gate", 0, 0
    perfvars.watch(first, time.monotonic(), ("gate.done", Gate()))
    try:
        _job(calls=24, persistent=True)
    finally:
        Gate.open.set()
    _settle()
    spans = tracectx.drain()
    assert [s for s in spans if s["name"] == "gate.done"]
    stamped = sorted(s["round"] for s in spans if s["name"] == "fold.done")
    folded = {}                     # rank -> the rounds it dispatched
    for s in spans:
        if s["name"] == "op" and s["last"]:
            folded.setdefault(s["rank"], []).append(s["round"])
    alive = sorted(rnd for rounds in folded.values()
                   for rnd in sorted(rounds)[-2:])
    assert stamped == alive and len(alive) < 24
    watcher = [t for t in threading.enumerate()
               if t.name == "tpu_mpi-span-watcher"]
    assert len(watcher) == 1 and watcher[0].is_alive()


@pytest.mark.parametrize("placement", ["one-device", "four-devices"])
def test_no_watcher_is_started_with_sampling_off(monkeypatch, placement):
    if placement == "one-device":
        _one_device(monkeypatch)
    monkeypatch.setattr(perfvars, "_watch_q", None)     # as in a new process
    seen = _watches(monkeypatch)
    _job()
    _job(calls=4, persistent=True)
    assert perfvars._watch_q is None and not seen   # the queue comes with
    assert tracectx.drain() == []                   # the thread


@pytest.mark.parametrize("own_chip", [False, True],
                         ids=["star", "exchange"])
def test_four_devices_enqueue_one_watch_of_a_fold_a_round(monkeypatch,
                                                          own_chip):
    """Ranks on a device each: the exchange (operands on their own chips)
    and the star (every operand on rank 0's: ranks 1 to 3 register the
    single-chip fold, rank 0 the exchange, whose rounds fall back to the
    generic fold, which is watched only where bytes crossed chips) each
    hand a round's fold over once, not twice."""
    _sample(monkeypatch, 1)
    seen = _watches(monkeypatch)
    _job(own_chip=own_chip)
    _settle()
    folds = [(cid, rnd) for cid, rnd, _r, stages in seen
             if any(name == "fold.done" for name, _a in stages)]
    assert len(folds) == len(set(folds))
    spans = tracectx.drain()
    armed = {(op["cid"], op["round"]) for op in spans
             if op["name"] == "op" and op["lane"] == "armed"}
    registered = {(op["cid"], op["round"]) for op in spans
                  if op["name"] == "op" and op["lane"] == "armed"
                  and op["last"] and (own_chip or op["rank"] != 0)}
    assert len(armed) >= CALLS - 4 and registered
    assert registered <= set(folds)
    done = [(s["cid"], s["round"]) for s in spans if s["name"] == "fold.done"]
    assert sorted(done) == sorted(folds)
    if own_chip:        # the operands were ready, then the output
        assert all([n for n, _a in st if n != "copy_out.done"]
                   in (["copy_in.done", "fold.done"], [])
                   for _c, _rd, _r, st in seen)


def test_t_prev_follows_the_thread(monkeypatch):
    """``t_prev`` on an ``op`` span is when the thread's previous host-path
    op ended, whatever its communicator: absent on a thread's first."""
    _one_device(monkeypatch)
    _sample(monkeypatch, 1)

    def body():
        comm = MPI.COMM_WORLD
        other = MPI.Comm_dup(comm)
        dev = comm.device
        send = MPI.DeviceBuffer(jnp.ones(COUNT, jnp.float32, device=dev),
                                device=dev)
        recv = MPI.DeviceBuffer(jnp.zeros(COUNT, jnp.float32, device=dev),
                                device=dev)
        for on in (comm, comm, other, comm):
            MPI.Allreduce(send, recv, MPI.SUM, on)

    run_spmd(body, N)
    ops = [s for s in tracectx.drain() if s["name"] == "op"]
    for r in range(N):
        mine = sorted((o for o in ops if o["rank"] == r),
                      key=lambda o: o["t0"])
        assert len(mine) == 4 and len({o["cid"] for o in mine}) == 2
        assert "t_prev" not in mine[0]
        for before, after in zip(mine, mine[1:]):
            assert after["t_prev"] == before["t1"] <= after["t0"]
        assert mine[2]["cid"] != mine[1]["cid"] == mine[3]["cid"]


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
