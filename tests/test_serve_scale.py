"""Broker at production scale (docs/serving.md "Scale-out"): procs-pool
backend, the zero-copy frame path, and multi-broker routing.

Layout mirrors the subsystem:

- **CidShard units**: the ``index/count`` grammar, typed rejection of bad
  specs, and the disjointness property — ranges of distinct shards never
  overlap, which is what makes the cross-broker T208 invariant sound.
- **Router assignment units**: HRW hashing is deterministic, balanced, and
  stable — removing a broker remaps ONLY the tenants it hosted.
- **merge_stats units**: fleet merge sums counter blocks, unions ledger
  tenants (collisions disambiguated), and preserves T208 under summing.
- **Zero-copy protocol units**: contiguous payloads cross the frame hop
  with zero marshal copies (pvar-counted), non-contiguous pays exactly
  one, the legacy lane pays one per blob, and frames wider than the iovec
  limit still round-trip bitwise.
- **Router integration** (threads backend): sessions pin to their HRW
  home inside its cid shard, a cross-broker cid is a typed SessionError,
  merged stats keep T208, junk first frames get a typed reply.
- **Procs backend + chaos** (``slow``): the contract suite's core ops on
  real worker processes with the copies/op gate, a mid-stream SIGKILL
  surfacing as typed errors with bitwise-stable survivors after the
  elastic restore, and a 1k-tenant soak through the router.
"""

import os
import signal
import socket
import threading
import time

import numpy as np
import pytest

from tpu_mpi import config, perfvars, serve
from tpu_mpi.error import MPIError, SessionError
from tpu_mpi.serve import protocol
from tpu_mpi.serve.broker import _stats_client, _ThreadPool
from tpu_mpi.serve.ledger import NS_FLOOR, CidShard
from tpu_mpi.serve.router import Router, assign_broker, merge_stats


# ---------------------------------------------------------------------------
# CidShard: the disjoint cid ranges behind multi-broker T208
# ---------------------------------------------------------------------------

def test_cid_shard_parse_and_bounds():
    s = CidShard.parse("2/4")
    assert (s.index, s.count) == (2, 4)
    assert s.base == NS_FLOOR + 2 * CidShard.SPAN
    assert s.limit == s.base + CidShard.SPAN
    assert s.owns(s.base) and s.owns(s.limit - 1)
    assert not s.owns(s.limit) and not s.owns(s.base - 1)
    assert not s.owns(("shrink", s.base, 1))      # tuple cids are pool-side
    # ""/None -> the single-broker whole-range shard
    d = CidShard.parse("")
    assert (d.index, d.count, d.base) == (0, 1, NS_FLOOR)


@pytest.mark.parametrize("spec", ["x", "1", "3/2", "-1/2", "1/0", "a/b"])
def test_cid_shard_bad_specs_typed(spec):
    with pytest.raises(MPIError):
        CidShard.parse(spec)


def test_cid_shard_disjointness_property():
    """Shards of one fleet are pairwise disjoint and tile the range
    contiguously — by construction, for every fleet width."""
    for count in range(1, 9):
        shards = [CidShard(i, count) for i in range(count)]
        for a in shards:
            for b in shards:
                if a is b:
                    continue
                assert a.limit <= b.base or b.limit <= a.base, (a, b)
                for cid in (b.base, b.limit - 1):
                    assert not a.owns(cid)
        for i in range(count - 1):
            assert shards[i].limit == shards[i + 1].base


def test_thread_pool_lease_refused_typed_when_shard_exhausted():
    pool = _ThreadPool(2, CidShard(0, 2))
    pool.ctx._ns_next_base = pool.shard.limit - 4
    with pytest.raises(SessionError, match="shard .* exhausted"):
        pool.lease_ns("hog", span=256)
    base, limit = pool.info()["shard"]
    assert (base, limit) == (pool.shard.base, pool.shard.limit)


# ---------------------------------------------------------------------------
# Router assignment: deterministic, balanced, minimally-disruptive
# ---------------------------------------------------------------------------

BROKERS = [f"127.0.0.1:{9000 + i}" for i in range(4)]


def test_assign_broker_deterministic():
    for t in ("alice", "bob", "", "tenant-with-|-pipe"):
        assert assign_broker(t, BROKERS) == assign_broker(t, list(BROKERS))
    # order of the broker list is irrelevant
    assert (assign_broker("alice", BROKERS)
            == assign_broker("alice", BROKERS[::-1]))


def test_assign_broker_stability_under_removal():
    """The HRW property ISSUE 15 buys: dropping a broker remaps only the
    tenants it hosted; everyone else keeps their home (no fleet-wide
    rehash, unlike modulo assignment)."""
    tenants = [f"t{i}" for i in range(300)]
    home = {t: assign_broker(t, BROKERS) for t in tenants}
    for gone in BROKERS:
        rest = [b for b in BROKERS if b != gone]
        for t in tenants:
            if home[t] != gone:
                assert assign_broker(t, rest) == home[t]


def test_assign_broker_spreads_load():
    tenants = [f"t{i}" for i in range(300)]
    counts = {b: 0 for b in BROKERS}
    for t in tenants:
        counts[assign_broker(t, BROKERS)] += 1
    assert all(c > 0 for c in counts.values()), counts
    assert max(counts.values()) < 300 * 0.6, counts


def test_assign_broker_empty_list_raises():
    with pytest.raises(MPIError):
        assign_broker("alice", [])


# ---------------------------------------------------------------------------
# merge_stats: the fleet view
# ---------------------------------------------------------------------------

def _report(i, tenants, totals):
    return {"address": f"b{i}", "backend": "threads",
            "shard": {"index": i, "count": 2},
            "pool": {"capacity": 2}, "totals": dict(totals),
            "serve_frame": {"ops": 10 * (i + 1), "copies": i},
            "queue": {"rejected_busy": i, "tenants": {}},
            "ledger": {"quota_bytes": 100, "flushes": i + 1,
                       "last_flush": 1000.0 + i, "tenants": tenants},
            "tenants_attached": sorted(tenants)}


def test_merge_stats_sums_counters_and_keeps_t208():
    r0 = _report(0, {"alice": {"measured": {"bytes_sent": 30}}},
                 {"bytes_sent": 30})
    r1 = _report(1, {"bob": {"measured": {"bytes_sent": 12}}},
                 {"bytes_sent": 12})
    m = merge_stats([r0, r1])
    assert m["broker_count"] == 2
    assert m["totals"] == {"bytes_sent": 42}
    assert m["serve_frame"] == {"ops": 30, "copies": 1}
    assert m["queue"]["rejected_busy"] == 1
    assert m["ledger"]["quota_bytes"] == 200
    assert m["ledger"]["last_flush"] == 1001.0
    assert [b["address"] for b in m["brokers"]] == ["b0", "b1"]
    # T208 across brokers: summed measured rows == summed pool totals
    summed = sum(row["measured"]["bytes_sent"]
                 for row in m["ledger"]["tenants"].values())
    assert summed == m["totals"]["bytes_sent"]


def test_merge_stats_disambiguates_tenant_collision():
    r0 = _report(0, {"alice": {"admitted_ops": 1}}, {})
    r1 = _report(1, {"alice": {"admitted_ops": 2}}, {})
    m = merge_stats([r0, r1])
    assert m["ledger"]["tenants"]["alice"] == {"admitted_ops": 1}
    assert m["ledger"]["tenants"]["alice@b1"] == {"admitted_ops": 2}


# ---------------------------------------------------------------------------
# Zero-copy frame path: the pvar-gated marshal count
# ---------------------------------------------------------------------------

def _frame_round_trip(arrays, kind=protocol.OP, meta=None):
    """send_frame -> recv_frame over a unix socketpair, sender threaded so
    wide frames can't deadlock on the kernel buffer. Returns
    (received arrays, serve_frame pvar delta)."""
    a, b = socket.socketpair()
    before = perfvars.serve_frame_snapshot()
    err = []

    def _send():
        try:
            protocol.send_frame(a, kind, dict(meta or {"oid": 1}), arrays)
        except BaseException as e:             # noqa: BLE001
            err.append(e)

    t = threading.Thread(target=_send, daemon=True)
    t.start()
    got_kind, got_meta, got = protocol.recv_frame(b)
    t.join(10)
    a.close()
    b.close()
    assert not err, err
    assert got_kind == kind
    after = perfvars.serve_frame_snapshot()
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in set(after) | set(before)}
    return got, delta


def test_zero_copy_contiguous_counts_zero_copies():
    arrays = [np.arange(1024, dtype=np.float32),
              np.array(7, dtype=np.int64),          # 0-d still a view
              np.random.default_rng(0).standard_normal((8, 8))]
    got, delta = _frame_round_trip(arrays)
    for want, g in zip(arrays, got):
        assert g.dtype == want.dtype and g.shape == want.shape
        assert g.tobytes() == np.asarray(want).tobytes()
    assert delta["ops"] == 1
    assert delta["copies"] == 0
    assert delta["sg_writes"] >= 1
    assert delta["zc_bytes"] == sum(np.asarray(x).nbytes for x in arrays)


def test_zero_copy_noncontiguous_pays_exactly_one_copy():
    arr = np.arange(64, dtype=np.float32)[::2]     # strided view
    assert not arr.flags.c_contiguous
    got, delta = _frame_round_trip([arr])
    assert got[0].tobytes() == np.ascontiguousarray(arr).tobytes()
    assert delta["copies"] == 1 and delta["ops"] == 1


def test_zero_copy_frame_wider_than_iovec_limit_round_trips():
    """A frame with more views than _IOV_MAX must resume sendmsg across
    calls and still land bitwise-intact."""
    arrays = [np.full(3, i, np.int32) for i in range(600)]
    got, delta = _frame_round_trip(arrays)
    assert len(got) == 600
    for i, g in enumerate(got):
        assert np.array_equal(g, np.full(3, i, np.int32))
    assert delta["sg_writes"] >= 2                 # forced >1 sendmsg call
    assert delta["copies"] == 0


def test_legacy_lane_counts_a_copy_per_blob(monkeypatch):
    monkeypatch.setenv("TPU_MPI_SERVE_ZEROCOPY", "0")
    config.load(refresh=True)
    try:
        arrays = [np.ones(16, np.float32), np.zeros(4, np.int64)]
        got, delta = _frame_round_trip(arrays)
        for want, g in zip(arrays, got):
            assert g.tobytes() == want.tobytes()
        assert delta["copies"] == 2 and delta["sg_writes"] == 0
        assert delta["zc_bytes"] == 0
    finally:
        monkeypatch.delenv("TPU_MPI_SERVE_ZEROCOPY")
        config.load(refresh=True)


# ---------------------------------------------------------------------------
# Router integration: a 2-broker fleet on the threads backend
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet():
    b0 = serve.Broker(nranks=2, token="tk", backend="threads", shard="0/2")
    b1 = serve.Broker(nranks=2, token="tk", backend="threads", shard="1/2")
    b0.run_in_thread()
    b1.run_in_thread()
    router = Router([b0.address, b1.address], token="tk")
    router.run_in_thread()
    yield router, b0, b1
    router.close()
    b0.close()
    b1.close()


def _home_of(tenant, b0, b1):
    return b0 if assign_broker(tenant, [b0.address, b1.address]) \
        == b0.address else b1


def test_router_pins_sessions_to_home_shard(fleet):
    router, b0, b1 = fleet
    seen = set()
    # homes hash over the brokers' ephemeral addresses: with 5 tenants all
    # landed on one broker once in 16 runs; 16 make that once in 32768
    for t in [f"tenant{i}" for i in range(16)]:
        s = serve.attach(router.address, tenant=t, token="tk")
        try:
            got = s.allreduce(np.ones(4, np.int64))
            assert np.array_equal(got, np.full(4, 2))
            home = _home_of(t, b0, b1)
            seen.add(home.pool.shard.index)
            # the leased cid range proves which broker owns the session
            assert home.pool.shard.owns(s.cid_base)
            assert home.pool.shard.owns(s.cid_limit - 1)
        finally:
            s.detach()
    assert seen == {0, 1}       # both brokers actually took tenants


def test_router_cross_broker_cid_is_typed_rejection(fleet):
    router, b0, b1 = fleet
    s = serve.attach(router.address, tenant="alice", token="tk")
    try:
        other = b1 if _home_of("alice", b0, b1) is b0 else b0
        stolen = serve.SessionComm(s, other.pool.shard.base + 5, 2)
        with pytest.raises(SessionError, match="outside its lease"):
            s.allreduce(np.ones(4), comm=stolen)
        # the rejection poisoned nothing
        assert np.array_equal(s.allreduce(np.ones(4, np.int64)),
                              np.full(4, 2))
    finally:
        s.detach()


def test_router_merged_stats_keep_t208(fleet):
    router, b0, b1 = fleet
    rep = _stats_client(router.address, "tk")
    assert rep["broker_count"] == 2
    assert len(rep["brokers"]) == 2
    totals = rep["totals"]
    summed = {}
    for e in rep["ledger"]["tenants"].values():
        for k, v in (e.get("measured") or {}).items():
            summed[k] = summed.get(k, 0) + v
    assert summed == {k: v for k, v in totals.items() if k in summed} \
        and set(summed) == set(totals)


def test_router_keyless_hello_gets_generated_tenant(fleet):
    router, b0, b1 = fleet
    s = serve.attach(router.address, token="tk")
    try:
        assert s.tenant                       # router or broker minted one
        assert np.array_equal(s.allreduce(np.ones(4, np.int64)),
                              np.full(4, 2))
    finally:
        s.detach()


def test_router_rejects_non_session_first_frame(fleet):
    router, _, _ = fleet
    sock = protocol.connect(router.address)
    try:
        protocol.send_frame(sock, protocol.PING, {"oid": 1})
        kind, meta, _ = protocol.recv_frame(sock)
        assert kind == protocol.ERROR
        with pytest.raises(SessionError, match="expects HELLO or STATS"):
            protocol.raise_for_error(meta)
    finally:
        sock.close()


def test_router_redirect_mode_goes_direct(fleet):
    """Redirect mode: the router answers HELLO with the home broker and
    the client re-dials it — after attach the session socket is a DIRECT
    connection to the home broker (the benchmark's headline lane)."""
    _, b0, b1 = fleet
    r = Router([b0.address, b1.address], token="tk", mode="redirect")
    r.run_in_thread()
    try:
        s = serve.attach(r.address, tenant="alice", token="tk")
        try:
            home = _home_of("alice", b0, b1)
            assert s.address == home.address        # re-dialed, not spliced
            assert home.pool.shard.owns(s.cid_base)
            assert np.array_equal(s.allreduce(np.ones(4, np.int64)),
                                  np.full(4, 2))
        finally:
            s.detach()
    finally:
        r.close()


def test_router_bad_mode_is_typed():
    with pytest.raises(MPIError, match="router mode"):
        Router(["127.0.0.1:9"], token="tk", mode="teleport")


def test_router_unreachable_home_is_typed():
    dead = Router(["127.0.0.1:9"], token="tk")   # discard port: nothing there
    dead.run_in_thread()
    try:
        with pytest.raises((SessionError, MPIError)):
            serve.attach(dead.address, tenant="alice", token="tk")
    finally:
        dead.close()


# ---------------------------------------------------------------------------
# Procs backend + chaos + soak (slow: real worker processes)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_procs_backend_contract_and_copy_gate():
    b = serve.Broker(nranks=2, token="tk", backend="procs")
    b.run_in_thread()
    try:
        assert b.pool.kind == "procs"
        s = serve.attach(b.address, tenant="alice", token="tk")
        try:
            parts = [np.arange(64, dtype=np.float32),
                     np.ones(64, np.float32)]
            want = parts[0] + parts[1]
            for _ in range(4):
                assert s.allreduce(parts).tobytes() == want.tobytes()
            assert np.array_equal(s.bcast(np.full(8, 3.0), root=0),
                                  np.full(8, 3.0))
            s.barrier()
            dup = s.comm_dup()
            assert s.cid_base <= dup.cid < s.cid_limit
            assert np.array_equal(
                s.allreduce(np.ones(4, np.int64), comm=dup), np.full(4, 2))
            s.comm_free(dup)
            st = s.stats()
            assert st["backend"] == "procs"
            sf = st["serve_frame"]
            assert sf["ops"] > 0
            assert sf["copies_per_op"] <= 1.0, sf   # the zero-copy gate
        finally:
            s.detach()
    finally:
        b.close()


@pytest.mark.slow
def test_procs_sigkill_is_typed_and_survivors_bitwise_stable():
    """Satellite 1 + the CI chaos assertion: SIGKILL a pool worker
    mid-stream; the window yields TYPED errors (never hangs), the elastic
    restore grows a replacement process via Comm_spawn, and the surviving
    lease computes bitwise-identical results afterwards."""
    b = serve.Broker(nranks=3, token="tk", backend="procs", elastic=True)
    b.run_in_thread()
    try:
        s = serve.attach(b.address, tenant="alice", token="tk")
        try:
            want = np.full(4, 3, np.int64)
            before = s.allreduce(np.ones(4, np.int64))
            assert before.tobytes() == want.tobytes()
            os.kill(b.pool._links[2].pid, signal.SIGKILL)
            deadline = time.monotonic() + 90
            after = None
            while time.monotonic() < deadline:
                try:
                    after = s.allreduce(np.ones(4, np.int64))
                    break
                except MPIError:
                    time.sleep(0.25)          # typed during the window: fine
            assert after is not None, "pool never restored"
            assert after.tobytes() == before.tobytes()
            resize = b.elastic_state["last_resize"]
            assert resize["grew"] >= 1 and resize["shrunk"] >= 1
            assert len(b.pool.healthy()) == 3
        finally:
            s.detach()
    finally:
        b.close()


@pytest.mark.slow
def test_router_1k_tenant_soak():
    """1000 tenants through the router on a 2-broker fleet: every attach
    succeeds, every collective is correct, both brokers take load, and the
    merged ledger still satisfies T208 at the end."""
    b0 = serve.Broker(nranks=2, token="tk", backend="threads", shard="0/2",
                      max_tenants=2048)
    b1 = serve.Broker(nranks=2, token="tk", backend="threads", shard="1/2",
                      max_tenants=2048)
    b0.run_in_thread()
    b1.run_in_thread()
    router = Router([b0.address, b1.address], token="tk")
    router.run_in_thread()
    try:
        for i in range(1000):
            s = serve.attach(router.address, tenant=f"t{i}", token="tk")
            try:
                got = s.allreduce(np.ones(4, np.int64))
                assert np.array_equal(got, np.full(4, 2)), (i, got)
            finally:
                s.detach()
        rep = _stats_client(router.address, "tk")
        soaked = [t for t in rep["ledger"]["tenants"] if t.startswith("t")]
        assert len(soaked) == 1000
        per_broker = [sum(1 for t in (b.ledger.report()["tenants"])
                          if t.startswith("t")) for b in (b0, b1)]
        assert all(n > 100 for n in per_broker), per_broker
        totals = rep["totals"]
        summed = {}
        for e in rep["ledger"]["tenants"].values():
            for k, v in (e.get("measured") or {}).items():
                summed[k] = summed.get(k, 0) + v
        assert summed == totals
    finally:
        router.close()
        b0.close()
        b1.close()
