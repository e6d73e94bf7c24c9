"""Hierarchical two-level collectives (docs/performance.md "Hierarchical
collectives"): the domain map, the topology key, the hier eligibility /
heuristic gates, topology-keyed fleet-DB isolation, and the proc-tier
composite runners — which must be bitwise-identical to the star
rendezvous, degrade to the flat tier on one-domain worlds, and fail
loudly (MPIError on every rank) when one rank drops off the hierarchy.
The bandit test proves "hier" participates as an exploration arm in
rank-identical lockstep, observed through the event IR's ``algo`` field.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import tpu_mpi as MPI
from tpu_mpi import config, topology, tune
from tpu_mpi.analyze import events as ev
from tpu_mpi.testing import run_spmd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def clean_config(monkeypatch):
    for k in ("TPU_MPI_COLL_ALGO", "TPU_MPI_TUNE_TABLE", "TPU_MPI_TUNE_DB",
              "TPU_MPI_DOMAINS", "TPU_MPI_HIER_MIN_BYTES", "TPU_MPI_TRACE",
              "TPU_MPI_TUNE_EXPLORE", "TPU_MPI_PVARS"):
        monkeypatch.delenv(k, raising=False)
    config.load(refresh=True)
    yield
    config.load(refresh=True)


class _FakeCtx:
    def __init__(self, addrs):
        self.addrs = addrs


# -- domain map / topology key -----------------------------------------------

def test_domain_map_from_env_override(monkeypatch):
    monkeypatch.setenv("TPU_MPI_DOMAINS", "2")
    config.load(refresh=True)
    assert topology.domain_map(None, tuple(range(8))) == (
        0, 0, 0, 0, 1, 1, 1, 1)
    assert topology.domain_shape(topology.domain_map(None, range(8))) == (2, 4)
    assert topology.domain_count(None, tuple(range(8))) == 2
    # 2 domains of 1 rank each is not a hierarchy
    assert topology.domain_count(None, (0, 1)) == 0
    monkeypatch.setenv("TPU_MPI_DOMAINS", "3")
    config.load(refresh=True)
    assert topology.domain_map(None, tuple(range(8))) is None   # 8 % 3
    monkeypatch.setenv("TPU_MPI_DOMAINS", "1")
    config.load(refresh=True)
    # explicit k=1 means "treat the world as one domain": flat
    assert topology.domain_map(None, tuple(range(8))) is None


def test_domain_map_derived_from_hosts():
    ctx = _FakeCtx(["10.0.0.1:70", "10.0.0.1:71", "10.0.0.2:70",
                    "10.0.0.2:71"])
    assert topology.domain_map(ctx, (0, 1, 2, 3)) == (0, 0, 1, 1)
    assert topology.domain_count(ctx, (0, 1, 2, 3)) == 2
    one_host = _FakeCtx(["10.0.0.1:70", "10.0.0.1:71"])
    assert topology.domain_map(one_host, (0, 1)) is None
    assert topology.domain_count(None, (0, 1)) == 0


def test_domain_shape_rejects_ragged_and_interleaved():
    assert topology.domain_shape(None) is None
    assert topology.domain_shape((0, 1, 0, 1)) is None      # interleaved
    assert topology.domain_shape((0, 0, 0, 1)) is None      # ragged sizes
    assert topology.domain_shape((0, 0, 1, 1, 2, 2)) == (3, 2)


def test_topology_key_spelling():
    arch = os.uname().machine
    assert tune.topology_key() == f"single-host/{arch}"
    assert tune.topology_key(2, 8) == f"2d4r/{arch}"
    assert tune.topology_key(4, 8, arch="tpu-v5e") == "4d2r/tpu-v5e"
    # degenerate shapes collapse to the flat key, never a bogus one
    assert tune.topology_key(2, 7) == f"single-host/{arch}"
    assert tune.topology_key(1, 8) == f"single-host/{arch}"
    # mini-TOML-safe: the key is used as a quoted table name
    assert "." not in tune.topology_key(2, 8).replace(f"/{arch}", "")


# -- eligibility / heuristic / candidates ------------------------------------

def test_hier_eligibility_gates():
    kw = dict(commutative=True, elementwise=True, numeric=True)
    assert tune.eligible("allreduce", "hier", 8, 65536, domains=2, **kw)
    assert not tune.eligible("allreduce", "hier", 8, 65536, domains=0, **kw)
    assert not tune.eligible("allreduce", "hier", 8, 65536, domains=3, **kw)
    assert not tune.eligible("allreduce", "hier", 4, 65536, domains=4, **kw)
    assert not tune.eligible("allreduce", "hier", 8, None, domains=2, **kw)
    assert not tune.eligible("allreduce", "hier", 8, 65536, domains=2,
                             commutative=True, elementwise=False)
    # allgather/alltoall have no fold: elementwise is not required
    assert tune.eligible("allgather", "hier", 8, 65536, domains=2)
    assert tune.eligible("alltoall", "hier", 8, 65536, domains=2)
    assert not tune.eligible("allgather", "hier", 8, 65536, domains=2,
                             numeric=False)


def test_hier_heuristic_crossover(monkeypatch):
    kw = dict(commutative=True, elementwise=True)
    floor = config.load().hier_min_bytes
    assert tune.heuristic("allreduce", 8, floor, domains=2, **kw) == "hier"
    assert tune.heuristic("allgather", 8, floor, domains=2) == "hier"
    assert tune.heuristic("alltoall", 8, floor, domains=2) == "hier"
    # below the floor / flat world: never hier
    assert tune.heuristic("allreduce", 8, floor - 1, domains=2,
                          **kw) != "hier"
    assert tune.heuristic("allreduce", 8, floor, domains=0, **kw) != "hier"
    monkeypatch.setenv("TPU_MPI_HIER_MIN_BYTES", "64")
    config.load(refresh=True)
    assert tune.heuristic("allreduce", 8, 64, domains=2, **kw) == "hier"


def test_shm_arm_clamped_on_multi_domain_worlds():
    # the one-segment shm fold spans the whole communicator; a world split
    # into >= 2 domains (real hosts or the TPU_MPI_DOMAINS emulation) has
    # no single shared segment, so the arm must drop out even when the
    # caller's shm flag says /dev/shm is there
    kw = dict(commutative=True, elementwise=True, shm=True)
    assert tune.eligible("allreduce", "shm", 8, 2048, domains=0, **kw)
    assert not tune.eligible("allreduce", "shm", 8, 2048, domains=2, **kw)
    assert "shm" not in tune.candidates("allreduce", 8, 65536, numeric=True,
                                        domains=2, **kw)


def test_shm_lane_stops_at_the_domain_boundary(monkeypatch):
    # ProcContext.shm_ok / coll_shm_ok: the TPU_MPI_DOMAINS emulation must
    # gate the bulk shm lane too — inter-domain traffic rides sockets or
    # the emulated fabric asymmetry would silently vanish. Instantiated
    # via __new__: the gate reads only size/local_rank/_same_host/cache.
    from tpu_mpi import backend

    def _ctx(rank, size):
        ctx = backend.ProcContext.__new__(backend.ProcContext)
        ctx.local_rank, ctx.size = rank, size
        ctx._same_host = (True,) * size
        ctx._domain_split_cache = None
        return ctx

    monkeypatch.setenv("TPU_MPI_DOMAINS", "2")
    config.load(refresh=True)
    ctx = _ctx(1, 8)
    assert ctx.shm_ok(0) and ctx.shm_ok(3)        # rank 1's domain: 0-3
    assert not ctx.shm_ok(4) and not ctx.shm_ok(7)
    assert ctx.coll_shm_ok([0, 1, 2, 3])          # one-domain sub-comm
    assert not ctx.coll_shm_ok(list(range(8)))    # world spans domains
    assert _ctx(5, 8).shm_ok(4) and not _ctx(5, 8).shm_ok(3)
    # a split that doesn't divide the world is ignored (flat, all-shm)
    assert _ctx(0, 7).shm_ok(6)

    monkeypatch.delenv("TPU_MPI_DOMAINS")
    config.load(refresh=True)
    ctx = _ctx(1, 8)
    assert ctx.shm_ok(7) and ctx.coll_shm_ok(list(range(8)))


def test_candidates_grow_hier_arm():
    assert "hier" in tune.candidates("allreduce", 8, 65536, commutative=True,
                                     elementwise=True, domains=2)
    assert "hier" not in tune.candidates("allreduce", 8, 65536,
                                         commutative=True, elementwise=True,
                                         domains=0)


def test_forced_hier_on_flat_world_degrades():
    # the eligibility clamp drops a hier pin on a one-domain world, so the
    # selection falls through instead of sending a 0-domain world into the
    # two-level runner
    kw = dict(commutative=True, elementwise=True)
    assert tune.select("allreduce", 8, 1 << 20, domains=0, **kw) != "hier"
    assert tune.select("allgather", 8, 1 << 20, domains=0) != "hier"


# -- topology-keyed fleet DB (satellite: cross-topology isolation) -----------

def _dump(path, cells, topo=None, size=8):
    """One fake per-rank pvar dump: cells = (coll, algo, nbytes, count,
    total_s)."""
    rec = {"kind": "tpu_mpi-pvars", "comms": [{"size": size, "times": [
        {"coll": c, "algo": a, "nbytes": b, "count": n, "total_s": s,
         "min_s": s / max(1, n), "max_s": s / max(1, n)}
        for c, a, b, n, s in cells]}]}
    if topo is not None:
        rec["topology"] = topo
    with open(path, "w") as f:
        json.dump(rec, f)


def _two_topology_db(tmp_path):
    """A fleet DB where the flat fabric measured ring fastest and the
    two-domain fabric measured hier fastest, at the same (n, bytes)."""
    flat, hier = tune.topology_key(0, 8), tune.topology_key(2, 8)
    _dump(tmp_path / "flat.json",
          [("allreduce", "ring", 65536, 20, 20e-5),
           ("allreduce", "star", 65536, 20, 20e-4)], topo=flat)
    _dump(tmp_path / "hier.json",
          [("allreduce", "hier", 65536, 20, 20e-5),
           ("allreduce", "star", 65536, 20, 20e-4)], topo=hier)
    db = str(tmp_path / "fleet.toml")
    rec = tune.merge_db(db, [str(tmp_path / "flat.json"),
                             str(tmp_path / "hier.json")], min_samples=8)
    return db, rec, flat, hier


def test_merge_produces_multi_topology_db(tmp_path):
    db, rec, flat, hier = _two_topology_db(tmp_path)
    assert set(rec["topologies"]) >= {flat, hier}
    text = open(db).read()
    assert f'topology = "{flat}"' in text          # the DB's own fabric
    assert f'topo."{hier}"' in text                # the foreign subtree
    # per-topology provenance rides along
    topos = {p.get("topology") for p in rec["provenance"]}
    assert topos >= {flat, hier}


def test_db_rows_never_cross_topologies(tmp_path):
    db, _, flat, hier = _two_topology_db(tmp_path)
    # each fabric sees exactly its own ladder...
    assert tune._table_lookup(tune.load_db_table(db, flat),
                              "allreduce", 8, 65536) == "ring"
    assert tune._table_lookup(tune.load_db_table(db, hier),
                              "allreduce", 8, 65536) == "hier"
    # ...and an unmeasured fabric sees nothing at all — in particular the
    # nearest-nranks interpolation cannot reach across topology keys
    assert tune.load_db_table(db, tune.topology_key(4, 8)) == {}
    assert tune.load_db_table(db, "8d4r/riscv") == {}


def test_select_resolves_per_topology(tmp_path, monkeypatch):
    db, _, flat, hier = _two_topology_db(tmp_path)
    monkeypatch.setenv("TPU_MPI_TUNE_DB", db)
    config.load(refresh=True)
    kw = dict(commutative=True, elementwise=True)
    assert tune.select("allreduce", 8, 65536, domains=0, **kw) == "ring"
    assert tune.select("allreduce", 8, 65536, domains=2, **kw) == "hier"
    # a 4-domain world matches neither recorded fabric: heuristic applies
    # (hier, since the payload clears the floor) — crucially NOT served
    # from the 2-domain fabric's rows
    monkeypatch.setenv("TPU_MPI_HIER_MIN_BYTES", str(1 << 30))
    config.load(refresh=True)
    assert tune.select("allreduce", 8, 65536, domains=4, **kw) != "hier"


def test_pin_and_measured_table_beat_fleet_db(tmp_path, monkeypatch):
    # precedence with mixed-topology rows: force-pin > per-job measured
    # table > fleet DB, on BOTH fabrics
    db, _, flat, hier = _two_topology_db(tmp_path)
    monkeypatch.setenv("TPU_MPI_TUNE_DB", db)
    config.load(refresh=True)
    kw = dict(commutative=True, elementwise=True)
    table = str(tmp_path / "job.toml")
    tune.write_table(table, {("allreduce", 8): [(0, "rdouble")]})
    monkeypatch.setenv("TPU_MPI_TUNE_TABLE", table)
    config.load(refresh=True)
    assert tune.select("allreduce", 8, 65536, domains=0, **kw) == "rdouble"
    assert tune.select("allreduce", 8, 65536, domains=2, **kw) == "rdouble"
    monkeypatch.setenv("TPU_MPI_COLL_ALGO", "allreduce=star")
    config.load(refresh=True)
    assert tune.select("allreduce", 8, 65536, domains=0, **kw) == "star"
    assert tune.select("allreduce", 8, 65536, domains=2, **kw) == "star"


def test_merge_default_topology_is_shared_key(tmp_path):
    # regression (satellite 1): merge_db's default fabric comes from the
    # shared topology_key() helper, not a hardcoded spelling
    _dump(tmp_path / "d.json", [("allreduce", "star", 64, 10, 10e-4)])
    db = str(tmp_path / "db.toml")
    tune.merge_db(db, [str(tmp_path / "d.json")], min_samples=1)
    assert f'topology = "{tune.topology_key()}"' in open(db).read()


# -- proc-tier composite runners ---------------------------------------------

def _run_procs(body: str, nprocs: int = 4, timeout: float = 240.0, env=None):
    script = textwrap.dedent(body)
    path = os.path.join("/tmp", f"tpu_mpi_hier_{abs(hash(body)) % 10**8}.py")
    with open(path, "w") as f:
        f.write(f"import sys; sys.path.insert(0, {REPO!r})\n" + script)
    full = dict(os.environ)
    for k in ("TPU_MPI_PROC_RANK", "TPU_MPI_COLL_ALGO", "TPU_MPI_TUNE_TABLE",
              "TPU_MPI_TUNE_DB", "TPU_MPI_DOMAINS", "TPU_MPI_TRACE"):
        full.pop(k, None)
    full.update(env or {})
    return subprocess.run(
        [sys.executable, "-m", "tpu_mpi.launcher", "-n", str(nprocs),
         "--procs", "--sim", "1", "--timeout", str(timeout - 20), path],
        capture_output=True, text=True, timeout=timeout, env=full, cwd=REPO)


# The hier/star bitwise matrix: payload sizes include 97 (prime, never
# divisible by the per-domain rank count) so the segment split exercises
# its remainder path, and a device-buffer lane checks the re-wrap.
_HIER_MATRIX_BODY = """
    import os
    import numpy as np
    import tpu_mpi as MPI
    from tpu_mpi import config

    MPI.Init()
    comm = MPI.COMM_WORLD
    rank, size = MPI.Comm_rank(comm), MPI.Comm_size(comm)

    def set_algo(spec):
        os.environ["TPU_MPI_COLL_ALGO"] = spec
        config.load(refresh=True)

    def data(dt, n=96):
        return (((np.arange(n) * 13) % 23) + rank + 1).astype(dt)

    failures = []

    def check(tag, ref, got):
        if np.asarray(ref).tobytes() != np.asarray(got).tobytes():
            failures.append(tag)

    OPS = [("SUM", MPI.SUM), ("PROD", MPI.PROD), ("MAX", MPI.MAX)]
    DTYPES = [np.float64, np.float32, np.int64]

    for opname, op in OPS:
        for dt in DTYPES:
            for n in (96, 97, 7):
                set_algo("allreduce=star")
                ref = np.asarray(MPI.Allreduce(data(dt, n), op, comm))
                set_algo("allreduce=hier")
                got = np.asarray(MPI.Allreduce(data(dt, n), op, comm))
                check(f"allreduce/hier/{opname}/{np.dtype(dt)}/n{n}",
                      ref, got)

    # device-buffer lane: the composite must re-wrap like the star does
    set_algo("allreduce=star")
    dref = MPI.Allreduce(MPI.DeviceBuffer(data(np.float32)), MPI.SUM, comm)
    set_algo("allreduce=hier")
    dgot = MPI.Allreduce(MPI.DeviceBuffer(data(np.float32)), MPI.SUM, comm)
    check("allreduce/hier/device",
          np.asarray(dref.value if hasattr(dref, "value") else dref),
          np.asarray(dgot.value if hasattr(dgot, "value") else dgot))

    for n in (96, 7):
        set_algo("allgather=star")
        ref = np.asarray(MPI.Allgather(data(np.float64, n), comm))
        set_algo("allgather=hier")
        got = np.asarray(MPI.Allgather(data(np.float64, n), comm))
        check(f"allgather/hier/n{n}", ref, got)

    for cnt in (1, 3):
        payload = np.arange(float(size * cnt)) + 100 * rank
        set_algo("alltoall=star")
        ref = np.asarray(MPI.Alltoall(payload, cnt, comm))
        set_algo("alltoall=hier")
        got = np.asarray(MPI.Alltoall(payload, cnt, comm))
        check(f"alltoall/hier/c{cnt}", ref, got)

    assert not failures, failures
    print(f"HIER-MATRIX-OK-{rank}")
    MPI.Finalize()
"""


def test_hier_matrix_bitwise_equals_star_two_domains():
    res = _run_procs(_HIER_MATRIX_BODY, nprocs=4,
                     env={"TPU_MPI_DOMAINS": "2"})
    assert res.returncode == 0, res.stderr[-4000:]
    for r in range(4):
        assert f"HIER-MATRIX-OK-{r}" in res.stdout


@pytest.mark.slow
def test_hier_matrix_eight_ranks_four_domains():
    res = _run_procs(_HIER_MATRIX_BODY, nprocs=8, timeout=420.0,
                     env={"TPU_MPI_DOMAINS": "4"})
    assert res.returncode == 0, res.stderr[-4000:]
    for r in range(8):
        assert f"HIER-MATRIX-OK-{r}" in res.stdout


def test_forced_hier_completes_on_one_domain_procs_world():
    # no TPU_MPI_DOMAINS, one simulated host: the pin is clamped by
    # eligibility and the job must run flat, correctly, with no hier event
    res = _run_procs("""
        import numpy as np
        import tpu_mpi as MPI
        from tpu_mpi.analyze import events as ev

        MPI.Init()
        comm = MPI.COMM_WORLD
        rank, size = MPI.Comm_rank(comm), MPI.Comm_size(comm)
        got = np.asarray(MPI.Allreduce(np.arange(512.0) + rank, MPI.SUM,
                                       comm))
        exp = np.arange(512.0) * size + sum(range(size))
        assert np.array_equal(got, exp)
        tr = ev.last_trace()
        algos = {e.algo for e in tr.events()
                 if e.kind == "coll" and str(e.op).startswith("Allreduce")}
        assert "hier" not in algos, algos
        print(f"DEGRADE-OK-{rank}")
        MPI.Finalize()
    """, nprocs=4, timeout=120.0,
        env={"TPU_MPI_COLL_ALGO": "allreduce=hier", "TPU_MPI_TRACE": "1"})
    assert res.returncode == 0, res.stderr[-4000:]
    for r in range(4):
        assert f"DEGRADE-OK-{r}" in res.stdout


def test_heuristic_selects_hier_in_event_ir_two_domains():
    # no pins: with two domains and a payload past the hier floor the
    # heuristic itself must route to the composite — proven structurally
    # through Event.algo on every rank, and a sub-floor payload stays flat
    res = _run_procs("""
        import numpy as np
        import tpu_mpi as MPI
        from tpu_mpi.analyze import events as ev
        from tpu_mpi.collective import _coll_select

        MPI.Init()
        comm = MPI.COMM_WORLD
        rank, size = MPI.Comm_rank(comm), MPI.Comm_size(comm)
        got = np.asarray(MPI.Allreduce(np.arange(1024.0) + rank, MPI.SUM,
                                       comm))
        assert np.array_equal(got, np.arange(1024.0) * size
                              + sum(range(size)))
        tr = ev.last_trace()
        algos = {e.algo for e in tr.events()
                 if e.kind == "coll" and str(e.op).startswith("Allreduce")}
        assert algos == {"hier"}, algos
        assert _coll_select(comm, "allreduce", 128, commutative=True,
                            elementwise=True, numeric=True) != "hier"
        print(f"HIER-ALGO-OK-{rank}")
        MPI.Finalize()
    """, nprocs=8, timeout=180.0,
        env={"TPU_MPI_DOMAINS": "2", "TPU_MPI_TRACE": "1"})
    assert res.returncode == 0, res.stderr[-4000:]
    for r in range(8):
        assert f"HIER-ALGO-OK-{r}" in res.stdout


def test_hier_flat_divergence_fails_loudly_not_deadlock():
    # one rank genuinely falling off the hierarchy (per-process pin) must
    # raise on every rank: the star arrival meets hier alg frames and the
    # cross-tier checks fire well before any deadlock budget
    res = _run_procs("""
        import os
        import time
        import numpy as np
        import tpu_mpi as MPI
        from tpu_mpi import config
        from tpu_mpi.error import MPIError

        MPI.Init()
        comm = MPI.COMM_WORLD
        rank = MPI.Comm_rank(comm)
        os.environ["TPU_MPI_COLL_ALGO"] = (
            "allgather=star" if rank == 0 else "allgather=hier")
        config.load(refresh=True)
        try:
            MPI.Allgather(np.arange(2048.0) + rank, comm)
        except MPIError:
            print(f"DIVERGE-OK-{rank}", flush=True)
        else:
            print(f"DIVERGE-MISSED-{rank}", flush=True)
        # keep this rank's transport open until every peer has observed
        # the failure broadcast — an early exit would turn a peer's clean
        # MPIError into a raw connection error mid-send
        time.sleep(3.0)
    """, nprocs=4, timeout=120.0, env={"TPU_MPI_DOMAINS": "2"})
    for r in range(4):
        assert f"DIVERGE-OK-{r}" in res.stdout, (res.stdout,
                                                 res.stderr[-3000:])
    assert "DIVERGE-MISSED" not in res.stdout


# -- the bandit explores hier arms in lockstep -------------------------------

def test_bandit_explores_hier_arm_in_lockstep(monkeypatch):
    from tpu_mpi import tune_online
    monkeypatch.setenv("TPU_MPI_DOMAINS", "2")
    monkeypatch.setenv("TPU_MPI_TRACE", "1")
    monkeypatch.setenv("TPU_MPI_PVARS", "1")
    monkeypatch.setenv("TPU_MPI_TUNE_EXPLORE", "0.5")
    monkeypatch.setenv("TPU_MPI_TUNE_SWAP_PERIOD", "100000")   # never swap
    config.load(refresh=True)
    tune_online.reset()
    try:
        def body():
            comm = MPI.COMM_WORLD
            rank = MPI.Comm_rank(comm)
            for _ in range(24):
                MPI.Allgather(np.arange(32.0) + rank, comm)

        run_spmd(body, nprocs=4)
        tr = ev.last_trace()
        assert tr is not None
        seqs = [[e.algo for e in tr.events(r) if e.kind == "coll"
                 and str(e.op).startswith("Allgather")] for r in range(4)]
        assert all(len(s) == 24 for s in seqs)
        # lockstep: every rank ran the identical per-call algo sequence
        assert seqs[0] == seqs[1] == seqs[2] == seqs[3]
        # ...which actually explored, and reached the hier arm
        assert len(set(seqs[0])) > 1, set(seqs[0])
        assert "hier" in set(seqs[0]), set(seqs[0])
    finally:
        tune_online.reset()
