"""Training-tier tests (PR 19, docs/training.md): gradient bucketing,
persistent-handle overlap vs the blocking control (bitwise-equal, faster),
ZeRO-sharded state at ~1/nranks, checkpoint resume/reshard, the
bucket-aware plan-cache reservation, the `tpurun --stats` training block,
and the hier (TPU_MPI_DOMAINS=2) path carrying gradient traffic —
including Reduce_scatter with uneven counts, which only flat worlds
exercised before this tier."""

import io
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import tpu_mpi as MPI
from tpu_mpi import perfvars
from tpu_mpi.testing import run_spmd
from tpu_mpi.train import DDPTrainer, FSDPTrainer, GradBucketer, make_trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec():
    """A small 'model': named params in forward order, mixed sizes."""
    rng = np.random.default_rng(7)
    return {f"p{i}": rng.standard_normal(n)
            for i, n in enumerate((300, 50, 400, 120, 10, 256))}


def _grads(step, rank):
    """Deterministic per-(step, rank) gradients for the _spec params."""
    rng = np.random.default_rng(10_000 * step + rank)
    return {name: rng.standard_normal(arr.size)
            for name, arr in _spec().items()}


def _feed(trainer, step):
    g = _grads(step, trainer.comm.rank())
    trainer.step((n, g[n]) for n in reversed(list(g)))


# -- bucketer ----------------------------------------------------------------

def test_bucketer_layout_and_views():
    spec = [("a", 100), ("b", 100), ("c", 300), ("d", 10)]
    bk = GradBucketer(spec, bucket_bytes=200 * 8)
    # a+b fill bucket 0; c overflows the bound alone; d trails
    assert [b.names for b in bk.buckets] == [["a", "b"], ["c"], ["d"]]
    assert len(bk) == 3
    done = bk.add("a", np.ones(100))
    assert done is None
    done = bk.add("b", np.full(100, 2.0))
    assert done is bk.buckets[0]
    assert done.send[:100].tolist() == [1.0] * 100
    np.copyto(done.recv, done.send)
    assert bk.out_view("b").tolist() == [2.0] * 100
    bk.reset()
    assert bk.add("a", np.ones(100)) is None   # arrival set cleared


def test_bucketer_oversized_param_gets_own_bucket():
    bk = GradBucketer([("big", 10_000)], bucket_bytes=64)
    assert len(bk) == 1
    assert bk.buckets[0].nbytes == 80_000


# -- DDP overlap vs control --------------------------------------------------

def test_ddp_overlap_bitwise_equals_control(nprocs):
    outs = {}

    def body():
        comm = MPI.COMM_WORLD
        tr = DDPTrainer(_spec(), comm, bucket_bytes=1024, overlap=True)
        tc = DDPTrainer(_spec(), comm, bucket_bytes=1024, overlap=False)
        assert len(tr.bucketer) > 1
        for s in range(4):
            _feed(tr, s)
            _feed(tc, s)
        if comm.rank() == 0:
            outs["overlap"] = {n: p.copy() for n, p in tr.params.items()}
            outs["control"] = {n: p.copy() for n, p in tc.params.items()}
            outs["ofrac"] = (tr.overlap_fraction(), tc.overlap_fraction())

    run_spmd(body, nprocs)
    for name, p in outs["overlap"].items():
        assert p.tobytes() == outs["control"][name].tobytes(), name
    # the control is fully blocking by construction; the overlap lane hid
    # at least part of its comm window behind the feed
    assert outs["ofrac"][1] == 0.0
    assert outs["ofrac"][0] > 0.0


def test_ddp_updates_do_not_alias_caller_params(nprocs):
    def body():
        comm = MPI.COMM_WORLD
        params = {n: np.ascontiguousarray(p)   # already float64-contiguous
                  for n, p in _spec().items()}
        before = {n: p.copy() for n, p in params.items()}
        tr = DDPTrainer(params, comm, bucket_bytes=1024)
        _feed(tr, 0)
        for n in params:
            assert params[n].tobytes() == before[n].tobytes()
            assert tr.params[n].tobytes() != before[n].tobytes()

    run_spmd(body, nprocs)


# -- FSDP sharded state ------------------------------------------------------

def test_fsdp_bitwise_equals_ddp_and_shards_state(nprocs):
    outs = {}

    def body():
        comm = MPI.COMM_WORLD
        ddp = DDPTrainer(_spec(), comm, bucket_bytes=1024)
        fsdp = FSDPTrainer(_spec(), comm)
        for s in range(4):
            _feed(ddp, s)
            _feed(fsdp, s)
        if comm.rank() == 0:
            outs["ddp"] = {n: p.copy() for n, p in ddp.params.items()}
            outs["fsdp"] = {n: p.copy() for n, p in fsdp.params.items()}
            outs["bytes"] = (ddp.opt_state_bytes(), fsdp.opt_state_bytes())

    run_spmd(body, nprocs)
    for name, p in outs["ddp"].items():
        assert p.tobytes() == outs["fsdp"][name].tobytes(), name
    full, shard = outs["bytes"]
    # shard = ceil(n/size) elements vs the full n: ~1/nranks (+padding)
    assert shard <= full // nprocs + 8 * nprocs


def test_make_trainer_honors_shard_state_config(nprocs, monkeypatch):
    from tpu_mpi import config
    monkeypatch.setenv("TPU_MPI_TRAIN_SHARD_STATE", "1")
    config.load(refresh=True)
    kinds = []

    def body():
        t = make_trainer(_spec(), MPI.COMM_WORLD)
        kinds.append(type(t).__name__)

    run_spmd(body, nprocs)
    assert set(kinds) == {"FSDPTrainer"}
    monkeypatch.setenv("TPU_MPI_TRAIN_SHARD_STATE", "0")
    config.load(refresh=True)
    kinds.clear()
    run_spmd(body, nprocs)
    assert set(kinds) == {"DDPTrainer"}


# -- checkpoint resume / reshard ---------------------------------------------

@pytest.mark.parametrize("cls", [DDPTrainer, FSDPTrainer])
def test_checkpoint_resume_bitwise(cls, nprocs, tmp_path):
    path = str(tmp_path / "train.ckpt")
    outs = {}

    def body():
        comm = MPI.COMM_WORLD
        ref = cls(_spec(), comm)
        for s in range(5):
            _feed(ref, s)
        two = cls(_spec(), comm)
        for s in range(2):
            _feed(two, s)
        two.save(path)
        resumed = cls(_spec(), comm)
        assert resumed.load(path) == 2
        for s in range(2, 5):
            _feed(resumed, s)
        if comm.rank() == 0:
            outs["ref"] = {n: p.copy() for n, p in ref.params.items()}
            outs["res"] = {n: p.copy() for n, p in resumed.params.items()}

    run_spmd(body, nprocs)
    for name, p in outs["ref"].items():
        assert p.tobytes() == outs["res"][name].tobytes(), name


# -- plan-cache reservation (overlap.py glue) --------------------------------

def test_plan_cache_reserve_lifts_eviction_cap():
    from tpu_mpi.overlap import PlanCache
    pc = PlanCache()
    base_cap = pc.stats()["cap"]
    assert pc.reserve(base_cap + 50) == base_cap + 50
    st = pc.stats()
    assert st["cap"] == base_cap + 50
    assert st["reserved"] == base_cap + 50
    # reservation is monotonic: a smaller later hint never shrinks it
    assert pc.reserve(4) == base_cap + 50


def test_trainer_hints_bucket_reservation(nprocs):
    from tpu_mpi.overlap import plans

    def body():
        DDPTrainer(_spec(), MPI.COMM_WORLD, bucket_bytes=1024)

    run_spmd(body, nprocs)
    st = plans.stats()
    assert st["reserved"] >= 2 * 2 + 8      # >= 2 buckets armed
    assert st["cap"] >= st["reserved"]


# -- train pvars + the --stats training block --------------------------------

def test_train_pvars_populate(nprocs):
    perfvars.pcontrol(1)
    perfvars.reset()

    def body():
        tr = DDPTrainer(_spec(), MPI.COMM_WORLD, bucket_bytes=1024)
        for s in range(3):
            _feed(tr, s)

    run_spmd(body, nprocs)
    tr = perfvars.snapshot()["train"]
    nb = tr["gauges"]["nbuckets"]
    assert nb > 1
    assert tr["steps"] == 3 * nprocs
    assert tr["bucket_flushes"] == 3 * nprocs * nb
    assert tr["starts"] == tr["waits"] == tr["bucket_flushes"]
    assert tr["comm_window_ns"] >= tr["wait_ns"] >= 0
    assert len(tr["step_ns_samples"]) == tr["steps"]
    assert tr["gauges"]["world"] == nprocs
    perfvars.reset()


def test_stats_training_block_renders():
    from tpu_mpi import stats
    rec = {"counters": {}, "gauges": {}, "colls": [],
           "train": {"steps": 4, "bucket_flushes": 12, "starts": 12,
                     "waits": 12, "wait_ns": 2_000_000,
                     "comm_window_ns": 10_000_000, "reshards": 1,
                     "gauges": {"nbuckets": 3, "bucket_bytes": 16384,
                                "world": 4},
                     "step_ns_samples": [1_000_000, 2_000_000,
                                         3_000_000, 4_000_000]}}
    rec2 = {"counters": {}, "gauges": {}, "colls": [],
            "train": {"steps": 4, "bucket_flushes": 12, "starts": 12,
                      "waits": 12, "wait_ns": 1_000_000,
                      "comm_window_ns": 5_000_000,
                      "gauges": {"nbuckets": 3, "bucket_bytes": 16384,
                                 "world": 4},
                      "step_ns_samples": [2_000_000] * 4}}
    agg = stats.aggregate([rec, rec2])
    assert agg["train"]["steps"] == 8                      # counters sum
    assert agg["train"]["wait_ns"] == 3_000_000
    assert agg["train"]["gauges"]["world"] == 4            # gauges max
    assert len(agg["train"]["step_ns_samples"]) == 8
    out = io.StringIO()
    stats.render(agg, out=out)
    text = out.getvalue()
    assert "training: 8 steps on world 4" in text
    assert "step p50 2.00ms" in text
    assert "gradient buckets: 3 x 16.0KiB cap, 24 flushes" in text
    assert "(24 starts / 24 waits on persistent handles)" in text
    assert "overlap: 80% of the 15.00ms comm window" in text
    assert "reshard events: 1" in text


def test_stats_render_empty_train_block_silent():
    from tpu_mpi import stats
    agg = stats.aggregate([{"counters": {}, "gauges": {}, "colls": []}])
    out = io.StringIO()
    stats.render(agg, out=out)
    assert "training:" not in out.getvalue()


# -- hier (TPU_MPI_DOMAINS=2) path -------------------------------------------

def _run_procs(body: str, nprocs: int = 4, timeout: float = 240.0, env=None):
    script = textwrap.dedent(body)
    path = os.path.join("/tmp", f"tpu_mpi_train_{abs(hash(body)) % 10**8}.py")
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


_UNEVEN_RS_BODY = """
    import numpy as np
    import tpu_mpi as MPI

    MPI.Init()
    comm = MPI.COMM_WORLD
    rank, size = MPI.Comm_rank(comm), MPI.Comm_size(comm)
    assert size == 4

    # uneven counts (prime total, a zero count, a dominant tail) — the
    # splits only flat worlds exercised before the training tier
    for counts in ([7, 5, 3, 2], [0, 9, 1, 7], [1, 1, 1, 94]):
        total = sum(counts)
        send = (np.arange(total, dtype=np.float64) * 3 + rank + 1)
        out = MPI.Reduce_scatter(send, None, counts, MPI.SUM, comm)
        # rank-ordered reference fold of every rank's contribution
        ref = np.zeros(total)
        for r in range(size):
            ref += np.arange(total) * 3 + r + 1
        lo = sum(counts[:rank])
        assert np.asarray(out).tobytes() == ref[lo:lo + counts[rank]].tobytes(), counts
        recv = np.zeros(counts[rank])
        MPI.Reduce_scatter(send, recv, counts, MPI.SUM, comm)
        assert recv.tobytes() == ref[lo:lo + counts[rank]].tobytes()
    MPI.Barrier(comm)
    print(f"RS-OK-{rank}", flush=True)
    MPI.Finalize()
"""


def test_reduce_scatter_uneven_counts_two_domains():
    res = _run_procs(_UNEVEN_RS_BODY, env={"TPU_MPI_DOMAINS": "2"})
    assert res.returncode == 0, res.stderr
    for r in range(4):
        assert f"RS-OK-{r}" in res.stdout


_TRAIN_DIGEST_BODY = """
    import hashlib
    import numpy as np
    import tpu_mpi as MPI
    from tpu_mpi.train import DDPTrainer, FSDPTrainer

    MPI.Init()
    comm = MPI.COMM_WORLD
    rank = MPI.Comm_rank(comm)

    def spec():
        rng = np.random.default_rng(7)
        return {f"p{i}": rng.standard_normal(n)
                for i, n in enumerate((300, 50, 400, 120, 10, 256))}

    def grads(step, rank):
        rng = np.random.default_rng(10_000 * step + rank)
        return {name: rng.standard_normal(arr.size)
                for name, arr in spec().items()}

    digests = []
    for cls in (DDPTrainer, FSDPTrainer):
        tr = cls(spec(), comm)
        for s in range(3):
            g = grads(s, rank)
            tr.step((n, g[n]) for n in reversed(list(g)))
        h = hashlib.sha256()
        for n in sorted(tr.params):
            h.update(tr.params[n].tobytes())
        digests.append(h.hexdigest())
    if rank == 0:
        print("DIGEST " + " ".join(digests), flush=True)
    MPI.Barrier(comm)
    MPI.Finalize()
"""


def test_trainer_traffic_two_domains_bitwise_equals_flat():
    """Gradient traffic on a 2-domain world (hier allreduce/allgather
    carrying the DDP buckets and the FSDP republish) must produce params
    bitwise equal to the flat star world."""
    flat = _run_procs(_TRAIN_DIGEST_BODY)
    assert flat.returncode == 0, flat.stderr
    hier = _run_procs(_TRAIN_DIGEST_BODY, env={
        "TPU_MPI_DOMAINS": "2",
        "TPU_MPI_COLL_ALGO": "allreduce=hier,allgather=hier",
        "TPU_MPI_HIER_MIN_BYTES": "0"})
    assert hier.returncode == 0, hier.stderr
    d_flat = [l for l in flat.stdout.splitlines() if l.startswith("DIGEST")]
    d_hier = [l for l in hier.stdout.splitlines() if l.startswith("DIGEST")]
    assert d_flat and d_flat == d_hier
