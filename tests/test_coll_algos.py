"""Collective algorithm portfolio: every proc-tier algorithm must be
bitwise-identical to the star rendezvous, and algorithm-tier divergence
must fail loudly (MPIError on every rank) instead of hanging.

The portfolio (tpu_mpi.tune.PORTFOLIO / backend runners): recursive
doubling + Rabenseifner + ring + shm Allreduce, dissemination + shm
Barrier, binomial-tree Bcast/Reduce/Gather/Scatter, ring Allgather,
pairwise Alltoall. Algorithms are forced one at a time via the
TPU_MPI_COLL_ALGO override (config reload in lockstep on every rank) and
the result bytes are compared against the star reference computed in the
same process — the determinism contract (docs/semantics.md) is bitwise,
not approximate, because every runner reuses the star's rank-ordered
fold or a segment-separable rank-order fold of it.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_procs(body: str, nprocs: int = 4, timeout: float = 240.0, env=None):
    script = textwrap.dedent(body)
    path = os.path.join("/tmp", f"tpu_mpi_algo_{abs(hash(body)) % 10**8}.py")
    with open(path, "w") as f:
        f.write(f"import sys; sys.path.insert(0, {REPO!r})\n" + script)
    full = dict(os.environ)
    full.pop("TPU_MPI_PROC_RANK", None)
    full.pop("TPU_MPI_COLL_ALGO", None)
    full.pop("TPU_MPI_TUNE_TABLE", None)
    full.update(env or {})
    return subprocess.run(
        [sys.executable, "-m", "tpu_mpi.launcher", "-n", str(nprocs),
         "--procs", "--sim", "1", "--timeout", str(timeout - 20), path],
        capture_output=True, text=True, timeout=timeout, env=full, cwd=REPO)


# One launch per world size runs the whole matrix in-process: the
# override swap (env + config reload) happens in lockstep on every rank,
# so each collective runs under exactly one forced algorithm.
_MATRIX_BODY = """
    import os
    import jax
    import numpy as np
    import tpu_mpi as MPI
    from tpu_mpi import config

    # the matrix wraps float64/int64 operands in DeviceBuffer: without this
    # they would be narrowed (now a typed error) and "bitwise" would compare
    # two float32 results
    jax.config.update("jax_enable_x64", True)
    MPI.Init()
    comm = MPI.COMM_WORLD
    rank, size = MPI.Comm_rank(comm), MPI.Comm_size(comm)

    def set_algo(spec):
        os.environ["TPU_MPI_COLL_ALGO"] = spec
        config.load(refresh=True)

    def data(dt, n=96):
        # integer-valued, rank-dependent, exercises non-associativity when
        # folded in the wrong order (23 and 13 are coprime)
        return (((np.arange(n) * 13) % 23) + rank + 1).astype(dt)

    failures = []

    def check(tag, ref, got):
        if np.asarray(ref).tobytes() != np.asarray(got).tobytes():
            failures.append(tag)

    OPS = [("SUM", MPI.SUM), ("PROD", MPI.PROD), ("MAX", MPI.MAX)]
    DTYPES = [np.float64, np.float32, np.int64]
    wrap = {
        "numpy": lambda a: a,
        "device": lambda a: MPI.DeviceBuffer(a),
    }
    unwrap = {
        "numpy": lambda r: np.asarray(r),
        "device": lambda r: np.asarray(r.value if hasattr(r, "value") else r),
    }

    # -- Allreduce / Reduce: algorithm x op x dtype x array kind ------------
    for opname, op in OPS:
        for dt in DTYPES:
            for kind in ("numpy", "device"):
                set_algo("allreduce=star,reduce=star")
                ref = unwrap[kind](MPI.Allreduce(wrap[kind](data(dt)), op, comm))
                rref = MPI.Reduce(wrap[kind](data(dt)), op, 0, comm)
                for algo in ("shm", "rdouble", "rabenseifner", "ring"):
                    set_algo(f"allreduce={algo}")
                    got = unwrap[kind](MPI.Allreduce(wrap[kind](data(dt)), op, comm))
                    check(f"allreduce/{algo}/{opname}/{np.dtype(dt)}/{kind}", ref, got)
                set_algo("reduce=binomial")
                rgot = MPI.Reduce(wrap[kind](data(dt)), op, 0, comm)
                if rank == 0:
                    check(f"reduce/binomial/{opname}/{np.dtype(dt)}/{kind}",
                          unwrap[kind](rref), unwrap[kind](rgot))

    # -- rooted family + allgather/alltoall: star vs the tree/ring/pairwise -
    for algo in ("star", "binomial"):
        set_algo(f"bcast={algo},gather={algo},scatter={algo}")
        buf = data(np.float64) if rank == 1 else np.zeros(96)
        MPI.Bcast(buf, 1, comm)
        check(f"bcast/{algo}", data(np.float64) - rank - 1 + 2, buf)
        obj = MPI.bcast({"r": rank} if rank == 1 else None, 1, comm)
        if obj != {"r": 1}:
            failures.append(f"bcast-obj/{algo}")
        g = MPI.Gather(data(np.int64), 0, comm)
        if rank == 0:
            exp = np.concatenate(
                [(((np.arange(96) * 13) % 23) + r + 1) for r in range(size)])
            check(f"gather/{algo}", exp.astype(np.int64), g)
        send = np.arange(float(8 * size)) if rank == 2 % size else None
        sc = MPI.Scatter(send, 8, 2 % size, comm)
        check(f"scatter/{algo}", np.arange(float(8 * size))[rank*8:(rank+1)*8], sc)

    for algo in ("star", "ring"):
        set_algo(f"allgather={algo}")
        ag = MPI.Allgather(data(np.float64), comm)
        exp = np.concatenate(
            [(((np.arange(96) * 13) % 23) + r + 1.0) for r in range(size)])
        check(f"allgather/{algo}", exp, ag)
    for algo in ("star", "pairwise"):
        set_algo(f"alltoall={algo}")
        at = MPI.Alltoall(np.arange(float(size)) + 100 * rank, 1, comm)
        exp = np.array([100.0 * s + rank for s in range(size)])
        check(f"alltoall/{algo}", exp, at)

    # -- Barrier: each algorithm completes and stays in lockstep ------------
    for algo in ("star", "shm", "dissemination"):
        set_algo(f"barrier={algo}")
        MPI.Barrier(comm)

    assert not failures, failures
    print(f"MATRIX-OK-{rank}")
    MPI.Finalize()
"""


@pytest.mark.parametrize("nprocs", [2, 4])
def test_algorithm_matrix_bitwise_equals_star(nprocs):
    res = _run_procs(_MATRIX_BODY, nprocs=nprocs)
    assert res.returncode == 0, res.stderr[-4000:]
    for r in range(nprocs):
        assert f"MATRIX-OK-{r}" in res.stdout


@pytest.mark.slow
def test_algorithm_matrix_eight_ranks():
    res = _run_procs(_MATRIX_BODY, nprocs=8, timeout=420.0)
    assert res.returncode == 0, res.stderr[-4000:]
    for r in range(8):
        assert f"MATRIX-OK-{r}" in res.stdout


def test_algorithm_divergence_fails_loudly_not_deadlock():
    # Ranks disagreeing on the ALGORITHM (not just the op) must raise on
    # every rank: rank 0 enters the recursive-doubling exchange while the
    # others run the star rendezvous. The cross-tier frame checks turn the
    # mixed arrival into MPIError/CollectiveMismatchError well before any
    # deadlock budget.
    res = _run_procs("""
        import os
        import numpy as np
        import tpu_mpi as MPI
        from tpu_mpi import config
        from tpu_mpi.error import MPIError

        MPI.Init()
        comm = MPI.COMM_WORLD
        rank = MPI.Comm_rank(comm)
        os.environ["TPU_MPI_COLL_ALGO"] = (
            "allreduce=rdouble" if rank == 0 else "allreduce=star")
        config.load(refresh=True)
        try:
            MPI.Allreduce(np.arange(32.0), MPI.SUM, comm)
        except MPIError:
            print(f"DIVERGE-OK-{rank}", flush=True)
        else:
            print(f"DIVERGE-MISSED-{rank}", flush=True)
    """, nprocs=2, timeout=120.0)
    assert "DIVERGE-OK-0" in res.stdout and "DIVERGE-OK-1" in res.stdout, (
        res.stdout, res.stderr[-3000:])
    assert "DIVERGE-MISSED" not in res.stdout
