"""ULFM-shaped fault tolerance (docs/fault-tolerance.md).

Three layers, mirroring how the subsystem is built:

- **Recovery semantics** on the threaded tier (fast, in-process):
  Comm_agree's AND fold, Comm_shrink producing a working survivor
  communicator, revocation turning pending AND future operations into
  RevokedError — including a revoke racing an in-flight collective — and
  the post-recovery trace verifying clean through analyze.matcher.
- **The failure detector's raw substrate**: a live NativeTransport pair,
  distinguishing a LATE peer (heartbeats stopped, age grows) from a DEAD
  one (socket closed, terminal -2).
- **Chaos, multi-process**: a rank SIGKILLed mid-job must surface as typed
  errors on every survivor (no hang), the survivors must shrink and keep
  computing, and the launcher must report the death and exit with
  EXIT_SHRUNK_OK. Checkpoint corruption (torn writes, truncation, stale
  format) must be typed MPIError, never a pickle/struct crash.
"""

import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import tpu_mpi as MPI
from tpu_mpi import analyze, checkpoint, config
from tpu_mpi.error import DeadlockError, MPIError, ProcFailedError, RevokedError
from tpu_mpi.launcher import EXIT_SHRUNK_OK
from tpu_mpi.testing import run_spmd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Recovery semantics (threaded tier)
# ---------------------------------------------------------------------------

def test_comm_agree_folds_bitwise_and(nprocs):
    def body():
        comm = MPI.COMM_WORLD
        rank = MPI.Comm_rank(comm)
        # default flag: unanimous true
        assert MPI.Comm_agree(comm) == 1
        # one dissenting bit pattern folds into everyone's result
        flag = 0b101 if rank == 0 else 0b111
        assert MPI.Comm_agree(comm, flag) == 0b101
        # zero from anyone ANDs to zero
        assert MPI.Comm_agree(comm, 0 if rank == 1 else 1) == 0

    run_spmd(body, nprocs)


def test_comm_shrink_without_failures_is_a_working_dup(nprocs):
    def body():
        comm = MPI.COMM_WORLD
        rank, size = MPI.Comm_rank(comm), MPI.Comm_size(comm)
        new = MPI.Comm_shrink(comm)
        assert new.cid != comm.cid
        assert MPI.Comm_size(new) == size
        assert MPI.Comm_rank(new) == rank
        out = MPI.Allreduce(np.full(4, float(rank + 1)), MPI.SUM, new)
        assert np.all(np.asarray(out) == sum(range(1, size + 1)))
        # the parent communicator is untouched
        MPI.Barrier(comm)

    run_spmd(body, nprocs)


def test_revoked_comm_raises_until_shrunk(nprocs):
    def body():
        world = MPI.COMM_WORLD
        rank, size = MPI.Comm_rank(world), MPI.Comm_size(world)
        comm2 = MPI.Comm_dup(world)
        MPI.Barrier(comm2)
        if rank == 0:
            MPI.Comm_revoke(comm2)
        MPI.Barrier(world)          # revocation is ctx state: now visible
        # every op on the revoked comm fails deterministically...
        with pytest.raises(RevokedError):
            MPI.Allreduce(np.ones(4), MPI.SUM, comm2)
        with pytest.raises(RevokedError):
            MPI.Send(np.ones(2), (rank + 1) % size, 9, comm2)
        # ...while an unrelated communicator is untouched
        MPI.Barrier(world)
        # agreement and shrink stay legal on the revoked comm (ULFM): the
        # recovery path must be reachable from exactly this state
        assert MPI.Comm_agree(comm2, 1) == 1
        new = MPI.Comm_shrink(comm2)
        out = MPI.Allreduce(np.array([float(rank)]), MPI.SUM, new)
        assert out[0] == sum(range(size))

    run_spmd(body, nprocs)


def test_revoke_wakes_an_inflight_collective(nprocs):
    """The satellite race: ranks already BLOCKED inside a collective on the
    comm when it is revoked must raise RevokedError, not sit out the
    deadlock budget."""
    def body():
        world = MPI.COMM_WORLD
        rank = MPI.Comm_rank(world)
        comm2 = MPI.Comm_dup(world)
        MPI.Barrier(world)
        if rank == 0:
            time.sleep(0.3)         # let the others park in the rendezvous
            MPI.Comm_revoke(comm2)
        else:
            t0 = time.monotonic()
            with pytest.raises(RevokedError):
                MPI.Allreduce(np.ones(2), MPI.SUM, comm2)   # rank 0 never joins
            assert time.monotonic() - t0 < 30.0
        MPI.Barrier(world)

    run_spmd(body, nprocs)


def test_post_recovery_trace_verifies_clean(nprocs, monkeypatch):
    """analyze.matcher on a traced shrink -> continue run: the recovery
    collectives (agree, shrink) and the post-recovery traffic must align
    across ranks like any other collective program."""
    monkeypatch.setenv("TPU_MPI_TRACE", "1")
    config.load(refresh=True)
    try:
        def body():
            world = MPI.COMM_WORLD
            rank, size = MPI.Comm_rank(world), MPI.Comm_size(world)
            comm2 = MPI.Comm_dup(world)
            MPI.Allreduce(np.ones(4), MPI.SUM, comm2)
            new = MPI.Comm_shrink(comm2)
            out = MPI.Allreduce(np.full(2, float(rank)), MPI.SUM, new)
            assert out[0] == sum(range(size))
            MPI.Barrier(new)

        run_spmd(body, nprocs)
        diags = analyze.verify_trace(analyze.last_trace())
        assert not diags, [str(d) for d in diags]
    finally:
        monkeypatch.delenv("TPU_MPI_TRACE")
        config.load(refresh=True)


# ---------------------------------------------------------------------------
# Op timeout: indefinite blocking -> typed DeadlockError
# ---------------------------------------------------------------------------

def test_op_timeout_turns_blocking_recv_into_deadlock_error(monkeypatch):
    monkeypatch.setenv("TPU_MPI_OP_TIMEOUT_MS", "600")
    config.load(refresh=True)
    try:
        def body():
            comm = MPI.COMM_WORLD
            t0 = time.monotonic()
            with pytest.raises(DeadlockError):
                MPI.Recv(np.zeros(4), 1 - MPI.Comm_rank(comm), 3, comm)
            # well under the 60 s deadlock default: the knob took effect
            assert time.monotonic() - t0 < 30.0

        run_spmd(body, 2)
    finally:
        monkeypatch.delenv("TPU_MPI_OP_TIMEOUT_MS")
        config.load(refresh=True)


# ---------------------------------------------------------------------------
# Failure-detector substrate: a live native-transport pair
# ---------------------------------------------------------------------------

@pytest.fixture
def native_pair():
    from tpu_mpi import _native
    try:
        _native.load()
    except Exception as e:          # no compiler / no build cache
        pytest.skip(f"native transport unavailable: {e}")
    a = _native.NativeTransport(0, 2)
    b = _native.NativeTransport(1, 2)
    addrs = [f"127.0.0.1:{a.port}", f"127.0.0.1:{b.port}"]
    a.set_peers(addrs)
    b.set_peers(addrs)
    yield a, b
    for t in (a, b):
        try:
            t.stop()
            t.close()
        except Exception:
            pass


def test_detector_off_reports_unknown(native_pair):
    a, b = native_pair
    assert a.peer_age_ms(1) == -1
    assert a.peer_age_ms(0) == -1


def test_late_peer_ages_dead_socket_is_terminal(native_pair):
    a, b = native_pair
    a.hb_enable(20)
    b.hb_enable(20)
    # both pumping heartbeats: the age stays bounded by a few intervals
    time.sleep(1.0)
    age = a.peer_age_ms(1)
    assert 0 <= age < 500, age
    # LATE peer: b stops emitting but its socket stays open — the age grows
    # past the interval, which is exactly the signal the Python detector
    # compares against TPU_MPI_FAILURE_TIMEOUT_MS. Not a dead verdict.
    b.hb_enable(0)
    time.sleep(0.7)
    age = a.peer_age_ms(1)
    assert age >= 500, age
    assert age != -2
    # DEAD peer: the socket closes — terminal -2, no timeout needed
    b.stop()
    b.close()
    deadline = time.monotonic() + 5.0
    while a.peer_age_ms(1) != -2 and time.monotonic() < deadline:
        time.sleep(0.05)
    assert a.peer_age_ms(1) == -2


# ---------------------------------------------------------------------------
# Checkpoint hardening: torn writes must be typed errors, never crashes
# ---------------------------------------------------------------------------

def _write_ckpt(path):
    def body():
        comm = MPI.COMM_WORLD
        r = MPI.Comm_rank(comm)
        checkpoint.save_sharded(
            path, {"w": np.full(64, float(r)), "step": np.array([7 + r])},
            comm)

    run_spmd(body, 2)


def _expect_load_error(path, match, *, shard=1):
    def body():
        with pytest.raises(MPIError, match=match):
            checkpoint.load_sharded(path, MPI.COMM_WORLD, shard=shard)

    run_spmd(body, 1)


def test_checkpoint_roundtrip_with_shard_override(tmp_path):
    path = str(tmp_path / "ck.bin")
    _write_ckpt(path)

    def body():
        comm = MPI.COMM_WORLD
        assert checkpoint.shard_count(path, comm) == 2
        # a single-rank comm can still read BOTH shards (the post-shrink
        # restore pattern), but the default self-shard load refuses the
        # size mismatch with a typed, actionable error
        for s in range(2):
            t = checkpoint.load_sharded(path, comm, shard=s)
            assert np.all(np.asarray(t["w"]) == float(s))
            assert int(np.asarray(t["step"])[0]) == 7 + s
        with pytest.raises(MPIError, match="pass shard="):
            checkpoint.load_sharded(path, comm)

    run_spmd(body, 1)


def test_checkpoint_truncated_head_is_typed(tmp_path):
    path = str(tmp_path / "ck.bin")
    _write_ckpt(path)
    with open(path, "r+b") as f:
        f.truncate(10)
    _expect_load_error(path, "truncated")


def test_checkpoint_truncated_payload_is_typed(tmp_path):
    path = str(tmp_path / "ck.bin")
    _write_ckpt(path)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - 16)       # cut into the LAST shard's arrays
    _expect_load_error(path, "truncated")


def test_checkpoint_payload_corruption_is_typed(tmp_path):
    path = str(tmp_path / "ck.bin")
    _write_ckpt(path)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:    # flip one payload byte (last shard)
        f.seek(size - 9)
        byte = f.read(1)
        f.seek(size - 9)
        f.write(bytes([byte[0] ^ 0xFF]))
    _expect_load_error(path, "payload CRC mismatch")


def test_checkpoint_header_corruption_is_typed(tmp_path):
    path = str(tmp_path / "ck.bin")
    _write_ckpt(path)
    with open(path, "r+b") as f:    # flip a byte inside the pickled header
        f.seek(40)
        byte = f.read(1)
        f.seek(40)
        f.write(bytes([byte[0] ^ 0xFF]))
    _expect_load_error(path, "header CRC mismatch")


def test_checkpoint_v1_format_is_rejected_with_guidance(tmp_path):
    path = str(tmp_path / "ck.bin")
    _write_ckpt(path)
    with open(path, "r+b") as f:
        f.seek(0)
        f.write(checkpoint._MAGIC_V1.to_bytes(8, "little"))
    _expect_load_error(path, "re-save")


def test_checkpoint_save_leaves_no_tmp_file(tmp_path):
    path = str(tmp_path / "ck.bin")
    _write_ckpt(path)
    assert os.path.exists(path)
    assert not os.path.exists(path + ".tmp")


# ---------------------------------------------------------------------------
# Chaos (multi-process): SIGKILL a rank, survivors recover
# ---------------------------------------------------------------------------

def _run_chaos(body: str, nprocs: int = 4, timeout: float = 180.0,
               env_extra: dict | None = None):
    """Like test_procs._run_procs but for jobs where a rank DIES: no OK
    assertion here (the dead rank prints nothing), and the failure
    detector is switched on."""
    script = textwrap.dedent(body)
    path = os.path.join("/tmp", f"tpu_mpi_chaos_{abs(hash(body)) % 10**8}.py")
    with open(path, "w") as f:
        f.write(f"import sys; sys.path.insert(0, {REPO!r})\n" + script)
    env = dict(os.environ)
    env.pop("TPU_MPI_PROC_RANK", None)
    env["TPU_MPI_HEARTBEAT_MS"] = "100"
    env["TPU_MPI_FAILURE_TIMEOUT_MS"] = "1500"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "tpu_mpi.launcher", "-n", str(nprocs),
         "--procs", "--sim", "1", "--timeout", str(timeout - 20), path],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


def test_chaos_sigkill_typed_errors_shrink_continue():
    """The tentpole end-to-end: rank 2 is SIGKILLed mid-sweep. Every
    survivor must get a typed ULFM error within the failure timeout (not a
    hang, not an AbortError), shrink to a 3-rank communicator, and keep
    computing on it. The launcher must name the dead rank and exit
    EXIT_SHRUNK_OK."""
    res = _run_chaos("""
        import os, signal, time
        import numpy as np
        import tpu_mpi as MPI
        from tpu_mpi.error import ProcFailedError, RevokedError

        MPI.Init()
        comm = MPI.COMM_WORLD
        rank, size = MPI.Comm_rank(comm), MPI.Comm_size(comm)
        out = MPI.Allreduce(np.ones(4), MPI.SUM, comm)
        assert np.all(np.asarray(out) == size)

        if rank == 2:
            os.kill(os.getpid(), signal.SIGKILL)

        t0 = time.monotonic()
        try:
            while True:
                MPI.Allreduce(np.ones(2), MPI.SUM, comm)
                time.sleep(0.01)
        except (ProcFailedError, RevokedError) as e:
            dt = time.monotonic() - t0
            assert dt < 10.0, f"typed error took {dt}s"
            print(f"FAULT-{rank} {type(e).__name__}", flush=True)

        MPI.Comm_revoke(comm)
        new = MPI.Comm_shrink(comm)
        assert MPI.Comm_size(new) == 3, MPI.Comm_size(new)
        out = MPI.Allreduce(np.array([1.0]), MPI.SUM, new)
        assert out[0] == 3.0
        print(f"OK-{rank}", flush=True)
        MPI.Finalize()
    """)
    assert res.returncode == EXIT_SHRUNK_OK, (res.returncode, res.stdout,
                                              res.stderr)
    for r in (0, 1, 3):
        assert f"FAULT-{r}" in res.stdout, res.stdout
        assert f"OK-{r}" in res.stdout, res.stdout
    assert "OK-2" not in res.stdout
    assert "rank 2 died (signal SIGKILL)" in res.stderr, res.stderr
    assert "[first failure]" in res.stderr


def test_chaos_agree_survives_coordinator_death():
    """Failure DURING Comm_agree: the agreement coordinator (lowest live
    rank, i.e. rank 0) dies before contributing; the survivors must fail
    over to the next coordinator and still decide — then shrink."""
    res = _run_chaos("""
        import os, signal, time
        import numpy as np
        import tpu_mpi as MPI

        MPI.Init()
        comm = MPI.COMM_WORLD
        rank, size = MPI.Comm_rank(comm), MPI.Comm_size(comm)
        MPI.Barrier(comm)

        if rank == 0:
            os.kill(os.getpid(), signal.SIGKILL)
        time.sleep(0.3)     # let the death land before agreeing

        v = MPI.Comm_agree(comm, 0b110 if rank == 1 else 0b111)
        assert v == 0b110, v
        new = MPI.Comm_shrink(comm)
        assert MPI.Comm_size(new) == 3
        assert MPI.Comm_rank(new) == rank - 1
        out = MPI.Allreduce(np.array([float(rank)]), MPI.SUM, new)
        assert out[0] == 6.0
        print(f"OK-{rank}", flush=True)
        MPI.Finalize()
    """)
    assert res.returncode == EXIT_SHRUNK_OK, (res.returncode, res.stdout,
                                              res.stderr)
    for r in (1, 2, 3):
        assert f"OK-{r}" in res.stdout, (res.stdout, res.stderr)
    assert "rank 0 died (signal SIGKILL)" in res.stderr


def test_launcher_reports_nonzero_exit_as_rank_failed():
    """A rank that EXITS nonzero (not a signal) is a failure, not a clean
    shrink: the launcher must exit EXIT_RANK_FAILED even in FT mode."""
    res = _run_chaos("""
        import sys
        import tpu_mpi as MPI
        MPI.Init()
        rank = MPI.Comm_rank(MPI.COMM_WORLD)
        MPI.Barrier(MPI.COMM_WORLD)
        if rank == 1:
            sys.exit(3)
        import time; time.sleep(1.0)
        print(f"OK-{rank}", flush=True)
        MPI.Finalize()
    """, timeout=120.0)
    from tpu_mpi.launcher import EXIT_RANK_FAILED
    assert res.returncode == EXIT_RANK_FAILED, (res.returncode, res.stderr)
    assert "rank 1 died (exit code 3)" in res.stderr, res.stderr


@pytest.mark.slow
def test_jacobi_ft_example_chaos_converges():
    """The full shrink -> restore -> continue loop: examples/11-jacobi-ft.py
    with an injected SIGKILL must reconverge on 3 ranks to the same answer
    the 4-rank run produces."""
    env = dict(os.environ)
    env.pop("TPU_MPI_PROC_RANK", None)
    env.update({"TPU_MPI_HEARTBEAT_MS": "100",
                "TPU_MPI_FAILURE_TIMEOUT_MS": "1500",
                "TPU_MPI_FT_KILL_SWEEP": "30"})
    res = subprocess.run(
        [sys.executable, "-m", "tpu_mpi.launcher", "-n", "4", "--procs",
         "--sim", "1", "--timeout", "400",
         os.path.join(REPO, "examples", "11-jacobi-ft.py")],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert res.returncode == EXIT_SHRUNK_OK, (res.returncode, res.stdout,
                                              res.stderr)
    assert "converged after" in res.stdout
    assert "on 3 rank(s)" in res.stdout
    for r in (0, 2, 3):
        assert f"OK-{r}" in res.stdout, res.stdout
