"""Overlap-engine sweep (ISSUE-3 acceptance artifact): what the host path's
chunk pipeline, persistent plans and background progress actually buy.

Four lane families over the thread tier (the deployment path a single-host
user hits):

- ``host_pipelined`` / ``host_monolithic`` — blocking Allreduce latency/algbw
  with the chunk pipeline ON (config default) vs OFF
  (``TPU_MPI_PIPELINE_MIN_BYTES=0``). Every pipelined row carries
  ``bitwise_equal``: the pipelined result's bytes are compared against the
  monolithic result on identical deterministic inputs — chunking elementwise
  rank-order folds is chunk-separable, so anything but ``true`` is a bug.
- ``host_persistent`` — the same op through the MPI-4 persistent handle
  (``Allreduce_init`` + Start/Wait per round): plan and schedule resolved
  once, each round pays only the rendezvous.
- ``overlap_host_idle`` / ``overlap_cpu_spin`` — the nonblocking story.
  Each row times (a) the blocking op, (b) a calibrated same-duration local
  window, (c) Iallreduce + window + Wait, and reports
  ``overlap_fraction = (t_op + t_window - t_total) / min(t_op, t_window)``
  (1.0 = the collective fully hid behind the window; <=0 = serialized).
  ``window_kind`` says what the window was:

  * ``host_idle`` — ``time.sleep``: the rank thread is off-CPU, modeling a
    dispatched device step (the TPU training-loop case, where the rank
    thread has handed work to the chip and the host core is free). This is
    the HEADLINE lane: the progress worker gets the core, so it measures
    the engine's actual ability to advance the op in the background.
  * ``cpu_spin`` — a numpy compute loop that KEEPS the core busy. On a
    1-core host (this CI box) the spin, the calibration and the progress
    worker all time-share one core under the GIL, so this lane is noisy
    and can report anything from serialized (-1) to apparent-full overlap
    (when contention inflates the measured window) — it is committed as
    the honesty control so the headline cannot be mistaken for it, not as
    a measurement of the engine.

The top-level ``overlap_fraction`` headline is the host_idle lane at the
largest size. ``pipelined_bitwise_equal`` summarizes the identity lane.

Usage: python benchmarks/overlap_sweep.py [--max-bytes N] [--min-bytes N]
       [--ranks N] [--repeats N] [-o results/file.json]
"""

from __future__ import annotations

import argparse
import hashlib
import os
import sys
import time

from common import detect_platform, emit, force_cpu_sim, size_sweep

_PIPE_ENV = "TPU_MPI_PIPELINE_MIN_BYTES"
_PIPE_INHERITED = os.environ.get(_PIPE_ENV)   # respect the caller's knob


def _set_pipeline(min_bytes: "int | None") -> None:
    """Flip the pipeline knob for this process (workers see it via config).
    ``None`` restores whatever the caller had set (the ON configuration)."""
    from tpu_mpi import config
    if min_bytes is None:
        if _PIPE_INHERITED is None:
            os.environ.pop(_PIPE_ENV, None)
        else:
            os.environ[_PIPE_ENV] = _PIPE_INHERITED
    else:
        os.environ[_PIPE_ENV] = str(min_bytes)
    config.load(refresh=True)


def _allreduce_digest(n: int, nranks: int) -> str:
    """SHA256 of the Allreduce result bytes on deterministic per-rank
    inputs — the cross-config bitwise-identity probe."""
    import numpy as np
    import tpu_mpi as MPI
    from tpu_mpi import spmd_run

    def body():
        MPI.Init()
        comm = MPI.COMM_WORLD
        rank = comm.rank()
        x = np.random.RandomState(1234 + rank).rand(n).astype(np.float32)
        out = MPI.Allreduce(x, MPI.SUM, comm)
        MPI.Finalize()
        return hashlib.sha256(np.asarray(out).tobytes()).hexdigest()

    digests = spmd_run(body, nranks)
    assert len(set(digests)) == 1, "ranks disagree on the Allreduce result"
    return digests[0]


def _time_blocking(n: int, nranks: int, repeats: int,
                   persistent: bool = False) -> float:
    """Best per-op seconds for a blocking (or persistent Start/Wait)
    Allreduce round across rank threads (max over ranks, min over blocks)."""
    import numpy as np
    import tpu_mpi as MPI
    from tpu_mpi import spmd_run

    iters = 3 if n * 4 >= (1 << 24) else 10

    def body():
        MPI.Init()
        comm = MPI.COMM_WORLD
        x = np.ones(n, np.float32)
        req = MPI.Allreduce_init(x, MPI.SUM, comm) if persistent else None

        def one():
            if persistent:
                MPI.Start(req)
                MPI.Wait(req)
            else:
                MPI.Allreduce(x, MPI.SUM, comm)

        one()                                     # warm: plan + buffers
        best = float("inf")
        for _ in range(repeats):
            MPI.Barrier(comm)
            t0 = time.perf_counter()
            for _ in range(iters):
                one()
            best = min(best, (time.perf_counter() - t0) / iters)
        MPI.Finalize()
        return best

    return max(spmd_run(body, nranks))


def _time_overlap(n: int, nranks: int, repeats: int, t_op: float,
                  window_kind: str) -> dict:
    """One overlap row: Iallreduce + a calibrated same-duration window +
    Wait, against the serial sum of their solo times."""
    import numpy as np
    import tpu_mpi as MPI
    from tpu_mpi import spmd_run

    def body():
        MPI.Init()
        comm = MPI.COMM_WORLD
        x = np.ones(n, np.float32)

        if window_kind == "host_idle":
            def window():
                time.sleep(t_op)
            t_win = t_op
        else:                                     # cpu_spin: calibrate work
            a = np.ones(4096, np.float32)
            reps, t = 1, 0.0
            while True:                           # double until >= t_op
                t0 = time.perf_counter()
                s = 0.0
                for _ in range(reps):
                    s += float(a @ a)
                t = time.perf_counter() - t0
                if t >= t_op or reps > 1 << 22:
                    break
                reps *= 2

            def window():
                s = 0.0
                for _ in range(reps):
                    s += float(a @ a)
                return s
            t_win = t

        # warm plan/buffers AND the per-comm nonblocking worker thread —
        # its lazy creation must not be billed to the first timed round
        MPI.Wait(MPI.Iallreduce(x, MPI.SUM, comm))
        best_total = float("inf")
        for _ in range(repeats):
            MPI.Barrier(comm)
            t0 = time.perf_counter()
            req = MPI.Iallreduce(x, MPI.SUM, comm)
            window()
            MPI.Wait(req)
            best_total = min(best_total, time.perf_counter() - t0)
        MPI.Finalize()
        return best_total, t_win

    results = spmd_run(body, nranks)
    t_total = max(r[0] for r in results)
    t_win = max(r[1] for r in results)
    frac = (t_op + t_win - t_total) / min(t_op, t_win)
    return {"bytes": n * 4, "window_kind": window_kind,
            "t_op_ms": round(t_op * 1e3, 3),
            "t_window_ms": round(t_win * 1e3, 3),
            "t_total_ms": round(t_total * 1e3, 3),
            "overlap_fraction": round(max(-1.0, min(1.0, frac)), 4)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-bytes", type=int, default=1 << 25)
    ap.add_argument("--min-bytes", type=int, default=1 << 20)
    ap.add_argument("--ranks", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("-o", "--out", default="-")
    args = ap.parse_args()

    # thread-tier sweep on numpy payloads: fake CPU devices suffice
    # everywhere
    force_cpu_sim(max(args.ranks, 2))

    sizes = size_sweep(args.max_bytes, min_bytes=args.min_bytes)
    record: dict = {"benchmark": "overlap_sweep", "platform": detect_platform(),
                    "ranks": args.ranks, "lanes": {}}

    piped, mono, persist = [], [], []
    for nbytes in sizes:
        n = max(1, nbytes // 4)
        _set_pipeline(None)                       # config default: ON >=1MiB
        d_pipe = _allreduce_digest(n, args.ranks)
        t_pipe = _time_blocking(n, args.ranks, args.repeats)
        t_pers = _time_blocking(n, args.ranks, args.repeats, persistent=True)
        _set_pipeline(0)                          # pipeline OFF
        d_mono = _allreduce_digest(n, args.ranks)
        t_mono = _time_blocking(n, args.ranks, args.repeats)
        _set_pipeline(None)
        eq = d_pipe == d_mono
        piped.append({"bytes": n * 4, "lat_us": round(t_pipe * 1e6, 1),
                      "algbw_gbps": round(n * 4 / t_pipe / 1e9, 3),
                      "bitwise_equal": eq})
        mono.append({"bytes": n * 4, "lat_us": round(t_mono * 1e6, 1),
                     "algbw_gbps": round(n * 4 / t_mono / 1e9, 3)})
        persist.append({"bytes": n * 4, "lat_us": round(t_pers * 1e6, 1),
                        "algbw_gbps": round(n * 4 / t_pers / 1e9, 3)})
        print(f"host {n * 4:>10d} B  pipelined {t_pipe * 1e6:>9.1f} us  "
              f"monolithic {t_mono * 1e6:>9.1f} us  "
              f"persistent {t_pers * 1e6:>9.1f} us  bitwise_equal={eq}",
              file=sys.stderr)
    record["lanes"]["host_pipelined"] = piped
    record["lanes"]["host_monolithic"] = mono
    record["lanes"]["host_persistent"] = persist
    record["pipelined_bitwise_equal"] = all(r["bitwise_equal"] for r in piped)

    idle, spin = [], []
    for nbytes in sizes:
        n = max(1, nbytes // 4)
        t_op = _time_blocking(n, args.ranks, args.repeats)
        row_i = _time_overlap(n, args.ranks, args.repeats, t_op, "host_idle")
        row_s = _time_overlap(n, args.ranks, args.repeats, t_op, "cpu_spin")
        idle.append(row_i)
        spin.append(row_s)
        print(f"overlap {n * 4:>10d} B  host_idle "
              f"{row_i['overlap_fraction']:>7.3f}  cpu_spin "
              f"{row_s['overlap_fraction']:>7.3f}", file=sys.stderr)
    record["lanes"]["overlap_host_idle"] = idle
    record["lanes"]["overlap_cpu_spin"] = spin
    # headline: the engine's background progress with the core free (the
    # dispatched-device-step case), at the largest size
    record["overlap_fraction"] = max(
        idle, key=lambda r: r["bytes"])["overlap_fraction"]
    record["overlap_window_kind"] = "host_idle"

    from common import assert_artifact_schema
    assert_artifact_schema(record)
    emit(args.out, record)


if __name__ == "__main__":
    main()
