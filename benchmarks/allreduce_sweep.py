"""Allreduce bandwidth sweep: GB/s vs message size, Float32, 8 B - 1 GB.

The BASELINE.json headline metric. Three lanes, each exercised when the
hardware allows:

- ``host``   — the framework's host-path ``MPI.Allreduce`` over rank threads
  (jitted fold + zero-copy DeviceBuffer rebind); runs everywhere, measures
  the deployment path a single-host user hits.
- ``host_persistent`` — the registered-buffer fast path (ISSUE-6): one
  ``Allreduce_init`` per size outside the timed loop, then ``Start``/``Wait``
  rounds against the plan-pinned wire buffers. Both host lanes also emit a
  ``pvars_phase`` block (rendezvous/fold/copy seconds + rendezvous share)
  at the largest swept size.
- ``ingraph`` — K-chained
  in-jit Allreduce folds (+ the donated-accumulator variant and
  reducescatter/allgather, all on the same size ladder), adaptive-slope
  timed so the per-call dispatch floor cancels; the lane that answers the north-star
  question of what the collectives cost where they actually run (inside
  compiled XLA code). The record also carries a ``ceiling_control`` block —
  the best-achievable same-traffic no-MPI-semantics schedule under the
  identical protocol — and the ``fold_vs_ceiling`` ratio.
- ``psum``   — in-graph ``lax.psum`` via ``tpu_mpi.xla.allreduce`` inside
  jit/shard_map (needs >= 2 XLA devices); the ICI lane. Reports ring bus
  bandwidth 2(n-1)/n * bytes / t.
- ``pallas`` — the hand-written Pallas ring-allreduce kernel
  (``tpu_mpi.xla.pallas_kernels.ring_allreduce``), same bus-bandwidth
  accounting (needs >= 2 devices).

- ``procs``  — the same host-path Allreduce across OS processes over the
  native C++ transport (ring reduce-scatter+allgather above the size
  threshold, star rendezvous below — the tier VERDICT r1 item 4 asked to
  quantify). Runs via ``launch_processes``.
- ``procs_<algo>`` — one lane per tpu_mpi.tune portfolio algorithm (star,
  shm, rdouble, rabenseifner, ring — plus ``procs_hier``, the two-level
  composite, whenever the world has a usable domain split: set
  ``TPU_MPI_DOMAINS=2`` to emulate it on one machine), each forced via
  TPU_MPI_COLL_ALGO in lockstep inside one SPMD launch; selected with
  ``--lanes procs_algos``. Hier rows carry a ``phase_s`` breakdown
  (intra_fold / inter_exchange / allgather seconds from a short pvar-on
  window after the timed loop), and the record is stamped with the
  world's ``topology`` key.

Usage: python benchmarks/allreduce_sweep.py [--max-bytes N] [--ranks N]
       [--lanes host,psum,pallas,procs,procs_algos] [-o results/file.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from common import best_block, detect_platform, emit, iters_for, size_sweep

REPEATS = 3


def bench_host(nranks: int, sizes: list[int], use_device: bool,
               persistent: bool = False) -> list[dict]:
    # chained honest-execution protocol — see
    # common.host_allreduce_times (VERDICT r2 weak #1). persistent=True is
    # the registered-buffer lane (ISSUE-6): one Allreduce_init outside the
    # timed loop, Start/Wait per op against the plan-pinned buffers.
    from common import host_allreduce_times

    tag = "hostP" if persistent else "host"
    rows = []
    for nbytes in sizes:
        n = max(1, nbytes // 4)
        warmup, iters = iters_for(nbytes)
        dt = best_block(host_allreduce_times(n, nranks, use_device,
                                             warmup, iters, REPEATS,
                                             persistent=persistent))
        rows.append({"bytes": n * 4, "lat_us": round(dt * 1e6, 2),
                     "algbw_gbps": round(n * 4 / dt / 1e9, 3)})
        print(f"{tag:<5} {n * 4:>11d} B  {dt * 1e6:>10.1f} us  "
              f"{rows[-1]['algbw_gbps']:>8.3f} GB/s", file=sys.stderr)
    return rows


def host_phase_breakdown(nranks: int, n_elems: int,
                         rounds: int = 50) -> dict:
    """Per-phase pvar evidence for the host lanes (ISSUE-6 satellite 1,
    extended for ISSUE-11): run ``rounds`` generic Allreduce calls with
    auto-arming disabled (the legacy "before" curve), ``rounds`` with the
    default auto-armed path (the promoted plain-call lane), and ``rounds``
    hand-armed persistent Start/Wait rounds, all back-to-back under one
    SPMD session with pvars on, snapshotting rank 0's rendezvous/fold/copy
    phase seconds after each. The default lane's rendezvous share
    collapsing toward the hand-armed lane's is the auto-arming signature."""
    import numpy as np
    import tpu_mpi as MPI
    from tpu_mpi import spmd_run

    os.environ["TPU_MPI_PVARS"] = "1"
    from tpu_mpi import config as _cfg
    _cfg.load(refresh=True)

    def body():
        MPI.Init()
        comm = MPI.COMM_WORLD
        rank = MPI.Comm_rank(comm)
        buf = np.ones(n_elems, np.float32)
        out = np.zeros(n_elems, np.float32)
        MPI.Allreduce(buf, out, MPI.SUM, comm)      # warm plan caches
        # barriers fence each measured window so one rank's section change
        # cannot bleed into a sibling's still-open spans (GIL time-sharing)
        # legacy window: auto-arming off — the pre-ISSUE-11 default path
        MPI.Barrier(comm)
        if rank == 0:
            os.environ["TPU_MPI_AUTO_ARM"] = "0"
        MPI.Barrier(comm)
        _cfg.load(refresh=True)
        MPI.Barrier(comm)
        comm.get_pvars(reset=True)
        for _ in range(rounds):
            MPI.Allreduce(buf, out, MPI.SUM, comm)
        legacy = comm.get_pvars(reset=True)
        # default window: auto-arm back on; warm past the threshold so the
        # measured rounds all ride the promoted registered path
        MPI.Barrier(comm)
        if rank == 0:
            os.environ.pop("TPU_MPI_AUTO_ARM", None)
        MPI.Barrier(comm)
        _cfg.load(refresh=True)
        for _ in range(8):
            MPI.Allreduce(buf, out, MPI.SUM, comm)
        MPI.Barrier(comm)
        comm.get_pvars(reset=True)
        for _ in range(rounds):
            MPI.Allreduce(buf, out, MPI.SUM, comm)
        generic = comm.get_pvars(reset=True)
        req = MPI.Allreduce_init(buf, out, MPI.SUM, comm)
        MPI.Start(req)
        MPI.Wait(req)                               # warm registered round
        MPI.Barrier(comm)
        comm.get_pvars(reset=True)
        for _ in range(rounds):
            MPI.Start(req)
            MPI.Wait(req)
        pers = comm.get_pvars(reset=True)
        MPI.Finalize()

        def lane(s):
            ph = {k: round(v, 6) for k, v in s["phase_s"].items()}
            tot = sum(ph.values())
            return {"rounds": rounds, "phase_s": ph,
                    "wait_s": round(s["wait_s"], 6),
                    "rendezvous_share": round(
                        ph.get("rendezvous", 0.0) / tot, 4) if tot else None}
        return {"host_legacy": lane(legacy), "host": lane(generic),
                "host_persistent": lane(pers)}

    res = spmd_run(body, nranks)
    out = res[0]
    out["bytes"] = n_elems * 4
    # cross-rank aggregate lanes: exactly one rank executes each round's
    # fold, so rank-0's share depends on WHICH rank folded (a scheduling
    # lottery at MiB payloads). Summing every rank's phases cancels that
    # attribution and gives a run-stable share — the number CI gates on.
    agg: dict = {}
    for name in ("host_legacy", "host", "host_persistent"):
        ph: dict = {}
        for r in res:
            for k, v in r[name]["phase_s"].items():
                ph[k] = round(ph.get(k, 0.0) + v, 6)
        tot = sum(ph.values())
        agg[name] = {"rounds": rounds, "phase_s": ph,
                     "rendezvous_share": round(
                         ph.get("rendezvous", 0.0) / tot, 4) if tot else None}
    out["aggregate"] = agg
    for name in ("host_legacy", "host", "host_persistent"):
        print(f"pvars {name:<16} rank0_share="
              f"{out[name]['rendezvous_share']} aggregate_share="
              f"{agg[name]['rendezvous_share']} "
              f"phase_s={out[name]['phase_s']}", file=sys.stderr)
    return out


def _bench_in_graph(sizes: list[int], fn_of_mesh, max_iters: int = 10 ** 9,
                    repeats: int = REPEATS) -> list[dict]:
    """Shared driver for the psum and pallas lanes."""
    import time
    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    n = len(devs)
    rows = []
    for nbytes in sizes:
        # MPI Allreduce semantics: every
        # rank contributes nbytes, so the sharded global operand is n*nbytes
        per_elems = max(1, nbytes // 4)
        cnt = per_elems * n
        warmup, iters = iters_for(nbytes)
        warmup, iters = min(warmup, max_iters), min(iters, max_iters)
        f = fn_of_mesh(devs, cnt)
        x = jnp.ones(cnt, jnp.float32)
        try:
            f(x).block_until_ready()
        except Exception as e:
            print(f"in-graph {nbytes}B skipped: {type(e).__name__}: {e}",
                  file=sys.stderr)
            continue
        for _ in range(warmup):
            f(x).block_until_ready()
        dt = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(iters):
                f(x).block_until_ready()
            dt = min(dt, (time.perf_counter() - t0) / iters)
        per_rank = per_elems * 4
        busbw = 2 * (n - 1) / n * per_rank / dt / 1e9
        rows.append({"bytes": per_rank, "lat_us": round(dt * 1e6, 2),
                     "busbw_gbps": round(busbw, 3)})
        print(f"graph {per_rank:>11d} B  {dt * 1e6:>10.1f} us  "
              f"{busbw:>8.3f} GB/s bus", file=sys.stderr)
    return rows


def bench_ingraph(nranks: int, sizes: list[int],
                  variants: tuple = ("allreduce",)) -> dict:
    """The in-graph lane: K-chained in-jit
    collective folds, adaptive slope timing, closed-form readback asserted.
    Runs on the real chip; see common.ingraph_collective_slope."""
    from common import ingraph_collective_slope, measure_dispatch_floor

    rtt = measure_dispatch_floor()
    out: dict = {}
    for variant in variants:
        rows = []
        done = set()                      # structural dedupe: one row/size
        for nbytes in sizes:
            n = max(1, nbytes // 4)
            if n * 4 in done:
                continue
            try:
                r = ingraph_collective_slope(variant, n, nranks, rtt=rtt)
            except Exception as e:
                print(f"ingraph {variant} {nbytes}B skipped: "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
                continue
            done.add(r["bytes"])
            row = {"bytes": r["bytes"],
                   "per_fold_us": r["per_fold_us"],
                   "algbw_gbps": r["algbw_gbps"],
                   "hbm_gbps_implied": r["hbm_gbps_implied"],
                   "hbm_model_binds": r["hbm_model_binds"],
                   "traffic_model": r["traffic_model"],
                   "k": r["k"], "slope_spread": r["slope_spread"]}
            rows.append(row)
            print(f"ingraph:{variant} {r['bytes']:>11d} B  "
                  f"{r['per_fold_us']:>10.1f} us/fold  "
                  f"{r['algbw_gbps']:>8.3f} GB/s  "
                  f"(HBM {r['hbm_gbps_implied']} GB/s, k={r['k']}, "
                  f"spread {r['slope_spread']})", file=sys.stderr)
        out[variant] = rows
    return out


def bench_psum(sizes: list[int]) -> list[dict]:
    import jax
    from jax.sharding import PartitionSpec as P
    import tpu_mpi as MPI
    from tpu_mpi import xla

    def make(devs, cnt):
        mesh = xla.make_mesh({"x": len(devs)}, devices=devs)
        return jax.jit(jax.shard_map(
            lambda v: xla.allreduce(v, MPI.SUM, axis="x"),
            mesh=mesh, in_specs=P("x"), out_specs=P()))
    return _bench_in_graph(sizes, make)


def bench_pallas(sizes: list[int]) -> list[dict]:
    import jax
    from jax.sharding import PartitionSpec as P
    from tpu_mpi import xla
    from tpu_mpi.xla import pallas_kernels as pk

    def make(devs, cnt):
        mesh = xla.make_mesh({"x": len(devs)}, devices=devs)
        return jax.jit(jax.shard_map(
            lambda v: pk.ring_allreduce(v, "sum", axis="x"),
            mesh=mesh, in_specs=P("x"), out_specs=P("x"),
            check_vma=False))   # pallas_call outputs carry no vma info
    import jax as _jax
    interp = _jax.devices()[0].platform != "tpu"
    # the interpret machine runs the kernel step-by-step in Python — cap the
    # iteration count there; Mosaic-on-TPU gets the full OSU schedule
    return _bench_in_graph(sizes, make,
                           max_iters=2 if interp else 10 ** 9,
                           repeats=1 if interp else REPEATS)


def bench_procs(nranks: int, max_bytes: int,
                algos: bool = False, min_bytes: int = 8) -> list[dict] | dict:
    """Cross-process Allreduce sweep: re-enter this script as an SPMD child
    under launch_processes; rank 0 writes rows to --rows-out.

    With ``algos=True`` the child additionally forces each eligible
    tpu_mpi.tune portfolio algorithm per size (TPU_MPI_COLL_ALGO + config
    reload in lockstep) and the return value is a dict of per-algorithm
    lanes (``procs_star``, ``procs_shm``, ...) instead of one list, so
    the crossovers the autotuner measures are visible in the artifact."""
    import tempfile
    from tpu_mpi.launcher import launch_processes

    extra = ["--algos"] if algos else []
    with tempfile.NamedTemporaryFile("r", suffix=".jsonl") as rows_f:
        code = launch_processes(
            os.path.abspath(__file__), nranks,
            ["--max-bytes", str(max_bytes), "--min-bytes", str(min_bytes),
             "--rows-out", rows_f.name] + extra,
            timeout=3600)
        if code != 0:
            print(f"procs lane failed with exit code {code}", file=sys.stderr)
            return {} if algos else []
        rows = [json.loads(l) for l in rows_f.read().splitlines()]
        if not algos:
            return rows
        lanes: dict = {}
        for row in rows:
            lanes.setdefault(f"procs_{row.pop('algo')}", []).append(row)
        return lanes


def _procs_child(max_bytes: int, rows_out: str, algos: bool = False,
                 min_bytes: int = 8) -> None:
    import time
    import numpy as np
    import tpu_mpi as MPI
    from tpu_mpi import config as _cfg
    from tpu_mpi import tune as _tune

    MPI.Init()
    comm = MPI.COMM_WORLD
    rank, size = comm.rank(), comm.size()

    def measure(n, warmup, iters):
        buf = np.ones(n, np.float32)
        out = np.zeros(n, np.float32)
        for _ in range(warmup):
            MPI.Allreduce(buf, out, MPI.SUM, comm)
        best = float("inf")
        for _ in range(REPEATS):
            MPI.Barrier(comm)
            t0 = time.perf_counter()
            for _ in range(iters):
                MPI.Allreduce(buf, out, MPI.SUM, comm)
            MPI.Barrier(comm)
            best = min(best, (time.perf_counter() - t0) / iters)
        return best

    with open(rows_out or os.devnull, "a") as f:
        for nbytes in size_sweep(max_bytes, min_bytes):
            n = max(1, nbytes // 4)
            warmup, iters = iters_for(nbytes)
            iters = max(2, iters // 4)       # wire rounds cost more
            if algos:
                # identical schedule on every rank: the eligibility inputs
                # (size, bytes, same-host shm, domain split) are rank-uniform
                lane = _tune.candidates(
                    "allreduce", size, n * 4, commutative=True,
                    elementwise=True, numeric=True,
                    shm=os.path.isdir("/dev/shm"),
                    domains=_tune._active_domains(size))
            else:
                lane = [None]
            for algo in lane:
                if algo is not None:
                    os.environ["TPU_MPI_COLL_ALGO"] = f"allreduce={algo}"
                    _cfg.load(refresh=True)
                best = measure(n, warmup, iters)
                phase = None
                if algo == "hier":
                    # per-phase evidence for the composite: a short pvar-on
                    # window AFTER the timed loop (pvars stay off while the
                    # lane latencies are measured), flipped in lockstep
                    os.environ["TPU_MPI_PVARS"] = "1"
                    _cfg.load(refresh=True)
                    buf = np.ones(n, np.float32)
                    out = np.zeros(n, np.float32)
                    comm.get_pvars(reset=True)
                    for _ in range(max(4, iters)):
                        MPI.Allreduce(buf, out, MPI.SUM, comm)
                    ph = comm.get_pvars(reset=True)["phase_s"]
                    os.environ.pop("TPU_MPI_PVARS", None)
                    _cfg.load(refresh=True)
                    phase = {k: round(ph.get(k, 0.0), 6)
                             for k in ("intra_fold", "inter_exchange",
                                       "allgather")}
                if rank == 0:
                    row = {"bytes": n * 4, "lat_us": round(best * 1e6, 2),
                           "algbw_gbps": round(n * 4 / best / 1e9, 3)}
                    if algo is not None:
                        row["algo"] = algo
                    if phase is not None:
                        row["phase_s"] = phase
                    f.write(json.dumps(row) + "\n")
                    f.flush()
                    tag = f"procs:{algo}" if algo else "procs"
                    print(f"{tag:<18} {n * 4:>11d} B  {best * 1e6:>10.1f} us"
                          f"  {row['algbw_gbps']:>8.3f} GB/s", file=sys.stderr)
            if algos:
                os.environ.pop("TPU_MPI_COLL_ALGO", None)
                _cfg.load(refresh=True)
    MPI.Finalize()


def main() -> None:
    # one 1 GB device op can outlast the default 60 s deadlock budget while
    # sibling rank-threads wait in Barrier — that is slowness, not
    # deadlock. Don't clobber an explicit override.
    os.environ.setdefault("TPU_MPI_DEADLOCK_TIMEOUT", "600")
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-bytes", type=int, default=1 << 30)
    ap.add_argument("--min-bytes", type=int, default=8,
                    help="smallest payload in the ladder; raise it to "
                         "extend an existing artifact's upper end without "
                         "re-measuring the small sizes")
    ap.add_argument("--ranks", type=int, default=4)
    ap.add_argument("--lanes",
                    default="host,host_persistent,ingraph,psum,pallas")
    ap.add_argument("--rows-out", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--algos", action="store_true",
                    help="per-algorithm procs lanes (procs_star, procs_shm, "
                         "...) forced via TPU_MPI_COLL_ALGO")
    ap.add_argument("-o", "--out", default="-")
    args = ap.parse_args()

    if os.environ.get("TPU_MPI_PROC_RANK") is not None:
        _procs_child(args.max_bytes, args.rows_out, args.algos,
                     args.min_bytes)
        return

    plat = detect_platform()
    sizes = size_sweep(args.max_bytes, args.min_bytes)
    lanes = args.lanes.split(",")
    from tpu_mpi import tune as _tune
    record: dict = {"benchmark": "allreduce_sweep", "platform": plat,
                    "ranks": args.ranks,
                    "topology": _tune.topology_key(
                        _tune._active_domains(args.ranks), args.ranks),
                    "lanes": {}}
    multi = plat["devices"] >= 2
    if "host" in lanes or "host_persistent" in lanes:
        use_device = plat["platform"] != "cpu"
        if "host" in lanes:
            record["lanes"]["host"] = bench_host(args.ranks, sizes,
                                                 use_device)
        if "host_persistent" in lanes:
            record["lanes"]["host_persistent"] = bench_host(
                args.ranks, sizes, use_device, persistent=True)
        # per-phase pvar evidence at the largest swept size: the persistent
        # lane's rendezvous share collapsing is the fast path's signature
        try:
            record["pvars_phase"] = host_phase_breakdown(
                args.ranks, max(1, sizes[-1] // 4))
        except Exception as e:
            print(f"pvar phase breakdown skipped: {type(e).__name__}: {e}",
                  file=sys.stderr)
    if "ingraph" in lanes:
        # sampled sizes: the adaptive slope spends ~0.5-2 s per (size,
        # variant); every 2nd size + the endpoints covers the curve. All
        # variants run the SAME ladder (ISSUE-1 satellite: rs/ag used to
        # stop at three spot sizes).
        sub = sizes[::2] + ([sizes[-1]] if (len(sizes) - 1) % 2 else [])
        ig = bench_ingraph(args.ranks, sub,
                           variants=("allreduce", "allreduce_donated",
                                     "reducescatter", "allgather"))
        record["lanes"]["ingraph"] = ig.pop("allreduce", [])
        for variant, rows in ig.items():
            record["lanes"][f"ingraph_{variant}"] = rows
        # the best-achievable same-traffic ceiling at the headline size,
        # under the identical chained adaptive-slope protocol; the
        # fold_vs_ceiling ratio is the ISSUE-1 acceptance metric
        headline = record["lanes"]["ingraph"]
        if headline:
            from common import ceiling_control_slope, fold_vs_ceiling
            top = max(headline, key=lambda r: r["bytes"])
            try:
                cc = ceiling_control_slope(max(1, top["bytes"] // 4),
                                           args.ranks)
                record["ceiling_control"] = cc
                record["fold_vs_ceiling"] = fold_vs_ceiling(
                    top["algbw_gbps"], cc)
                print(f"ceiling[{cc['schedule']}] {cc['bytes']:>11d} B  "
                      f"{cc['algbw_gbps']:>8.3f} GB/s  "
                      f"fold_vs_ceiling={record['fold_vs_ceiling']}",
                      file=sys.stderr)
            except Exception as e:
                print(f"ceiling control skipped: {type(e).__name__}: {e}",
                      file=sys.stderr)
    if "psum" in lanes and multi:
        record["lanes"]["psum"] = bench_psum(sizes)
    if "pallas" in lanes and multi:
        # the interpret machine (CPU-sim) executes the kernel step-by-step in
        # Python (~1 s/call + minutes-long "compiles") — there it is a
        # liveness check on two sizes, not a measurement; Mosaic-on-TPU runs
        # the sampled sweep for real
        interp = plat["platform"] != "tpu"
        sub = sizes[:2] if interp else (
            sizes[::4] + ([sizes[-1]] if (len(sizes) - 1) % 4 else []))
        record["lanes"]["pallas"] = bench_pallas(sub)
    if "procs" in lanes:
        record["lanes"]["procs"] = bench_procs(
            args.ranks, args.max_bytes, min_bytes=args.min_bytes)
    if "procs_algos" in lanes or args.algos:
        record["lanes"].update(
            bench_procs(args.ranks, args.max_bytes, algos=True,
                        min_bytes=args.min_bytes))
    from common import assert_artifact_schema
    assert_artifact_schema(record)        # artifact hygiene: fail, not emit
    emit(args.out, record)


if __name__ == "__main__":
    main()
