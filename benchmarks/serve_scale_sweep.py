"""Serve-tier scale sweep: 1k concurrent tenants, 1 vs 2 brokers, zero-copy.

The production-scale story (docs/serving.md "Scale-out") is quantified on
three axes:

- **fleet throughput** — thousands of tenants hold live leases
  CONCURRENTLY while a fixed driver pool interleaves small Allreduces
  across all of them; ops/s and attach/s are measured on a single broker
  and on a 2-broker fleet behind the session router in REDIRECT mode (HRW
  assignment at attach, data path direct to the home broker — disjoint cid
  shards). The scale-out mechanism is honest even on one core: per-op
  broker cost grows with live tenants (scheduler ring, per-tenant maps and
  reader threads, working-set cache pressure), so halving the tenants per
  broker cuts per-op cost — the committed gate is 2-broker >= 1.5x
  single-broker ops/s with the full herd attached.
- **DRR fairness** — a contention window with per-tenant driver threads
  hammering one broker; Jain's index over per-tenant completed ops.
- **zero-copy frame path** — the same workload on the sendmsg
  scatter-gather lane vs the legacy marshal lane
  (``TPU_MPI_SERVE_ZEROCOPY=0``); the gate is copies/op <= 1 on the
  zero-copy lane, with the legacy before-number committed alongside.
- **C10k front door** — the event-transport broker is stormed with
  pipelined attaches (``serve.attach_many``) over a sessions x window
  grid; ``front_door.open_sockets`` is read mid-hold to prove the herd
  is truly concurrent, a sampled op burst and the DRR fairness window
  run with the full herd attached, and teardown is a mass raw-close
  (10k simultaneous hangups drained by the poll loop). Gates: >= 10k
  concurrent sockets on one broker, pipelined attach above the old
  ~900/s serial baseline, Jain >= 0.99 at scale.

Run:
    python benchmarks/serve_scale_sweep.py [--tenants 8000] [--ops 2]
        [--drivers 32] [--fd-sessions 10000] [--quick]
        [--json benchmarks/results/serve-scale-cpusim.json]

``--quick`` (the CI smoke) shrinks the tenant count and skips the
speedup gate (a loaded CI box makes relative throughput noisy); the
schema and the copies/op gate still apply.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import re
import subprocess
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


def percentiles(samples_s: list) -> dict:
    xs = sorted(samples_s)
    at = lambda q: xs[min(len(xs) - 1, int(q * len(xs)))]
    return {"n": len(xs), "p50_ms": at(0.50) * 1e3, "p90_ms": at(0.90) * 1e3,
            "p99_ms": at(0.99) * 1e3, "min_ms": xs[0] * 1e3,
            "max_ms": xs[-1] * 1e3}


def jain(xs: list) -> float:
    if not xs:
        return 0.0
    return (sum(xs) ** 2) / (len(xs) * sum(x * x for x in xs)) \
        if any(xs) else 0.0


def _drive(sessions, ops_per_tenant: int, drivers: int, x):
    """Interleave ``ops_per_tenant`` Allreduces over every live session
    from a fixed driver pool (the 1k-tenant concurrency model: all leases
    live at once, bounded op parallelism). Returns (latencies_s, errors)."""
    work: "queue.Queue" = queue.Queue()
    for _ in range(ops_per_tenant):
        for s in sessions:
            work.put(s)
    lat, errors = [], []
    lock = threading.Lock()

    def worker():
        while True:
            try:
                s = work.get_nowait()
            except queue.Empty:
                return
            t0 = time.perf_counter()
            try:
                s.allreduce(x)
            except BaseException as e:          # noqa: BLE001
                with lock:
                    errors.append(repr(e))
                return
            dt = time.perf_counter() - t0
            with lock:
                lat.append(dt)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(drivers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return lat, errors


def spawn_broker(nranks: int, token: str, max_tenants: int,
                 shard=None) -> tuple:
    """Run a broker as its OWN OS process (production shape: separate heap,
    separate GIL, client and broker never time-share an interpreter) and
    return ``(proc, address)`` once it prints its socket. Spawned via
    ``-c`` rather than ``-m``: runpy would execute broker.py a second time
    over the copy ``tpu_mpi.serve`` already imported."""
    cmd = [sys.executable, "-c",
           "import sys; sys.argv = ['broker'] + sys.argv[1:]; "
           "import tpu_mpi.serve.broker as b; raise SystemExit(b.main())",
           "--nranks", str(nranks), "--token", token,
           "--max-tenants", str(max_tenants)]
    if shard:
        cmd += ["--shard", shard]
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, env=env)
    line = p.stdout.readline()
    m = re.search(r"socket=([^\s,]+)", line)
    if not m:
        p.kill()
        raise RuntimeError(f"broker never came up: {line!r}")
    return p, m.group(1)


def stop_brokers(procs) -> None:
    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=20)
        except subprocess.TimeoutExpired:
            p.kill()


def bench_fleet(target, tenants: int, ops: int, drivers: int,
                rounds: int, token: str) -> dict:
    """Attach ``tenants`` concurrent leases (through the router when the
    target is one, else straight at the single broker), drive the op phase
    ``rounds`` times (best rate kept — a 1-core box is noisy), detach.
    """
    from tpu_mpi import serve
    x = np.ones(8, np.float32)
    sessions = []
    t0 = time.perf_counter()
    for i in range(tenants):
        sessions.append(serve.attach(target, tenant=f"t{i}", token=token))
    attach_wall = time.perf_counter() - t0
    try:
        rates, lat = [], []
        for _ in range(rounds):
            t1 = time.perf_counter()
            rlat, errors = _drive(sessions, ops, drivers, x)
            op_wall = time.perf_counter() - t1
            assert not errors, errors[:3]
            assert len(rlat) == tenants * ops
            rates.append(len(rlat) / op_wall)
            lat.extend(rlat)
        return {"tenants": tenants, "ops_per_tenant": ops,
                "drivers": drivers, "rounds": rounds,
                "attach_per_s": tenants / attach_wall,
                "ops_per_s": max(rates), "ops_per_s_rounds": rates,
                "op_latency": percentiles(lat)}
    finally:
        for s in sessions:
            try:
                s.detach()
            except BaseException:               # noqa: BLE001
                pass


def bench_fairness(address, tenants: int, window_s: float,
                   token: str) -> dict:
    """Per-tenant driver threads hammer one broker back-to-back for a
    fixed window; DRR should hand out near-equal op counts (Jain ~1)."""
    from tpu_mpi import serve
    x = np.ones(64, np.float32)
    counts = [0] * tenants
    stop = time.perf_counter() + window_s

    def body(i):
        s = serve.attach(address, tenant=f"fair{i}", token=token)
        try:
            while time.perf_counter() < stop:
                s.allreduce(x)
                counts[i] += 1
        finally:
            s.detach()

    threads = [threading.Thread(target=body, args=(i,), daemon=True)
               for i in range(tenants)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"tenants": tenants, "window_s": window_s,
            "ops_per_tenant": counts, "jain_index": jain(counts),
            "total_ops": sum(counts)}


def _broker_stats(address: str, token: str) -> dict:
    """Lease-less STATS probe (same frame `tpurun --serve --stats` sends):
    one connect, one STATS, one reply — the broker closes the socket, so
    this never perturbs the attached herd it is measuring."""
    from tpu_mpi.serve import protocol
    sock = protocol.connect(address, timeout=30.0)
    try:
        protocol.send_frame(sock, protocol.STATS, {"token": token})
        kind, meta, _ = protocol.recv_frame(sock)
        if kind != protocol.STATS:
            raise RuntimeError(f"stats probe got kind {kind}: {meta}")
        return meta
    finally:
        sock.close()


def bench_frontdoor(address: str, grid: list, token: str,
                    fair_tenants: int, fair_window: float,
                    sample_ops: int = 256) -> dict:
    """The C10k lane: storm one event-transport broker with pipelined
    attaches (serve.attach_many) at each (sessions, window) grid point,
    read ``front_door.open_sockets`` MID-HOLD to prove the herd is truly
    concurrent, and — with the largest herd still attached — drive a
    sampled op burst plus the DRR fairness window. Teardown is raw socket
    close (10k serial DETACH round trips would dominate the lane), which
    doubles as a mass-hangup drain test on the event loop."""
    from tpu_mpi import serve
    x = np.ones(8, np.float32)

    # serial-attach baseline: what the thread-per-connection front door
    # gave us (one HELLO/LEASE round trip at a time)
    n_base = 100
    t0 = time.perf_counter()
    for i in range(n_base):
        serve.attach(address, tenant=f"base{i}", token=token).detach()
    serial_attach_per_s = n_base / (time.perf_counter() - t0)

    rows = []
    last = len(grid) - 1
    held = {}
    for gi, (sessions, window) in enumerate(grid):
        t0 = time.perf_counter()
        herd = serve.attach_many(address, sessions, token=token,
                                 window=window)
        attach_wall = time.perf_counter() - t0
        fd = _broker_stats(address, token).get("front_door") or {}
        row = {"sessions": sessions, "window": window,
               "attach_wall_s": attach_wall,
               "attach_per_s": sessions / attach_wall,
               "open_sockets": fd.get("open_sockets", 0),
               "engine": fd.get("engine"),
               "recv_lease_hit_rate": (fd.get("recv_lease") or {})
               .get("hit_rate")}
        if gi == last:
            # ops still flow with the full herd attached: one op across a
            # sample of the herd, driven by a small thread pool
            sample = herd[:min(sample_ops, len(herd))]
            t1 = time.perf_counter()
            lat, errors = _drive(sample, 1, min(32, len(sample)), x)
            assert not errors, errors[:3]
            row["held_ops_per_s"] = len(lat) / (time.perf_counter() - t1)
            row["held_op_latency"] = percentiles(lat)
            held["fairness"] = bench_fairness(address, fair_tenants,
                                              fair_window, token)
        rows.append(row)
        # raw-close teardown: mass EPOLLHUP, broker revokes every lease
        t2 = time.perf_counter()
        for s in herd:
            try:
                s._sock.close()
            except OSError:
                pass
        deadline = time.perf_counter() + 120.0
        open_after = None
        while time.perf_counter() < deadline:
            open_after = (_broker_stats(address, token)
                          .get("front_door") or {}).get("open_sockets")
            if not open_after or open_after <= 1:   # <= 1: the probe's own
                break                               # socket counts itself
            time.sleep(0.25)
        row["drain_s"] = time.perf_counter() - t2
        row["open_sockets_after_drain"] = open_after

    return {"serial_attach_per_s": serial_attach_per_s,
            "grid": rows,
            "max_concurrent_sockets": max(r["open_sockets"] for r in rows),
            "best_attach_per_s": max(r["attach_per_s"] for r in rows),
            "jain_index": held["fairness"]["jain_index"],
            "fairness_at_scale": held["fairness"]}


def bench_copies(nranks: int, reps: int, token: str) -> dict:
    """The before/after for the zero-copy frame path: the same workload on
    the legacy marshal lane vs the sendmsg scatter-gather lane, copies/op
    read from the broker's serve_frame pvar block."""
    from tpu_mpi import config, serve

    def one_lane(zerocopy: bool) -> dict:
        os.environ["TPU_MPI_SERVE_ZEROCOPY"] = "1" if zerocopy else "0"
        config.load(refresh=True)
        try:
            b = serve.Broker(nranks=nranks, token=token)
            b.run_in_thread()
            try:
                before = b.stats()["serve_frame"]
                s = serve.attach(b.address, tenant="lane", token=token)
                x = np.ones(4096, np.float32)
                t0 = time.perf_counter()
                for _ in range(reps):
                    s.allreduce(x)
                wall = time.perf_counter() - t0
                s.detach()
                after = b.stats()["serve_frame"]
            finally:
                b.close()
            ops = after.get("ops", 0) - before.get("ops", 0)
            copies = after.get("copies", 0) - before.get("copies", 0)
            return {"ops": ops, "copies": copies,
                    "copies_per_op": copies / ops if ops else 0.0,
                    "zc_bytes": after.get("zc_bytes", 0)
                    - before.get("zc_bytes", 0),
                    "ops_per_s": reps / wall}
        finally:
            os.environ.pop("TPU_MPI_SERVE_ZEROCOPY", None)
            config.load(refresh=True)

    legacy = one_lane(False)
    zerocopy = one_lane(True)
    return {"reps": reps, "payload_bytes": 4096 * 4,
            "legacy": legacy, "zerocopy": zerocopy}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tenants", type=int, default=8000)
    ap.add_argument("--ops", type=int, default=2)
    ap.add_argument("--drivers", type=int, default=32)
    ap.add_argument("--nranks", type=int, default=2)
    ap.add_argument("--fair-tenants", type=int, default=16)
    ap.add_argument("--fair-window", type=float, default=5.0)
    ap.add_argument("--copy-reps", type=int, default=200)
    ap.add_argument("--rounds", type=int, default=2,
                    help="op-phase repeats per lane (best rate kept)")
    ap.add_argument("--fd-sessions", type=int, default=10000,
                    help="largest herd in the front-door C10k lane")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: shrink the sweep, skip the speedup gate")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    if args.quick:
        args.tenants = min(args.tenants, 64)
        args.ops = min(args.ops, 2)
        args.rounds = 1
        args.fair_window = min(args.fair_window, 1.0)
        args.copy_reps = min(args.copy_reps, 40)
        args.fd_sessions = min(args.fd_sessions, 128)

    # 10k concurrent client sockets need headroom over the usual 1024 soft
    # cap; brokers are subprocesses and inherit the raised limit
    try:
        import resource
        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        if soft < hard:
            resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    except (ImportError, ValueError, OSError):
        pass

    from tpu_mpi import serve
    from tpu_mpi.serve.router import Router
    token = "bench"
    cap = max(2048, args.tenants + 64)

    # -- lane A: one broker process, the whole tenant herd -------------------
    p, addr = spawn_broker(args.nranks, token, cap)
    serve.attach(addr, tenant="warmup", token=token).detach()
    single = bench_fleet(addr, args.tenants, args.ops, args.drivers,
                         args.rounds, token)
    fairness = bench_fairness(addr, args.fair_tenants,
                              args.fair_window, token)
    stop_brokers([p])

    # -- lane B: 2 broker processes behind the router, sharded by HRW --------
    p0, a0 = spawn_broker(args.nranks, token, cap, shard="0/2")
    p1, a1 = spawn_broker(args.nranks, token, cap, shard="1/2")
    router = Router([a0, a1], token=token, mode="redirect")
    router.run_in_thread()
    serve.attach(router.address, tenant="warmup", token=token).detach()
    fleet = bench_fleet(router.address, args.tenants, args.ops,
                        args.drivers, args.rounds, token)
    fleet["router_mode"] = router.mode
    router.close()
    stop_brokers([p0, p1])

    # -- lane C: C10k front door — pipelined attach storms, one broker -------
    if args.quick:
        fd_grid = [(args.fd_sessions, 64)]
    else:
        fd_grid = [(args.fd_sessions // 4, 256),
                   (args.fd_sessions // 2, 512),
                   (args.fd_sessions, 512)]
    pf, af = spawn_broker(args.nranks, token,
                          max(2048, args.fd_sessions + 256))
    serve.attach(af, tenant="warmup", token=token).detach()
    front_door = bench_frontdoor(af, fd_grid, token, args.fair_tenants,
                                 args.fair_window)
    stop_brokers([pf])

    copies = bench_copies(args.nranks, args.copy_reps, token)
    speedup = fleet["ops_per_s"] / single["ops_per_s"]

    gate = {
        "two_broker_speedup_min": 1.5,
        "two_broker_speedup": speedup,
        "zerocopy_copies_per_op_max": 1.0,
        "zerocopy_copies_per_op": copies["zerocopy"]["copies_per_op"],
        "front_door_sockets_min": 10000,
        "front_door_sockets": front_door["max_concurrent_sockets"],
        "front_door_attach_per_s_min": 900.0,
        "front_door_attach_per_s": front_door["best_attach_per_s"],
        "front_door_jain_min": 0.99,
        "front_door_jain": front_door["jain_index"],
        "passed": (copies["zerocopy"]["copies_per_op"] <= 1.0
                   and (args.quick or speedup >= 1.5)
                   and (args.quick
                        or (front_door["max_concurrent_sockets"] >= 10000
                            and front_door["best_attach_per_s"] > 900.0
                            and front_door["jain_index"] >= 0.99))),
    }
    result = {
        "benchmark": "serve-scale",
        "substrate": "cpu-sim",
        "nranks_per_broker": args.nranks,
        "broker_isolation": "process",
        "transport": "loopback-tcp",
        "quick": bool(args.quick),
        "single_broker": single,
        "two_broker_router": fleet,
        "two_broker_speedup": speedup,
        "fairness": fairness,
        "front_door": front_door,
        "copies": copies,
        "gate": gate,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    print(f"single broker     {single['ops_per_s']:10.1f} ops/s   "
          f"attach {single['attach_per_s']:8.1f}/s   "
          f"p99 {single['op_latency']['p99_ms']:.3f} ms")
    print(f"2-broker router   {fleet['ops_per_s']:10.1f} ops/s   "
          f"attach {fleet['attach_per_s']:8.1f}/s   "
          f"p99 {fleet['op_latency']['p99_ms']:.3f} ms   "
          f"({speedup:.2f}x)")
    print(f"DRR fairness      jain {fairness['jain_index']:.4f} over "
          f"{fairness['tenants']} tenants, {fairness['total_ops']} ops")
    for r in front_door["grid"]:
        print(f"front door        {r['sessions']:6d} sockets "
              f"(held {r['open_sockets']:6d})   attach "
              f"{r['attach_per_s']:8.1f}/s (window {r['window']})   "
              f"drain {r['drain_s']:.1f}s")
    print(f"front door        serial-attach baseline "
          f"{front_door['serial_attach_per_s']:.1f}/s   jain@scale "
          f"{front_door['jain_index']:.4f}")
    print(f"copies/op         legacy {copies['legacy']['copies_per_op']:.2f}"
          f" -> zerocopy {copies['zerocopy']['copies_per_op']:.2f}   "
          f"(zc {copies['zerocopy']['ops_per_s']:.0f} ops/s vs legacy "
          f"{copies['legacy']['ops_per_s']:.0f})")
    print(f"gate: {'PASS' if gate['passed'] else 'FAIL'}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=2)
        print(f"wrote {args.json}")
    return 0 if gate["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
