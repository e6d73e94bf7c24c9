"""General generator of host-path MPI collective traffic, OSU style: N rank
threads (`tpu_mpi.spmd_run`), `DeviceBuffer` operands on `comm.device`, one
collective called in a closed loop for the window. What is called, on what
and how it is closed comes from the traffic file:

  op        allreduce | allgather | alltoall | bcast | reduce_scatter
  reduce    sum | max | min               (allreduce, reduce_scatter)
  dtype     float32 | bfloat16 | int32
  counts    elements of one rank's operand, one entry per rung. More than
            one is a ladder or an application's iteration: the window
            cycles through the rungs, one block of each in turn, and a
            metric is the geometric mean of the medians of the rungs that
            yield it. `sync`, `chain` and `block_ops` may each be a list
            parallel to `counts` (a scalar holds for every rung). With more
            than one rung every rung calls on a communicator of its own
            (`Comm_dup`), as a library layered on MPI does: the program
            keeps one armed signature per communicator and rank, so rungs
            that shared one would demote each other and register (compile)
            again at every turn
  buffers   reuse: the same send/recv DeviceBuffers every call (a streak
            the program can arm on) | fresh: a rotating pool of `pool`
            pairs, so buffer identity churns and nothing ever arms
  operands  device                       (host operands are not measured)
  sync      per-block: `block_ops` calls, then the block is closed once |
            per-op: every rank waits for its own result after every call
  chain     true (allreduce/sum only): rank 0 feeds each result back as its
            next operand, so op k+1 cannot start before op k has finished,
            and a per-block close is rank 0's one-element readback, held to
            the closed form first + k * (sum of the others)
  block_ops calls between two barriers

Samples. per-block: one per block, (barrier exit -> close) / block_ops,
the slowest closing rank's; `coll_algbw` comes from these rungs. per-op:
one per op index, the slowest rank's (call entered -> its result ready);
`coll_latency_p50` and `coll_latency_p99` come from these rungs. A traffic
without a rung of a kind yields no metric of that kind. Every block is
checked: a chained one by the closed form at element 0, any other against
the plain reference on the device, outside the timed samples. Before the
window the first result and after it the last are compared whole. Operands
are 0/1 integers from --seed, so every sum is exact in any order."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import stats
from yardstick.harness import annotate

OPS = ("allreduce", "allgather", "alltoall", "bcast", "reduce_scatter")
TILE = 1 << 20      # operands repeat a seeded 0/1 block of this many elements
WARM_PER_OP = 64    # ops of the warm-up block where every op is synced


def rung(tr: dict, key: str, i: int):
    """Rung i's value of a parameter that may be one value for every rung
    or a list parallel to `counts`."""
    v = tr.get(key)
    return v[i] if isinstance(v, list) else v


def validate(tr: dict) -> None:
    def need(key, allowed, i=0):
        if rung(tr, key, i) not in allowed:
            raise ValueError(f"traffic {key}={tr.get(key)!r}, allowed: "
                             f"{sorted(map(str, allowed))}")
    need("op", OPS)
    need("reduce", ("sum", "max", "min"))
    need("dtype", ("float32", "bfloat16", "int32"))
    need("buffers", ("reuse", "fresh"))
    need("operands", ("device",))
    if not tr.get("counts"):
        raise ValueError("counts must name at least one size")
    for key in ("sync", "chain", "block_ops"):
        if isinstance(tr.get(key), list) and \
                len(tr[key]) != len(tr["counts"]):
            raise ValueError(f"traffic {key} is a list of {len(tr[key])}, "
                             f"counts has {len(tr['counts'])} rungs")
    for i in range(len(tr["counts"])):
        need("sync", ("per-block", "per-op"), i)
        need("chain", (True, False), i)
        if rung(tr, "chain", i) and \
                (tr["op"], tr["reduce"]) != ("allreduce", "sum"):
            raise ValueError("chain is defined for allreduce/sum only")
        if int(rung(tr, "block_ops", i) or 0) < 1:
            raise ValueError("block_ops must be positive")


def seeded_operand(seed: int, rank: int, count: int, dtype, device):
    """0/1 integers drawn from the seed, on `device`, in one jitted call: a
    random block of at most TILE elements, repeated to `count`."""
    block = min(count, TILE)
    reps = -(-count // block)

    def make(key):
        bits = jax.random.bernoulli(key, 0.5, (block,)).astype(dtype)
        return jnp.tile(bits, reps)[:count]
    key = jax.random.fold_in(jax.random.key(seed), rank)
    return jax.jit(make, out_shardings=jax.sharding.SingleDeviceSharding(
        device))(key)


class Size:
    """One rung of the ladder: operands, expectations and samples."""

    def __init__(self, run, ref, i: int, devs: list):
        tr, n = run.traffic, len(devs)
        self.rung = i
        self.count = count = int(tr["counts"][i])
        self.per_op = rung(tr, "sync", i) == "per-op"
        self.block_ops = int(rung(tr, "block_ops", i))
        self.dtype = jnp.dtype(tr["dtype"])
        self.payload_bytes = count * self.dtype.itemsize
        self.operands = [seeded_operand(run.seed, r, count, self.dtype, devs[r])
                         for r in range(n)]
        home = [jax.device_put(x, devs[0]) for x in self.operands]
        want = ref.expected(tr["op"], tr["reduce"], home)
        self.first = [jax.device_put(w, devs[r]) for r, w in enumerate(want)]
        self.out_count = int(want[0].shape[0])
        self.chain = bool(rung(tr, "chain", i))
        if self.chain:      # the closed form's two terms, kept on rank 0's chip
            self.x0 = home[0]
            self.others = ref.fold("sum", home[1:])
            self.v0, self.s0 = float(self.x0[0]), float(self.others[0])
            self.bound = stats.chain_ops_bound(tr["dtype"], n)
        self.k = 0                          # chained ops so far (rank 0)
        self.i = [0] * n                    # calls so far, by rank
        self.block_s = [[] for _ in range(n)]   # per-block mode
        self.enter = [[] for _ in range(n)]     # per-op mode
        self.done = [[] for _ in range(n)]
        self.last = [None] * n              # every rank's last result
        self.ops = 0                        # in the window


def run(run) -> None:
    import tpu_mpi as MPI
    from tpu_mpi import config as program_config

    cfg, tr = run.config, run.traffic
    validate(tr)
    ref = run.cell.reference()
    n = int(cfg["ranks"])
    devs = [run.devices[r % len(run.devices)] for r in range(n)]
    if len(set(devs)) != int(cfg["distinct_devices"]):
        raise RuntimeError(f"{run.cell.name}: ranks sit on {len(set(devs))} "
                           f"devices, the configuration says "
                           f"{cfg['distinct_devices']}")
    mpi_op = {"sum": MPI.SUM, "max": MPI.MAX, "min": MPI.MIN}[tr["reduce"]]
    pool = int(tr.get("pool", 3)) if tr["buffers"] == "fresh" else 1
    arm_after = int(program_config.load().auto_arm_threshold)
    sizes = [Size(run, ref, i, devs) for i in range(len(tr["counts"]))]
    run.phase("operands and reference")
    ctl: dict = {}                  # rank 0's stop decisions, by block
    bad = {"blocks": {}, "why": []}     # failed blocks -> their ops, any rank
    equal = jax.jit(lambda a, b: jnp.array_equal(a, b))

    def body():
        MPI.Init()
        comm = MPI.COMM_WORLD
        rank = comm.rank()
        dev = comm.device
        if dev != devs[rank]:
            raise RuntimeError(f"rank {rank} owns {dev}, expected {devs[rank]}")
        # a communicator per rung where there are several (see `counts`)
        comms = [comm] if len(sizes) == 1 else \
            [MPI.Comm_dup(comm) for _ in sizes]
        ops_done = 0

        def pairs_of(sz):
            out = []
            for _ in range(pool):
                buf = MPI.DeviceBuffer(sz.operands[rank], device=dev)
                recv = None if tr["op"] == "bcast" else MPI.DeviceBuffer(
                    jnp.zeros(sz.out_count, sz.dtype, device=dev), device=dev)
                out.append((buf, recv))
            return out

        def call(sz, buf, recv):
            c, on = sz.count, comms[sz.rung]
            if tr["op"] == "allreduce":
                MPI.Allreduce(buf, recv, mpi_op, on)
            elif tr["op"] == "allgather":
                MPI.Allgather(buf, recv, c, on)
            elif tr["op"] == "alltoall":
                MPI.Alltoall(buf, recv, c // n, on)
            elif tr["op"] == "bcast":
                MPI.Bcast(buf, 0, on)
                return buf
            else:
                MPI.Reduce_scatter(buf, recv, [c // n] * n, mpi_op, on)
            return recv

        def fail(sz, ops: int, why: str) -> None:
            # keyed by the block's last call, so four ranks count it once
            bad["blocks"][sz.rung, sz.i[rank]] = ops
            bad["why"].append(f"rank {rank}: {why}")

        def check_on_device(sz, res, ops: int) -> None:
            """Outside the timed samples: this rank's result against the
            plain reference, whole, on this rank's chip."""
            v = res.value
            if v.devices() != {dev}:
                raise RuntimeError(f"rank {rank}: result on {v.devices()}, "
                                   f"not on {dev}")
            if not bool(equal(v, sz.first[rank])):
                fail(sz, ops, f"{tr['op']}[{sz.count}] differs from the "
                              f"plain reference")

        def block(sz, pairs, nops: int, timed: bool) -> None:
            """`nops` calls and their close; one sample (per-block) or
            `nops` samples (per-op) when `timed`."""
            nonlocal ops_done
            per_op = sz.per_op
            if sz.chain and rank == 0 and sz.k + nops > sz.bound:
                pairs[sz.i[rank] % pool][0].value = sz.x0     # restart, exact
                sz.k = 0
            res = None
            t0 = time.perf_counter()
            for _ in range(nops):
                buf, recv = pairs[sz.i[rank] % pool]
                if per_op:
                    t_in = time.perf_counter()
                with annotate("ys:op"):
                    res = call(sz, buf, recv)
                if per_op:
                    with annotate("ys:sync"):
                        jax.block_until_ready(res.value)
                    if timed:
                        sz.enter[rank].append(t_in)
                        sz.done[rank].append(time.perf_counter())
                sz.i[rank] += 1
                if sz.chain and rank == 0:
                    with annotate("ys:rebind"):
                        pairs[sz.i[rank] % pool][0].value = res.value
                    sz.k += 1
            closes = not per_op and (rank == 0 or not sz.chain)
            if sz.chain and rank == 0:
                with annotate("ys:readback"):
                    got = float(res.value[0])
            elif closes:
                with annotate("ys:sync"):
                    jax.block_until_ready(res.value)
            if timed and closes:
                sz.block_s[rank].append(time.perf_counter() - t0)
            if sz.chain:
                if rank == 0 and got != sz.v0 + sz.k * sz.s0:
                    fail(sz, nops, f"chained readback {got} != "
                               f"{sz.v0 + sz.k * sz.s0} after {sz.k} ops")
            else:
                check_on_device(sz, res, nops)
            sz.last[rank] = res
            if rank == 0:
                ops_done += nops
                if timed:
                    sz.ops += nops

        all_pairs = [pairs_of(sz) for sz in sizes]
        MPI.Barrier(comm)
        if rank == 0:
            run.phase("rank threads and buffers")
        for sz, pairs in zip(sizes, all_pairs):     # this cell's shapes only
            # the first result, whole, against the plain reference
            block(sz, pairs, 1, timed=False)
            if rank == 0:
                jax.block_until_ready(sz.last[0].value)
                run.phase("first call")
            if sz.chain:
                check_on_device(sz, sz.last[rank], 1)
                sz.first[rank] = None       # the closed form takes over
            # past the program's arming threshold, then a block as measured
            block(sz, pairs, arm_after + 2, timed=False)
            if rank == 0:
                jax.block_until_ready(sz.last[0].value)
                run.phase("calls to arm")
            block(sz, pairs, min(sz.block_ops, WARM_PER_OP) if sz.per_op
                  else sz.block_ops, timed=False)
        MPI.Barrier(comm)
        if bad["blocks"]:
            raise RuntimeError(f"wrong before the window: {bad['why'][:3]}")
        if rank == 0:
            run.window_begin()
        MPI.Barrier(comm)
        step = 0                    # one block of each rung in turn
        while True:
            si = step % len(sizes)
            if rank == 0:
                run.trace_tick(ops_done)
                ctl[step] = si == 0 and step > 0 and \
                    run.elapsed() >= run.seconds
            with annotate("ys:barrier"):
                MPI.Barrier(comm)
            if ctl[step]:
                break
            block(sizes[si], all_pairs[si], sizes[si].block_ops, timed=True)
            step += 1
        MPI.Barrier(comm)
        if rank == 0:
            run.window_end(ops_done)
        MPI.Finalize()

    from tpu_mpi import spmd_run
    spmd_run(body, n)

    # -- after the window: the last result of every rank, whole -------------
    failed = sum(bad["blocks"].values())
    correct = not failed
    for sz in sizes:
        for r in range(n):
            got = jax.device_put(sz.last[r].value, devs[0])
            want = ref.chained_allreduce(sz.x0, sz.others, sz.k) \
                if sz.chain else jax.device_put(sz.first[r], devs[0])
            if not bool(equal(got, want)):
                correct = False
                bad["why"].append(f"rank {r}: the last {tr['op']}[{sz.count}] "
                                  f"differs from the plain reference")
    for why in bad["why"][:5]:
        print("FAILED:", why)

    # -- samples -> metrics ---------------------------------------------------
    p50s, p99s, bws = [], [], []
    for sz in sizes:
        if sz.per_op:
            lat = np.max(np.asarray(sz.done) - np.asarray(sz.enter), axis=0)
        else:
            closing = [b for b in sz.block_s if b]
            lat = np.max(np.asarray(closing), axis=0) / sz.block_ops
        q = stats.quartiles(lat)
        row = (f"{tr['op']}[{sz.count} x {tr['dtype']}] n={q['n']} samples  "
               f"per-op q1 {q['q1'] * 1e6:.2f} us  median "
               f"{q['median'] * 1e6:.2f} us  q3 {q['q3'] * 1e6:.2f} us  "
               f"spread {100 * q['spread']:.2f}%")
        if sz.per_op:
            p50s.append(q["median"] * 1e6)
            if len(lat) >= 1000:    # ten samples or more beyond the 99th
                p99s.append(stats.percentile(lat, 99.0) * 1e6)
                row += f"  p99 {p99s[-1]:.2f} us"
        else:
            bws.append(stats.coll_algbw_gbps(sz.payload_bytes, q["median"]))
            row += f"  algbw {bws[-1]:.3f} GB/s"
        run.row(row)
    metrics = {}
    if bws:
        metrics["coll_algbw"] = stats.geomean(bws)
    if p50s:
        metrics["coll_latency_p50"] = stats.geomean(p50s)
    if p50s and len(p99s) == len(p50s):
        metrics["coll_latency_p99"] = stats.geomean(p99s)
    attempted = sum(sz.ops for sz in sizes)
    run.results = {"metrics": metrics, "correct": correct,
                   "attempted": attempted, "failed": min(failed, attempted)}
    # what one fold moves is defined where every op has one size
    run.facts = {"ops": attempted, "ranks": n, "op": tr["op"],
                 "payload_bytes": sizes[0].payload_bytes
                 if len(sizes) == 1 else None}
