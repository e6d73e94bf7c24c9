"""Shared harness for the benchmark sweeps (BASELINE.json `metric` +
`configs[4]`: Allreduce GB/s vs message size, OSU-style P2P latency/BW).

The reference publishes no numbers (SURVEY.md §6) — these sweeps are the
repo's own deliverable. Conventions follow the OSU micro-benchmarks: per
message size, several warmup rounds, then the best of REPEATS timed blocks
(max-across-ranks within a block, min across blocks), bandwidth in GB/s
(1e9 bytes/s).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Callable, Sequence

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def iters_for(nbytes: int) -> tuple[int, int]:
    """(warmup, iters) scaled down for big messages, OSU-style."""
    if nbytes <= 1 << 16:
        return 10, 100
    if nbytes <= 1 << 22:
        return 5, 40
    if nbytes <= 1 << 26:
        return 3, 10
    return 2, 5


def host_allreduce_times(n_elems: int, nranks: int, use_device: bool,
                         warmup: int, iters: int, repeats: int,
                         persistent: bool = False) -> list[list[float]]:
    """Honest-execution host-path Allreduce timing of
    ``allreduce_sweep.py`` (VERDICT r2 weak #1: the round-2 protocol
    measured async dispatch and reported >HBM-peak bandwidth).

    Iterations chain data-dependently — rank 0 feeds the combined result
    back as its next contribution, so op k+1 cannot start before op k's
    output exists — and each timed block ends with a one-element host
    readback on rank 0 (dispatch is asynchronous: only a readback proves
    the chain executed). The readback is ASSERTED against the closed-form
    chain value, so a bench whose work did not actually execute fails
    loudly instead of printing a bandwidth number.

    Chain algebra: rank 0 starts at ones and rebinds to each result; ranks
    1..n-1 contribute ones forever — after k completed ops the result is
    ``1 + k*(nranks-1)`` elementwise (linear growth, no overflow, exact in
    float32 for every op count used here).

    Returns times[rank][repeat]; only rank 0's blocks include the forcing
    readback, so aggregate with :func:`best_block` (max-per-repeat keys on
    rank 0).

    ``persistent=True`` is the registered-buffer lane (ISSUE-6,
    docs/performance.md "Registered buffers"): the plan is created ONCE via
    ``Allreduce_init`` outside the timed loop, and each timed op is one
    Start/Wait round against the plan-pinned buffers — the lane that kills
    the per-call parse/plan/worker dispatch overhead.
    """
    import numpy as np
    import tpu_mpi as MPI
    from tpu_mpi import spmd_run

    def body():
        MPI.Init()
        comm = MPI.COMM_WORLD
        rank = comm.rank()
        ops = 0
        if use_device:
            import jax.numpy as jnp
            from tpu_mpi.buffers import DeviceBuffer
            buf = DeviceBuffer(jnp.ones(n_elems, jnp.float32))
            out = DeviceBuffer(jnp.zeros(n_elems, jnp.float32))

            def rebind():
                buf.value = out.value        # host-side rebind: the chain

            def readback():
                return float(out.value[0])
        else:
            buf = np.ones(n_elems, np.float32)
            out = np.zeros(n_elems, np.float32)

            def rebind():
                np.copyto(buf, out)          # same chain, host arrays

            def readback():
                return float(out[0])

        if persistent:
            req = MPI.Allreduce_init(buf, out, MPI.SUM, comm)

            def coll():
                MPI.Start(req)
                MPI.Wait(req)
        else:
            def coll():
                MPI.Allreduce(buf, out, MPI.SUM, comm)

        def step():
            coll()
            if rank == 0:
                rebind()

        def force():
            got, want = readback(), float(1 + ops * (nranks - 1))
            assert got == want, (
                f"chained Allreduce readback {got} != expected {want} after "
                f"{ops} ops — the timed work did not execute correctly")

        for _ in range(warmup):
            step()
            ops += 1
        reps = []
        for _ in range(repeats):
            MPI.Barrier(comm)
            t0 = time.perf_counter()
            for _ in range(iters):
                step()
                ops += 1
            if rank == 0:
                force()
            reps.append((time.perf_counter() - t0) / iters)
        MPI.Finalize()
        return reps

    return spmd_run(body, nranks)


def time_chain(step, force, warmup: int, iters: int, repeats: int) -> float:
    """Best per-op seconds over ``repeats`` blocks of ``iters`` chained ops;
    each block ends in a forcing readback that ``force(ops)`` must assert
    against the closed-form chain value (unexecuted or wrong work fails the
    bench instead of timing as fast). Used by
    benchmarks/overhead_probe.py."""
    ops = 0
    for _ in range(warmup):
        step()
        ops += 1
    force(ops)                      # also forces warmup completion
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            step()
            ops += 1
        force(ops)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def gen_of(device) -> str:
    """TPU generation key for a jax device (canonical copy —
    mfu_probe.py delegates here so a new generation is added once). A device
    the capability table does not know is an error: a ratio against some
    other chip's peak is not a measurement."""
    from tpu_mpi.implementations import CAPABILITIES
    kind = getattr(device, "device_kind", "").lower().replace(" ", "")
    if "v5lite" in kind:
        return "v5e"
    for key in sorted(CAPABILITIES, key=len, reverse=True):
        if key in kind:
            return key
    raise KeyError(f"device_kind {getattr(device, 'device_kind', None)!r} is "
                   f"not in tpu_mpi.implementations.CAPABILITIES")


def hbm_gbps_of(gen: str) -> float:
    from tpu_mpi.implementations import capabilities
    return float(capabilities(gen)["hbm_gbps"])


def best_of_calls(call: Callable[[int], None], k: int,
                  repeats: int) -> float:
    """One warm call at k, then best-of-``repeats`` timed calls — the shared
    measurement kernel of every adaptive-slope lane (headline + controls
    measure under ONE protocol by construction)."""
    call(k)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        call(k)
        best = min(best, time.perf_counter() - t0)
    return best


def measure_dispatch_floor(repeats: int = 5) -> float:
    """Seconds for one scalar jit op + host readback — the per-call
    dispatch floor, re-measured whenever cited."""
    import jax
    import jax.numpy as jnp
    f0 = jax.jit(lambda v: v + 1.0)
    s = jnp.zeros(())
    for _ in range(3):
        s = f0(s)
    float(s)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        s = f0(s)
        float(s)
        best = min(best, time.perf_counter() - t0)
    return best


def adaptive_slope(time_of: Callable[[int], float], rtt: float,
                   k0: int = 4, k_cap: int = 1 << 20,
                   slope_repeats: int = 3) -> dict:
    """Per-step seconds from (t(2k)-t(k))/k with k grown until the call is
    EXECUTION-dominated. t(call) behaves like max(dispatch_floor, exec) +
    jitter, so a fixed-K slope dissolves into noise whenever exec <
    dispatch_floor. k escalates geometrically until
    ``t(k) >= max(4*rtt, 0.25 s)``, guaranteeing both ends of the slope sit
    on the execution-scaling regime; the final slope is taken
    ``slope_repeats`` times for a run-to-run spread (VERDICT r4 done-bar:
    variance < 10%)."""
    import math
    target = max(4 * rtt, 0.25)
    k = k0
    while True:
        t1 = time_of(k)
        if t1 >= target or k >= k_cap:
            break
        # jump straight toward the execution-dominated regime: per-step
        # exec is at least (t1 - rtt)/k, so k*target/exec_est lands near
        # target; cap the jump so one mis-estimate can't cost minutes
        exec_est = max(t1 - rtt, 1e-9)
        k = min(k_cap, k * min(64, max(2, math.ceil(target / exec_est))))
    slopes = []
    t2 = None
    for _ in range(slope_repeats):
        t1 = time_of(k)
        t2 = time_of(2 * k)
        slopes.append((t2 - t1) / k)
    mid = sorted(slopes)[len(slopes) // 2]
    spread = (max(slopes) - min(slopes)) / mid if mid > 0 else float("inf")
    return {"per_step_s": mid, "k": k, "t_k_ms": round(t1 * 1e3, 2),
            "t_2k_ms": round(t2 * 1e3, 2),
            "slope_spread": round(spread, 4),
            "slopes_us": [round(s * 1e6, 2) for s in slopes]}


# Human-readable HBM traffic model per in-graph variant, stated beside
# hbm_model_binds in every row (ISSUE-1 satellite): what one fold reads and
# writes, hence what "implied HBM" divides by.
_TRAFFIC_MODELS = {
    "allreduce": "(n+1)*bytes: n operand-stream reads + 1 result write",
    "allreduce_donated": "(n+1)*bytes: n operand-stream reads + 1 result "
                         "write aliased into the donated accumulator",
    "reducescatter": "(n+1)/n*bytes: n shard-slice reads + 1 shard write",
    "allgather": "2*shard*n bytes: shard read + full concat write",
    "ceiling_control": "(n+1)*bytes: same streams, best schedule, no MPI "
                       "rank-order semantics",
}


def ingraph_collective_slope(variant: str, n_elems: int, nranks: int,
                             repeats: int = 3, rtt: "float | None" = None,
                             k_cap: int = 1 << 20) -> dict:
    """The in-graph lane: K data-dependently chained collective folds inside
    ONE jit on the device, per-fold seconds from the adaptive slope
    (t(2K)-t(K))/K — the per-call dispatch floor cancels. This measures
    where a TPU framework's collectives actually live: compiled XLA code.

    ``variant``:

    - ``allreduce``       — the same rank-ordered left fold the host path's
      ``collective._jitted_fold`` compiles (nranks operand reads + 1 result
      write of the payload; roofline algbw = HBM/(nranks+1));
    - ``allreduce_donated`` — the registered host lane's fold compilation
      (ISSUE-6): ONE AOT executable with ``donate_argnums`` on the
      accumulator, called K times from the host with each result chained
      back in as the next donated acc — the in-graph twin of the
      ``PlanRegistration`` per-round fold. Donation lets XLA alias the
      result into the consumed acc buffer (honored on TPU; the CPU backend
      treats donation as advisory). Unlike the fori_loop variants, per-fold
      executable dispatch is PART of this measurement — that is the cost
      the registered lane actually pays per persistent round;
    - ``reducescatter``   — this chip computes rank 0's shard: nranks
      shard-slice reads + one shard write ((nranks+1)/nranks * payload);
    - ``allgather``       — shard in, full concat out (~2x payload).

    Honesty guards: contributions are runtime jit arguments (never
    constant-foldable); every fold adds a loop-index-derived term
    (``j mod 2`` — loop-invariant code motion cannot hoist the combine, and
    the chain value stays inside float32's exact-integer range at any K);
    the fold count is a DYNAMIC argument of one compiled while-loop program
    (no cross-fold fusion, no per-K recompiles); every call ends in a host
    readback asserted against the closed-form chain value (the K folds
    chain data-dependently INSIDE the jit; calls are separated by the
    blocking readback, so each starts from a fresh operand)."""
    import jax
    import jax.numpy as jnp
    import tpu_mpi as MPI

    opfn = MPI.SUM.fn
    shard = max(1, n_elems // nranks)
    nbytes = n_elems * 4
    if variant in ("allreduce", "allreduce_donated"):
        peer_elems, acc_elems = n_elems, n_elems
        traffic = (nranks + 1) * nbytes

        def one_fold(acc, peers, jf):
            a = acc
            for o in peers:
                a = opfn(a, o + jf)       # +j%2: iteration-dep., no LICM
            return a

        def expect_of(k):                 # closed-form value after k folds
            return float(1 + (nranks - 1) * (k + k // 2))
    elif variant == "reducescatter":
        peer_elems, acc_elems = n_elems, shard
        traffic = (nranks + 1) * shard * 4

        def one_fold(acc, peers, jf):
            a = acc
            for o in peers:
                a = opfn(a, o[:shard] + jf)
            return a

        def expect_of(k):
            return float(1 + (nranks - 1) * (k + k // 2))
    elif variant == "allgather":
        peer_elems, acc_elems = shard, shard
        traffic = 2 * shard * nranks * 4

        def one_fold(acc, peers, jf):
            grown = acc + 1.0            # iteration-dependent via acc itself
            full = jnp.concatenate([grown] + list(peers))
            # the barrier keeps the concat's full write live (no
            # slice-through-DCE); next fold consumes only the first shard
            return jax.lax.optimization_barrier(full)[:shard]

        def expect_of(k):
            return float(1 + k)
    else:
        raise ValueError(f"unknown variant {variant!r}")

    peers = tuple(jnp.ones(peer_elems, jnp.float32)
                  for _ in range(nranks - 1))

    @jax.jit
    def f(x, k, *ps):
        def body(j, acc):
            return one_fold(acc, ps, jnp.asarray(j % 2, jnp.float32))
        return jax.lax.fori_loop(0, k, body, x)

    x0 = jnp.ones(acc_elems, jnp.float32)

    if variant == "allreduce_donated":
        # One AOT executable per fold, accumulator donated — the exact
        # compilation collective._registered_device_fold runs per
        # persistent round. The k folds chain through the donated buffer
        # at the Python level; per-call(k) constants (operand alloc,
        # readback) still cancel in the slope, per-FOLD dispatch does not
        # — by design, it is the registered lane's real per-round cost.
        import warnings

        def dfold(acc, jf, *ps):
            a = acc
            for o in ps:
                a = opfn(a, o + jf)
            return a

        jfs = (jnp.asarray(0.0, jnp.float32), jnp.asarray(1.0, jnp.float32))
        with warnings.catch_warnings():
            # CPU backend: "some donated buffers were not usable" — there
            # donation is advisory and the row measures dispatch alone
            warnings.simplefilter("ignore")
            fc = (jax.jit(dfold, donate_argnums=(0,))
                  .lower(x0, jfs[0], *peers).compile())

        def call(k):
            acc = jnp.ones(acc_elems, jnp.float32)   # donated away per fold
            for j in range(k):
                acc = fc(acc, jfs[j % 2], *peers)
            got, want = float(acc[0]), expect_of(k)
            assert got == want, (
                f"in-graph {variant} chain readback {got} != {want} "
                f"— the timed folds did not execute correctly")
    else:
        def call(k):
            y = f(x0, k, *peers)
            got = float(y[0])             # the readback forces completion
            want = expect_of(k)
            assert got == want, (
                f"in-graph {variant} chain readback {got} != {want} "
                f"— the timed folds did not execute correctly")

    def time_of(k):
        return best_of_calls(call, k, repeats)

    call(1)                               # compile (dynamic k: one program)
    if rtt is None:
        rtt = measure_dispatch_floor()
    # keep the closed-form chain value float32-EXACT at the largest k the
    # slope can evaluate (2*k_cap): 1 + (nranks-1)*(2k + k) must stay under
    # 2^24, or the readback assert fires spuriously at high rank counts
    if variant in ("allreduce", "allreduce_donated", "reducescatter"):
        k_cap = min(k_cap, ((1 << 24) - 2) // (3 * max(1, nranks - 1)))
    sl = adaptive_slope(time_of, rtt, k_cap=k_cap)
    per_fold = sl["per_step_s"]
    implied = traffic / per_fold / 1e9
    hbm_spec = hbm_gbps_of(gen_of(jax.devices()[0]))
    out = {
        "variant": variant,
        "bytes": nbytes,
        "nranks": nranks,
        "per_fold_s": per_fold,          # unrounded, for derived math
        "k": sl["k"],
        "t_k_ms": sl["t_k_ms"], "t_2k_ms": sl["t_2k_ms"],
        "dispatch_floor_ms": round(rtt * 1e3, 2),
        "slope_spread": sl["slope_spread"],
        "slopes_us": sl["slopes_us"],
        "per_fold_us": round(per_fold * 1e6, 2),
        "traffic_model_bytes": traffic,
        "traffic_model": _TRAFFIC_MODELS[variant],
        "hbm_gbps_implied": round(implied, 1),
        # implied > HBM peak does NOT mean the timing lies — it means the
        # HBM traffic model stops binding at this size (the while-loop's
        # working set stays VMEM-resident / XLA keeps invariant operands
        # on-chip across folds), so the fold legitimately beats the
        # HBM roofline. Flagged so artifacts never imply >peak HBM.
        "hbm_model_binds": bool(implied <= 1.05 * hbm_spec),
        "algbw_gbps": round(nbytes / per_fold / 1e9, 3),
    }
    if variant == "allreduce_donated":
        out["donated"] = True
    return out


def ceiling_control_slope(n_elems: int, nranks: int, repeats: int = 3,
                          rtt: "float | None" = None,
                          k_cap: int = 1 << 20) -> dict:
    """Best-achievable same-traffic ceiling (the ISSUE-1 control): a tuned
    nranks-stream read-reduce-write with NO MPI semantics — the reduction
    need not honor rank order, so any schedule XLA likes is fair — timed
    under the IDENTICAL K-chained adaptive-slope protocol as the headline
    fold. ``fold_vs_ceiling = headline algbw / ceiling algbw`` then says how
    much of what this chip can physically do at this traffic pattern the
    MPI-semantics fold achieves.

    Candidate schedules: the rank-ordered left chain (what the fold itself
    does) and a balanced pairwise tree (shorter dependence chain, same
    traffic). The ceiling is the faster candidate. Honesty guards are the
    headline lane's own: contributions are runtime jit arguments, every fold
    adds the ``j mod 2`` iteration term, the fold count is a dynamic
    argument of one compiled while-loop, and every call ends in a host
    readback asserted against the closed-form chain value — which is
    schedule-independent because the chain stays inside float32's
    exact-integer range."""
    import jax
    import jax.numpy as jnp

    nbytes = n_elems * 4
    traffic = (nranks + 1) * nbytes

    def chain(acc, peers, jf):
        a = acc
        for o in peers:
            a = a + (o + jf)
        return a

    def tree(acc, peers, jf):
        vals = [acc] + [o + jf for o in peers]
        while len(vals) > 1:
            nxt = [vals[i] + vals[i + 1] for i in range(0, len(vals) - 1, 2)]
            if len(vals) % 2:
                nxt.append(vals[-1])
            vals = nxt
        return vals[0]

    peers = tuple(jnp.ones(n_elems, jnp.float32) for _ in range(nranks - 1))
    x0 = jnp.ones(n_elems, jnp.float32)
    if rtt is None:
        rtt = measure_dispatch_floor()
    # same float32-exactness clamp as the headline lane (values identical)
    k_cap = min(k_cap, ((1 << 24) - 2) // (3 * max(1, nranks - 1)))

    candidates = {}
    for name, fold in (("chain", chain), ("tree", tree)):
        @jax.jit
        def f(x, k, *ps, _fold=fold):
            def body(j, acc):
                return _fold(acc, ps, jnp.asarray(j % 2, jnp.float32))
            return jax.lax.fori_loop(0, k, body, x)

        def call(k, _f=f):
            y = _f(x0, k, *peers)
            got, want = float(y[0]), float(1 + (nranks - 1) * (k + k // 2))
            assert got == want, (
                f"ceiling {name} chain readback {got} != {want} "
                f"— the timed folds did not execute correctly")

        call(1)
        sl = adaptive_slope(lambda k: best_of_calls(call, k, repeats), rtt,
                            k_cap=k_cap)
        candidates[name] = {
            "per_fold_s": sl["per_step_s"],
            "per_fold_us": round(sl["per_step_s"] * 1e6, 2),
            "k": sl["k"], "slope_spread": sl["slope_spread"],
            "algbw_gbps": round(nbytes / sl["per_step_s"] / 1e9, 3),
        }
    best = min(candidates, key=lambda n: candidates[n]["per_fold_s"])
    win = candidates[best]
    return {
        "variant": "ceiling_control",
        "bytes": nbytes, "nranks": nranks,
        "schedule": best,
        "candidates": candidates,
        "per_fold_s": win["per_fold_s"],
        "per_fold_us": win["per_fold_us"],
        "k": win["k"], "slope_spread": win["slope_spread"],
        "dispatch_floor_ms": round(rtt * 1e3, 2),
        "traffic_model_bytes": traffic,
        "traffic_model": _TRAFFIC_MODELS["ceiling_control"],
        "algbw_gbps": win["algbw_gbps"],
        "readback_asserted": True,
        "protocol": "adaptive_slope_chained",
    }


def fold_vs_ceiling(headline_algbw: float, ceiling: dict) -> float:
    """The acceptance ratio: headline MPI-semantics fold algbw over the
    same-traffic no-semantics ceiling's algbw."""
    return round(headline_algbw / ceiling["algbw_gbps"], 4)


def assert_artifact_schema(record: dict) -> None:
    """Artifact-hygiene gate (CI bench-smoke; every sweep emit): fails
    loudly on the regressions ISSUE-1 flags — duplicate per-size rows
    within a lane, in-graph rows missing their honesty/traffic fields, or a
    missing/incomplete ceiling-control block when the in-graph lane ran."""
    lanes = record.get("lanes")
    assert isinstance(lanes, dict) and lanes, "record has no lanes"
    for name, rows in lanes.items():
        if not isinstance(rows, list):
            continue
        sizes = [r["bytes"] for r in rows]
        dup = sorted({b for b in sizes if sizes.count(b) > 1})
        assert not dup, f"lane {name!r} has duplicate rows for bytes {dup}"
        if name.startswith("ingraph"):
            for r in rows:
                for field in ("slope_spread", "traffic_model",
                              "hbm_gbps_implied", "algbw_gbps"):
                    assert field in r, f"lane {name!r} row missing {field!r}"
    if any(n.startswith("ingraph") for n, r in lanes.items()
           if isinstance(r, list) and r):
        cc = record.get("ceiling_control")
        assert isinstance(cc, dict), "missing ceiling_control block"
        for field in ("schedule", "candidates", "slope_spread",
                      "algbw_gbps", "readback_asserted"):
            assert field in cc, f"ceiling_control missing {field!r}"
        assert cc["readback_asserted"] is True
        assert "fold_vs_ceiling" in record, "missing fold_vs_ceiling ratio"


def control_block(n_elems: int = 1 << 26, gemm_m: int = 4096,
                  repeats: int = 3, rtt: "float | None" = None) -> dict:
    """Same-session calibration stamped into every TPU artifact: the per-call
    dispatch floor, measured HBM GB/s (elementwise adaptive slope), and the
    GEMM slope TFLOP/s — captured back-to-back with whatever measurement
    cites them, so each artifact carries its own conditions. All three use
    the execution-dominated adaptive-slope protocol (see
    :func:`adaptive_slope`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    out: dict = {}
    if rtt is None:
        rtt = measure_dispatch_floor()
    # the SAME floor the caller's adaptive slopes used, so the stamp
    # describes the measurement it accompanies
    out["dispatch_floor_ms"] = round(rtt * 1e3, 3)

    # HBM: elementwise chain (1 read + 1 write per step), dynamic step count;
    # the j%2 term keeps the chain loop-index-dependent AND inside float32's
    # exact-integer range at any k (see ingraph_collective_slope)
    @jax.jit
    def ew(v, k):
        def body(j, acc):
            return acc + (1.0 + jnp.asarray(j % 2, jnp.float32))
        return jax.lax.fori_loop(0, k, body, v)

    x0 = jnp.zeros(n_elems, jnp.float32)

    def ew_call(k):
        y = ew(x0, k)
        got, want = float(y[0]), float(k + k // 2)
        assert got == want, (got, want)

    ew_call(1)
    sl = adaptive_slope(lambda k: best_of_calls(ew_call, k, repeats), rtt)
    out["hbm_per_step_s"] = sl["per_step_s"]   # unrounded, for derived math
    out["hbm_gbps_measured"] = round(2 * n_elems * 4 / sl["per_step_s"] / 1e9, 1)
    out["hbm_slope_spread"] = sl["slope_spread"]

    # GEMM: bf16 matmul chain with cheap renorm (mfu_probe.py body), dynamic k
    m = gemm_m
    b_mat = (jax.random.normal(jax.random.PRNGKey(0), (m, m), jnp.float32)
             / np.sqrt(m)).astype(jnp.bfloat16)

    @jax.jit
    def gemm(a, k, b):
        def body(i, acc):
            nxt = jnp.dot(acc, b, preferred_element_type=jnp.float32)
            sc = jax.lax.rsqrt(jnp.mean(nxt[:256] * nxt[:256]) + 1e-30)
            return (nxt * sc).astype(jnp.bfloat16)
        return jax.lax.fori_loop(0, k, body, a)

    ga = {"a": jax.random.normal(jax.random.PRNGKey(1), (m, m),
                                 jnp.float32).astype(jnp.bfloat16)}

    def g_call(k):
        ga["a"] = gemm(ga["a"], k, b_mat)
        assert np.isfinite(float(jnp.asarray(ga["a"][0, 0], jnp.float32)))

    g_call(1)
    sl = adaptive_slope(lambda k: best_of_calls(g_call, k, repeats), rtt)
    out["gemm_slope_tflops"] = round(2.0 * m ** 3 / sl["per_step_s"] / 1e12, 2)
    out["gemm_slope_spread"] = sl["slope_spread"]
    out["captured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    return out


def best_block(times: Sequence[Sequence[float]]) -> float:
    """times[rank][repeat] → min over repeats of max over ranks."""
    nrep = len(times[0])
    return min(max(t[i] for t in times) for i in range(nrep))


def size_sweep(max_bytes: int, min_bytes: int = 8) -> list[int]:
    """Power-of-two byte sizes, 8 B … max_bytes."""
    out, b = [], min_bytes
    while b <= max_bytes:
        out.append(b)
        b <<= 1
    return out


def force_cpu_sim(n_devices: int) -> None:
    """Pin this process to n fake XLA CPU devices (the tests/conftest.py
    substrate). Call before first jax use."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}").strip()


def detect_platform() -> dict:
    """One-shot platform record for the results file."""
    import jax
    devs = jax.devices()
    return {
        "devices": len(devs),
        "platform": devs[0].platform,
        "device_kind": getattr(devs[0], "device_kind", "?"),
        "python": sys.version.split()[0],
    }


def emit(path: str, record: dict) -> None:
    record = dict(record, timestamp=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    if path == "-":
        print(json.dumps(record, indent=2))
        return
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(f"wrote {path}", file=sys.stderr)
