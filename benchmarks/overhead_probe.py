"""Device-lane overhead breakdown probe.

Decomposes the per-op time of the host-path device lane (MPI.Allreduce of
Float32[2^26] over 4 rank threads on one chip) into dispatch, allocation,
fold execution and MPI machinery:

  A. ``dispatch_floor``    — jitted scalar +1, chained: pure per-call
                             dispatch, operand-size ~zero.
  B. ``elementwise``       — jitted ``x+1`` over Float32[2^26] (2x payload of
                             HBM traffic), chained. The *irreducible per-op
                             floor* of any single-dispatch 256 MiB op.
  C. ``elementwise_donate``— same with ``donate_argnums=0``: eliminates the
                             256 MiB alloc+free churn each chained op causes
                             (diagnostic only — MPI semantics forbid donating
                             user-visible send buffers).
  D. ``fold4``             — the Allreduce combine itself, outside all MPI
                             machinery: one jitted 4-operand left-fold sum
                             (4 reads + 1 write = 5x payload), chained.
  E. ``fused_elementwise`` — in-jit chained ``x+1`` steps, ADAPTIVE slope
                             (common.adaptive_slope via control_block):
                             the chip's actual HBM rate under this harness
                             (2x traffic).
  F. ``fused_fold4``       — in-jit chained 4-operand folds, adaptive slope
                             (common.ingraph_collective_slope — the bench
                             headline lane): the *measured* execution
                             roofline for the Allreduce fold, replacing the
                             spec-sheet 819 GB/s in the breakdown model.
  G. ``mpi_allreduce``     — the full MPI.Allreduce device lane, 4 rank
                             threads (the chained protocol of
                             benchmarks/common.py).

Every chain is data-dependent (op k+1 consumes op k's output) and every timed
block ends with a one-element readback asserted against the closed-form chain
value — unexecuted work fails instead of timing as fast.

Derived breakdown written to the artifact:
  dispatch_floor_ms = B - E_per_step        (per-dispatch overhead at 256 MiB)
  alloc_churn_ms    = B - C                 (part of the floor that is buffer
                                             alloc/free, removable by donation)
  mpi_overhead_ms   = G - D                 (rendezvous + buffer normalization)
  model_ms          = (B - E_per_step) + F_per_step   (floor + measured
                                             execution roofline for the fold)
  mpi_vs_model      = G / model_ms

Run: ``python benchmarks/overhead_probe.py [out.json]`` (default: stdout; a
device number is only ever written by a run on the chip).

A separate pvar-overhead lane (``--pvars [out.json]``, default
``benchmarks/results/overhead-pvars-cpusim.json``) measures the cost of
the always-on performance-variable counters (docs/observability.md):
host-path ping-pong and star Allreduce with collection off vs on. The
off lane must stay within noise of the pre-pvars baseline — its fast
path is one generation-checked tuple compare per op.

An online-autotuner lane (``--online [out.json]``, default
``benchmarks/results/overhead-online-cpusim.json``) runs the same cases
with the bandit's decision point live: exploration off (the deployment
default, compared against the committed pre-bandit pvars-on baseline —
must be neutral) and exploration on at 10% (the exploration tax).
"""

from __future__ import annotations

import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
for p in (_REPO, _HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from common import (best_block, control_block, detect_platform, emit,
                    host_allreduce_times, ingraph_collective_slope,
                    measure_dispatch_floor, time_chain as _time_chain)

N_ELEMS = 1 << 26           # Float32[2^26] = 256 MiB, the headline payload
NBYTES = N_ELEMS * 4
WARMUP, ITERS, REPEATS = 3, 20, 6


def _log(msg: str) -> None:
    print(f"probe: {msg}", file=sys.stderr, flush=True)


def case_dispatch_floor(jax, jnp) -> float:
    f = jax.jit(lambda x: x + 1.0)
    box = [jnp.zeros((), jnp.float32)]

    def step():
        box[0] = f(box[0])

    def force(ops):
        got = float(box[0])
        assert got == float(ops), (got, ops)

    return _time_chain(step, force, 10, 100, 4)


def case_elementwise(jax, jnp, donate: bool, n_elems: int = N_ELEMS,
                     iters: int = ITERS, repeats: int = REPEATS) -> float:
    f = jax.jit(lambda x: x + 1.0,
                donate_argnums=(0,) if donate else ())
    box = [jnp.zeros(n_elems, jnp.float32)]

    def step():
        box[0] = f(box[0])

    def force(ops):
        got = float(box[0][0])
        assert got == float(ops), (got, ops)

    return _time_chain(step, force, WARMUP, iters, repeats)


def case_fold4(jax, jnp) -> float:
    ones = [jnp.ones(N_ELEMS, jnp.float32) for _ in range(3)]

    def fold(x0, x1, x2, x3):
        acc = x0
        for x in (x1, x2, x3):      # same left fold as collective._jitted_fold
            acc = acc + x
        return acc

    f = jax.jit(fold)
    box = [jnp.ones(N_ELEMS, jnp.float32)]

    def step():
        box[0] = f(box[0], *ones)

    def force(ops):
        got = float(box[0][0])
        assert got == float(1 + 3 * ops), (got, ops)

    return _time_chain(step, force, WARMUP, ITERS, REPEATS)


def case_floor_vs_size(jax, jnp) -> list[dict]:
    """Map the per-op floor against operand size."""
    rows = []
    for mib in (1, 4, 8, 32, 64, 128, 256):
        n = (mib << 20) // 4
        t = case_elementwise(jax, jnp, donate=False, n_elems=n,
                             iters=10, repeats=3)
        rows.append({"mib": mib, "lat_ms": round(t * 1e3, 3)})
        _log(f"  floor[{mib} MiB] = {t * 1e3:.2f} ms")
    return rows


def _pvars_case(pvars_on: bool, pp_iters: int = 2000,
                ar_iters: int = 300, repeats: int = 5,
                extra_env: dict | None = None) -> dict:
    """Per-op host-path latencies (µs) with pvar collection off/on.
    ``extra_env`` overlays the lane's env after the defaults (the online
    lane uses it to flip the bandit knobs)."""
    import numpy as np

    import tpu_mpi as MPI
    from tpu_mpi import config, perfvars
    from tpu_mpi.testing import run_spmd

    os.environ["TPU_MPI_PVARS"] = "1" if pvars_on else "0"
    os.environ["TPU_MPI_COLL_ALGO"] = "allreduce=star"
    for k, v in (extra_env or {}).items():
        os.environ[k] = v
    config.load(refresh=True)
    perfvars.reset()
    out = {}

    def pingpong():
        comm = MPI.COMM_WORLD
        r = comm.rank()
        buf = np.ones(64, dtype=np.float64)
        rbuf = np.empty_like(buf)
        for _ in range(200):            # warmup
            if r == 0:
                MPI.Send(buf, 1, 7, comm)
                MPI.Recv(rbuf, 1, 7, comm)
            else:
                MPI.Recv(rbuf, 0, 7, comm)
                MPI.Send(buf, 0, 7, comm)
        best = float("inf")
        for _ in range(repeats):
            MPI.Barrier(comm)
            t0 = time.perf_counter()
            for _ in range(pp_iters):
                if r == 0:
                    MPI.Send(buf, 1, 7, comm)
                    MPI.Recv(rbuf, 1, 7, comm)
                else:
                    MPI.Recv(rbuf, 0, 7, comm)
                    MPI.Send(buf, 0, 7, comm)
            best = min(best, (time.perf_counter() - t0) / (2 * pp_iters))
        if r == 0:
            out["pingpong_us"] = round(best * 1e6, 3)
            if pvars_on:
                assert comm.get_pvars()["sends"] > 0   # collection really on

    run_spmd(pingpong, 2)

    def allreduce():
        comm = MPI.COMM_WORLD
        x = np.ones(1024, dtype=np.float64)
        y = np.empty_like(x)
        for _ in range(20):
            MPI.Allreduce(x, y, MPI.SUM, comm)
        best = float("inf")
        for _ in range(repeats):
            MPI.Barrier(comm)
            t0 = time.perf_counter()
            for _ in range(ar_iters):
                MPI.Allreduce(x, y, MPI.SUM, comm)
            best = min(best, (time.perf_counter() - t0) / ar_iters)
        if comm.rank() == 0:
            out["allreduce_star_us"] = round(best * 1e6, 3)
            if pvars_on:
                assert comm.get_pvars()["ops"]

    run_spmd(allreduce, 4)
    perfvars.reset()     # isolate the persistent lane's wait_s evidence

    def persistent():
        # registered fast path (ISSUE-6): plan bound once, Start/Wait per
        # round. The snapshot must show wait_s == 0 — the round's wall
        # clock is owned by its op scope, and the outermost-owner rule
        # keeps the inner Wait from double-counting it (the bug this
        # probe's earlier revision had).
        comm = MPI.COMM_WORLD
        x = np.ones(1024, dtype=np.float64)
        y = np.empty_like(x)
        req = MPI.Allreduce_init(x, y, MPI.SUM, comm)
        for _ in range(20):
            MPI.Start(req)
            MPI.Wait(req)
        best = float("inf")
        for _ in range(repeats):
            MPI.Barrier(comm)
            t0 = time.perf_counter()
            for _ in range(ar_iters):
                MPI.Start(req)
                MPI.Wait(req)
            best = min(best, (time.perf_counter() - t0) / ar_iters)
        if comm.rank() == 0:
            out["allreduce_persistent_us"] = round(best * 1e6, 3)
            if pvars_on:
                s = comm.get_pvars()
                rounds = sum(v for k, v in s["ops"].items()
                             if k.startswith("allreduce"))
                assert rounds > 0, s["ops"]
                assert s["wait_s"] == 0.0, s["wait_s"]   # no double count
                out["persistent_rounds"] = rounds
                out["persistent_wait_s"] = s["wait_s"]
                out["persistent_phase_s"] = {
                    k: round(v, 6) for k, v in s["phase_s"].items()}

    run_spmd(persistent, 4)
    perfvars.reset()
    return out


def pvars_lane(out_path: str) -> None:
    platform = detect_platform()
    _log(f"platform: {platform}")
    saved = {k: os.environ.get(k) for k in ("TPU_MPI_PVARS",
                                            "TPU_MPI_COLL_ALGO")}
    try:
        off = _pvars_case(False)
        _log(f"pvars off: {off}")
        on = _pvars_case(True)
        _log(f"pvars on:  {on}")
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
        from tpu_mpi import config
        config.load(refresh=True)
    overhead = {k: round((on[k] - off[k]) / off[k] * 100, 2)
                for k in off if off[k] > 0}
    _log(f"overhead %: {overhead}")
    emit(out_path, {
        "benchmark": "overhead_pvars",
        "platform": platform,
        "pvars_off_us": off,
        "pvars_on_us": on,
        "overhead_pct": overhead,
    })


def online_lane(out_path: str, baseline_path: str | None = None) -> None:
    """Online-autotuner decision-point overhead: the pvars-on cases with
    the bandit code present but exploration OFF (the deployment default —
    must stay within noise of the committed pre-bandit baseline's pvars-on
    lane) and with exploration ON at 10% (the exploration tax: decide()
    bookkeeping plus the rerouted calls; the thread tier executes in
    process either way, so this isolates the engine's own cost)."""
    import json

    platform = detect_platform()
    _log(f"platform: {platform}")
    knobs = ("TPU_MPI_PVARS", "TPU_MPI_COLL_ALGO", "TPU_MPI_TUNE_EXPLORE",
             "TPU_MPI_TUNE_SWAP_PERIOD")
    saved = {k: os.environ.get(k) for k in knobs}
    try:
        off = _pvars_case(True, extra_env={"TPU_MPI_TUNE_EXPLORE": "0"})
        _log(f"explore off: {off}")
        # unpin the algorithm (a force-pin suppresses exploration) and
        # park the swap milestone out of reach so the lane times decide()
        # itself, not the amortized TuneSwap rendezvous
        on = _pvars_case(True, extra_env={
            "TPU_MPI_TUNE_EXPLORE": "0.1",
            "TPU_MPI_TUNE_SWAP_PERIOD": "1000000",
            "TPU_MPI_COLL_ALGO": ""})
        _log(f"explore on:  {on}")
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
        from tpu_mpi import config, tune_online
        config.load(refresh=True)
        tune_online.reset()
    common = [k for k in off if k in on and isinstance(off[k], float)
              and off[k] > 0]
    on_pct = {k: round((on[k] - off[k]) / off[k] * 100, 2) for k in common}
    _log(f"explore-on overhead %: {on_pct}")
    baseline = None
    base_pct = None
    if baseline_path and os.path.exists(baseline_path):
        with open(baseline_path) as f:
            baseline = json.load(f).get("pvars_on_us")
    if baseline:
        base_pct = {k: round((off[k] - baseline[k]) / baseline[k] * 100, 2)
                    for k in baseline
                    if k in off and isinstance(baseline[k], float)
                    and baseline[k] > 0}
        _log(f"explore-off vs pre-bandit baseline %: {base_pct}")
    emit(out_path, {
        "benchmark": "overhead_online",
        "platform": platform,
        "explore_off_us": off,
        "explore_on_us": on,
        "explore_on_overhead_pct": on_pct,
        "baseline_pvars_on_us": baseline,
        "off_vs_baseline_pct": base_pct,
    })


def main() -> None:
    if sys.argv[1:2] == ["--pvars"]:
        out = sys.argv[2] if len(sys.argv) > 2 else \
            os.path.join(_HERE, "results", "overhead-pvars-cpusim.json")
        pvars_lane(out)
        return
    if sys.argv[1:2] == ["--online"]:
        out = sys.argv[2] if len(sys.argv) > 2 else \
            os.path.join(_HERE, "results", "overhead-online-cpusim.json")
        online_lane(out, baseline_path=os.path.join(
            _HERE, "results", "overhead-pvars-cpusim.json"))
        return
    out_path = sys.argv[1] if len(sys.argv) > 1 else "-"
    platform = detect_platform()
    _log(f"platform: {platform}")
    import jax
    import jax.numpy as jnp

    t_null = case_dispatch_floor(jax, jnp)
    _log(f"A dispatch_floor     = {t_null * 1e3:.3f} ms")
    t_ew = case_elementwise(jax, jnp, donate=False)
    _log(f"B elementwise        = {t_ew * 1e3:.3f} ms")
    t_ewd = case_elementwise(jax, jnp, donate=True)
    _log(f"C elementwise_donate = {t_ewd * 1e3:.3f} ms")
    t_fold = case_fold4(jax, jnp)
    _log(f"D fold4              = {t_fold * 1e3:.3f} ms")
    rtt = measure_dispatch_floor()
    ctl = control_block(n_elems=N_ELEMS, rtt=rtt)
    t_few = ctl["hbm_per_step_s"]           # unrounded slope
    _log(f"E fused_elementwise  = {t_few * 1e3:.3f} ms/step (adaptive)")
    ig = ingraph_collective_slope("allreduce", N_ELEMS, 4, rtt=rtt)
    t_ffold = ig["per_fold_s"]              # unrounded slope
    _log(f"F fused_fold4        = {t_ffold * 1e3:.3f} ms/step (adaptive)")
    size_rows = case_floor_vs_size(jax, jnp)

    _log("G mpi_allreduce (4 rank threads, device lane) ...")
    times = host_allreduce_times(N_ELEMS, 4, True, WARMUP, ITERS, REPEATS)
    t_mpi = best_block(times)
    _log(f"G mpi_allreduce      = {t_mpi * 1e3:.3f} ms")

    floor = t_ew - t_few
    model = floor + t_ffold
    derived = {
        "dispatch_floor_ms": round(floor * 1e3, 3),
        "alloc_churn_ms": round((t_ew - t_ewd) * 1e3, 3),
        "mpi_overhead_ms": round((t_mpi - t_fold) * 1e3, 3),
        "hbm_gbps_measured_elementwise": ctl["hbm_gbps_measured"],
        # "implied": the 5x traffic model's rate; when the fold's working
        # set stays VMEM-resident the model stops binding and this may
        # legitimately exceed HBM peak — hbm_model_binds says which
        "hbm_gbps_implied_fold": ig["hbm_gbps_implied"],
        "hbm_model_binds": ig["hbm_model_binds"],
        "model_ms": round(model * 1e3, 3),
        "mpi_vs_model": round(t_mpi / model, 4),
        "mpi_algbw_gbps": round(NBYTES / t_mpi / 1e9, 3),
        "model_algbw_gbps": round(NBYTES / model / 1e9, 3),
    }
    _log(f"derived: {derived}")
    emit(out_path, {
        "benchmark": "overhead_probe",
        "platform": platform,
        "n_elems": N_ELEMS,
        "payload_mib": NBYTES >> 20,
        "cases_ms": {
            "dispatch_floor": round(t_null * 1e3, 3),
            "elementwise": round(t_ew * 1e3, 3),
            "elementwise_donate": round(t_ewd * 1e3, 3),
            "fold4": round(t_fold * 1e3, 3),
            "fused_elementwise_per_step": round(t_few * 1e3, 3),
            "fused_fold4_per_step": round(t_ffold * 1e3, 3),
            "mpi_allreduce": round(t_mpi * 1e3, 3),
        },
        "floor_vs_size": size_rows,
        "derived": derived,
        "control": ctl,
        "ingraph_slope": {k: ig[k] for k in
                          ("k", "slope_spread", "hbm_model_binds")},
    })


if __name__ == "__main__":
    main()
