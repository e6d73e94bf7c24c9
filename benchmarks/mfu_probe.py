"""Single-chip MFU proof (VERDICT r3 next-item #2; r4 next #3 shape sweep).

Protocol: the execution-dominated **adaptive slope** (common.adaptive_slope
— per-step exec = (t(2K)-t(K))/K with K grown until the call time clearly
exceeds the per-call dispatch floor, which a fixed-K slope cannot
guarantee), stamped with the same-session control block.

  A. control block — dispatch floor, HBM GB/s, GEMM slope TFLOP/s
     (common.control_block; VERDICT bar: >=40% MFU on the GEMM control).
  B. ``ring_attention`` — the fused Pallas block vs the precision-matched
     naive-XLA body, swept over (T, d, dtype) shapes. The bf16 rows run
     the bf16 MXU path (f32 softmax state/accumulation) in BOTH bodies,
     so fused-vs-naive is apples-to-apples.

Sanity per timed call: one-element readback, assert finite. The fused and
naive bodies are cross-checked against each other at one step per shape.

Usage: python benchmarks/mfu_probe.py [-o results/mfu-tpu.json]
"""

from __future__ import annotations

import argparse
import sys

import numpy as np

from common import (adaptive_slope, best_of_calls, control_block,
                    detect_platform, emit, gen_of, measure_dispatch_floor)

# (T_local, d, dtype): 1024/f32 keeps r3/r4 continuity; the bf16 rows are
# the MXU-rate path the kernel is built for (VERDICT r4 next #3)
SHAPES = [
    (1024, 128, "float32"),
    (1024, 128, "bfloat16"),
    (2048, 128, "bfloat16"),
    (4096, 128, "bfloat16"),
]
REPEATS = 3


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("-o", "--out", default="-")
    args = ap.parse_args()

    plat = detect_platform()
    record: dict = {"benchmark": "mfu_probe", "platform": plat,
                    "protocol": "adaptive slope (common.adaptive_slope): "
                                "per-step exec = (t(2K)-t(K))/K with K grown "
                                "until calls are execution-dominated; every "
                                "call chains data-dependently and ends in a "
                                "forced readback"}
    if plat["platform"] != "tpu":
        record["skipped"] = "no TPU backend"
        emit(args.out, record)
        return

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from tpu_mpi.implementations import CAPABILITIES
    from tpu_mpi.xla import make_mesh, pallas_kernels as pk

    dev = [d for d in jax.devices() if d.platform == "tpu"][:1]
    gen = gen_of(dev[0])
    peak = CAPABILITIES[gen]["bf16_tflops"] * 1e12
    record["generation"] = gen
    record["bf16_peak_tflops"] = peak / 1e12

    # ---- A. control block (same-session stamp + GEMM bar) -----------------
    rtt = measure_dispatch_floor()
    record["control"] = control_block(rtt=rtt)
    fps_gemm = record["control"]["gemm_slope_tflops"] * 1e12
    record["gemm_mfu"] = round(fps_gemm / peak, 4)
    print(f"control: dispatch floor "
          f"{record['control']['dispatch_floor_ms']} ms, "
          f"HBM {record['control']['hbm_gbps_measured']} GB/s, GEMM "
          f"{record['control']['gemm_slope_tflops']} TFLOP/s "
          f"({record['gemm_mfu'] * 100:.1f}% MFU)", file=sys.stderr)

    # ---- B. attention shape sweep: fused Pallas vs naive XLA --------------
    mesh = make_mesh({"x": 1}, devices=dev)
    record["attention"] = []

    for t_, d_, dtn in SHAPES:
        dt = jnp.dtype(dtn)
        keys = jax.random.split(jax.random.PRNGKey(7), 3)
        q0, kk_, vv_ = (jax.random.normal(s, (t_, d_), jnp.float32).astype(dt)
                        for s in keys)
        step_flops = 4.0 * t_ * t_ * d_

        def fused_body(a, b, c):
            return pk.ring_attention(a, b, c, axis="x", interpret=False)

        # true-f32 MXU for the f32 row (XLA's DEFAULT runs f32 matmuls as
        # bf16 passes on TPU — the Pallas kernel's f32 path is exact, so
        # the control must be too); bf16 rows use the native bf16 path
        prec = (jax.lax.Precision.HIGHEST if dtn == "float32"
                else jax.lax.Precision.DEFAULT)

        def naive_body(a, b, c):
            # precision-matched control: same mixed precision as the
            # kernel (matmuls at input dtype with f32 accumulation,
            # softmax state in f32), fused however XLA likes
            s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=prec)
            s = s / np.sqrt(d_)
            p = jax.nn.softmax(s, axis=-1)
            return jax.lax.dot_general(p.astype(a.dtype), c,
                                       (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32,
                                       precision=prec).astype(a.dtype)

        def chain_of(body):
            def f(a, steps, b, c):
                def step(i, acc):
                    return body(acc, b, c)
                return jax.lax.fori_loop(0, steps, step, a)
            g = jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=(P(), None, P(), P()), out_specs=P(),
                check_vma=False))
            st = {"a": q0}

            def call(ksteps):
                st["a"] = g(st["a"], ksteps, kk_, vv_)
                v0 = float(np.asarray(st["a"])[0, 0])
                assert np.isfinite(v0), v0

            call(1)   # compile once (dynamic trip count)
            return call

        def slope_of(call):
            sl = adaptive_slope(
                lambda k: best_of_calls(call, k, REPEATS), rtt)
            return sl

        fused_call, naive_call = chain_of(fused_body), chain_of(naive_body)
        # one-step numerics cross-check (fused vs naive, same inputs)
        one_f = jax.jit(jax.shard_map(
            fused_body, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
            check_vma=False))
        one_n = jax.jit(jax.shard_map(
            naive_body, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
            check_vma=False))
        got = np.asarray(one_f(q0, kk_, vv_), np.float32)
        want = np.asarray(one_n(q0, kk_, vv_), np.float32)
        rel = float(np.abs(got - want).max()
                    / max(np.abs(want).max(), 1e-9))
        tol = 0.05 if dtn == "bfloat16" else 2e-4
        assert rel < tol, f"fused/naive mismatch at {t_}x{d_} {dtn}: {rel}"

        sf, sn = slope_of(fused_call), slope_of(naive_call)
        per_f, per_n = sf["per_step_s"], sn["per_step_s"]
        row = {
            "shape": [t_, d_], "dtype": dtn,
            "one_step_rel_err_fused_vs_naive": round(rel, 5),
            "fused": {"per_step_us": round(per_f * 1e6, 1),
                      "tflops": round(step_flops / per_f / 1e12, 2),
                      "mfu": round(step_flops / per_f / peak, 4),
                      "k": sf["k"], "slope_spread": sf["slope_spread"]},
            "naive_xla": {"per_step_us": round(per_n * 1e6, 1),
                          "tflops": round(step_flops / per_n / 1e12, 2),
                          "mfu": round(step_flops / per_n / peak, 4),
                          "k": sn["k"], "slope_spread": sn["slope_spread"]},
            "fused_over_naive_speed": round(per_n / per_f, 3),
        }
        # noise guard (kept from r4): a slope implying more than the chip's
        # peak — or a non-positive one — means jitter beat the adaptive
        # protocol; flag the row rather than assert an impossible number
        for lane in (row["fused"], row["naive_xla"]):
            lane["resolved"] = bool(0 < lane["tflops"] * 1e12 <= 1.05 * peak)
        record["attention"].append(row)
        print(f"attn {t_}x{d_} {dtn}: fused {per_f * 1e6:.0f} us "
              f"({row['fused']['tflops']} TF, {row['fused']['mfu'] * 100:.0f}"
              f"% MFU) vs naive {per_n * 1e6:.0f} us "
              f"({row['naive_xla']['tflops']} TF) -> "
              f"{row['fused_over_naive_speed']}x", file=sys.stderr)

    # "somewhere" means ANY row may satisfy both clauses at once — taking
    # argmax by speed first could miss a row that wins on speed AND clears
    # the MFU bar when the speed argmax happens to be a low-MFU shape
    record["fused_wins_somewhere"] = any(
        r["fused_over_naive_speed"] >= 1.0 and r["fused"]["mfu"] >= 0.65
        for r in record["attention"])
    record["gemm_mfu_target_met"] = bool(record["gemm_mfu"] >= 0.40)
    emit(args.out, record)
    if not record["gemm_mfu_target_met"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
