"""Flagship end-to-end cost artifact (VERDICT r4 next #4).

Measures the FULL DP×TP×SP transformer train step (models/transformer.py —
Megatron column/row-parallel f/g operators, ring attention over 'sp',
gradient psum over dp/sp, SGD update) on the real chip, and an IDENTICAL
hand-written pure-JAX train step with no tpu_mpi wrappers, no shard_map and
no collectives, as the control. Both use the execution-dominated adaptive
slope (common.adaptive_slope) with the train steps chained K-deep inside
one jit (params feed forward — data-dependent by construction) and a
finite-loss readback per call.

On this 1-chip environment the mesh is dp×tp×sp = 1×1×1: XLA should compile
the size-1 collectives away, so the framework-vs-control delta bounds the
IN-GRAPH overhead of the sharding machinery (the dryrun proves multi-chip
correctness; this proves the machinery costs nothing when compiled).

Writes flagship-mfu-tpu.json: step time, achieved model FLOP/s, MFU,
framework-vs-control delta, same-session control block.

Usage: python benchmarks/flagship_probe.py [-o results/flagship-mfu-tpu.json]
"""

from __future__ import annotations

import argparse
import sys

import numpy as np

from common import (adaptive_slope, best_of_calls, control_block,
                    detect_platform, emit, gen_of, measure_dispatch_floor)

# a real (small-LLM-block-sized) config: bf16 params/activations, f32 loss
D_MODEL, N_HEADS, N_LAYERS, D_FF = 1024, 16, 8, 4096
VOCAB, SEQ, BATCH = 32768, 1024, 8
LR = 1e-3
REPEATS = 3


def model_flops_per_step() -> float:
    """Analytic matmul FLOPs of one train step (fwd + bwd ~= 3x fwd)."""
    b, t, d, f, v = BATCH, SEQ, D_MODEL, D_FF, VOCAB
    per_layer = (2 * b * t * d * 3 * d        # qkv
                 + 2 * 2 * b * t * t * d      # scores + pv
                 + 2 * b * t * d * d          # proj
                 + 2 * 2 * b * t * d * f)     # ffn in/out
    fwd = N_LAYERS * per_layer + 2 * b * t * d * v   # + logits
    return 3.0 * fwd


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("-o", "--out", default="-")
    args = ap.parse_args()

    plat = detect_platform()
    record: dict = {
        "benchmark": "flagship_probe", "platform": plat,
        "config": {"d_model": D_MODEL, "n_heads": N_HEADS,
                   "n_layers": N_LAYERS, "d_ff": D_FF, "vocab": VOCAB,
                   "seq": SEQ, "batch": BATCH, "dtype": "bfloat16"},
        "protocol": "adaptive slope over K train steps chained inside one "
                    "jit (params carry forward); framework lane = "
                    "models/transformer.py local_step under shard_map on a "
                    "1x1x1 dp*tp*sp mesh; control lane = identical "
                    "hand-written pure-JAX step (no shard_map, no "
                    "collectives, no tpu_mpi)"}
    if plat["platform"] != "tpu":
        record["skipped"] = "no TPU backend"
        emit(args.out, record)
        return

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from tpu_mpi.implementations import CAPABILITIES
    from tpu_mpi.xla import make_mesh
    from tpu_mpi.models.transformer import (TransformerConfig, _xent,
                                            transformer_forward,
                                            transformer_init,
                                            transformer_param_specs)

    dev = [d for d in jax.devices() if d.platform == "tpu"][:1]
    gen = gen_of(dev[0])
    peak = CAPABILITIES[gen]["bf16_tflops"] * 1e12
    record["generation"] = gen
    record["bf16_peak_tflops"] = peak / 1e12

    rtt = measure_dispatch_floor()
    cfg = TransformerConfig(vocab=VOCAB, d_model=D_MODEL, n_heads=N_HEADS,
                            n_layers=N_LAYERS, d_ff=D_FF, max_seq=SEQ,
                            dtype=jnp.bfloat16)
    params0 = transformer_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ), 0, VOCAB)
    labels = jax.random.randint(jax.random.PRNGKey(2), (BATCH, SEQ), 0, VOCAB)
    flops = model_flops_per_step()
    record["model_flops_per_step"] = flops

    # ---- framework lane: the real DP*TP*SP step, axes of size 1 ------------
    mesh = make_mesh({"dp": 1, "tp": 1, "sp": 1}, devices=dev)
    specs = transformer_param_specs(cfg, "tp")

    def fw_local(params, k, tok, lab):
        def one(params):
            def loss_fn(p):
                logits = transformer_forward(cfg, p, tok, tp_axis="tp",
                                             sp_axis="sp")
                return _xent(logits, lab)
            loss, grads = jax.value_and_grad(loss_fn)(params)
            grads = jax.tree_util.tree_map(
                lambda g: lax.psum(g, ("dp", "sp")), grads)
            new = jax.tree_util.tree_map(
                lambda p, g: (p - LR * g).astype(p.dtype), params, grads)
            return new, lax.pmean(loss, ("dp", "sp"))

        def body(i, carry):
            p, _ = carry
            return one(p)
        return lax.fori_loop(0, k, body, (params, jnp.zeros((), jnp.float32)))

    data_spec = P("dp", "sp")
    fw_step = jax.jit(jax.shard_map(
        fw_local, mesh=mesh,
        in_specs=(specs, None, data_spec, data_spec),
        out_specs=(specs, P())))

    # ---- control lane: identical math, no framework ------------------------
    def ctl_local(params, k, tok, lab):
        def one(params):
            def loss_fn(p):
                logits = transformer_forward(cfg, p, tok, tp_axis=None,
                                             sp_axis=None)
                return _xent(logits, lab)
            loss, grads = jax.value_and_grad(loss_fn)(params)
            new = jax.tree_util.tree_map(
                lambda p, g: (p - LR * g).astype(p.dtype), params, grads)
            return new, loss

        def body(i, carry):
            p, _ = carry
            return one(p)
        return lax.fori_loop(0, k, body, (params, jnp.zeros((), jnp.float32)))

    ctl_step = jax.jit(ctl_local)

    def lane(step_fn, label):
        st = {"p": params0}

        def call(k):
            st["p"], loss = step_fn(st["p"], k, tokens, labels)
            v = float(loss)
            assert np.isfinite(v), f"{label}: non-finite loss {v}"

        call(1)                           # compile once (dynamic k)
        sl = adaptive_slope(lambda k: best_of_calls(call, k, REPEATS), rtt)
        per = sl["per_step_s"]
        row = {"per_step_ms": round(per * 1e3, 3),
               "model_tflops": round(flops / per / 1e12, 2),
               "mfu": round(flops / per / peak, 4),
               "k": sl["k"], "slope_spread": sl["slope_spread"]}
        print(f"{label}: {per * 1e3:.2f} ms/step = "
              f"{row['model_tflops']} TFLOP/s ({row['mfu'] * 100:.1f}% MFU, "
              f"k={sl['k']}, spread {sl['slope_spread']})", file=sys.stderr)
        return row, per

    fw_row, fw_per = lane(fw_step, "framework dp*tp*sp")
    ctl_row, ctl_per = lane(ctl_step, "hand-written control")
    record["framework"] = fw_row
    record["control_lane"] = ctl_row
    delta = fw_per / ctl_per - 1.0
    record["framework_overhead_frac"] = round(delta, 4)
    record["overhead_under_3pct"] = bool(delta < 0.03)
    record["control"] = control_block(rtt=rtt)
    print(f"framework vs control: {delta * 100:+.2f}%", file=sys.stderr)
    emit(args.out, record)


if __name__ == "__main__":
    main()
