"""Device time of a train step with latent attention in sandwich-normed
blocks, by the scopes inside a layer's attention half: what
`kinds_scope_reduce.py` does by layer kind, for a step built by
`generators/lm_latent_train_step.py`. `scope_reduce`'s and
`kinds_scope_reduce`'s parsing is imported, not copied, and the step's HLO
text is `moe_scope_reduce.step_hlo_text`'s (compiled again after the window,
past the persistent cache, and checked against the traced names).

Scopes (tpu_mpi/models/transformer.py:_latent_attn, _attn_ffn_block): under
`layer_<i>/attn`: `q_latent` and `kv_latent` (down-projection, the latent's
norm, up-projection and the cut into heads), `rope`, the fused kernel's own
calls (`causal_attention_fwd`, `causal_attention_bwd`: a `tpu_custom_call`
each, kept as calls and seconds by direction), `out` (the heads' part of the
output projection) and `norm_out` (the sandwich's norm of the half's
output); what lies under `attn` outside those (the norm before it, the
residual add, the kernel's row sums of o x do and the sum of the shared
rotary key's gradient over the heads) is `attn_rest`. `norm_out` under
`layer_<i>/mlp` is `mlp_norm_out`. A transposing copy of a parameter carries
the parameter's own name and goes to the scope that uses the leaf. Every
other op is `other` (the FFN halves, embedding, head and optimizer, which
`kinds_scope_reduce` reads): a program without these scopes (the parent of
the PR that added them) has nothing under them and the readers report
nothing."""

from __future__ import annotations

from typing import Optional

from yardstick import kinds_scope_reduce, moe_scope_reduce, scope_reduce

KEY = "latent_scope_reduce"
INSIDE_ATTN = ("q_latent", "kv_latent", "rope", "out", "norm_out")
ATTN = INSIDE_ATTN + ("kernel_fwd", "kernel_bwd", "attn_rest")
SCOPES = ATTN + ("mlp_norm_out", "other")
LEAF = {"w_dq": "q_latent", "w_uq": "q_latent", "q_latent_norm": "q_latent",
        "w_dkv": "kv_latent", "w_ukv": "kv_latent",
        "kv_latent_norm": "kv_latent", "w_proj": "out", "ln1": "attn_rest",
        "ln1_out": "norm_out", "ln2_out": "mlp_norm_out"}


def scope_of(op_name: str) -> str:
    leaf = kinds_scope_reduce.PARAMETER.match(op_name)
    if leaf:
        return LEAF.get(leaf.group(3), "other")
    kernel = kinds_scope_reduce.KERNEL.search(op_name)
    parts = scope_reduce.WRAPPERS.sub("", op_name).replace(")", "").split("/")
    for n, p in enumerate(parts):
        if p.startswith("layer_") and p[6:].isdigit():
            rest = parts[n + 1:]
            if "attn" in rest:
                if kernel:
                    return "kernel_" + kernel.group(1)
                inside = [s for s in rest if s in INSIDE_ATTN]
                return inside[0] if inside else "attn_rest"
            if "mlp" in rest and "norm_out" in rest:
                return "mlp_norm_out"
            break
    return "other"


def per_step(run) -> Optional[dict]:
    """{"ms": device milliseconds per step by scope on the busiest chip,
    "calls": {"fwd" | "bwd": the kernel's calls per step}}, or None: no
    trace, a program without this step or with nothing under the latent
    layer's scopes, or a text that is not the executable that ran."""
    if KEY in run.prepared:
        return run.prepared[KEY]
    run.prepared[KEY] = None
    steps = run.traced_ops()
    if not steps or not hasattr(run.cell.generator(), "build"):
        return None
    text = moe_scope_reduce.step_hlo_text(run)
    ops = run.trace.busiest.ops
    absent = scope_reduce.absent_share(ops, text)
    if absent > scope_reduce.MAX_ABSENT:
        run.row(f"latent scopes: {100.0 * absent:.2f}% of the traced op time "
                "is under names the recompiled step's HLO text does not "
                "have: not the executable that ran; nothing is reported")
        return None
    names = dict(scope_reduce.INSTRUCTION.findall(text))
    secs = {s: 0.0 for s in SCOPES}
    calls = {"fwd": 0.0, "bwd": 0.0}
    scopes = {}
    for name, (count, s) in ops.items():
        scopes[name] = scope = scope_of(names.get(name, ""))
        secs[scope] += s
        if scope.startswith("kernel_"):
            calls[scope[7:]] += count / steps
    if not any(secs[s] for s in INSIDE_ATTN):
        return None             # no latent layer's scope in this program
    ms = {k: v / steps * 1e3 for k, v in secs.items()}
    out = run.prepared[KEY] = {"ms": ms, "calls": calls}
    run.row("device ms per step inside the latent layers' attention halves "
            "(op time summed, the busiest chip): " + "  ".join(
                f"{k} {v:.3f}" for k, v in ms.items())
            + f"; the fused kernel's calls per step: fwd {calls['fwd']:.2f} "
            f"bwd {calls['bwd']:.2f}")
    heavy = sorted(((s, n) for n, (_c, s) in ops.items()
                    if scopes[n] in ATTN), reverse=True)[:24]
    run.row("heaviest ops under attn, ms per step (all layers' calls of the "
            "op together): " + "  ".join(
                f"{n} [{scopes[n]}] {s / steps * 1e3:.3f}" for s, n in heavy))
    return out


def per_step_ms(run) -> Optional[dict]:
    out = per_step(run)
    return None if out is None else out["ms"]
