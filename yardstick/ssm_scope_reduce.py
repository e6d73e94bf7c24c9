"""Device time of a train step with state-space layers, by the scopes inside
a state-space layer's first half: what `latent_scope_reduce.py` does for
latent attention, for a step built by `generators/lm_ssm_train_step.py`.
`scope_reduce`'s and `kinds_scope_reduce`'s parsing is imported, not copied,
and the step's HLO text is `moe_scope_reduce.step_hlo_text`'s (compiled
again after the window, past the persistent cache, and checked against the
traced names).

Scopes (tpu_mpi/models/transformer.py:_ssm_mixer, _attn_ffn_block): under
`layer_<i>/mixer`: `in_proj` (the one product that gives the gate, the
convolution's channels and dt), `conv` (the causal convolution, silu and the
cut into x, B, C), `scan` (dt's softplus, the decays, the chunked products
of `parallel/ssm.py:scan`, forward, their recomputation and backward, and
the skip term), `gate_norm`, `out_proj`; what lies under `mixer` outside
those (the norm before it, the residual's multiplier and add) is
`mixer_rest`. A transposing copy of a parameter carries the parameter's own
name and goes to the scope that uses the leaf. Every other op is `other`
(the attention layer, the FFN halves, embedding, head and optimizer, which
`kinds_scope_reduce` reads): a program without these scopes (the parent of
the PR that added them) has nothing under them and the readers report
nothing."""

from __future__ import annotations

from typing import Optional

from yardstick import (kinds_scope_reduce, lm_kinds_flops, moe_scope_reduce,
                       scope_reduce)

KEY = "ssm_scope_reduce"
INSIDE = ("in_proj", "conv", "scan", "gate_norm", "out_proj")
MIXER = INSIDE + ("mixer_rest",)
SCOPES = MIXER + ("other",)
LEAF = {"w_ssm_in": "in_proj", "conv_w": "conv", "conv_b": "conv",
        "dt_bias": "scan", "a_log": "scan", "d_skip": "scan",
        "ssm_norm": "gate_norm", "w_ssm_out": "out_proj"}


def scope_of(op_name: str) -> str:
    leaf = kinds_scope_reduce.PARAMETER.match(op_name)
    if leaf:
        return LEAF.get(leaf.group(3), "other")
    parts = scope_reduce.WRAPPERS.sub("", op_name).replace(")", "").split("/")
    for n, p in enumerate(parts):
        if p.startswith("layer_") and p[6:].isdigit():
            rest = parts[n + 1:]
            if "mixer" in rest:
                inside = [s for s in rest[rest.index("mixer") + 1:]
                          if s in INSIDE]
                return inside[0] if inside else "mixer_rest"
            break
    return "other"


def per_step_ms(run) -> Optional[dict]:
    """Device milliseconds per step by scope on the busiest chip, or None:
    no trace, a program without this step or with nothing under a
    state-space layer's scopes, or a text that is not the executable that
    ran."""
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
        run.row(f"mixer scopes: {100.0 * absent:.2f}% of the traced op time "
                "is under names the recompiled step's HLO text does not "
                "have: not the executable that ran; nothing is reported")
        return None
    names = dict(scope_reduce.INSTRUCTION.findall(text))
    secs = {s: 0.0 for s in SCOPES}
    scopes = {}
    for name, (_count, s) in ops.items():
        scopes[name] = scope_of(names.get(name, ""))
        secs[scopes[name]] += s
    if not any(secs[s] for s in INSIDE):
        return None             # no state-space layer's scope in this program
    ms = run.prepared[KEY] = {k: v / steps * 1e3 for k, v in secs.items()}
    run.row("device ms per step inside the state-space layers' mixers (op "
            "time summed, the busiest chip): " + "  ".join(
                f"{k} {v:.3f}" for k, v in ms.items()))
    heavy = sorted(((s, n) for n, (_c, s) in ops.items()
                    if scopes[n] in MIXER), reverse=True)[:24]
    run.row("heaviest ops under mixer, ms per step (all layers' calls of the "
            "op together): " + "  ".join(
                f"{n} [{scopes[n]}] {s / steps * 1e3:.3f}" for s, n in heavy))
    # what neither this reducer nor `kinds_scope_reduce` names: its
    # `(unscoped)` holds the mixers too, so the rest is listed here
    kinds = lm_kinds_flops.layer_kinds(run.config["model"])
    unnamed = sorted(((s, n) for n, (_c, s) in ops.items()
                      if scopes[n] == "other" and kinds_scope_reduce.scope_of(
                          names.get(n, ""), kinds) == scope_reduce.REST),
                     reverse=True)
    run.row(f"under no scope at all {sum(s for s, _n in unnamed) / steps * 1e3:.3f}"
            " ms per step; the heaviest: " + "  ".join(
                f"{n} {s / steps * 1e3:.3f}" for s, n in unnamed[:12]))
    return ms
