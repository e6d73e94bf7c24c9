"""Device time of a train step whose layers differ in kind, by the model's
named scopes and by layer kind: what `moe_scope_reduce.py` does for a uniform
stack of expert layers, for a step built by
`generators/lm_kinds_train_step.py`. `scope_reduce`'s parsing is imported,
not copied; the rules on op names no metadata gives are `moe_scope_reduce`'s
(a transposing copy of a parameter carries the parameter's own name).

Scopes (tpu_mpi/models/transformer.py, tpu_mpi/parallel/ep.py): `embed`,
`layer_<i>/attn`, `layer_<i>/mlp` with `router`, `dispatch`, `experts`,
`combine`, `shared` (a sparse layer) or `dense` (a dense one) inside it,
`head_loss`, `optimizer`. What lies under `mlp` outside those (the norm
before it, the residual add) is `mlp_rest`. A layer's `attn` goes to
`attn_window` or `attn_full` by the layer's window in the configuration's
`model` block, and the fused kernel's own calls there
(`causal_attention_fwd`, `causal_attention_bwd`: a `tpu_custom_call` each)
are kept apart as calls and seconds by kind and direction, for the two
attention rooflines. A layer recomputed in the backward pass runs its
forward kernel twice: the calls say so.

The step is compiled again after the window from this checkout's own
model, past the persistent cache, and checked against the traced names, as
`moe_scope_reduce` does (its `step_hlo_text`)."""

from __future__ import annotations

import re
from typing import Optional

from yardstick import lm_kinds_flops, moe_scope_reduce, scope_reduce

KEY = "kinds_scope_reduce"
REST = scope_reduce.REST
INSIDE_MLP = ("router", "dispatch", "experts", "combine", "shared", "dense")
HELD_MOE = ("router", "dispatch", "experts", "combine")
SCOPES = ("embed", "attn_window", "attn_full") + INSIDE_MLP + (
    "mlp_rest", "head_loss", "optimizer", REST)
TOP = ("embed", "head_loss", "optimizer")
PARAMETER = re.compile(
    r"^params\[\W*(\w+)\W*\](?:\[(\d+)\]\[\W*(\w+)\W*\])?")
KERNEL = re.compile(r"causal_attention_(fwd|bwd)")
ATTN_LEAVES = ("ln1", "w_q", "w_k", "w_v", "w_qkv", "w_proj", "q_norm",
               "k_norm")


def scope_of(op_name: str, kinds: list) -> str:
    """The scope of an op by its `op_name`; `kinds` = [(window, sparse)] a
    layer (lm_kinds_flops.layer_kinds)."""
    def attn(i: int) -> str:
        return "attn_window" if kinds[i][0] else "attn_full"
    leaf = PARAMETER.match(op_name)
    if leaf:
        top, i, name = leaf.groups()
        if name is None:
            return {"embed": "embed", "ln_f": "head_loss",
                    "lm_head": "head_loss"}.get(top, REST)
        i = int(i)
        if name in ATTN_LEAVES:
            return attn(i)
        if name.startswith("w_shared"):
            return "shared"
        if name == "w_router":
            return "router"
        if name in ("w_in", "w_gate", "w_out"):
            return "experts" if kinds[i][1] else "dense"
        return "mlp_rest" if name == "ln2" else REST
    parts = scope_reduce.WRAPPERS.sub("", op_name).replace(")", "").split("/")
    for n, p in enumerate(parts):
        if p.startswith("layer_") and p[6:].isdigit():
            rest = parts[n + 1:]
            if "attn" in rest:
                return attn(int(p[6:]))
            if "mlp" in rest:
                inside = [s for s in rest if s in INSIDE_MLP]
                return inside[0] if inside else "mlp_rest"
        if p in TOP:
            return p
    return REST


def kernel_of(op_name: str, kinds: list) -> Optional[tuple]:
    """("window" | "full", "fwd" | "bwd") of the fused attention kernel's
    call, None for any other op."""
    found = KERNEL.search(op_name)
    layer = re.search(r"layer_(\d+)", op_name)
    if not found or not layer:
        return None
    return ("window" if kinds[int(layer.group(1))][0] else "full",
            found.group(1))


def per_step(run) -> Optional[dict]:
    """{"ms": device milliseconds per step by scope on the busiest chip,
    "kernel": {kind: {direction: {"calls": per step, "ms": per step}}}}, or
    None: no trace, a program without this step or these scopes, or a text
    that is not the executable that ran."""
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
        run.row(f"scopes: {100.0 * absent:.2f}% of the traced op time is "
                "under names the recompiled step's HLO text does not have: "
                "not the executable that ran; the scope readers report "
                "nothing")
        return None
    kinds = lm_kinds_flops.layer_kinds(run.config["model"])
    names = dict(scope_reduce.INSTRUCTION.findall(text))
    secs = {s: 0.0 for s in SCOPES}
    kernel = {k: {d: {"calls": 0.0, "ms": 0.0} for d in ("fwd", "bwd")}
              for k in ("window", "full")}
    scopes = {}
    for name, (count, s) in ops.items():
        op_name = names.get(name, "")
        scopes[name] = scope_of(op_name, kinds)
        secs[scopes[name]] += s
        which = kernel_of(op_name, kinds)
        if which:
            cell = kernel[which[0]][which[1]]
            cell["calls"] += count / steps
            cell["ms"] += s / steps * 1e3
    total = sum(secs.values())
    if total <= 0.0 or secs[REST] >= total:
        return None
    ms = {k: v / steps * 1e3 for k, v in secs.items()}
    out = run.prepared[KEY] = {"ms": ms, "kernel": kernel}
    run.row("device ms per step by the model's scopes (op time summed, the "
            "busiest chip): " + "  ".join(f"{k} {v:.3f}"
                                          for k, v in ms.items())
            + f"  named {100.0 * (1.0 - secs[REST] / total):.2f}% of "
            f"{total / steps * 1e3:.3f} ms; {100.0 * absent:.3f}% of the "
            "op time under names the step's HLO text lacks")
    run.row("the fused attention kernel, calls and device ms per step: "
            + "  ".join(f"{k} {d} {c['calls']:.2f} x "
                        f"{c['ms'] / c['calls'] if c['calls'] else 0.0:.3f}"
                        f" = {c['ms']:.3f}"
                        for k, both in kernel.items()
                        for d, c in both.items()))
    unnamed = sorted(((s, n) for n, (_c, s) in ops.items()
                      if scopes[n] == REST),
                     reverse=True)[:16]
    run.row("heaviest unscoped ops, ms per step: " + "  ".join(
        f"{n} {s / steps * 1e3:.3f}" for s, n in unnamed))
    return out


def per_step_ms(run) -> Optional[dict]:
    out = per_step(run)
    return None if out is None else out["ms"]


def attn_roofline(run, kind: str) -> Optional[float]:
    """%: the fused kernel's products as executed in the layers of `kind`
    ("window" | "full"), forward and backward by the calls the trace
    counts, over `bf16_flops` and the kernel's device time there."""
    out = per_step(run)
    facts = run.facts.get("attention", {}).get(kind)
    if out is None or run.peaks is None or not facts \
            or not facts.get("kernel_flops"):
        return None
    cells = out["kernel"][kind]
    ms = sum(c["ms"] for c in cells.values())
    if ms <= 0.0:
        return None
    flops = sum(facts["kernel_flops"][d] * cells[d]["calls"]
                for d in ("fwd", "bwd"))
    return 100.0 * flops / run.peaks["bf16_flops"] * 1e3 / ms
