"""Device time of a train step of a decoder-hybrid-decoder model, by the
scopes inside its layers' first halves: what `ssm_scope_reduce.py` does for a
Mamba-2 stack, for a step built by `generators/lm_sambay_train_step.py`.
`scope_reduce`'s, `kinds_scope_reduce`'s and `ssm_scope_reduce`'s parsing is
imported, not copied, and the step's HLO text is
`moe_scope_reduce.step_hlo_text`'s (compiled again after the window, past
the persistent cache, and checked against the traced names).

Scopes (tpu_mpi/models/transformer.py:_mamba_mixer, _gmu_mixer, _diff_attn,
_attn_ffn_block), by the layer's kind in the configuration's `model` block:
under `layer_<i>/mixer` of a mamba layer `in_proj`, `conv`, `x_proj` (the
product that gives dt's low-rank form, B and C, dt's projection and its
softplus), `scan` (`parallel/ssm.py:selective_scan`: the decays, the
recurrence, its recomputation and backward, the skip term; a loop is counted
once, by its outermost `while` instruction's own event, which spans its
body's ops and the loop's control between them: `nested_in_loops`), `gate`,
`out_proj`, and `mamba_rest` for what lies under `mixer` and none of them
(the LayerNorm before it, the residual's add); under `layer_<i>/mixer` of a
gated memory unit `gmu` (both products and the gate) and `gmu_rest`; under
`layer_<i>/attn` everything by the layer's kind, `attn_window`, `attn_full`
(the one layer whose keys and values are shared) and `attn_cross`, and beside
that, counted a second time, `diff`: what lies under `attn/diff` in any of
them (the subtraction of the two softmaxes, the pair norm and the scale: what
differential attention adds around the kernel's calls). A transposing copy of
a parameter carries the parameter's own name and goes to the scope that uses
the leaf. Every other op is `other` (the FFN halves, embedding, head and
optimizer, which `kinds_scope_reduce` reads): a program without these scopes
(the parent of the PR that added them) has nothing under them and the
readers report nothing."""

from __future__ import annotations

import re
from typing import Optional

from yardstick import (kinds_scope_reduce, lm_sambay_flops, moe_scope_reduce,
                       scope_reduce, ssm_scope_reduce)

KEY = "sambay_scope_reduce"
MAMBA = ("in_proj", "conv", "x_proj", "scan", "gate", "out_proj")
MAMBA_ALL = MAMBA + ("mamba_rest",)
ATTN = ("attn_window", "attn_full", "attn_cross")
SCOPES = MAMBA_ALL + ("gmu", "gmu_rest") + ATTN + ("other",)
LEAF = dict(ssm_scope_reduce.LEAF, w_ssm_x="x_proj", w_ssm_dt="x_proj",
            dt_bias="x_proj", w_gmu_in="gmu", w_gmu_out="gmu")
HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
CALLED = re.compile(r"\b(?:body|condition|to_apply|calls)=%?([\w.\-]+)")
ATTN_LEAVES = kinds_scope_reduce.ATTN_LEAVES + (
    "ln1_b", "b_q", "b_k", "b_v", "b_proj", "lambda_q1", "lambda_k1",
    "lambda_q2", "lambda_k2", "diff_norm")


def scope_of(op_name: str, mixers: list) -> tuple:
    """(the scope of an op by its `op_name`, whether it lies under
    `attn/diff`); `mixers` = lm_sambay_flops.layer_mixers a layer."""
    leaf = kinds_scope_reduce.PARAMETER.match(op_name)
    if leaf:
        _top, i, name = leaf.groups()
        if name is None:
            return "other", False
        mixer = mixers[int(i)]
        if mixer in lm_sambay_flops.ATTENDS:
            return ("attn_" + mixer if name in ATTN_LEAVES else "other"), False
        if name in ("ln1", "ln1_b"):
            return mixer + "_rest", False
        return LEAF.get(name, "other"), False
    parts = scope_reduce.WRAPPERS.sub("", op_name).replace(")", "").split("/")
    for n, p in enumerate(parts):
        if p.startswith("layer_") and p[6:].isdigit():
            rest, mixer = parts[n + 1:], mixers[int(p[6:])]
            if "attn" in rest and mixer in lm_sambay_flops.ATTENDS:
                return "attn_" + mixer, "diff" in rest[rest.index("attn"):]
            if "mixer" in rest and mixer == "mamba":
                inside = [s for s in rest[rest.index("mixer") + 1:]
                          if s in MAMBA]
                return (inside[0] if inside else "mamba_rest"), False
            if "mixer" in rest and mixer == "gmu":
                return ("gmu" if "gmu" in rest else "gmu_rest"), False
            break
    return "other", False


def nested_in_loops(text: str) -> set:
    """The instructions of an optimized HLO module's text that lie inside a
    `while`: in its body or condition, or in a computation those call. An
    HLO `while` (the selective scan's loops over chunks and over a chunk's
    tokens, forward and backward) has an event of its own in the trace that
    spans its body's ops, which have theirs. Summed, a loop nested in a loop
    reads three times its time (455 ms under `scan`); the bodies' scoped ops
    alone leave out the ops in a loop that carry no scope and the loop's
    control (132.9 ms where the loops take 179.2: PERF.md section 5). So the
    outermost `while` is the loop's time and what is nested is left out. (A
    `conditional`'s branches would need the same; this step has none.)"""
    where, calls, inside, here = {}, {}, set(), None
    for line in text.splitlines():
        if line[:1] not in (" ", "\t"):
            head = HEADER.match(line)
            here = head.group(1) if head else None
            continue
        name = scope_reduce.NAME.match(line)
        if name is None or here is None:
            continue
        where[name.group(1)] = here
        called = CALLED.findall(line)
        calls.setdefault(here, set()).update(called)
        if " body=" in line and " condition=" in line:      # a `while`
            inside.update(called)
    todo = list(inside)
    while todo:
        for c in calls.get(todo.pop(), ()):
            if c not in inside:
                inside.add(c)
                todo.append(c)
    return {name for name, comp in where.items() if comp in inside}


def per_step_ms(run) -> Optional[dict]:
    """Device milliseconds per step by scope on the busiest chip, with
    `diff` beside them, or None: no trace, a program without this step or
    with nothing under these scopes, or a text that is not the executable
    that ran."""
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
        run.row(f"hybrid scopes: {100.0 * absent:.2f}% of the traced op time "
                "is under names the recompiled step's HLO text does not "
                "have: not the executable that ran; nothing is reported")
        return None
    mixers = lm_sambay_flops.layer_mixers(run.config["model"])
    names = dict(scope_reduce.INSTRUCTION.findall(text))
    nested = nested_in_loops(text)
    secs = {s: 0.0 for s in SCOPES + ("diff",)}
    scopes = {}
    left_out = 0.0
    for name, (_count, s) in ops.items():
        if name in nested:      # its outermost loop's event spans it
            left_out += s
            continue
        scopes[name], diff = scope_of(names.get(name, ""), mixers)
        secs[scopes[name]] += s
        if diff:
            secs["diff"] += s
    if not any(secs[s] for s in MAMBA + ("gmu",) + ATTN):
        return None             # none of this model's scopes in the program
    ms = run.prepared[KEY] = {k: v / steps * 1e3 for k, v in secs.items()}
    run.row("device ms per step inside the hybrid layers' first halves (op "
            "time summed, the busiest chip; `diff` is counted under its "
            "layer's attn too): " + "  ".join(
                f"{k} {v:.3f}" for k, v in ms.items())
            + f"  (a loop is its outermost `while` instruction's event; the "
            f"events of what is nested in loops, {left_out / steps * 1e3:.3f}"
            " ms, are left out: those events span them)")
    for label, which in (("mamba mixers", MAMBA_ALL),
                         ("attention layers", ATTN),
                         ("gated memory units", ("gmu", "gmu_rest"))):
        heavy = sorted(((s, n) for n, (_c, s) in ops.items()
                        if scopes.get(n) in which), reverse=True)[:12]
        run.row(f"heaviest ops of the {label}, ms per step (all layers' "
                "calls of the op together): " + "  ".join(
                    f"{n} [{scopes[n]}] {s / steps * 1e3:.3f}"
                    for s, n in heavy))
    return ms
