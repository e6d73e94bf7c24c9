"""Device time of a train step with delta-rule layers, by the scopes inside
its layers' first halves: what `sambay_scope_reduce.py` does for a
decoder-hybrid-decoder stack, for a step built by
`generators/lm_gdn_train_step.py`. `scope_reduce`'s, `kinds_scope_reduce`'s
and `sambay_scope_reduce`'s parsing is imported, not copied (the last one's
`nested_in_loops` too: the delta scan's state runs through a `while` a
chunk, forward and backward), and the step's HLO text is
`moe_scope_reduce.step_hlo_text`'s (compiled again after the window, past
the persistent cache, and checked against the traced names).

Scopes (tpu_mpi/models/transformer.py:_gdn_mixer, _attn, _attn_ffn_block),
by the layer's kind in the configuration's `model` block: under
`layer_<i>/mixer` of a delta-rule layer `in_proj` (the products that give q,
k, v, z and b, a), `conv` (the causal convolution and silu), `prep` (the cut
into heads, the L2 norms of q and k, beta and g), `scan`
(`parallel/delta.py:delta_scan`: the decay sums, the triangular system's
inverse, the chunk products, the state's chain over the chunks, everything
computed again for the backward pass, and that pass; a loop is counted once,
by its outermost `while` instruction's own event), `gate_norm`, `out_proj`,
and `gdn_rest` for what lies under `mixer` and none of them (the norm before
it, the residual's add); under `layer_<i>/attn` of an attention layer
everything as `attn`, and beside that, counted a second time, `gate_extra`:
what lies under `attn/qk_norm` (the norms of each head's q and k and the
rotation of a head's first values) and `attn/out_gate` (the sigmoid gate on
the attention's output): what this attention adds around the kernel's
calls. A transposing copy of a parameter carries the parameter's own name and
goes to the scope that uses the leaf. Every other op is `other` (the expert
halves, embedding, head and optimizer, which `kinds_scope_reduce` reads): a
program without these scopes (the parent of the PR that added them) has
nothing under them and the readers report nothing."""

from __future__ import annotations

from typing import Optional

from yardstick import (kinds_scope_reduce, lm_gdn_flops, moe_scope_reduce,
                       sambay_scope_reduce, scope_reduce)

KEY = "gdn_scope_reduce"
GDN = ("in_proj", "conv", "prep", "scan", "gate_norm", "out_proj")
GDN_ALL = GDN + ("gdn_rest",)
SCOPES = GDN_ALL + ("attn", "other")
EXTRA = ("qk_norm", "out_gate")
LEAF = {"w_gdn_in": "in_proj", "w_gdn_ba": "in_proj", "conv_w": "conv",
        "a_log": "prep", "dt_bias": "prep", "gdn_norm": "gate_norm",
        "w_gdn_out": "out_proj", "ln1": "gdn_rest"}


def scope_of(op_name: str, mixers: list) -> tuple:
    """(the scope of an op by its `op_name`, whether it lies under
    `attn/qk_norm` or `attn/out_gate`); `mixers` =
    lm_gdn_flops.layer_mixers a layer."""
    leaf = kinds_scope_reduce.PARAMETER.match(op_name)
    if leaf:
        _top, i, name = leaf.groups()
        if name is None:
            return "other", False
        if mixers[int(i)] == "gdn":
            return LEAF.get(name, "other"), False
        return ("attn" if name in kinds_scope_reduce.ATTN_LEAVES
                else "other"), False
    parts = scope_reduce.WRAPPERS.sub("", op_name).replace(")", "").split("/")
    for n, p in enumerate(parts):
        if p.startswith("layer_") and p[6:].isdigit():
            rest, mixer = parts[n + 1:], mixers[int(p[6:])]
            if "attn" in rest and mixer == "full":
                inside = rest[rest.index("attn") + 1:]
                return "attn", any(s in inside for s in EXTRA)
            if "mixer" in rest and mixer == "gdn":
                inside = [s for s in rest[rest.index("mixer") + 1:]
                          if s in GDN]
                return (inside[0] if inside else "gdn_rest"), False
            break
    return "other", False


def per_step_ms(run) -> Optional[dict]:
    """Device milliseconds per step by scope on the busiest chip, with
    `gate_extra` beside them, or None: no trace, a program without this step
    or with nothing under these scopes, or a text that is not the executable
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
        run.row(f"delta scopes: {100.0 * absent:.2f}% of the traced op time "
                "is under names the recompiled step's HLO text does not "
                "have: not the executable that ran; nothing is reported")
        return None
    mixers = lm_gdn_flops.layer_mixers(run.config["model"])
    names = dict(scope_reduce.INSTRUCTION.findall(text))
    nested = sambay_scope_reduce.nested_in_loops(text)
    secs = {s: 0.0 for s in SCOPES + ("gate_extra",)}
    scopes = {}
    left_out = 0.0
    for name, (_count, s) in ops.items():
        if name in nested:      # its outermost loop's event spans it
            left_out += s
            continue
        scopes[name], extra = scope_of(names.get(name, ""), mixers)
        secs[scopes[name]] += s
        if extra:
            secs["gate_extra"] += s
    if not any(secs[s] for s in GDN):
        return None             # none of a delta layer's scopes in the program
    ms = run.prepared[KEY] = {k: v / steps * 1e3 for k, v in secs.items()}
    run.row("device ms per step inside the delta-rule and attention layers' "
            "first halves (op time summed, the busiest chip; `gate_extra` is "
            "counted under `attn` too): " + "  ".join(
                f"{k} {v:.3f}" for k, v in ms.items())
            + f"  (a loop is its outermost `while` instruction's event; the "
            f"events of what is nested in loops, {left_out / steps * 1e3:.3f}"
            " ms, are left out: those events span them)")
    for label, which in (("delta-rule mixers", GDN_ALL),
                         ("attention layers", ("attn",))):
        heavy = sorted(((s, n) for n, (_c, s) in ops.items()
                        if scopes.get(n) in which), reverse=True)[:16]
        run.row(f"heaviest ops of the {label}, ms per step (all layers' "
                "calls of the op together): " + "  ".join(
                    f"{n} [{scopes[n]}] {s / steps * 1e3:.3f}"
                    for s, n in heavy))
    return ms
