"""Device time of a train step with KDA layers, by the scopes inside those
layers' first halves: what
`gdn_scope_reduce.py` does for a scalar decay's stack, for a step built by
`generators/lm_kda_train_step.py`. `scope_reduce`'s, `kinds_scope_reduce`'s
and `sambay_scope_reduce`'s parsing is imported, not copied (the last one's
`nested_in_loops` too: the scan's state runs through a `while` a chunk,
forward and backward), and the step's HLO text is
`moe_scope_reduce.step_hlo_text`'s (compiled again after the window, past
the persistent cache, and checked against the traced names).

Scopes (tpu_mpi/models/transformer.py:_kda_mixer, _attn_ffn_block), by the
layer's kind in the configuration's `model` block: under `layer_<i>/mixer` of a kda layer `in_proj` (the products that give q,
k, v and the low-rank maps' inputs and b), `conv` (the causal convolution
and silu), `prep` (the cut into heads, the L2 norms of q and k, beta),
`decay` (the low-rank map's second product, softplus and x -exp(a_log): the
[tokens x heads x key width] float32 decay, forward and again in the
backward pass), `scan` (`parallel/delta.py:delta_scan`: the decay sums, the
decayed products by halves, the triangular system's inverse, the chunk
products, the state's chain over the chunks, everything computed again for
the backward pass, and that pass; a loop is counted once, by its outermost
`while` instruction's own event), `gate_norm`, `out_proj`, and `kda_rest`
for what lies under `mixer` and none of them (the norm before it, the
residual's add). The latent layers' `layer_<i>/attn` is read by
`latent_scope_reduce.py` as it stands, not here. A transposing copy of a
parameter carries the parameter's own name and goes to the scope that uses
the leaf. Every other op is `other` (the latent layers, the FFN halves,
embedding, head and optimizer, which other reducers read): a program without these
scopes (the parent of the PR that added them) has nothing under them and
the readers report nothing."""

from __future__ import annotations

from typing import Optional

from yardstick import (kinds_scope_reduce, lm_kda_flops, moe_scope_reduce,
                       sambay_scope_reduce, scope_reduce)

KEY = "kda_scope_reduce"
KDA = ("in_proj", "conv", "prep", "decay", "scan", "gate_norm", "out_proj")
KDA_ALL = KDA + ("kda_rest",)
SCOPES = KDA_ALL + ("other",)
LEAF = {"w_kda_in": "in_proj", "w_kda_low": "in_proj", "conv_w": "conv",
        "w_kda_f": "decay", "a_log": "decay", "dt_bias": "decay",
        "w_kda_g": "gate_norm", "kda_norm": "gate_norm",
        "w_kda_out": "out_proj", "ln1": "kda_rest"}


def scope_of(op_name: str, mixers: list) -> str:
    """The scope of an op by its `op_name`; `mixers` =
    lm_kda_flops.layer_mixers a layer."""
    leaf = kinds_scope_reduce.PARAMETER.match(op_name)
    if leaf:
        _top, i, name = leaf.groups()
        if name is None:
            return "other"
        return LEAF.get(name, "other") if mixers[int(i)] == "kda" else "other"
    parts = scope_reduce.WRAPPERS.sub("", op_name).replace(")", "").split("/")
    for n, p in enumerate(parts):
        if p.startswith("layer_") and p[6:].isdigit():
            rest = parts[n + 1:]
            if "mixer" in rest and mixers[int(p[6:])] == "kda":
                inside = [s for s in rest[rest.index("mixer") + 1:]
                          if s in KDA]
                return inside[0] if inside else "kda_rest"
            break
    return "other"


def per_step(run) -> Optional[dict]:
    """{"ms": device milliseconds per step by scope on the busiest chip}, or
    None: no trace, a program without this step or with nothing under a kda
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
        run.row(f"kda scopes: {100.0 * absent:.2f}% of the traced op time "
                "is under names the recompiled step's HLO text does not "
                "have: not the executable that ran; nothing is reported")
        return None
    mixers = lm_kda_flops.layer_mixers(run.config["model"])
    names = dict(scope_reduce.INSTRUCTION.findall(text))
    nested = sambay_scope_reduce.nested_in_loops(text)
    secs = {s: 0.0 for s in SCOPES}
    scopes = {}
    left_out = 0.0
    for name, (count, s) in ops.items():
        if name in nested:      # its outermost loop's event spans it
            left_out += s
            continue
        scopes[name] = scope = scope_of(names.get(name, ""), mixers)
        secs[scope] += s
    if not any(secs[s] for s in KDA):
        return None             # none of a kda layer's scopes in the program
    ms = {k: v / steps * 1e3 for k, v in secs.items()}
    out = run.prepared[KEY] = {"ms": ms}
    run.row("device ms per step inside the kda layers' first halves (op "
            "time summed, the busiest chip): " + "  ".join(
                f"{k} {v:.3f}" for k, v in ms.items())
            + "  (a loop is its outermost `while` instruction's event; the "
            f"events of what is nested in loops, {left_out / steps * 1e3:.3f}"
            " ms, are left out: those events span them)")
    heavy = sorted(((s, n) for n, (_c, s) in ops.items()
                    if scopes.get(n) in KDA_ALL), reverse=True)[:16]
    run.row("heaviest ops of the kda mixers, ms per step (all layers' calls "
            "of the op together): " + "  ".join(
                f"{n} [{scopes[n]}] {s / steps * 1e3:.3f}" for s, n in heavy))
    return out


def per_step_ms(run) -> Optional[dict]:
    out = per_step(run)
    return None if out is None else out["ms"]
