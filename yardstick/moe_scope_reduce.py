"""Device time of a sparse-expert train step by the model's named scopes:
what `scope_reduce.py` does for the flagship, for a step built by
`generators/lm_train_step.py` (scope_reduce compiles the flagship's step from
seven fixed keys and knows a closed set of scopes, and may not be edited).
Its parsing is imported, not copied.

Scopes (tpu_mpi/models/transformer.py, tpu_mpi/parallel/ep.py): `embed`,
`layer_<i>/attn`, `layer_<i>/mlp` with `router`, `dispatch`, `experts`,
`combine` inside it, `head_loss`, `aux_loss`, `optimizer`. What lies under
`mlp` outside the four (the norm before the router, the residual add) is
`mlp_rest`. The v5e's compiler replaces `lax.ragged_dot` by a kernel of its
own and names the instruction `ragged-dot-*` with no scope in its metadata
(seen in the compiled step, PR 25): such an instruction is the experts'
grouped multiplication by its name. Where the compiler re-lays a
parameter for a kernel (a transposing `copy` of a layer's `w_in`, `w_gate` or
`w_out`: 0.8 ms each for 64 experts' 268 MB), the instruction's `op_name` is
the parameter's own, `params['layers'][i]['w_out']`: it goes to the scope
that uses that leaf. The step is compiled again after the
window from this checkout's own model, past the persistent cache (its key
leaves metadata out), and checked against the traced names as
scope_reduce does."""

from __future__ import annotations

import re
from typing import Optional

from yardstick import scope_reduce

KEY = "moe_scope_reduce"
REST = scope_reduce.REST
INSIDE_MLP = ("router", "dispatch", "experts", "combine")
SCOPES = ("embed", "attn") + INSIDE_MLP + (
    "mlp_rest", "head_loss", "aux_loss", "optimizer", REST)
MOE = INSIDE_MLP + ("mlp_rest",)
TOP = ("embed", "head_loss", "aux_loss", "optimizer")
PARAMETER = re.compile(r"^params\[\W*(\w+)\W*\](?:\[\d+\]\[\W*(\w+)\W*\])?")
#: the scope that uses a leaf of transformer_init's tree, in a model with experts
LEAF = {"embed": "embed", "ln_f": "head_loss", "lm_head": "head_loss",
        "ln1": "attn", "w_qkv": "attn", "w_proj": "attn", "q_norm": "attn",
        "k_norm": "attn", "ln2": "mlp_rest", "w_router": "router",
        "w_in": "experts", "w_gate": "experts", "w_out": "experts"}


def scope_of(instruction: str, op_name: str) -> str:
    if instruction.startswith("ragged-dot"):
        return "experts"
    leaf = PARAMETER.match(op_name)
    if leaf:
        return LEAF.get(leaf.group(2) or leaf.group(1), REST)
    parts = scope_reduce.WRAPPERS.sub("", op_name).replace(")", "").split("/")
    for i, p in enumerate(parts):
        if p.startswith("layer_"):
            rest = parts[i + 1:]
            if "attn" in rest:
                return "attn"
            if "mlp" in rest:
                inside = [s for s in rest if s in INSIDE_MLP]
                return inside[0] if inside else "mlp_rest"
        if p in TOP:
            return p
    return REST


def scopes_of_hlo(text: str) -> dict:
    out = {name: scope_of(name, "") for name in scope_reduce.NAME.findall(text)}
    out.update({name: scope_of(name, op)
                for name, op in scope_reduce.INSTRUCTION.findall(text)})
    return out


def step_hlo_text(run) -> str:
    """The optimized HLO of the cell's step, compiled from shapes by this
    checkout's own model, past the persistent cache."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tpu_mpi.models.transformer import transformer_init
    model, mesh, step, specs = run.cell.generator().build(run)
    shapes = jax.eval_shape(lambda k: transformer_init(k, model),
                            jax.random.key(0))
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        shapes, specs)
    tok = jax.ShapeDtypeStruct(
        (int(run.traffic["batch"]), int(run.traffic["seq"])), jnp.int32,
        sharding=NamedSharding(mesh, P("dp", "sp")))
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()            # the decision to use the cache is kept
    try:
        return step.lower(params, tok, tok).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        cc.reset_cache()


def per_step_ms(run) -> Optional[dict]:
    """Device milliseconds per step by scope on the busiest chip, or None:
    no trace, a program without this step or these scopes (the parent of
    the PR that added them), or a text that is not the executable that ran."""
    if KEY in run.prepared:
        return run.prepared[KEY]
    run.prepared[KEY] = None
    steps = run.traced_ops()
    if not steps or not hasattr(run.cell.generator(), "build"):
        return None
    text = step_hlo_text(run)
    ops = run.trace.busiest.ops
    absent = scope_reduce.absent_share(ops, text)
    if absent > scope_reduce.MAX_ABSENT:
        run.row(f"scopes: {100.0 * absent:.2f}% of the traced op time is "
                "under names the recompiled step's HLO text does not have: "
                "not the executable that ran; the scope readers report "
                "nothing")
        return None
    scopes = scopes_of_hlo(text)
    secs = {s: 0.0 for s in SCOPES}
    for name, (_n, s) in ops.items():
        secs[scopes.get(name, REST)] += s
    total = sum(secs.values())
    if total <= 0.0 or secs[REST] >= total:
        return None
    out = {k: v / steps * 1e3 for k, v in secs.items()}
    run.prepared[KEY] = out
    run.row("device ms per step by the model's scopes (op time summed, the "
            "busiest chip): " + "  ".join(f"{k} {v:.3f}"
                                          for k, v in out.items())
            + f"  named {100.0 * (1.0 - secs[REST] / total):.2f}% of "
            f"{total / steps * 1e3:.3f} ms; {100.0 * absent:.3f}% of the "
            "op time under names the step's HLO text lacks")
    unnamed = sorted(((s, n) for n, (_c, s) in ops.items()
                      if scopes.get(n, REST) == REST), reverse=True)[:16]
    run.row("heaviest unscoped ops, ms per step: " + "  ".join(
        f"{n} {s / steps * 1e3:.3f}" for s, n in unnamed))
    return out
