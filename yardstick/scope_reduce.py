"""Device time of a train step by the model's named scopes.

The model marks its parts with `jax.named_scope` (tpu_mpi/models/
transformer.py): `embed`, `layer_<i>/attn`, `layer_<i>/mlp`, `head_loss`
(the head's matmul and the cross-entropy), `optimizer`. JAX writes the
scope into every HLO instruction's `op_name` metadata, forward
(`jvp(layer_3)/attn/...`) and backward (`transpose(jvp(layer_3))/attn/...`).

On the v5e the trace's `XLA Ops` events carry no `tf_op`/`long_name` stat
(looked at in PR 23: only `device_offset_ps`, `device_duration_ps`), so the
scope of an event is found through the compiled step's HLO text: the event
is named by its whole instruction, `%fusion.151 = ...`, and the text gives
`fusion.151`'s `op_name`. A fusion carries the metadata of one of the
instructions fused into it; that one names the fusion's scope. The step is
lowered and compiled again here, after the window, from shapes alone,
because the generator keeps its executable to itself; and with the
persistent compile cache set aside, because its key leaves metadata out: an
executable found there may carry the scopes of whichever checkout compiled
it first (seen in PR 23: the parent's step came back with this model's
scopes). That second compile costs every traced run of the cell about 12 s
after its window (PR 23, on the v5e). The compiler is deterministic, so the
instruction names should be those of the executable that ran; that is
checked as far as names go: where more than `MAX_ABSENT` of the traced op
time belongs to names this text does not have, the text is another
program's and nothing is reported.

A model without scopes (the parent of the PR that added them) has every
op in `(unscoped)`; the readers then report nothing."""

from __future__ import annotations

import re
from typing import Optional

SCOPES = ("embed", "attn", "mlp", "head_loss", "optimizer")
REST = "(unscoped)"
KEY = "scope_reduce"
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                         r"metadata=\{[^}]*?op_name=\"([^\"]*)\"", re.M)
NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s", re.M)
#: traced op time that may belong to names the step's HLO text lacks (ops of
#: other executables inside the interval: a readback's slice)
MAX_ABSENT = 0.01
WRAPPERS = re.compile(r"\b(?:jvp|transpose|vmap|remat|checkpoint|"
                      r"custom_jvp|custom_vjp)\(")


def scope_of(op_name: str) -> str:
    """`jit(local_step)/transpose(jvp(layer_3))/attn/mul` -> `attn`."""
    parts = WRAPPERS.sub("", op_name).replace(")", "").split("/")
    for i, p in enumerate(parts):
        if p.startswith("layer_") and i + 1 < len(parts) \
                and parts[i + 1] in ("attn", "mlp"):
            return parts[i + 1]
        if p in SCOPES:
            return p
    return REST


def scopes_of_hlo(text: str) -> dict:
    """{instruction name: scope} of an optimized HLO module's text."""
    return {name: scope_of(op) for name, op in INSTRUCTION.findall(text)}


def absent_share(op_seconds: dict, text: str) -> float:
    """The share of the traced op seconds under instruction names that the
    HLO text does not define."""
    names = set(NAME.findall(text))
    total = sum(secs for _n, secs in op_seconds.values())
    gone = sum(secs for name, (_n, secs) in op_seconds.items()
               if name not in names)
    return gone / total if total > 0.0 else 1.0


def by_scope(op_seconds: dict, scopes: dict) -> dict:
    """Seconds by scope of {instruction name: [count, seconds]}."""
    out = {s: 0.0 for s in SCOPES + (REST,)}
    for name, (_n, secs) in op_seconds.items():
        out[scopes.get(name, REST)] += secs
    return out


def step_hlo_text(run) -> str:
    """The optimized HLO of the cell's train step, compiled from shapes by
    this checkout's own model, past the persistent cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()            # the decision to use the cache is kept
    try:
        return _compile_step(run).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        cc.reset_cache()


def _compile_step(run):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tpu_mpi import xla
    from tpu_mpi.models.transformer import (TransformerConfig,
                                            transformer_init,
                                            transformer_train_step)
    cfg, tr = run.config, run.traffic
    batch, seq = int(tr["batch"]), int(tr["seq"])
    model = TransformerConfig(
        vocab=cfg["vocab"], d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_layers=cfg["n_layers"], d_ff=cfg["d_ff"], max_seq=seq,
        dtype=jnp.dtype(cfg["dtype"]))
    mesh = xla.make_mesh(dict(cfg["mesh"]), devices=run.devices)
    step, specs = transformer_train_step(model, mesh, lr=cfg["lr"])
    shapes = jax.eval_shape(lambda k: transformer_init(k, model),
                            jax.random.key(0))
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        shapes, specs)
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                               sharding=NamedSharding(mesh, P("dp", "sp")))
    return step.lower(params, tok, tok).compile()


def per_step_ms(run) -> Optional[dict]:
    """Device milliseconds per step by scope on the busiest chip, or None
    (no trace, or a model that names no scope)."""
    if KEY in run.prepared:
        return run.prepared[KEY]
    run.prepared[KEY] = None
    steps = run.traced_ops()
    if not steps:
        return None
    text = step_hlo_text(run)
    absent = absent_share(run.trace.busiest.ops, text)
    if absent > MAX_ABSENT:
        run.row(f"scopes: {100.0 * absent:.2f}% of the traced op time is "
                "under names the recompiled step's HLO text does not have: "
                "not the executable that ran; the scope readers report "
                "nothing")
        return None
    secs = by_scope(run.trace.busiest.ops, scopes_of_hlo(text))
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
    return out
