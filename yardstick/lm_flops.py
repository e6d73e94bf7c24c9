"""Operations of one train step of a language model described by a
configuration's `model` block (the fields of tpu_mpi's `TransformerConfig`),
computed from shapes alone. No JAX here, so the tests pin them on a hand
count.

Matrix-multiply FLOPs, forward and backward (backward = 2 x forward);
recomputation in the backward pass is not counted. Only the parameters a
token uses count: `experts_per_tok` of the `n_experts`, the router, QK and PV
as the model computes them (a full seq x seq matrix under a causal mask),
and a head of its own where the embedding is not tied."""

from __future__ import annotations

from typing import Mapping


def expert_flops_per_layer(model: Mapping, batch: int, seq: int) -> float:
    """The grouped multiplications of one layer's experts, forward and
    backward: tokens x experts_per_tok rows through `gate`, `in` and `out`."""
    rows = int(batch) * int(seq) * int(model.get("experts_per_tok", 1))
    return 3.0 * 2 * rows * 3 * int(model["d_model"]) * int(model["d_ff"])


def flops_per_step(model: Mapping, batch: int, seq: int) -> float:
    b, t = int(batch), int(seq)
    d, v = int(model["d_model"]), int(model["vocab"])
    attn = (2 * b * t * d * 3 * d          # q, k, v
            + 2 * 2 * b * t * t * d        # scores + pv
            + 2 * b * t * d * d)           # output projection
    if model.get("n_experts"):
        ffn = 2 * b * t * d * int(model["n_experts"]) \
            + expert_flops_per_layer(model, b, t) / 3.0
    else:
        ffn = 2 * b * t * 2 * d * int(model["d_ff"])
    fwd = int(model["n_layers"]) * (attn + ffn) + 2 * b * t * d * v
    return 3.0 * fwd
