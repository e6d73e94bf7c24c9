"""Operations of one train step of one rank's share of a language model with
latent attention, computed from shapes alone: what `lm_kinds_flops.py` is for
grouped-query layers, for a configuration's `model` block (the fields of
tpu_mpi's `TransformerConfig`) with `kv_latent`, `q_latent`, `d_rope`,
`d_value` and `heads_held`. No JAX here, so the tests pin every count on a
hand count. The FFN halves, the router and the head are `lm_kinds_flops`'s
counts, imported.

Matrix-multiply FLOPs, forward and backward (backward = 2 x forward);
recomputation in the backward pass is not counted. A head's scores contract
over `d_head + d_rope` values and its probabilities over `d_value`: a
(query, key) pair costs 2 x (d_head + d_rope + d_value). Two counts stand
side by side and are not to be mixed:

- the **model's** (`flops_per_step`, what `train_mfu` divides): the pairs
  under the causal mask, t x (t + 1) / 2 a head. (`lm_kinds_flops` counts
  the full t x t matrix for its full layers; this count is the lower one,
  so an MFU read from it is the more careful.)
- the **kernel's as executed** (`kernel_flops`, what
  `latent_kernel_roofline` divides): only the pairs of (query block, key
  block) that the fused kernel visits at its block size, each computed
  whole: forward the scores and the values' product; backward the scores
  again, dv, dp, dk and dq, where the three that contract or produce a
  query or key are `d_head + d_rope` wide and the two on the values' side
  `d_value`."""

from __future__ import annotations

from typing import Mapping, Optional

from yardstick import lm_kinds_flops


def heads_here(model: Mapping) -> int:
    held = model.get("heads_held")
    return int(held[1]) if held else int(model["n_heads"])


def widths(model: Mapping) -> tuple:
    """(d_head, d_rope, d_value)."""
    dh = int(model["d_head"])
    return dh, int(model["d_rope"]), int(model.get("d_value") or dh)


def attn_projection_flops(model: Mapping, tokens: int) -> float:
    """Forward: both down-projections, both up-projections of the heads
    here, and their part of the output projection."""
    d, h = int(model["d_model"]), heads_here(model)
    cq, ckv = int(model["q_latent"]), int(model["kv_latent"])
    dh, dr, dv = widths(model)
    return 2.0 * tokens * (d * cq + cq * h * (dh + dr) + d * (ckv + dr)
                           + ckv * h * (dh + dv) + h * dv * d)


def attn_score_flops(model: Mapping, batch: int, seq: int) -> float:
    """Forward: scores and the values' product over the pairs the causal
    mask leaves, of the heads here."""
    dh, dr, dv = widths(model)
    pairs = seq * (seq + 1) / 2.0
    return 2.0 * batch * heads_here(model) * pairs * (dh + dr + dv)


def kernel_flops(model: Mapping, batch: int, seq: int, blocks: tuple) -> dict:
    """{"fwd", "bwd"}: the fused kernel's products as executed in ONE layer,
    over the heads here, at `blocks` = (query block, key block)."""
    bq, bk = blocks
    dh, dr, dv = widths(model)
    n = int(batch) * heads_here(model) * lm_kinds_flops.visited_pairs(
        seq, bq, bk, 0)
    pair = 2.0 * bq * bk
    return {"fwd": pair * n * ((dh + dr) + dv),
            "bwd": pair * n * (3 * (dh + dr) + 2 * dv)}


def flops_per_step(model: Mapping, batch: int, seq: int,
                   held_rows: Optional[float] = None) -> float:
    """The model's FLOPs of this rank's share: what its tokens need through
    the parameters that are here. `held_rows`: the rows a sparse layer's
    held experts compute (one number, the layers' mean); None: balanced."""
    b, t = int(batch), int(seq)
    d, v, f = int(model["d_model"]), int(model["vocab"]), int(model["d_ff"])
    tokens = b * t
    if held_rows is None:
        held_rows = tokens * int(model["experts_per_tok"]) * \
            int(model["experts_held"][1]) / int(model["n_experts"])
    fwd = 2.0 * tokens * d * v                      # the head
    for _window, sparse in lm_kinds_flops.layer_kinds(model):
        fwd += attn_projection_flops(model, tokens) \
            + attn_score_flops(model, b, t)
        if sparse:
            fwd += 2.0 * tokens * d * int(model["n_experts"])      # router
            fwd += lm_kinds_flops.held_expert_flops(model, held_rows) / 3.0
            fwd += 2.0 * tokens * 3 * d * f * int(
                model.get("n_shared_experts", 0))
        else:
            fwd += 2.0 * tokens * 3 * d * int(model["d_ff_dense"])
    return 3.0 * fwd
