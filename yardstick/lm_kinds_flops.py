"""Operations of one train step of one rank's share of a language model whose
layers differ in kind, computed from shapes alone: what `lm_flops.py` is for
a uniform stack, for a configuration's `model` block (the fields of
tpu_mpi's `TransformerConfig`) with `attn_windows`, `ffn_kinds`, grouped
key/value heads, a head width of its own, a shared expert and
`experts_held`. No JAX here, so the tests pin every count on a hand count.

Matrix-multiply FLOPs, forward and backward (backward = 2 x forward);
recomputation in the backward pass is not counted. Two counts of attention's
score products (QK and PV) stand side by side and are not to be mixed:

- the **model's** (`flops_per_step`, what `train_mfu` divides): the full
  seq x seq matrix under the causal mask for a full layer, seq x window for
  a window layer, as `lm_flops.py` counts a causal model's;
- the **kernel's as executed** (`attn_kernel_flops`, what the two attention
  rooflines divide): only the pairs of (query block, key block) that the
  fused kernel visits at its block size, each computed whole, 2 products of
  2 x bq x bk x head_dim forward and 5 backward (the scores again, dv, dp,
  dk, dq). A pair on the diagonal or on the window's edge counts whole: the
  MXU multiplies the masked scores too.

Routed experts count the rows that land on the held experts, which the
caller reads from the program's counter (`held_rows`); for a planning count
before any run, tokens x experts_per_tok x held / n_experts."""

from __future__ import annotations

from typing import Mapping, Optional


def layer_kinds(model: Mapping) -> list:
    """[(window, sparse)] a layer."""
    n = int(model["n_layers"])
    windows = list(model.get("attn_windows") or [0] * n)
    ffn = list(model.get("ffn_kinds") or
               ["sparse" if model.get("n_experts") else "dense"] * n)
    return [(int(w), f == "sparse") for w, f in zip(windows, ffn)]


def visited_pairs(t: int, bq: int, bk: int, window: int) -> int:
    """Pairs of (query block, key block) of one head that hold a visible
    (query, key): key <= query, and under a window query - key < window."""
    pairs = 0
    for qi in range(t // bq):
        for ki in range(t // bk):
            seen = ki * bk <= qi * bq + (bq - 1)
            if window:
                seen = seen and ki * bk + (bk - 1) >= qi * bq - (window - 1)
            pairs += bool(seen)
    return pairs


def attn_kernel_flops(model: Mapping, batch: int, seq: int, window: int,
                      blocks: tuple) -> dict:
    """{"fwd", "bwd"}: the fused kernel's products as executed in ONE layer
    of that window, over all query heads, at `blocks` = (query block, key
    block)."""
    bq, bk = blocks
    dh = int(model.get("d_head") or
             int(model["d_model"]) // int(model["n_heads"]))
    pair = 2.0 * bq * bk * dh
    n = int(batch) * int(model["n_heads"]) * visited_pairs(seq, bq, bk, window)
    return {"fwd": 2 * pair * n, "bwd": 5 * pair * n}


def held_expert_flops(model: Mapping, rows: float) -> float:
    """`rows` rows through the routed experts' three matrices, forward and
    backward: 3 x 2 x rows x 3 x d_model x d_ff."""
    return 3.0 * 2 * rows * 3 * int(model["d_model"]) * int(model["d_ff"])


def flops_per_step(model: Mapping, batch: int, seq: int,
                   held_rows: Optional[float] = None) -> float:
    """The model's FLOPs of this rank's share: what its tokens need through
    the parameters that are here. `held_rows`: the rows a sparse layer's
    held experts compute (one number, the layers' mean); None: balanced."""
    b, t = int(batch), int(seq)
    d, v = int(model["d_model"]), int(model["vocab"])
    h = int(model["n_heads"])
    dh = int(model.get("d_head") or d // h)
    hk = int(model.get("n_kv_heads") or h)
    f = int(model["d_ff"])
    tokens = b * t
    if held_rows is None:
        first_count = model.get("experts_held") or (0, model.get("n_experts", 0))
        held_rows = tokens * int(model.get("experts_per_tok", 1)) * \
            int(first_count[1]) / max(1, int(model.get("n_experts", 0)))
    fwd = 2.0 * tokens * d * v                      # the head
    for window, sparse in layer_kinds(model):
        keys = min(window, t) if window else t
        fwd += (2.0 * tokens * d * (h + 2 * hk) * dh        # q, k, v
                + 2 * 2.0 * b * t * keys * h * dh           # scores + pv
                + 2.0 * tokens * h * dh * d)                # output projection
        if sparse:
            fwd += 2.0 * tokens * d * int(model["n_experts"])      # router
            fwd += held_expert_flops(model, held_rows) / 3.0
            fwd += 2.0 * tokens * 3 * d * f * int(
                model.get("n_shared_experts", 0))
        else:
            wide = int(model.get("d_ff_dense") or f) if \
                model.get("n_experts") else f
            fwd += 2.0 * tokens * (3 if model.get("dense_gated") else 2) \
                * d * wide
    return 3.0 * fwd
