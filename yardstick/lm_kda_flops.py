"""Operations and bytes of one train step of one rank's share of a language
model whose layers are linear attention with a delta rule whose decay is a
vector a key channel ("kda") or positionless latent attention, the first
before a dense gated FFN and the others before routed experts beside a
shared one, computed from shapes alone: what `lm_gdn_flops.py` is for a
scalar decay, for a configuration's `model` block (the fields of tpu_mpi's
`TransformerConfig`) with `mixer_kinds`, `ffn_kinds`, the `gdn_*` sizes,
`kda_rank` and latent attention without a query latent. No JAX here, so the
tests pin every count on a hand count. The latent layers' scores and the
fused kernel's products as executed are `lm_latent_flops.py`'s, the routed
experts' rows `lm_kinds_flops.py`'s, imported.

`flops_per_step` is the **model's** count, what `train_mfu` divides:
matrix-multiply FLOPs, forward and backward (backward = 2 x forward);
recomputation in the backward pass is not counted. Every matrix counts once
a token (both halves of each low-rank map). A latent layer's scores count
the pairs under the causal mask, t x (t + 1) / 2 a head, 192 wide, and
their values 128 wide. A kda layer's scan counts **as the recurrence**,
whatever form the program gives it: a token and head's three products with
its [key width x value width] state, what the state says of the key, the
write and the read, 3 x 2 x key width x value width. The decay's
multiplication (a vector here), the convolution's taps, the norms, the gates
and the activations are elementwise and are not counted, as nowhere else.
Routed experts count the rows that land on the held experts (`held_rows`,
from the program's counter; None: balanced).

Two counts of the scan ALONE stand beside that, for `kda_scan_roofline`,
both written for the mathematics of the chunked form at the model's chunk
and not for the form the program runs today, so that a later kernel is read
against the same work:

- `scan_chunked_flops`: a chunk of L tokens and a head needs its two
  decayed [L x L] forms (of k and of q against k: L x L x key width each,
  however the decay is split into factors), the unit lower-triangular system
  solved for `beta V` and `beta (K o exp Gamma)` by substitution (the
  strictly lower triangle times value width + key width columns), W S, K^T
  U and Q S (L x key width x value width each) and the masked scores times
  U (L x L x value width); masked products count whole, as the MXU computes
  them. Backward = 2 x forward. An explicit inverse, rounds that multiply
  zeros, and everything computed again in the backward pass are the form's
  business and no such FLOP.
- `scan_least_bytes`: forward q, k, v, g, beta in and o out, once; backward
  those again, do in, and dq, dk, dv, dg, dbeta out, once; q, k, v, o at
  the model's dtype, beta float32 and **g float32 [tokens x heads x key
  width]**, a quarter of the forward bytes. No state, no [chunk x chunk]
  array, nothing twice."""

from __future__ import annotations

from typing import Mapping, Optional

from yardstick import lm_kinds_flops, lm_latent_flops
from yardstick.lm_gdn_flops import gdn_sizes, held_experts


def layer_mixers(model: Mapping) -> list:
    """"kda" | "latent" a layer."""
    return ["kda" if m == "kda" else "latent" for m in model["mixer_kinds"]]


def sparse_layers(model: Mapping) -> int:
    return sum(sparse for _w, sparse in lm_kinds_flops.layer_kinds(model))


def mixer_matrix_params(model: Mapping, mixer: str) -> int:
    """The parameters of a mixer's matrices (what a token multiplies)."""
    d = int(model["d_model"])
    if mixer == "kda":
        hk, dk, hv, dv = gdn_sizes(model)
        r = int(model["kda_rank"])
        return d * (2 * hk * dk + hv * dv) + d * (2 * r + hv) \
            + r * (hv * dk + hv * dv) + hv * dv * d
    h = int(model["n_heads"])
    dh, dr, dv = lm_latent_flops.widths(model)
    ckv = int(model["kv_latent"])
    return d * h * (dh + dr) + d * (ckv + dr) + ckv * h * (dh + dv) \
        + h * dv * d


def mixer_other_params(model: Mapping, mixer: str) -> int:
    """A mixer's convolution taps, the recurrence's leaves and its norms."""
    if mixer == "kda":
        hk, dk, hv, dv = gdn_sizes(model)
        return int(model.get("gdn_conv", 4)) * (2 * hk * dk + hv * dv) \
            + hv * dk + hv + dv         # dt_bias, a_log, the output norm
    return int(model["kv_latent"])      # the latent's norm


def ffn_matrix_params(model: Mapping, sparse: bool) -> int:
    """A layer's second half as it is HERE: the dense gated FFN, or the
    router, the held experts and the shared expert."""
    d, f = int(model["d_model"]), int(model["d_ff"])
    if not sparse:
        return 3 * d * int(model["d_ff_dense"])
    return d * int(model["n_experts"]) + held_experts(model) * 3 * d * f \
        + int(model.get("n_shared_experts", 0)) * 3 * d * f


def params_count(model: Mapping) -> int:
    """Every parameter that is here (two norms a layer, an untied head, the
    final norm)."""
    d = int(model["d_model"])
    total = int(model["vocab"]) * d * (1 if model.get("tie_embeddings", True)
                                       else 2) + d
    for mixer, (_w, sparse) in zip(layer_mixers(model),
                                   lm_kinds_flops.layer_kinds(model)):
        total += mixer_matrix_params(model, mixer) \
            + mixer_other_params(model, mixer) \
            + ffn_matrix_params(model, sparse) + 2 * d
    return total


def flops_by_part(model: Mapping, batch: int, seq: int,
                  held_rows: Optional[float] = None) -> dict:
    """Forward matrix FLOPs of one step by part, all layers of a kind
    together: `kda_matrices`, `kda_scan` (as the recurrence),
    `latent_matrices`, `latent_scores`, `dense_ffn`, `router`, `shared`,
    `held_experts`, `head`."""
    b, t = int(batch), int(seq)
    tokens = b * t
    d, f = int(model["d_model"]), int(model["d_ff"])
    if held_rows is None:
        held_rows = tokens * int(model["experts_per_tok"]) \
            * held_experts(model) / int(model["n_experts"])
    mixers = layer_mixers(model)
    n_kda, n_latent = mixers.count("kda"), mixers.count("latent")
    n_sparse = sparse_layers(model)
    _hk, dk, hv, dv = gdn_sizes(model)
    return {
        "kda_matrices": n_kda * 2.0 * tokens
        * mixer_matrix_params(model, "kda"),
        "kda_scan": n_kda * 3 * 2.0 * tokens * hv * dk * dv,
        "latent_matrices": n_latent * 2.0 * tokens
        * mixer_matrix_params(model, "latent"),
        "latent_scores": n_latent
        * lm_latent_flops.attn_score_flops(model, b, t),
        "dense_ffn": (len(mixers) - n_sparse) * 2.0 * tokens * 3 * d
        * int(model["d_ff_dense"]),
        "router": n_sparse * 2.0 * tokens * d * int(model["n_experts"]),
        "shared": n_sparse * 2.0 * tokens * 3 * d * f
        * int(model.get("n_shared_experts", 0)),
        "held_experts": n_sparse
        * lm_kinds_flops.held_expert_flops(model, held_rows) / 3.0,
        "head": 2.0 * tokens * d * int(model["vocab"]),
    }


def flops_per_step(model: Mapping, batch: int, seq: int,
                   held_rows: Optional[float] = None) -> float:
    return 3.0 * sum(flops_by_part(model, batch, seq, held_rows).values())


def scan_chunked_flops(model: Mapping, batch: int, seq: int) -> dict:
    """{"fwd", "bwd"}: the matrix FLOPs ONE kda layer's scan needs in its
    chunked form at the model's chunk."""
    _hk, dk, hv, dv = gdn_sizes(model)
    length = int(model.get("gdn_chunk", 64))
    chunks = int(batch) * -(-int(seq) // length)
    lower = length * (length - 1) // 2
    by_head = 2 * 2.0 * length * length * dk \
        + 2.0 * lower * (dk + dv) \
        + 3 * 2.0 * length * dk * dv + 2.0 * length * length * dv
    fwd = chunks * hv * by_head
    return {"fwd": fwd, "bwd": 2 * fwd}


def scan_least_bytes(model: Mapping, batch: int, seq: int,
                     itemsize: int = 2) -> dict:
    """{"fwd", "bwd"}: bytes ONE kda layer's scan must move."""
    hk, dk, hv, dv = gdn_sizes(model)
    tokens = int(batch) * int(seq)
    inputs = itemsize * (2 * hk * dk + hv * dv) + 4 * hv * dk + 4 * hv
    out = itemsize * hv * dv
    return {"fwd": tokens * (inputs + out),
            "bwd": tokens * (inputs + out + inputs)}
