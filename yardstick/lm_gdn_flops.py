"""Operations and bytes of one train step of one rank's share of a language
model whose layers are linear attention with a gated delta rule ("gdn") or
gated softmax attention, each before routed experts beside a gated shared
expert, computed from shapes alone: what `lm_kinds_flops.py` is for a stack
of attention kinds, for a configuration's `model` block (the fields of
tpu_mpi's `TransformerConfig`) with `mixer_kinds` and the `gdn_*` sizes. No
JAX here, so the tests pin every count on a hand count.

`flops_per_step` is the **model's** count, what `train_mfu` divides:
matrix-multiply FLOPs, forward and backward (backward = 2 x forward);
recomputation in the backward pass is not counted. Every matrix counts once
a token (an attention layer's `w_q` is twice as wide: its gate). An
attention layer's scores count as `lm_kinds_flops.py` counts a causal
model's: the full seq x seq matrix. A delta-rule layer's scan counts **as the
recurrence**, whatever form the program gives it: a token and value head's
three products with its [key width x value width] state, what the state says
of the key, the write and the read, 3 x 2 x key width x value width. The
decay's multiplication, the convolution's taps, the norms, the gates and the
activations are elementwise and are not counted, as nowhere else. Routed
experts count the rows that land on the held experts (`held_rows`, from the
program's counter; None: balanced).

Two counts of the scan ALONE stand beside that, for `gdn_scan_roofline`, both
written for the mathematics of the chunked form at the model's chunk and not
for the form the program runs today, so that a later kernel is read against
the same work:

- `scan_chunked_flops`: a chunk of L tokens and a value head needs K K^T and
  Q K^T (L x L x key width each, once a KEY head), the unit lower-triangular
  system solved for `beta V` and `beta exp(gamma) K` by substitution (the
  strictly lower triangle times value width + key width columns), W S, K^T U
  and Q S (L x key width x value width each) and the masked scores times U
  (L x L x value width); masked products count whole, as the MXU computes
  them. Backward = 2 x forward. An explicit inverse, and everything
  computed again in the backward pass, is the form's business and no such
  FLOP.
- `scan_least_bytes`: forward q, k, v, g, beta in and o out, once; backward
  those again, do in, and dq, dk, dv, dg, dbeta out, once; q, k, v, o at
  the model's dtype, g and beta float32. No state, no [chunk x chunk] array,
  nothing twice."""

from __future__ import annotations

from typing import Mapping, Optional


def layer_mixers(model: Mapping) -> list:
    """"gdn" | "full" a layer."""
    return ["gdn" if m == "gdn" else "full" for m in model["mixer_kinds"]]


def gdn_sizes(model: Mapping) -> tuple:
    """(key heads, key width, value heads, value width) of a delta layer."""
    return (int(model["gdn_key_heads"]), int(model["gdn_key_dim"]),
            int(model["gdn_value_heads"]), int(model["gdn_value_dim"]))


def heads(model: Mapping) -> tuple:
    """(query heads, key/value heads, a head's width)."""
    h = int(model["n_heads"])
    return h, int(model["n_kv_heads"]), \
        int(model.get("d_head") or int(model["d_model"]) // h)


def held_experts(model: Mapping) -> int:
    return int((model.get("experts_held") or (0, model["n_experts"]))[1])


def mixer_matrix_params(model: Mapping, mixer: str) -> int:
    """The parameters of a mixer's matrices (what a token multiplies)."""
    d = int(model["d_model"])
    if mixer == "gdn":
        hk, dk, hv, dv = gdn_sizes(model)
        return d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d
    h, hk, dh = heads(model)
    gate = 2 if model.get("attn_out_gate") else 1
    return d * (gate * h + 2 * hk) * dh + h * dh * d


def mixer_other_params(model: Mapping, mixer: str) -> int:
    """A mixer's convolution taps, the recurrence's leaves and its norms."""
    if mixer == "gdn":
        hk, dk, hv, dv = gdn_sizes(model)
        return int(model.get("gdn_conv", 4)) * (2 * hk * dk + hv * dv) \
            + 2 * hv + dv
    return 2 * heads(model)[2]          # q_norm, k_norm


def expert_half_matrix_params(model: Mapping) -> int:
    """A layer's second half as it is HERE: the router, the held experts,
    the shared expert and its gate."""
    d, f = int(model["d_model"]), int(model["d_ff"])
    shared = int(model.get("n_shared_experts", 0))
    return d * int(model["n_experts"]) + held_experts(model) * 3 * d * f \
        + shared * 3 * d * f + (d if model.get("shared_expert_gate") else 0)


def params_count(model: Mapping) -> int:
    """Every parameter that is here (two norms a layer, an untied head, the
    final norm)."""
    d = int(model["d_model"])
    total = int(model["vocab"]) * d * (1 if model.get("tie_embeddings", True)
                                       else 2) + d
    for mixer in layer_mixers(model):
        total += mixer_matrix_params(model, mixer) \
            + mixer_other_params(model, mixer) \
            + expert_half_matrix_params(model) + 2 * d
    return total


def flops_per_step(model: Mapping, batch: int, seq: int,
                   held_rows: Optional[float] = None) -> float:
    b, t = int(batch), int(seq)
    tokens = b * t
    d, f = int(model["d_model"]), int(model["d_ff"])
    if held_rows is None:
        held_rows = tokens * int(model["experts_per_tok"]) \
            * held_experts(model) / int(model["n_experts"])
    fwd = 2.0 * tokens * d * int(model["vocab"])                # the head
    for mixer in layer_mixers(model):
        fwd += 2.0 * tokens * mixer_matrix_params(model, mixer)
        if mixer == "gdn":
            _hk, dk, hv, dv = gdn_sizes(model)
            fwd += 3 * 2.0 * tokens * hv * dk * dv      # told, write, read
        else:
            h, _hk, dh = heads(model)
            fwd += 2 * 2.0 * b * t * t * h * dh         # scores + pv
        fwd += 2.0 * tokens * d * int(model["n_experts"])           # router
        fwd += 2.0 * held_rows * 3 * d * f
        shared = int(model.get("n_shared_experts", 0))
        fwd += 2.0 * tokens * shared * 3 * d * f
        if model.get("shared_expert_gate"):
            fwd += 2.0 * tokens * d
    return 3.0 * fwd


def scan_chunked_flops(model: Mapping, batch: int, seq: int) -> dict:
    """{"fwd", "bwd"}: the matrix FLOPs ONE delta layer's scan needs in its
    chunked form at the model's chunk."""
    hk, dk, hv, dv = gdn_sizes(model)
    length = int(model.get("gdn_chunk", 64))
    chunks = int(batch) * -(-int(seq) // length)
    lower = length * (length - 1) // 2
    by_key_head = 2 * 2.0 * length * length * dk                # K K^T, Q K^T
    by_value_head = 2.0 * lower * (dk + dv) \
        + 3 * 2.0 * length * dk * dv + 2.0 * length * length * dv
    fwd = chunks * (hk * by_key_head + hv * by_value_head)
    return {"fwd": fwd, "bwd": 2 * fwd}


def scan_least_bytes(model: Mapping, batch: int, seq: int,
                     itemsize: int = 2) -> dict:
    """{"fwd", "bwd"}: bytes ONE delta layer's scan must move."""
    hk, dk, hv, dv = gdn_sizes(model)
    tokens = int(batch) * int(seq)
    inputs = itemsize * (2 * hk * dk + hv * dv) + 4 * 2 * hv    # q k v, g beta
    out = itemsize * hv * dv
    return {"fwd": tokens * (inputs + out),
            "bwd": tokens * (inputs + out + inputs)}
