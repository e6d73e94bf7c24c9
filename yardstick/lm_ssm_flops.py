"""Operations and bytes of one train step of a language model with
state-space (Mamba-2) layers among its attention layers, computed from
shapes alone: what `lm_kinds_flops.py` is for a stack of attention kinds, for
a configuration's `model` block (the fields of tpu_mpi's `TransformerConfig`)
with `mixer_kinds` and the `ssm_*` sizes. No JAX here, so the tests pin
every count on a hand count.

`flops_per_step` is the **model's** count, what `train_mfu` divides:
matrix-multiply FLOPs, forward and backward (backward = 2 x forward);
recomputation in the backward pass is not counted. An attention layer's
scores count under the causal mask (seq x (seq + 1) / 2 pairs a head). A
state-space layer's scan counts **as the recurrence**, whatever form the
program gives it: a token and head's two products with its [head width x
state] state, the update `dt x B^T` and the read `S C`, 2 x 2 x head width x
state. The chunked form the program runs does about twice that, in
products of other shapes; that is its business and no model FLOP. The
decay's multiplication, the convolution's four taps, the norms and the
activations are elementwise and are not counted, as nowhere else.

`scan_least_bytes` is the scan's least traffic with HBM, what
`ssm_scan_roofline` divides: forward x, B, C, dt in and y out, once;
backward those again, dy in, and dx, dB, dC, ddt out, once; every array at
the model's dtype (dt as the in-projection gives it). No implementation can
move less, so the share reads the same work whether XLA's fusions or a
kernel do it, and cannot pass 100."""

from __future__ import annotations

from typing import Mapping


def layer_mixers(model: Mapping) -> list:
    """"attention" | "ssm" a layer."""
    n = int(model["n_layers"])
    return list(model.get("mixer_kinds") or ["attention"] * n)


def widths(model: Mapping) -> tuple:
    """(inner, heads, head width, state) of a state-space layer."""
    h, p = int(model["ssm_heads"]), int(model["ssm_head_dim"])
    return h * p, h, p, int(model["ssm_state"])


def mixer_matrix_params(model: Mapping, mixer: str) -> int:
    """The parameters of a mixer's matrices (what a token multiplies)."""
    d = int(model["d_model"])
    if mixer == "ssm":
        inner, h, _p, n = widths(model)
        return d * (2 * inner + 2 * n + h) + inner * d
    heads = int(model["n_heads"])
    dh = int(model.get("d_head") or d // heads)
    kv = int(model.get("n_kv_heads") or heads)
    return d * (heads + 2 * kv) * dh + heads * dh * d


def params_count(model: Mapping) -> int:
    """Every parameter of the model, norms, biases and the recurrence's
    scalars included (a dense gated FFN a layer, a tied or untied head)."""
    d, f = int(model["d_model"]), int(model["d_ff"])
    total = int(model["vocab"]) * d * (1 if model.get("tie_embeddings", True)
                                       else 2) + d
    for mixer in layer_mixers(model):
        total += mixer_matrix_params(model, mixer) + 2 * d \
            + (3 if model.get("dense_gated") else 2) * d * f
        if mixer == "ssm":
            inner, h, _p, n = widths(model)
            channels = inner + 2 * n
            total += int(model.get("ssm_conv", 4)) * channels + channels \
                + 3 * h + inner
    return total


def flops_per_step(model: Mapping, batch: int, seq: int) -> float:
    b, t = int(batch), int(seq)
    tokens = b * t
    d, f = int(model["d_model"]), int(model["d_ff"])
    fwd = 2.0 * tokens * d * int(model["vocab"])                # the head
    for mixer in layer_mixers(model):
        fwd += 2.0 * tokens * mixer_matrix_params(model, mixer)
        if mixer == "ssm":
            _inner, h, p, n = widths(model)
            fwd += 2 * 2.0 * tokens * h * p * n                 # update + read
        else:
            heads = int(model["n_heads"])
            dh = int(model.get("d_head") or d // heads)
            fwd += 2 * 2.0 * b * heads * dh * (t * (t + 1) // 2)
        fwd += 2.0 * tokens * (3 if model.get("dense_gated") else 2) * d * f
    return 3.0 * fwd


def scan_least_bytes(model: Mapping, batch: int, seq: int,
                     itemsize: int = 2) -> dict:
    """{"fwd", "bwd"}: bytes ONE state-space layer's scan must move."""
    inner, h, _p, n = widths(model)
    tokens = int(batch) * int(seq)
    inputs = inner + 2 * n + h                      # x, B, C, dt a token
    return {"fwd": itemsize * tokens * (inputs + inner),
            "bwd": itemsize * tokens * (inputs + inner + inputs)}
