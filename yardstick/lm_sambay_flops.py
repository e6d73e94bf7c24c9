"""Operations and bytes of one train step of a decoder-hybrid-decoder
language model (Mamba-1 layers, window and full differential attention,
gated memory units and cross-attention over one layer's keys and values),
computed from shapes alone: what `lm_ssm_flops.py` is for a Mamba-2 stack,
for a configuration's `model` block (the fields of tpu_mpi's
`TransformerConfig`) with `mixer_kinds`, `attn_windows`, `memory_from`,
`kv_from` and the `ssm_*` sizes. No JAX here, so the tests pin every count on
a hand count.

`flops_per_step` is the **model's** count, what `train_mfu` divides:
matrix-multiply FLOPs, forward and backward (backward = 2 x forward);
recomputation in the backward pass is not counted. Every matrix counts once
a token. An attention layer's two softmaxes are its n_heads query heads:
each scores under its mask (full: seq x (seq + 1) / 2 pairs; window w: w x (w
+ 1) / 2 + (seq - w) x w) against a `d_head`-wide key and reads a 2 x
`d_head`-wide value, 2 x d_head + 2 x 2 x d_head a pair; a cross layer's
likewise (it has no key/value projection). A mamba layer's scan counts **as
the recurrence**, whatever form the program gives it: a token and channel's
update of its `ssm_state` values and their read, 2 x 2 x inner x state. The
decays' exponentials (one a token, channel and state index: 671 M a layer at
8192 x 5120 x 16), the convolution's taps, the norms, the subtraction of the
two softmaxes and the activations are elementwise and are not counted, as
nowhere else: which is why the scan's share of the time is no FLOP count's.

`scan_least_bytes` is the selective scan's least traffic with HBM, what
`sel_scan_roofline` divides: forward x, dt, B, C in and y out, once;
backward those again, dy in, and dx, ddt, dB, dC out, once; every array at
the model's dtype. No implementation can move less, so the share reads the
same work whether XLA's loops or a kernel do it, and cannot pass 100."""

from __future__ import annotations

from typing import Mapping

ATTENDS = ("window", "full", "cross")


def layer_mixers(model: Mapping) -> list:
    """"mamba" | "window" | "full" | "gmu" | "cross" a layer."""
    windows = list(model.get("attn_windows") or [0] * int(model["n_layers"]))
    return [("window" if w else "full") if m == "attention" else m
            for m, w in zip(model["mixer_kinds"], windows)]


def widths(model: Mapping) -> tuple:
    """(inner, state, dt rank) of a mamba layer."""
    return (int(model["ssm_expand"]) * int(model["d_model"]),
            int(model["ssm_state"]), int(model["ssm_dt_rank"]))


def heads(model: Mapping) -> tuple:
    """(query heads, key/value heads, a head's width)."""
    h = int(model["n_heads"])
    return h, int(model["n_kv_heads"]), \
        int(model.get("d_head") or int(model["d_model"]) // h)


def mixer_matrix_params(model: Mapping, mixer: str) -> int:
    """The parameters of a mixer's matrices (what a token multiplies)."""
    d = int(model["d_model"])
    inner, n, r = widths(model)
    if mixer == "mamba":
        return d * 2 * inner + inner * (r + 2 * n) + r * inner + inner * d
    if mixer == "gmu":
        return 2 * d * inner
    h, hk, dh = heads(model)
    kv = 0 if mixer == "cross" else 2 * hk * dh
    return d * (h * dh + kv) + h * dh * d


def mixer_other_params(model: Mapping, mixer: str) -> int:
    """A mixer's biases, the recurrence's leaves, lambda's four vectors and
    the pair norm."""
    inner, n, _r = widths(model)
    if mixer == "mamba":        # conv taps and bias, dt_bias, A_log, D
        return int(model.get("ssm_conv", 4)) * inner + inner + inner \
            + inner * n + inner
    if mixer == "gmu":
        return 0
    h, hk, dh = heads(model)
    d = int(model["d_model"])
    bias = (h * dh + d + (0 if mixer == "cross" else 2 * hk * dh)) \
        if model.get("attn_bias") else 0
    return bias + 4 * dh + 2 * dh


def params_count(model: Mapping) -> int:
    """Every parameter of the model (a gated FFN and two LayerNorms with
    bias a layer, a tied embedding, the final LayerNorm)."""
    d, f = int(model["d_model"]), int(model["d_ff"])
    total = int(model["vocab"]) * d + 2 * d
    for mixer in layer_mixers(model):
        total += mixer_matrix_params(model, mixer) \
            + mixer_other_params(model, mixer) + 3 * d * f + 4 * d
    return total


def score_pairs(seq: int, window: int) -> int:
    """(query, key) pairs one head scores under the causal mask."""
    t, w = int(seq), min(int(window) or int(seq), int(seq))
    return w * (w + 1) // 2 + (t - w) * w


def flops_per_step(model: Mapping, batch: int, seq: int) -> float:
    b, t = int(batch), int(seq)
    tokens = b * t
    d, f = int(model["d_model"]), int(model["d_ff"])
    inner, n, _r = widths(model)
    h, _hk, dh = heads(model)
    windows = list(model.get("attn_windows") or [0] * int(model["n_layers"]))
    fwd = 2.0 * tokens * d * int(model["vocab"])                # the head
    for mixer, window in zip(layer_mixers(model), windows):
        fwd += 2.0 * tokens * (mixer_matrix_params(model, mixer) + 3 * d * f)
        if mixer == "mamba":
            fwd += 2 * 2.0 * tokens * inner * n                 # update + read
        elif mixer in ATTENDS:
            fwd += b * h * score_pairs(t, window) * (2.0 * dh + 2.0 * 2 * dh)
    return 3.0 * fwd


def scan_least_bytes(model: Mapping, batch: int, seq: int,
                     itemsize: int = 2) -> dict:
    """{"fwd", "bwd"}: bytes ONE mamba layer's selective scan must move."""
    inner, n, _r = widths(model)
    tokens = int(batch) * int(seq)
    inputs = 2 * inner + 2 * n                      # x, dt, B, C a token
    return {"fwd": itemsize * tokens * (inputs + inner),
            "bwd": itemsize * tokens * (inputs + inner + inputs)}
