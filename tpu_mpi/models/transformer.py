"""Flagship model: a GPT-style transformer trained with DP × TP × SP.

Proves the whole substrate at once (SURVEY.md §2.5 / §5): batch sharded over
'dp' (gradient psum), attention heads + FFN hidden sharded over 'tp'
(Megatron column/row-parallel with the f/g operators from
tpu_mpi.parallel.tp), sequence sharded over 'sp' with exact ring attention
(ppermute ring from tpu_mpi.parallel.ring), RoPE positions offset per
sequence shard. Everything is one shard_map-wrapped, jitted, differentiable
train step — the TPU-native shape of a program the reference's users would
write with Allreduce!/Sendrecv!/Alltoall! by hand.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import perfvars
from ..parallel.dp import allreduce_grads
from ..parallel.ep import (grouped_products, held_row_buffer, moe_dropless,
                           moe_dropless_held, rows_at)
from ..parallel.ring import (fused_attention_selected, local_attention,
                             ring_attention)
from ..parallel.tp import column_parallel, row_parallel
from ..xla import choice, head_norm_kernels
from ..xla import pallas_kernels as pk


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 256
    max_seq: int = 512
    dtype: Any = jnp.float32
    # What follows describes a public architecture's block as data; the
    # defaults are the flagship's (RMSNorm eps 1e-6, tanh-GELU MLP, tied
    # head), whose program they leave as it was.
    norm_eps: float = 1e-6
    qk_norm: bool = False       # RMSNorm over the whole q and k vectors,
    #                             learned scale, before the split into heads
    n_experts: int = 0          # > 0: each layer's FFN is n_experts gated ones,
    experts_per_tok: int = 1    # out(silu(gate(x)) * in(x)), of width d_ff; a
    #                             token's top experts_per_tok by router softmax
    #                             (float32) run it, weights not renormalised,
    #                             no token dropped
    router_aux_coef: float = 0.0    # x the load-balancing loss, added to the loss
    tie_embeddings: bool = True     # False: an `lm_head` of its own
    remat_attn: bool = False    # keep no [b, h, s, s] scores for the backward
    #                             pass: where the plain attention runs, it is
    #                             recomputed there (`jax.checkpoint`); the
    #                             fused kernel keeps none as it is
    # A stack whose layers differ, as data: one entry a layer in each list
    # (an empty list: every layer alike, as above). A layer's *kind* is what
    # its program depends on (`layer_kind`); layers of one kind share a trace.
    d_head: int = 0             # a head's width; 0: d_model // n_heads
    n_kv_heads: int = 0         # > 0: grouped-query attention with `w_q`,
    #                             `w_k`, `w_v` of their own; query head j reads
    #                             key/value head j // (n_heads / n_kv_heads).
    #                             0: n_heads of each, packed in `w_qkv`
    qk_norm_heads: bool = False     # RMSNorm of q and of k over each head's
    #                             values, one learned scale [head_dim] each
    rope_theta: float = 10000.0
    attn_windows: tuple = ()    # a layer's window: query p sees keys p - w + 1
    #                             .. p; 0: every key up to p
    rope_full_layers: bool = True   # False: a layer with window 0 rotates
    #                             nothing (positions come from its neighbours)
    ffn_kinds: tuple = ()       # "dense" | "sparse" a layer; empty: sparse
    #                             everywhere if n_experts else dense
    d_ff_dense: int = 0         # a dense layer's width beside experts; 0: d_ff
    dense_gated: bool = False   # dense FFN out(silu(gate(x)) * in(x)), not
    #                             out(gelu(in(x)))
    n_shared_experts: int = 0   # a gated FFN of width n_shared_experts x d_ff
    #                             that every token runs, beside the routed ones
    router_score: str = "softmax"   # | "sigmoid": scores of the router's
    #                             logits, float32, the top experts_per_tok win
    router_renorm: bool = False     # the chosen scores divided by their sum
    router_scale: float = 1.0   # x the weights of the routed experts' outputs
    experts_held: tuple = ()    # (first, count): this rank holds experts
    #                             [first, first + count) of the n_experts the
    #                             router scores, and computes their part of
    #                             the layer (`parallel.ep.moe_dropless_held`);
    #                             empty: all of them
    remat_layers: tuple = ()    # a layer's recomputation in the backward
    #                             pass: "" none, "ffn" its FFN half
    # Latent attention: queries and keys/values are up-projected, a head at
    # a time, from two narrow normed latents; a head's scores are the sum
    # of an unrotated product (`d_head` wide) and a rotated one (`d_rope`
    # wide) whose key is ONE vector a token that all heads share, scaled by
    # (d_head + d_rope) ** -0.5.
    kv_latent: int = 0          # > 0: the key/value latent's width; leaves
    #                             `w_dkv` [d, kv_latent + d_rope] (the latent
    #                             and the shared rotary key), `kv_latent_norm`,
    #                             `w_ukv` [kv_latent, heads x (d_head + d_value)]
    q_latent: int = 0           # the query latent's width: `w_dq`,
    #                             `q_latent_norm`, `w_uq` [q_latent, heads x
    #                             (d_head + d_rope)]; 0: no query latent, one
    #                             `w_q` [d, heads x (d_head + d_rope)]
    d_rope: int = 0             # the rotated part's width; RoPE turns it alone
    #                             (under `rope_full_layers` False nothing
    #                             turns: the part and the shared key are
    #                             scored as they stand)
    d_value: int = 0            # a value head's width; 0: head_dim
    heads_held: tuple = ()      # (first, count): this rank holds heads
    #                             [first, first + count) of n_heads and adds
    #                             their part of the output projection's sum
    #                             (latent attention: every head has its own
    #                             keys and values, and which heads these are
    #                             is the weights' business: `count` shapes the
    #                             program); empty: all of them
    norm_out: bool = False      # sandwich norm: x + RMSNorm(sublayer(
    #                             RMSNorm(x))), scales `ln1_out`, `ln2_out`
    # State-space (Mamba-2) layers among the attention ones: such a layer's
    # first half is `_ssm_mixer` (in-projection, a short causal convolution,
    # the selective scan of `parallel.ssm`, a gated norm, out-projection)
    # in place of attention; its FFN half is the model's.
    mixer_kinds: tuple = ()     # one of `MIXERS` a layer; empty: attention
    ssm_expand: int = 2         # the scan runs ssm_expand x d_model wide, =
    ssm_heads: int = 0          #   ssm_heads heads of
    ssm_head_dim: int = 0       #   ssm_head_dim each, over a state of
    ssm_state: int = 0          #   ssm_state a head's value; B and C are one
    #                             vector a token for all heads (one group)
    ssm_conv: int = 4           # the convolution's taps: a token and the
    #                             ssm_conv - 1 before it, with a bias
    ssm_chunk: int = 256        # tokens a chunk of the scan
    # A decoder-hybrid-decoder stack: beside the residual stream TWO side
    # values, each written by one layer and read by later ones. "mamba": a
    # Mamba-1 layer (`_mamba_mixer`: ssm_expand x d_model wide, ssm_state a
    # channel, ssm_conv taps, dt through a rank of ssm_dt_rank, chunks of
    # ssm_chunk), of which layer `memory_from`'s scan output, before its
    # gate, is the memory; "gmu": a gated memory unit, out(memory x
    # silu(in(x))), no mixing over the sequence of its own; "cross":
    # attention with queries of its own over layer `kv_from`'s keys and
    # values (an "attention" layer with window 0).
    ssm_dt_rank: int = 0
    memory_from: int = -1
    kv_from: int = -1
    diff_attn: bool = False     # differential attention: n_heads / 2 heads,
    #                             each the difference of two softmaxes (`_diff_attn`)
    attn_bias: bool = False     # biases `b_q`, `b_k`, `b_v`, `b_proj` of
    #                             differential attention (refused without)
    norm_kind: str = "rms"      # | "layer": LayerNorm (mean and variance), a
    #                             learned scale and a bias `<norm>_b`
    # Scalars on the residual stream, as data; 1.0 (and 0.0) add no equation.
    embed_multiplier: float = 1.0       # x the embedding's rows
    residual_multiplier: float = 1.0    # x each half's output, before the add
    logits_divisor: float = 1.0         # logits / this
    attn_scale: float = 0.0     # the scores' scale; 0: head_dim ** -0.5
    # Linear attention with a gated delta rule: a "gdn" layer's first half is
    # `_gdn_mixer` (in-projections, a short causal convolution without bias,
    # L2-normed queries and keys, the scan of `parallel.delta`, a norm a head
    # and then a gate, out-projection) in place of attention.
    gdn_key_heads: int = 0      # queries and keys: gdn_key_heads heads of
    gdn_key_dim: int = 0        #   gdn_key_dim; values and the gate:
    gdn_value_heads: int = 0    #   gdn_value_heads heads (a multiple) of
    gdn_value_dim: int = 0      #   gdn_value_dim; value head h reads key
    #                             head h // (value heads / key heads)
    gdn_conv: int = 4           # the convolution's taps over q, k and v
    gdn_chunk: int = 64         # tokens a chunk of the scan, a power of two
    # A "kda" layer is the same rule with a decay a key CHANNEL (`_kda_mixer`;
    # the gdn_* sizes are its own): the decay and the output's sigmoid gate
    # each come through a low-rank map, d_model -> kda_rank -> all heads.
    kda_rank: int = 0
    # What follows is data on the attention and expert halves; each adds no
    # equation at its default.
    attn_out_gate: bool = False     # `w_q` is twice as wide, [queries | gate]:
    #                             the attention's output x sigmoid(gate),
    #                             before the output projection
    rotary_dim: int = 0         # > 0: RoPE turns the first rotary_dim values
    #                             of a head (after its norm) and the rest pass
    norm_unit_offset: bool = False  # RMSNorm scales by 1 + w, float32 inside
    #                             and rounded once: the stream's norms and the
    #                             norms of q and k; w starts near 0, where a
    #                             bfloat16 step moves it
    shared_expert_gate: bool = False    # the shared expert's output x
    #                             sigmoid(y w_shared_sigmoid), one a token

    def __post_init__(self):
        for name in ("attn_windows", "ffn_kinds", "experts_held",
                     "remat_layers", "heads_held", "mixer_kinds"):
            value = tuple(getattr(self, name))
            object.__setattr__(self, name, value)   # a JSON list is welcome
            if value and not name.endswith("_held") \
                    and len(value) != self.n_layers:
                raise ValueError(f"{name} has {len(value)} entries for "
                                 f"{self.n_layers} layers")
        if self.kv_latent and not (self.d_rope and self.d_head):
            raise ValueError("latent attention names kv_latent, d_rope and "
                             "d_head together")
        if self.q_latent and not self.kv_latent:
            raise ValueError("q_latent is latent attention's: set kv_latent")
        if self.kv_latent and (self.n_kv_heads or self.qk_norm
                               or self.qk_norm_heads or any(self.attn_windows)):
            raise ValueError("latent attention has a key and a value a head, "
                             "norms on its latents alone and no window")
        if self.heads_held and not (
                self.kv_latent and 0 <= self.heads_held[0]
                and 0 < self.heads_held[1]
                and sum(self.heads_held) <= self.n_heads):
            raise ValueError(f"heads_held={self.heads_held}: a share "
                             f"(first, count) of n_heads={self.n_heads}, of "
                             f"latent attention's heads")
        if set(self.remat_layers) - {"", "ffn"}:
            raise ValueError(f"remat_layers={self.remat_layers}: a layer "
                             f"recomputes \"\" (nothing) or \"ffn\"")
        if self.n_kv_heads and self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_heads={self.n_heads} is no multiple of "
                             f"n_kv_heads={self.n_kv_heads}")
        if set(self.mixer_kinds) - set(MIXERS):
            raise ValueError(f"mixer_kinds={self.mixer_kinds}: a layer's "
                             f"mixer is one of {MIXERS}")
        if "ssm" in self.mixer_kinds and not (
                self.ssm_state > 0 and self.ssm_conv > 0 and self.ssm_chunk > 0
                and self.ssm_heads * self.ssm_head_dim
                == self.ssm_expand * self.d_model):
            raise ValueError(
                f"state-space layers are ssm_heads={self.ssm_heads} x "
                f"ssm_head_dim={self.ssm_head_dim} = ssm_expand="
                f"{self.ssm_expand} x d_model={self.d_model} wide, with "
                f"ssm_state, ssm_conv and ssm_chunk above 0")
        if self.attn_scale and self.kv_latent:
            raise ValueError("latent attention scales its scores by "
                             "(d_head + d_rope) ** -0.5: no attn_scale")
        if self.norm_kind not in ("rms", "layer"):
            raise ValueError(f"norm_kind={self.norm_kind!r}: \"rms\" or "
                             f"\"layer\"")
        if self.diff_attn and not (
                self.n_kv_heads and self.n_kv_heads % 2 == 0
                and self.n_heads % 2 == 0 and not self.kv_latent
                and not self.qk_norm and not self.qk_norm_heads):
            raise ValueError(
                f"differential attention pairs its heads: n_heads="
                f"{self.n_heads} and n_kv_heads={self.n_kv_heads} are even "
                f"and above 0, with no latent and no norm of q and k")
        if self.attn_bias and not self.diff_attn:
            raise ValueError("attn_bias adds its biases in differential "
                             "attention alone: set diff_attn with it")
        self._check_gdn_and_gates()
        self._check_side_values()

    def _check_gdn_and_gates(self):
        """A delta-rule layer has its sizes; a rotated share fits its head;
        the gates and the norm's offset stand where their equations do."""
        chunk = self.gdn_chunk
        if "kda" in self.mixer_kinds and self.kda_rank <= 0:
            raise ValueError("kda layers name kda_rank above 0, the width of "
                             "the decay's and the gate's low-rank maps")
        if {"gdn", "kda"} & set(self.mixer_kinds) and not (
                self.gdn_key_heads > 0 and self.gdn_key_dim > 0
                and self.gdn_value_dim > 0 and self.gdn_conv > 0
                and self.gdn_value_heads >= self.gdn_key_heads
                and self.gdn_value_heads % self.gdn_key_heads == 0
                and chunk > 0 and chunk & (chunk - 1) == 0):
            raise ValueError(
                f"delta-rule layers name gdn_key_heads={self.gdn_key_heads} of "
                f"gdn_key_dim={self.gdn_key_dim}, gdn_value_heads="
                f"{self.gdn_value_heads} (a multiple of the key heads) of "
                f"gdn_value_dim={self.gdn_value_dim} and gdn_conv above 0, "
                f"with gdn_chunk={chunk} a power of two")
        if self.rotary_dim and (
                self.rotary_dim % 2 or self.rotary_dim > self.head_dim
                or self.kv_latent or self.diff_attn
                or not (self.qk_norm or self.qk_norm_heads)):
            raise ValueError(
                f"rotary_dim={self.rotary_dim}: an even share of a head of "
                f"{self.head_dim}, turned after the norm of q and k (set "
                f"qk_norm or qk_norm_heads; latent attention has d_rope, "
                f"differential attention no positions)")
        if self.attn_out_gate and (self.kv_latent or self.diff_attn
                                   or not self.n_kv_heads):
            raise ValueError(
                "attn_out_gate widens a `w_q` of its own (set n_kv_heads); "
                "latent and differential attention have no such gate")
        if self.norm_unit_offset and (
                self.norm_kind != "rms" or self.norm_out or self.qk_norm
                or self.kv_latent):
            raise ValueError(
                "norm_unit_offset is RMSNorm's, of the stream and of each "
                "head's q and k: no LayerNorm, sandwich norm, whole-vector "
                "norm of q and k or latent's norms with it")
        if self.shared_expert_gate and not self.n_shared_experts:
            raise ValueError("shared_expert_gate gates a shared expert: set "
                             "n_shared_experts")

    def _check_side_values(self):
        """The layers that write and read the two side values stand where
        the values exist: the memory is a "mamba" layer's, before every
        "gmu"; the shared keys and values a full "attention" layer's, before
        every "cross"."""
        kinds = self.mixer_kinds
        if "mamba" in kinds and not (
                self.ssm_state > 0 and self.ssm_conv > 0 and self.ssm_chunk > 0
                and self.ssm_dt_rank > 0 and self.ssm_expand > 0):
            raise ValueError("mamba layers name ssm_state, ssm_conv, ssm_chunk, "
                             "ssm_dt_rank and ssm_expand above 0")
        for reader, source, writer in (("gmu", self.memory_from, "mamba"),
                                       ("cross", self.kv_from, "attention")):
            first = kinds.index(reader) if reader in kinds else None
            if first is None and source < 0:
                continue
            if not (0 <= source < len(kinds) and kinds[source] == writer
                    and (first is None or source < first)):
                raise ValueError(
                    f"a {reader!r} layer reads what layer {source} wrote: "
                    f"that is a {writer!r} layer before the first of them")
        if "cross" in kinds and not (
                self.diff_attn and self.n_kv_heads
                and not (self.attn_windows and self.attn_windows[self.kv_from])):
            raise ValueError("a \"cross\" layer is differential attention "
                             "over a full (window 0) layer's keys and values")

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def n_experts_here(self) -> int:
        return self.experts_held[1] if self.experts_held else self.n_experts

    @property
    def n_heads_here(self) -> int:
        return self.heads_held[1] if self.heads_held else self.n_heads

    @property
    def value_dim(self) -> int:
        return self.d_value or self.head_dim

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def gdn_widths(self) -> tuple:
        """(queries' and keys' width, values' and the gate's) of a delta-rule
        layer, all heads side by side."""
        return (self.gdn_key_heads * self.gdn_key_dim,
                self.gdn_value_heads * self.gdn_value_dim)

    @property
    def mamba_inner(self) -> int:
        """A Mamba-1 layer's width, the memory's and a gated memory unit's."""
        return self.ssm_expand * self.d_model

    def layer_kind(self, i: int) -> "LayerKind":
        sparse = self.ffn_kinds[i] == "sparse" if self.ffn_kinds \
            else bool(self.n_experts)
        mixer = self.mixer_kinds[i] if self.mixer_kinds else "attention"
        window = self.attn_windows[i] if self.attn_windows else 0
        return LayerKind(window if mixer == "attention" else 0, sparse,
                         self.remat_layers[i] if self.remat_layers else "",
                         mixer)


MIXERS = ("attention", "ssm", "mamba", "gmu", "cross", "gdn", "kda")


class LayerKind(NamedTuple):
    """What a layer's program depends on beyond the model's config."""
    window: int         # 0: full causal attention
    sparse: bool        # routed experts (and shared ones), else a dense FFN
    remat: str          # "" or "ffn"
    mixer: str = "attention"    # or another of `MIXERS` in its place


def transformer_init(key, cfg: TransformerConfig) -> dict:
    def dense(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(cfg.dtype)

    def scale(key, n):
        """A norm's scale: ones, or under `norm_unit_offset` the w of 1 + w,
        drawn near 0 and not at it, so that a dropped offset shows."""
        if cfg.norm_unit_offset:
            return dense(key, (n,), 0.02)
        return jnp.ones((n,), cfg.dtype)

    keys = jax.random.split(key, 2 + 4 * cfg.n_layers)
    d = cfg.d_model
    hd = cfg.n_heads * cfg.head_dim         # d_model unless d_head says so
    if cfg.kv_latent:
        hd = cfg.n_heads_here * cfg.value_dim
    params = {
        "embed": dense(keys[0], (cfg.vocab, d), d ** -0.5),
        "ln_f": scale(jax.random.fold_in(keys[1], 2), d),
        "layers": [],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[1], (d, cfg.vocab), d ** -0.5)
    if cfg.norm_kind == "layer":
        params["ln_f_b"] = dense(jax.random.fold_in(keys[1], 1), (d,), 0.02)
    for i in range(cfg.n_layers):
        k = keys[2 + 4 * i: 6 + 4 * i]
        sparse = cfg.layer_kind(i).sparse
        experts = (cfg.n_experts_here,) if sparse else ()
        f = cfg.d_ff_dense or cfg.d_ff if cfg.n_experts and not sparse \
            else cfg.d_ff
        layer = {"ln1": scale(jax.random.fold_in(k[0], 20), d)}
        # the leaves a public block adds draw from keys of their own, so
        # the flagship's are the flagship's whatever else is configured
        mixer = cfg.layer_kind(i).mixer
        attends = mixer in ("attention", "cross")
        if mixer == "ssm":
            layer.update(_ssm_init(cfg, k[0], k[1], dense))
        elif mixer == "mamba":
            layer.update(_mamba_init(cfg, k[0], k[1], dense))
        elif mixer == "gdn":
            layer.update(_gdn_init(cfg, k[0], k[1], dense))
        elif mixer == "kda":
            layer.update(_kda_init(cfg, k[0], k[1], dense))
        elif mixer == "gmu":
            inner = cfg.mamba_inner
            layer["w_gmu_in"] = dense(k[0], (d, inner), d ** -0.5)
            layer["w_gmu_out"] = dense(k[1], (inner, d),
                                       (2 * inner * cfg.n_layers) ** -0.5)
        elif cfg.kv_latent:
            h, cq, ckv = cfg.n_heads_here, cfg.q_latent, cfg.kv_latent
            queries = h * (cfg.head_dim + cfg.d_rope)
            shapes = {"w_dkv": (6, (d, ckv + cfg.d_rope)),
                      "w_ukv": (7, (ckv, h * (cfg.head_dim + cfg.value_dim)))}
            if cq:
                shapes.update(w_dq=(4, (d, cq)), w_uq=(5, (cq, queries)))
                layer["q_latent_norm"] = jnp.ones((cq,), cfg.dtype)
            else:           # no query latent: one product
                shapes["w_q"] = (5, (d, queries))
            for name, (n, shape) in shapes.items():
                layer[name] = dense(jax.random.fold_in(k[0], n), shape,
                                    shape[0] ** -0.5)
            layer["kv_latent_norm"] = jnp.ones((ckv,), cfg.dtype)
        elif cfg.n_kv_heads:
            kv = cfg.n_kv_heads * cfg.head_dim
            layer["w_q"] = dense(jax.random.fold_in(k[0], 1),
                                 (d, hd * (2 if cfg.attn_out_gate else 1)),
                                 d ** -0.5)    # [queries | gate]
            if mixer != "cross":    # a cross layer's are another layer's
                layer["w_k"] = dense(jax.random.fold_in(k[0], 2), (d, kv),
                                     d ** -0.5)
                layer["w_v"] = dense(jax.random.fold_in(k[0], 3), (d, kv),
                                     d ** -0.5)
        else:
            layer["w_qkv"] = dense(k[0], (d, 3 * hd), d ** -0.5)
        if attends:
            layer["w_proj"] = dense(k[1], (hd, d),
                                    (2 * hd * cfg.n_layers) ** -0.5)
        if attends and cfg.attn_bias:
            for n, w in enumerate(("w_q", "w_k", "w_v", "w_proj")):
                if w in layer:
                    layer["b" + w[1:]] = dense(jax.random.fold_in(k[0], 8 + n),
                                               layer[w].shape[1:], 0.02)
        if attends and cfg.diff_attn:
            for n, name in enumerate(("lambda_q1", "lambda_k1", "lambda_q2",
                                      "lambda_k2")):
                layer[name] = (0.1 * jax.random.normal(
                    jax.random.fold_in(k[0], 12 + n), (cfg.head_dim,),
                    jnp.float32))
            layer["diff_norm"] = jnp.ones((2 * cfg.head_dim,), cfg.dtype)
        layer.update({
            "ln2": scale(jax.random.fold_in(k[2], 7), d),
            "w_in": dense(k[2], experts + (d, f), d ** -0.5),
            "w_out": dense(k[3], experts + (f, d),
                           (2 * f * cfg.n_layers) ** -0.5),
        })
        if cfg.norm_kind == "layer":
            layer["ln1_b"] = dense(jax.random.fold_in(k[2], 5), (d,), 0.02)
            layer["ln2_b"] = dense(jax.random.fold_in(k[2], 6), (d,), 0.02)
        if cfg.norm_out:
            layer["ln1_out"] = jnp.ones((d,), cfg.dtype)
            layer["ln2_out"] = jnp.ones((d,), cfg.dtype)
        if cfg.qk_norm and attends:
            layer["q_norm"] = jnp.ones((d,), cfg.dtype)
            layer["k_norm"] = jnp.ones((d,), cfg.dtype)
        if cfg.qk_norm_heads and attends:
            layer["q_norm"] = scale(jax.random.fold_in(k[0], 21), cfg.head_dim)
            layer["k_norm"] = scale(jax.random.fold_in(k[0], 22), cfg.head_dim)
        if sparse or cfg.dense_gated:
            layer["w_gate"] = dense(jax.random.fold_in(k[2], 1),
                                    experts + (d, f), d ** -0.5)
        if sparse:
            layer["w_router"] = dense(jax.random.fold_in(k[2], 2),
                                      (d, cfg.n_experts), d ** -0.5)
        if sparse and cfg.n_shared_experts:
            fs = cfg.n_shared_experts * f
            layer["w_shared_gate"] = dense(jax.random.fold_in(k[2], 3),
                                           (d, fs), d ** -0.5)
            layer["w_shared_in"] = dense(jax.random.fold_in(k[2], 4),
                                         (d, fs), d ** -0.5)
            layer["w_shared_out"] = dense(jax.random.fold_in(k[3], 1), (fs, d),
                                          (2 * fs * cfg.n_layers) ** -0.5)
        if sparse and cfg.shared_expert_gate:
            layer["w_shared_sigmoid"] = dense(jax.random.fold_in(k[2], 8),
                                              (d, 1), d ** -0.5)
        params["layers"].append(layer)
    return params


def _ssm_init(cfg: TransformerConfig, key, key_out, dense) -> dict:
    """The leaves of a state-space layer's mixer. `w_ssm_in` [d, z | x B C |
    dt]: the gate (inner wide), the convolution's channels (inner + 2 x
    state) and a dt a head; `conv_w` [taps, channels] and `conv_b`;
    `ssm_norm` the gated norm's scale; `w_ssm_out` [inner, d]. The three
    leaves of the recurrence itself are float32, as its arithmetic is:
    `a_log` (A = -exp(a_log); log(1..heads), the family's initialisation),
    `d_skip` (ones) and `dt_bias` (the inverse softplus of values drawn
    log-uniformly from [0.001, 0.1])."""
    d, inner, h = cfg.d_model, cfg.ssm_inner, cfg.ssm_heads
    channels = inner + 2 * cfg.ssm_state
    keys = jax.random.split(key, 4)
    dt = jnp.exp(jax.random.uniform(keys[3], (h,), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return {
        "w_ssm_in": dense(keys[0], (d, inner + channels + h), d ** -0.5),
        "conv_w": dense(keys[1], (cfg.ssm_conv, channels),
                        cfg.ssm_conv ** -0.5),
        "conv_b": dense(keys[2], (channels,), cfg.ssm_conv ** -0.5),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "a_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
        "d_skip": jnp.ones((h,), jnp.float32),
        "ssm_norm": jnp.ones((inner,), cfg.dtype),
        "w_ssm_out": dense(key_out, (inner, d),
                           (2 * inner * cfg.n_layers) ** -0.5),
    }


def _gdn_init(cfg: TransformerConfig, key, key_out, dense) -> dict:
    """The leaves of a delta-rule layer's mixer. `w_gdn_in` [d, q | k | v |
    z]: queries and keys (key heads x key width each), values and the gate
    (value heads x value width each); `w_gdn_ba` [d, b | a]: a write strength
    and a decay's input a value head; `conv_w` [taps, q | k | v], no bias;
    `gdn_norm` [value width] the output norm's scale, at one; `w_gdn_out`
    [values, d]. Float32, as the recurrence's arithmetic: `a_log` (the decay
    is -exp(a_log) x softplus(a + dt_bias); log of values drawn uniformly
    from (0, 16)) and `dt_bias` (ones): the family's initialisation."""
    d, hv = cfg.d_model, cfg.gdn_value_heads
    kw, vw = cfg.gdn_widths
    keys = jax.random.split(key, 4)
    return {
        "w_gdn_in": dense(keys[0], (d, 2 * kw + 2 * vw), d ** -0.5),
        "w_gdn_ba": dense(keys[1], (d, 2 * hv), d ** -0.5),
        "conv_w": dense(keys[2], (cfg.gdn_conv, 2 * kw + vw),
                        cfg.gdn_conv ** -0.5),
        "a_log": jnp.log(jax.random.uniform(keys[3], (hv,), jnp.float32,
                                            1e-3, 16.0)),
        "dt_bias": jnp.ones((hv,), jnp.float32),
        "gdn_norm": jnp.ones((cfg.gdn_value_dim,), cfg.dtype),
        "w_gdn_out": dense(key_out, (vw, d), (2 * vw * cfg.n_layers) ** -0.5),
    }


def _kda_init(cfg: TransformerConfig, key, key_out, dense) -> dict:
    """The leaves of a "kda" layer's mixer. `w_kda_in` [d, q | k | v] and
    `conv_w` [taps, q | k | v], no bias, as a delta-rule layer's; `w_kda_low`
    [d, f | g | b]: the decay's and the gate's kda_rank-wide inputs and a
    write strength a value head; `w_kda_f` [kda_rank, value heads x key
    width] and `w_kda_g` [kda_rank, values]: the low-rank maps' second
    halves; `kda_norm` [value width], at one; `w_kda_out` [values, d].
    Float32, as the recurrence's arithmetic: `a_log` [value heads] (log of
    values drawn uniformly from (1, 16)) and `dt_bias` [value heads x key
    width] (the inverse softplus of values drawn log-uniformly from [0.001,
    0.1]): a token's decay exp(-exp(a_log) softplus(. + dt_bias)) spans 0.2
    to 0.999 over the channels."""
    d, hv, r = cfg.d_model, cfg.gdn_value_heads, cfg.kda_rank
    kw, vw = cfg.gdn_widths
    decays = hv * cfg.gdn_key_dim
    keys = jax.random.split(key, 7)
    dt = jnp.exp(jax.random.uniform(keys[4], (decays,), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return {
        "w_kda_in": dense(keys[0], (d, 2 * kw + vw), d ** -0.5),
        "w_kda_low": dense(keys[1], (d, 2 * r + hv), d ** -0.5),
        "conv_w": dense(keys[2], (cfg.gdn_conv, 2 * kw + vw),
                        cfg.gdn_conv ** -0.5),
        "a_log": jnp.log(jax.random.uniform(keys[3], (hv,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "w_kda_f": dense(keys[5], (r, decays), r ** -0.5),
        "w_kda_g": dense(keys[6], (r, vw), r ** -0.5),
        "kda_norm": jnp.ones((cfg.gdn_value_dim,), cfg.dtype),
        "w_kda_out": dense(key_out, (vw, d), (2 * vw * cfg.n_layers) ** -0.5),
    }


def _mamba_init(cfg: TransformerConfig, key, key_out, dense) -> dict:
    """The leaves of a Mamba-1 layer's mixer. `w_ssm_in` [d, x | z] (the
    convolution's channels and the gate, inner wide each); `conv_w` [taps,
    inner] and `conv_b`; `w_ssm_x` [inner, dt_low | B | C] (ssm_dt_rank +
    2 x ssm_state); `w_ssm_dt` [ssm_dt_rank, inner], uniform within
    ssm_dt_rank ** -0.5; `w_ssm_out` [inner, d]. Float32, as the recurrence's
    arithmetic: `dt_bias` [inner] (the inverse softplus of values drawn
    log-uniformly from [0.001, 0.1]), `a_log` [inner, state] (A = -exp(a_log);
    log(1..state) a channel) and `d_skip` (ones): the family's published
    initialisation."""
    d, inner, n, r = cfg.d_model, cfg.mamba_inner, cfg.ssm_state, cfg.ssm_dt_rank
    keys = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(keys[3], (inner,), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return {
        "w_ssm_in": dense(keys[0], (d, 2 * inner), d ** -0.5),
        "conv_w": dense(keys[1], (cfg.ssm_conv, inner), cfg.ssm_conv ** -0.5),
        "conv_b": dense(keys[2], (inner,), cfg.ssm_conv ** -0.5),
        "w_ssm_x": dense(keys[4], (inner, r + 2 * n), inner ** -0.5),
        "w_ssm_dt": jax.random.uniform(
            keys[5], (r, inner), jnp.float32, -r ** -0.5,
            r ** -0.5).astype(cfg.dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (inner, n)),
        "d_skip": jnp.ones((inner,), jnp.float32),
        "w_ssm_out": dense(key_out, (inner, d),
                           (2 * inner * cfg.n_layers) ** -0.5),
    }


def transformer_param_specs(cfg: TransformerConfig, tp_axis: Optional[str]) -> dict:
    """PartitionSpec pytree matching transformer_init's params: qkv/ffn-in
    column-sharded, proj/ffn-out row-sharded over the tp axis; everything
    else replicated."""
    col = P(None, tp_axis)
    row = P(tp_axis, None)
    rep = P()

    def layer(i):
        sparse = cfg.layer_kind(i).sparse
        # every rank holds every expert it holds whole (a layer with
        # experts runs at tp 1 only: `_attn_ffn_block`)
        out = {"ln1": rep, "w_proj": row, "ln2": rep,
               "w_in": rep if sparse else col,
               "w_out": rep if sparse else row}
        mixer = cfg.layer_kind(i).mixer
        attends = mixer in ("attention", "cross")
        if mixer == "ssm":  # whole on every rank (tp 1 and sp 1 only: `_ssm_mixer`)
            del out["w_proj"]
            out.update({name: rep for name in (
                "w_ssm_in", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
                "ssm_norm", "w_ssm_out")})
        elif mixer == "mamba":      # likewise (`_mamba_mixer`)
            del out["w_proj"]
            out.update({name: rep for name in (
                "w_ssm_in", "conv_w", "conv_b", "w_ssm_x", "w_ssm_dt",
                "dt_bias", "a_log", "d_skip", "w_ssm_out")})
        elif mixer == "gdn":        # likewise (`_gdn_mixer`)
            del out["w_proj"]
            out.update({name: rep for name in (
                "w_gdn_in", "w_gdn_ba", "conv_w", "a_log", "dt_bias",
                "gdn_norm", "w_gdn_out")})
        elif mixer == "kda":        # likewise (`_kda_mixer`)
            del out["w_proj"]
            out.update({name: rep for name in (
                "w_kda_in", "w_kda_low", "conv_w", "a_log", "dt_bias",
                "w_kda_f", "w_kda_g", "kda_norm", "w_kda_out")})
        elif mixer == "gmu":
            del out["w_proj"]
            out.update(w_gmu_in=rep, w_gmu_out=rep)
        elif cfg.kv_latent:   # whole on every rank (tp 1 only: `_latent_attn`)
            out.update(w_dkv=rep, w_ukv=rep, w_proj=rep, kv_latent_norm=rep)
            if cfg.q_latent:
                out.update(w_dq=rep, w_uq=rep, q_latent_norm=rep)
            else:
                out.update(w_q=rep)
        elif cfg.n_kv_heads:
            # differential attention is whole on every rank (`_diff_attn`)
            part = rep if cfg.diff_attn else col
            out.update(w_q=part)
            if mixer != "cross":
                out.update(w_k=part, w_v=part)
        else:
            out["w_qkv"] = col
        if attends and cfg.attn_bias:
            out.update(b_q=rep, b_proj=rep)
            if mixer != "cross":
                out.update(b_k=rep, b_v=rep)
        if attends and cfg.diff_attn:
            out.update(w_proj=rep, lambda_q1=rep, lambda_k1=rep,
                       lambda_q2=rep, lambda_k2=rep, diff_norm=rep)
        if cfg.norm_kind == "layer":
            out.update(ln1_b=rep, ln2_b=rep)
        if cfg.norm_out:
            out.update(ln1_out=rep, ln2_out=rep)
        if cfg.qk_norm and attends:
            out.update(q_norm=P(tp_axis), k_norm=P(tp_axis))
        if cfg.qk_norm_heads and attends:
            out.update(q_norm=rep, k_norm=rep)
        if sparse:
            out.update(w_gate=rep, w_router=rep)
            if cfg.n_shared_experts:
                out.update(w_shared_gate=rep, w_shared_in=rep,
                           w_shared_out=rep)
            if cfg.shared_expert_gate:
                out["w_shared_sigmoid"] = rep
        elif cfg.dense_gated:
            out["w_gate"] = col
        return out
    specs = {"embed": rep, "ln_f": rep,
             "layers": [layer(i) for i in range(cfg.n_layers)]}
    if not cfg.tie_embeddings:
        specs["lm_head"] = rep
    if cfg.norm_kind == "layer":
        specs["ln_f_b"] = rep
    return specs


def _rms_norm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def _rms_norm_offset(x, w, eps: float = 1e-6):
    """RMSNorm with the scale 1 + w: float32 inside, rounded once."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _layer_norm(x, scale, bias, eps: float):
    """LayerNorm: the mean taken off, the variance's root divided out
    (float32 inside, rounded once), a learned scale and a bias."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype) \
        * scale + bias


def _norm(cfg: TransformerConfig, x, leaves: dict, name: str):
    """The model's norm of the stream with the scale `leaves[name]` (and,
    for a LayerNorm, the bias `leaves[name + "_b"]`)."""
    if cfg.norm_kind == "layer":
        return _layer_norm(x, leaves[name], leaves[name + "_b"], cfg.norm_eps)
    return _scaled_rms(cfg, x, leaves[name])


def _scaled_rms(cfg: TransformerConfig, x, scale):
    """The model's RMSNorm over the last axis: x `scale`, or (`norm_unit_
    offset`) x (1 + `scale`)."""
    if cfg.norm_unit_offset:
        return _rms_norm_offset(x, scale, cfg.norm_eps)
    return _rms_norm(x, scale, cfg.norm_eps)


def _rope_table(positions, theta: float, width: int, lane_in_head):
    """(cos, sin) [t, lanes] float32 for lanes whose place within a rotary
    head of ``width`` is ``lane_in_head`` (static; < 0: the lane passes): each
    angle stands twice, once for either half of the head, and ``sin``
    carries the first half's minus sign; a passing lane reads cos 1, sin 0.
    Positions are *global*, so sequence shards agree."""
    half = width // 2
    lane_in_head = np.asarray(lane_in_head)
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    per_lane = jnp.where(lane_in_head >= 0, freqs[lane_in_head % half], 0.0)
    ang = positions[:, None].astype(jnp.float32) * per_lane[None, :]
    sign = np.where(lane_in_head < half, -1.0, 1.0).astype(np.float32)
    return jnp.cos(ang), jnp.sin(ang) * sign


@jax.custom_vjp
def _turn(x, cos, sin):
    """``x cos + swap(x) sin`` over the last axis, one rotary head: `swap`
    exchanges the head's halves (a flip of its [2, half] view: no half-width
    slice, no concatenate). Float32 inside, rounded to x's type once. The
    transpose of a rotation is the rotation by the negative angle, so the
    gradient is this function again with `sin` negated: one elementwise pass
    that keeps the tables alone."""
    *lead, width = x.shape
    swapped = jnp.flip(x.reshape(*lead, 2, width // 2), -2).reshape(x.shape)
    return (x.astype(jnp.float32) * cos
            + swapped.astype(jnp.float32) * sin).astype(x.dtype)


_turn.defvjp(lambda x, cos, sin: (_turn(x, cos, sin), (cos, sin)),
             lambda kept, d: (_turn(d, kept[0], -kept[1]), None, None))


def _rope_halves(x, positions, theta: float = 10000.0):
    """Rotary embeddings as two half-width products, concatenated, and
    whatever autodiff makes of that: what `_rope` was, and what a head of
    odd width (whose last value passes) still takes."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]   # (t, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:2 * half]
    out = [x1 * cos - x2 * sin, x1 * sin + x2 * cos]
    if x.shape[-1] % 2:
        out.append(x[..., 2 * half:].astype(jnp.float32))
    return jnp.concatenate(out, axis=-1).astype(x.dtype)


def _rope(x, positions, theta: float = 10000.0):
    """Rotary embeddings of x [..., t, width], each last axis one head;
    positions are *global* so sequence shards agree. x cos2 + swap(x) sin2
    with the inverse rotation for a backward (`_turn`), as plain `jnp`: what
    a small operand takes (latent attention's one shared rotary key) and
    what the kernels' contracts refuse (`_rope_heads`, `_norm_and_rope`).
    The form counts in ``perfvars.snapshot()["rope_forms"]``: `dense`, or
    `halves` where the width is odd."""
    width = x.shape[-1]
    if width % 2:
        perfvars.note("rope_forms", "halves")
        return _rope_halves(x, positions, theta)
    perfvars.note("rope_forms", "dense")
    return _turn(x, *_rope_table(positions, theta, width, np.arange(width)))


def _cut_heads(row, heads: int):
    """(b, t, heads x width) -> (b, heads, t, width)."""
    b, t, _ = row.shape
    return row.reshape(b, t, heads, -1).transpose(0, 2, 1, 3)


def _rope_heads(row, positions, theta: float, heads: int, parts: tuple):
    """A token-major row [b, t, heads x period], every head the ``parts`` =
    ((width, rotated), ..) side by side (a packed projection's q | k | v, a
    latent query's unrotated | rotated), as one [b, heads, t, width] array a
    part with the rotated ones turned. Where the backend and the pattern
    select it (`xla.choice`, `pallas_kernels.rope_heads_blocks`: widths of
    64 or multiples of 128, a rotary width of 64 or 128) that is one kernel
    each way, `pallas_kernels.rope_heads`: the rotation runs on the row,
    where it is 128 lanes dense, and the cut into heads is the kernel's
    write; elsewhere the cut, then `_rope` on each rotated part."""
    turned = [w for w, rotated in parts if rotated]
    run = choice.decide(choice.ROPE_HEADS, row.shape[1], heads, parts,
                        row.dtype, also=bool(turned), count=len(turned))
    if run:
        cos, sin = _rope_table(positions, theta, turned[0],
                               pk.rope_heads_lanes(parts))
        return pk.rope_heads(row, cos, sin, heads, parts,
                             interpret=run.interpret)
    ends = np.cumsum([w for w, _rotated in parts])
    return tuple(
        _rope(part, positions, theta) if rotated else part
        for part, (_w, rotated) in zip(
            jnp.split(_cut_heads(row, heads), ends[:-1], axis=-1), parts))


def transformer_forward(cfg: TransformerConfig, params: dict,
                        tokens: jnp.ndarray, *, tp_axis: Optional[str] = None,
                        sp_axis: Optional[str] = None) -> jnp.ndarray:
    """Logits for a (possibly dp/sp-sharded) local token block.

    tokens: (batch_local, seq_local) int32. Inside shard_map, ``tp_axis`` /
    ``sp_axis`` name live mesh axes; with both None this is a plain
    single-device forward (the driver's single-chip entry).
    """
    return _forward(cfg, params, tokens, tp_axis=tp_axis, sp_axis=sp_axis)[0]


def _forward(cfg: TransformerConfig, params: dict, tokens: jnp.ndarray, *,
             tp_axis: Optional[str] = None, sp_axis: Optional[str] = None):
    """(logits, routed): the whole float32 logits [b, t, vocab] of
    `_trunk`'s stream, and its `routed`."""
    x, routed = _trunk(cfg, params, tokens, tp_axis=tp_axis, sp_axis=sp_axis)
    with jax.named_scope("head_loss"):
        x = _norm(cfg, x, params, "ln_f")
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = (x @ head).astype(jnp.float32)                   # (b, t, V)
        if cfg.logits_divisor != 1.0:
            logits = logits / cfg.logits_divisor
        return logits, routed


def _trunk(cfg: TransformerConfig, params: dict, tokens: jnp.ndarray, *,
           tp_axis: Optional[str] = None, sp_axis: Optional[str] = None):
    """(x, routed): the stream [b, t, d] after the last layer, before the
    final norm and the head, and `routed`, which holds, for each layer with
    experts, its router's (summed probabilities [n_experts] float32,
    token-slots per expert [n_experts] int32) over this block's tokens, and
    where the rank holds a share of the experts a third entry, what the
    held layer did (`parallel.ep.moe_dropless_held`'s [rows computed, rows
    gathered, further buffers ran]); empty otherwise."""
    b, t = tokens.shape
    d, h = cfg.d_model, cfg.n_heads
    tp = 1 if tp_axis is None else lax.axis_size(tp_axis)
    if h % tp != 0:
        raise ValueError(f"n_heads={h} must be divisible by tp size {tp}")
    h_local = h // tp
    dh = cfg.head_dim

    # global positions for this sequence shard (RoPE must see them)
    if sp_axis is not None:
        sp_idx = lax.axis_index(sp_axis)
        positions = sp_idx * t + jnp.arange(t)
    else:
        positions = jnp.arange(t)

    # named scopes (embed, layer_<i>/attn, layer_<i>/mlp, head_loss) reach
    # every op's metadata, forward and backward: a device trace is read by
    # them (PERF.md section 3, "train step")
    with jax.named_scope("embed"):
        x = rows_at(params["embed"], tokens, scope="embed")      # (b, t, d)
        if cfg.embed_multiplier != 1.0:
            x = x * cfg.embed_multiplier
    routed = []
    side = {}       # the values beside the stream: "memory", "kv"
    for i, layer in enumerate(params["layers"]):
        kind = cfg.layer_kind(i)
        block = _block_traced_once(cfg, kind, tp_axis, sp_axis,
                                   choice.trace_key())
        with jax.named_scope(f"layer_{i}"):
            x, sent, wrote = block(layer, x, positions,
                                   _side_read(cfg, kind, i, side))
        # every layer of a kind hands out what its kind can (one trace a
        # kind); the one the model names is the one that is read
        if i == cfg.memory_from:
            side["memory"] = wrote["memory"]
        if i == cfg.kv_from:
            side["kv"] = wrote["kv"]
        if sent is not None:
            routed.append(sent)
    return x, routed


@functools.lru_cache(maxsize=None)
def _block_traced_once(cfg: TransformerConfig, kind: LayerKind,
                       tp_axis: Optional[str], sp_axis: Optional[str],
                       kernels: tuple):
    """`_attn_ffn_block` behind a `jax.jit` of its own, one for each kind of
    layer the model has. The layers of a kind have one shape, and they are
    unrolled in Python: jitted, layers 2..n of a kind in a program (and a
    second program over the same shapes) find the first one's trace, its
    linearization and its transpose where JAX keeps them, every kernel body
    in it is traced once, and the lowered module holds one function a kind
    and direction, called once a layer (the compiler inlines the calls,
    and each inlined op's name gains its call's `layer_<i>`). That is what
    keeps a step's trace, which is set-up time, from growing with depth
    (PERF.md, Set-up). ``kernels`` is `choice.trace_key`: the kernels are
    selected inside the trace, so what selects them is part of the key."""
    def block(layer, x, positions, side):
        return _attn_ffn_block(cfg, layer, x, positions, tp_axis=tp_axis,
                               sp_axis=sp_axis, kind=kind, side=side)
    return jax.jit(block)


def _side_read(cfg: TransformerConfig, kind: LayerKind, i: int,
               side: dict) -> dict:
    """What layer ``i`` is handed beside the stream, as traced inputs of its
    kind's one function: a gated memory unit the memory, a cross layer the
    shared keys and values, a differential attention layer its depth (its
    `lambda_init` is a function of it, and layers of a kind share a trace).
    Every other layer nothing: its function's inputs are what they were.
    Each read counts in ``perfvars.snapshot()["side_values"]``."""
    out = {}
    if kind.mixer == "gmu":
        perfvars.note("side_values", "memory")
        out["memory"] = side["memory"]
    if kind.mixer == "cross":
        perfvars.note("side_values", "kv")
        out["kv"] = side["kv"]
    if cfg.diff_attn and kind.mixer in ("attention", "cross"):
        out["depth"] = jnp.float32(i)
    return out


def _attn_ffn_block(cfg: TransformerConfig, layer: dict, x: jnp.ndarray,
                    positions: jnp.ndarray, *, tp_axis: Optional[str],
                    sp_axis: Optional[str], kind: Optional[LayerKind] = None,
                    side: Optional[dict] = None):
    """One transformer layer (pre-norm attention + FFN), tp/sp aware —
    shared by the flat forward and the pipelined 4-axis stage. ``kind`` is
    the layer's (`cfg.layer_kind(i)`; None: layer 0's). Returns the layer's
    output, what its router sent where (None without experts) and what it
    wrote beside the stream (`side`: what it read there, `_side_read`; a
    "mamba" layer writes `memory`, a differential "attention" layer `kv`,
    any other nothing). With `norm_out` each half's output is normed before
    it joins the residual (scope `norm_out`); where the rank holds a share
    of the heads or of the experts that output is a partial sum, and is
    normed as it stands."""
    kind = cfg.layer_kind(0) if kind is None else kind
    side = side or {}
    tp = 1 if tp_axis is None else lax.axis_size(tp_axis)
    h_local = cfg.n_heads_here // tp

    attn = functools.partial(_attn, cfg, h_local=h_local, tp_axis=tp_axis,
                             sp_axis=sp_axis, window=kind.window)
    if cfg.remat_attn:
        # the fused kernel (a ring of one, where it is selected) keeps no
        # [b, h, s, s] scores as it is; the plain attention is recomputed
        b, t, _ = x.shape
        alone = sp_axis is None or lax.axis_size(sp_axis) == 1
        if not (alone and fused_attention_selected(
                (b, h_local, t, cfg.head_dim), x.dtype, cfg.d_rope,
                cfg.value_dim)):
            attn = jax.checkpoint(attn)

    def normed(out, scale):
        if cfg.norm_out:
            with jax.named_scope("norm_out"):
                out = _rms_norm(out, layer[scale], cfg.norm_eps)
        if cfg.residual_multiplier != 1.0:
            out = out * cfg.residual_multiplier
        return out
    perfvars.note("mixer_kinds", kind.mixer)
    wrote = {}
    if kind.mixer == "ssm":
        with jax.named_scope("mixer"):
            x = x + normed(_ssm_mixer(cfg, layer, x, tp_axis=tp_axis,
                                      sp_axis=sp_axis), "ln1_out")
    elif kind.mixer in ("gdn", "kda"):
        mixer = _gdn_mixer if kind.mixer == "gdn" else _kda_mixer
        with jax.named_scope("mixer"):
            x = x + normed(mixer(cfg, layer, x, tp_axis=tp_axis,
                                 sp_axis=sp_axis), "ln1_out")
    elif kind.mixer in ("mamba", "gmu") or cfg.diff_attn:
        for axis in (tp_axis, sp_axis):
            if axis is not None and lax.axis_size(axis) > 1:
                raise NotImplementedError(
                    f"a layer of a decoder-hybrid-decoder stack runs at tp 1 "
                    f"and sp 1 ({axis!r} has {lax.axis_size(axis)} ranks): "
                    f"its state and its side values are not cut")
        with jax.named_scope("attn" if kind.mixer in ("attention", "cross")
                             else "mixer"):
            if kind.mixer == "mamba":
                out, wrote = _mamba_mixer(cfg, layer, x)
            elif kind.mixer == "gmu":
                out = _gmu_mixer(cfg, layer, x, side["memory"])
            else:
                out, wrote = _diff_attn(cfg, layer, x, window=kind.window,
                                        depth=side["depth"],
                                        kv=side.get("kv"))
            x = x + normed(out, "ln1_out")
    else:
        with jax.named_scope("attn"):
            x = x + normed(attn(layer, x, positions), "ln1_out")
    if kind.sparse and tp > 1:
        # sharding an expert's width over tp needs the sums of the
        # rows' and the weights' cotangents that `parallel/tp.py`'s
        # operators make, and under `check_vma` they count twice
        # (PERF.md section 7): not offered until a cell measures it
        raise NotImplementedError(
            "a layer with experts runs at tp 1; shard its tokens "
            "over dp or sp")

    def ffn(layer, x):
        """What the FFN half adds to the residual, and the router's word."""
        y = _norm(cfg, x, layer, "ln2")
        if kind.sparse:
            return _expert_ffn(cfg, layer, y)
        with jax.named_scope("dense"):
            if cfg.dense_gated:
                if tp_axis is not None:
                    hmid = jax.nn.silu(column_parallel(
                        y, layer["w_gate"], axis=tp_axis)) * column_parallel(
                            y, layer["w_in"], axis=tp_axis)
                    return row_parallel(hmid, layer["w_out"],
                                        axis=tp_axis), None
                return _gated_ffn(y, layer["w_gate"], layer["w_in"],
                                  layer["w_out"]), None
            if tp_axis is not None:
                hmid = jax.nn.gelu(column_parallel(y, layer["w_in"],
                                                   axis=tp_axis))
                return row_parallel(hmid, layer["w_out"], axis=tp_axis), None
            return jax.nn.gelu(y @ layer["w_in"]) @ layer["w_out"], None
    if kind.remat == "ffn":
        ffn = jax.checkpoint(ffn)
    with jax.named_scope("mlp"):
        out, sent = ffn(layer, x)
        x = x + normed(out, "ln2_out")
    return x, sent, wrote


@jax.custom_vjp
def _weighed_silu_gate(gate, up, scale):
    """``scale[:, None] x silu(gate) x up``: an expert's hidden activation
    times its row's router weight, the product in float32 and rounded once.
    The gradient is written out so that ``d gate``, ``d up`` and the
    weight's ``d scale`` (a row-wise dot, summed in float32) come from one
    pass over ``gate``, ``up`` and the cotangent; left to autodiff the dot is
    a pass of its own that reads all three again (0.57 ms a layer at
    OLMoE's shape on the v5e, PERF.md PR 31)."""
    h = jax.nn.silu(gate) * up
    return (h.astype(jnp.float32) *
            scale[:, None].astype(jnp.float32)).astype(h.dtype)


def _weighed_silu_gate_bwd(kept, d):
    gate, up, scale = (a.astype(jnp.float32) for a in kept)
    d = d.astype(jnp.float32)
    sig = jax.nn.sigmoid(gate)
    act = gate * sig                                # silu(gate)
    d_h = d * scale[:, None]
    d_gate = d_h * up * (sig * (1.0 + gate * (1.0 - sig)))
    d_scale = jnp.sum(d * (act * up), axis=-1)
    return tuple(g.astype(a.dtype) for g, a in
                 zip((d_gate, d_h * act, d_scale), kept))


_weighed_silu_gate.defvjp(
    lambda gate, up, scale: (_weighed_silu_gate(gate, up, scale),
                             (gate, up, scale)), _weighed_silu_gate_bwd)


def _gated_ffn(y, w_gate, w_in, w_out):
    """out(silu(gate(y)) * in(y)): a dense gated FFN, and a shared expert."""
    return (jax.nn.silu(y @ w_gate) * (y @ w_in)) @ w_out


def _expert_ffn(cfg: TransformerConfig, layer: dict, y: jnp.ndarray):
    """The routed FFN of one layer: what is added to the residual, and the
    router's (summed scores, token-slots) per expert. Router logits from the
    layer's dtype accumulate in float32 and the scores (`router_score`: a
    softmax over all experts, or each logit's sigmoid) are float32; a
    token's top `experts_per_tok` scores weigh its experts' outputs, as
    they are or (`router_renorm`) divided by their sum, x `router_scale`.
    Every token-slot is computed: `parallel.ep.moe_dropless` sorts the slots
    by expert and the experts run as three grouped matrix multiplications
    over the row groups (`parallel.ep.grouped_products`): on a TPU, at
    widths that are multiples of 128 and a slot count that is a multiple of
    128, the grouped Pallas kernel (forward and both backward products);
    anywhere else `lax.ragged_dot`. `moe_dropless` hands the experts each
    row's router weight and they multiply it into the hidden activation
    (`_weighed_silu_gate`: `d_ff` wide, in the pass that computes
    silu(gate) x in anyway), so the sum back over a token's experts is a
    plain float32 sum and its gradient a plain copy. Where the rank holds a
    share of the experts (`experts_held`) it adds their part of the sum
    alone (`parallel.ep.moe_dropless_held`, which weighs the rows itself),
    and a third entry says what that took. A shared expert
    (`n_shared_experts`) is a gated FFN every token runs, added beside,
    under `shared_expert_gate` x sigmoid(y w_shared_sigmoid), one weight a
    token (float32, scope `shared/shared_gate`)."""
    b, t, d = y.shape
    rows = y.reshape(b * t, d)
    with jax.named_scope("router"):
        logits = jnp.dot(rows, layer["w_router"],
                         preferred_element_type=jnp.float32)
        if cfg.router_score == "sigmoid":
            probs = jax.nn.sigmoid(logits)
        else:
            probs = jax.nn.softmax(logits, axis=-1)
        weights, chosen = lax.top_k(probs, cfg.experts_per_tok)
        if cfg.router_renorm:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        if cfg.router_scale != 1.0:
            weights = weights * cfg.router_scale

    def experts(xs, sizes, scale=None):
        product = grouped_products(sizes)
        gate, up = product(xs, layer["w_gate"]), product(xs, layer["w_in"])
        if scale is None:
            h = jax.nn.silu(gate) * up
        else:           # the rows' weights, where the rows are narrowest
            h = _weighed_silu_gate(gate, up, scale)
        return product(h, layer["w_out"])

    weights = weights.astype(rows.dtype)
    if cfg.experts_held:
        first, held = cfg.experts_held
        out, slots, did = moe_dropless_held(
            rows, chosen, weights, experts, cfg.n_experts, first, held,
            buffer_rows=held_row_buffer(b * t * cfg.experts_per_tok,
                                        cfg.n_experts, held, b * t))
        sent = (probs.sum(axis=0), slots, did)
    else:
        out, slots = moe_dropless(rows, chosen, weights, experts,
                                  cfg.n_experts)
        sent = (probs.sum(axis=0), slots)
    if cfg.n_shared_experts:
        with jax.named_scope("shared"):
            shared = _gated_ffn(rows, layer["w_shared_gate"],
                                layer["w_shared_in"], layer["w_shared_out"])
            if cfg.shared_expert_gate:
                with jax.named_scope("shared_gate"):
                    weight = jax.nn.sigmoid(jnp.dot(
                        rows, layer["w_shared_sigmoid"],
                        preferred_element_type=jnp.float32))    # [rows, 1]
                    shared = (shared.astype(jnp.float32)
                              * weight).astype(shared.dtype)
            out = out + shared
    return out.reshape(b, t, d), sent


def load_balancing_loss(routed: list, n_tokens: int) -> jnp.ndarray:
    """The auxiliary loss of `transformers`' `load_balancing_loss_func` over
    all layers' routers together: n_experts x sum over experts of (token-
    slots routed there / tokens) x (mean router probability), the means
    over layers x tokens. Balanced routing gives `experts_per_tok`."""
    prob_sum = sum(r[0] for r in routed)
    slots = sum(r[1] for r in routed)
    rows = len(routed) * n_tokens
    n_experts = prob_sum.shape[0]
    return n_experts * jnp.sum(
        (slots.astype(jnp.float32) / rows) * (prob_sum / rows))


def transformer_expert_counts(cfg: TransformerConfig, params: dict,
                              tokens: jnp.ndarray) -> jnp.ndarray:
    """[n_layers, n_experts] int32: the token-slots each layer's router
    sends to each expert for this batch. Every row sums to tokens x
    `experts_per_tok`: nothing is dropped. A forward pass of its own, for a
    caller to run outside whatever it times."""
    _x, routed = _trunk(cfg, params, tokens)
    return jnp.stack([r[1] for r in routed])


def transformer_held_counts(cfg: TransformerConfig, params: dict,
                            tokens: jnp.ndarray):
    """What the layers that hold a share of the experts (`experts_held`) did
    with this batch, one row a layer with experts: ([layers, n_experts]
    int32 token-slots the router sent to each of all its experts, [layers,
    3] int32 rows the held experts computed, rows the layer gathered for
    them, whether the further buffers ran). Nothing was dropped where rows
    computed equals the slots of the held experts. A forward pass of its
    own, as `transformer_expert_counts`."""
    _x, routed = _trunk(cfg, params, tokens)
    return (jnp.stack([r[1] for r in routed]),
            jnp.stack([r[2] for r in routed]))


def _attn(cfg: TransformerConfig, layer: dict, x: jnp.ndarray,
          positions: jnp.ndarray, *, h_local: int, tp_axis: Optional[str],
          sp_axis: Optional[str], window: int = 0) -> jnp.ndarray:
    """The attention half of a layer: what is added to the residual."""
    b, t, _ = x.shape
    dh = cfg.head_dim
    y = _norm(cfg, x, layer, "ln1")
    if cfg.kv_latent:
        return _latent_attn(cfg, layer, y, positions, tp_axis=tp_axis,
                            sp_axis=sp_axis)
    # A projection's product is a token-major row, [b, t, heads x width]:
    # 128 lanes dense whatever a head's width. Where nothing stands between
    # it and the rotation, q and k are rotated there and the cut into the
    # [b, heads, t, width] that the attention wants is the same pass
    # (`_rope_heads`). A norm of q and k stands between: the heads are cut
    # first, as they were, then normed and rotated where they are
    # (`_norm_and_rope`: one pass where a kernel is selected).
    rotate = bool(window or cfg.rope_full_layers)
    normed = cfg.qk_norm or cfg.qk_norm_heads
    if cfg.n_kv_heads:
        if tp_axis is not None and lax.axis_size(tp_axis) > 1:
            raise NotImplementedError(
                "grouped-query attention runs at tp 1 (its key/value heads "
                "are not yet cut over tp)")
        q, k, v = (y @ layer[w] for w in ("w_q", "w_k", "w_v"))
        if cfg.attn_out_gate:       # `w_q`: [every head's queries | its gate]
            q, gate = jnp.split(q, 2, axis=-1)
        q, k, v = (_rope_heads(
            a, positions, cfg.rope_theta, a.shape[2] // dh,
            ((dh, rotated and rotate and not normed),))[0]
            for a, rotated in ((q, True), (k, True), (v, False)))
    else:
        if tp_axis is not None:
            qkv = column_parallel(y, layer["w_qkv"], axis=tp_axis)
        else:
            qkv = y @ layer["w_qkv"]                          # (b, t, 3d/tp)
        # w_qkv columns are packed per head ([head][q|k|v][dh]) so a
        # contiguous tp column shard holds whole heads and the sharded
        # forward equals the single-device one.
        q, k, v = _rope_heads(
            qkv, positions, cfg.rope_theta, h_local,
            ((dh, rotate and not normed),) * 2 + ((dh, False),))
    if normed:
        with jax.named_scope("qk_norm"):
            q, k = (_norm_and_rope(cfg, a, layer[n],
                                   positions if rotate else None, tp_axis)
                    for a, n in ((q, "q_norm"), (k, "k_norm")))
    if cfg.attn_scale:      # the attention scales by dh ** -0.5 itself
        q = q * (cfg.attn_scale * dh ** 0.5)
    if sp_axis is not None:
        o = ring_attention(q, k, v, axis=sp_axis, causal=True, window=window)
    else:
        o = local_attention(q, k, v, window)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, h_local * dh)
    if cfg.attn_out_gate:
        with jax.named_scope("out_gate"):   # recomputed, as `gate_norm` is
            o = jax.checkpoint(lambda o, gate: (
                o.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(o.dtype))(o, gate)
    if tp_axis is not None:
        return row_parallel(o, layer["w_proj"], axis=tp_axis)
    return o @ layer["w_proj"]


def _latent_attn(cfg: TransformerConfig, layer: dict, y: jnp.ndarray,
                 positions: jnp.ndarray, *, tp_axis: Optional[str],
                 sp_axis: Optional[str]) -> jnp.ndarray:
    """Latent attention of the normed input ``y``, in its up-projected form
    (what training computes; scoring against the latent itself is a serving
    rewrite). Queries: a `q_latent`-wide normed latent, up-projected a head
    at a time into an unrotated part (`d_head`) and a rotated one
    (`d_rope`); with no query latent (`q_latent` 0) one product `w_q` gives
    the same row. Keys and values: `w_dkv` gives a `kv_latent`-wide latent and
    ONE `d_rope`-wide rotary key a token; the normed latent is up-projected
    a head at a time into an unrotated key (`d_head`) and a value
    (`d_value`). RoPE turns the rotated parts alone, and under
    `rope_full_layers` False nothing (a layer that leaves positions to its
    neighbours: both parts are scored as they stand); head j scores (q_j
    k_j^T + q_rope_j k_rope^T) x (d_head + d_rope) ** -0.5
    (`local_attention`: the shared key is read through the kernel's index
    map, never broadcast). The heads here (`heads_held`, or all) add their
    part of the output projection. Scopes: `q_latent` (or `q_proj` where
    there is no query latent), `kv_latent` (down, norm, up), `rope` (and the
    query row's cut into heads), `out`."""
    if tp_axis is not None and lax.axis_size(tp_axis) > 1:
        raise NotImplementedError(
            "latent attention runs at tp 1: a rank's heads are `heads_held`, "
            "and the exchange that sums their parts is not built")
    b, t, _ = y.shape
    h, dh, dr, dv = cfg.n_heads_here, cfg.head_dim, cfg.d_rope, cfg.value_dim

    if cfg.q_latent:
        with jax.named_scope("q_latent"):
            c_q = _rms_norm(y @ layer["w_dq"], layer["q_latent_norm"],
                            cfg.norm_eps)
            q_row = c_q @ layer["w_uq"]     # (b, t, h x [unrotated | rotated])
    else:
        with jax.named_scope("q_proj"):
            q_row = y @ layer["w_q"]
    with jax.named_scope("kv_latent"):
        down = y @ layer["w_dkv"]
        c_kv = _rms_norm(down[..., :cfg.kv_latent], layer["kv_latent_norm"],
                         cfg.norm_eps)
        k_rope = down[:, None, :, cfg.kv_latent:]       # one head for all
        kv = _cut_heads(c_kv @ layer["w_ukv"], h)
        k, v = kv[..., :dh], kv[..., dh:]
    with jax.named_scope("rope"):       # and the query row's cut into heads
        turns = cfg.rope_full_layers
        q, q_rope = _rope_heads(q_row, positions, cfg.rope_theta, h,
                                ((dh, False), (dr, turns)))
        if turns:
            k_rope = _rope(k_rope, positions, cfg.rope_theta)
    if sp_axis is not None:
        o = ring_attention(q, k, v, axis=sp_axis, causal=True,
                           rope=(q_rope, k_rope))
    else:
        o = local_attention(q, k, v, rope=(q_rope, k_rope))
    with jax.named_scope("out"):
        return o.transpose(0, 2, 1, 3).reshape(b, t, h * dv) @ layer["w_proj"]


def _ssm_mixer(cfg: TransformerConfig, layer: dict, x: jnp.ndarray, *,
               tp_axis: Optional[str], sp_axis: Optional[str]) -> jnp.ndarray:
    """A state-space (Mamba-2) layer's first half: what is added to the
    residual. One product gives the gate z, the convolution's channels and
    a dt a head; the channels pass a causal depthwise convolution
    (`ssm_conv` taps, with bias) and silu and are cut into x (heads x head
    width), B and C (one state-wide vector each, for all heads); dt =
    softplus(dt + dt_bias), A = -exp(a_log); `parallel.ssm.scan` computes
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t in its
    chunked form; RMSNorm(y x silu(z)) over the whole inner width (gate
    first, then norm) and the out-projection. Scopes: `in_proj`, `conv`,
    `scan`, `gate_norm`, `out_proj`. The state runs along the whole
    sequence and every head's B and C are the one group's: `sp` > 1 and
    `tp` > 1 are refused."""
    for axis in (tp_axis, sp_axis):
        if axis is not None and lax.axis_size(axis) > 1:
            raise NotImplementedError(
                f"a state-space layer runs at tp 1 and sp 1 ({axis!r} has "
                f"{lax.axis_size(axis)} ranks): its state would cross "
                f"sequence shards and its heads share one B and C")
    from ..parallel import ssm      # a program without such a layer pays
    #                                 no import for it
    b, t, _ = x.shape
    inner, h, n = cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_state
    y = _norm(cfg, x, layer, "ln1")
    with jax.named_scope("in_proj"):
        proj = y @ layer["w_ssm_in"]
        z, dt = proj[..., :inner], proj[..., 2 * inner + 2 * n:]
    with jax.named_scope("conv"):
        xs, b_in, c_in = ssm.conv_silu(
            proj, layer["conv_w"], layer["conv_b"], start=inner,
            cuts=(inner, inner + n))
    with jax.named_scope("scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"])
        o = ssm.scan(xs.reshape(b, t, h, cfg.ssm_head_dim), dt,
                     -jnp.exp(layer["a_log"]), b_in, c_in, layer["d_skip"],
                     cfg.ssm_chunk).reshape(b, t, inner)
    with jax.named_scope("gate_norm"):
        o = _rms_norm(o * jax.nn.silu(z), layer["ssm_norm"], cfg.norm_eps)
    with jax.named_scope("out_proj"):
        return o @ layer["w_ssm_out"]


def _kept_as_rounded(x):
    """x as it stands, where the backward pass keeps it. The compiler may
    keep more precision than it is asked for: of a float32 result rounded to
    bfloat16 and read again as float32 by what is recomputed, it keeps the
    float32, twice the bytes, until the backward pass (z, q and k and the
    gated output of a delta-rule layer: 270 MB a layer at 8192 tokens). No
    arithmetic: a fence the two conversions do not meet across."""
    return lax.optimization_barrier(x)


def _l2_normed(x, eps: float = 1e-6):
    """x / sqrt(sum x^2 + eps) over the last axis, float32 inside."""
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True)
                           + eps)


def _l2_normed_rows(x, heads: int, scale: float = 1.0):
    """The rows x [b, t, heads x width], of x's type, with each head's values
    L2-normed (`_l2_normed`) and scaled by the constant ``scale``: float32
    inside, rounded once. Heads of 128 lanes are one Pallas kernel each way
    over the rows as they stand where a kernel can run (`choice.HEAD_NORM`,
    `xla/head_norm_kernels.py`: its backward keeps x alone); else XLA's
    passes over the four dimensions."""
    b, t, width = x.shape
    run = choice.decide(choice.HEAD_NORM, t, width, width // heads, x.dtype)
    if run:
        return head_norm_kernels.l2_norm(x, scale=scale,
                                         interpret=run.interpret)
    out = _l2_normed(x.reshape(b, t, heads, -1))
    return (out if scale == 1.0 else out * scale).astype(x.dtype).reshape(
        x.shape)


def _delta_layer_alone(tp_axis: Optional[str], sp_axis: Optional[str]):
    """A delta-rule layer's state runs along the whole sequence and its
    convolution mixes a head's neighbours in time: `sp` > 1 and `tp` > 1 are
    refused."""
    for axis in (tp_axis, sp_axis):
        if axis is not None and lax.axis_size(axis) > 1:
            raise NotImplementedError(
                f"a delta-rule layer runs at tp 1 and sp 1 ({axis!r} has "
                f"{lax.axis_size(axis)} ranks): its state would cross "
                f"sequence shards and its heads are not cut")


def _head_norm_gated(cfg: TransformerConfig, o: jnp.ndarray, scale, act: str,
                     *gate_from):
    """RMSNorm over each head's values of o [b, t, heads, width] (a plain
    ``scale`` [width]) x ``act`` ("silu" or "sigmoid") of the gate's
    float32 pre-activation: norm first, gate after. ``gate_from``: the
    pre-activation as rows z [b, t, heads x width], or g_in [b, t, rank] and
    w [rank, heads x width], whose product it is. Heads of 128 lanes are one
    Pallas kernel each way over the rows where a kernel can run
    (`choice.HEAD_NORM`, `xla/head_norm_kernels.py`): the product is taken
    inside it, its backward keeps o, the scale and ``gate_from`` alone and
    computes the statistics and the gate again, so nothing is recomputed
    around it and its bfloat16 result needs no fence. On the plain path the
    whole is recomputed in the backward pass from o and ``gate_from`` as
    they stand (its own `jax.checkpoint`: left to the compiler, a float32
    copy of o is what it keeps)."""
    b, t, heads, width = o.shape
    product = len(gate_from) == 2
    run = choice.decide(
        choice.HEAD_NORM, t, heads * width, width, o.dtype,
        gate_from[0].shape[-1] if product else 0,
        also=scale.shape == (width,) and all(g.dtype == o.dtype
                                             for g in gate_from))
    if run:
        return head_norm_kernels.gated_rms_norm(
            o.reshape(b, t, -1), scale, *gate_from, act=act,
            eps=cfg.norm_eps, interpret=run.interpret).reshape(o.shape)

    def plain(o, scale, *gate_from):
        pre = jnp.dot(*gate_from, preferred_element_type=jnp.float32) \
            if product else gate_from[0].astype(jnp.float32)
        gate = jax.nn.silu(pre) if act == "silu" else jax.nn.sigmoid(pre)
        return (_rms_norm(o, scale, cfg.norm_eps).astype(jnp.float32)
                * gate.reshape(o.shape)).astype(o.dtype)
    return _kept_as_rounded(jax.checkpoint(plain)(o, scale, *gate_from))


def _gdn_mixer(cfg: TransformerConfig, layer: dict, x: jnp.ndarray, *,
               tp_axis: Optional[str], sp_axis: Optional[str]) -> jnp.ndarray:
    """A delta-rule (linear attention) layer's first half: what is added to
    the residual. One product gives q | k | v | z (queries and keys of
    `gdn_key_heads` heads, values and the gate of `gdn_value_heads`), a
    second b | a, one of each a value head; q, k and v pass a causal
    depthwise convolution (`gdn_conv` taps, no bias) and silu; each head's q
    and k are L2-normed (x / sqrt(sum x^2 + 1e-6)) and q scaled by
    gdn_key_dim ** -0.5; beta = sigmoid(b), g = -exp(a_log) x softplus(a +
    dt_bias), float32; `parallel.delta.delta_scan` computes S_t = exp(g_t)
    S_{t-1}, S_t += k_t (beta_t (v_t - S_t^T k_t))^T, o_t = S_t^T q_t in its
    chunked form, value head h over key head h // (value heads / key heads);
    then RMSNorm over each head's values (`gdn_norm`, a plain scale) x
    silu(z): norm first, gate after, where `_ssm_mixer` gates first and
    norms the whole width; and the out-projection. Scopes: `in_proj`,
    `conv`, `prep` (the L2 norms, beta and g), `scan`, `gate_norm`,
    `out_proj`. At heads of 128 lanes on a TPU the norms are the kernel pair
    of `xla/head_norm_kernels.py` over the ROWS [b, t, heads x 128] the
    convolution's kernels write and the scan's read (`_l2_normed_rows`,
    `_head_norm_gated`): q, k, v and o are cut into heads by reshapes that
    cancel against the scan's own, and no array is laid out again between
    `conv` and `out_proj`. What runs again in the backward pass and what is
    kept: the convolution and the L2 norms are one recomputed function of
    the products as they stand (`scan_operands`), its results fenced as
    rows: the convolution runs again for q and k, whose norms' backward
    kernels read it and nothing else, and the norms' own results are not
    computed again (the scan keeps its operands: the compiler drops what
    nobody reads); the gated norm keeps o, z and its scale (the kernel's
    inputs) and runs once each way, where the plain path recomputes it
    under its own `jax.checkpoint`. `sp` > 1 and
    `tp` > 1 are refused (`_delta_layer_alone`)."""
    _delta_layer_alone(tp_axis, sp_axis)
    from ..parallel import delta, ssm   # a program without such a layer
    #                                     pays no import for it
    b, t, _ = x.shape
    hk, hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    kw, vw = cfg.gdn_widths
    f32 = jnp.float32
    y = _norm(cfg, x, layer, "ln1")
    with jax.named_scope("in_proj"):
        # the convolution's channels and the gate as two products of the one
        # leaf's columns: cut after ONE product, the backward pass keeps the
        # 12288-wide row AND its 8192-wide part (128 MB more a layer at 8192
        # tokens: PERF.md section 6, PR 45)
        w = layer["w_gdn_in"]
        qkv, z = y @ w[:, :2 * kw + vw], _kept_as_rounded(
            y @ w[:, 2 * kw + vw:])
        beta, a = jnp.split(y @ layer["w_gdn_ba"], 2, axis=-1)
    def scan_operands(qkv, beta, a, conv_w, a_log, dt_bias):
        with jax.named_scope("conv"):
            parts = ssm.conv_silu(qkv, conv_w, cuts=(kw, 2 * kw))
        with jax.named_scope("prep"):
            q, k, v = parts
            g = -jnp.exp(a_log) * jax.nn.softplus(a.astype(f32) + dt_bias)
            return (_l2_normed_rows(q, hk, cfg.gdn_key_dim ** -0.5),
                    _l2_normed_rows(k, hk), v, g,
                    jax.nn.sigmoid(beta.astype(f32)))
    # recomputed in the backward pass from the products as they stand: the
    # convolution's output before and after silu and q and k before their
    # norms are 320 MB a layer at 8192 tokens that only elementwise ops read
    # (q, k and v stay the ROWS the kernels write and the scan's read up to
    # here: a fence over their four-dimensional form is an array of its own,
    # a copy each way and layer, where the reshapes on either side cancel)
    q, k, v, g, beta = _kept_as_rounded(jax.checkpoint(scan_operands)(
        qkv, beta, a, layer["conv_w"], layer["a_log"], layer["dt_bias"]))
    with jax.named_scope("scan"):
        o = delta.delta_scan(
            q.reshape(b, t, hk, -1), k.reshape(b, t, hk, -1),
            v.reshape(b, t, hv, -1), g, beta, cfg.gdn_chunk)
    with jax.named_scope("gate_norm"):
        o = _head_norm_gated(cfg, o, layer["gdn_norm"], "silu", z)
    with jax.named_scope("out_proj"):
        return o.reshape(b, t, vw) @ layer["w_gdn_out"]


def _kda_mixer(cfg: TransformerConfig, layer: dict, x: jnp.ndarray, *,
               tp_axis: Optional[str], sp_axis: Optional[str]) -> jnp.ndarray:
    """A "kda" layer's first half: the delta rule of `_gdn_mixer` with a
    decay a key CHANNEL, what is added to the residual. One product gives q
    | k | v, a second f | g | b: the `kda_rank`-wide inputs of the decay's
    and of the gate's low-rank maps, and a write strength a value head; q,
    k and v pass the convolution, silu, the L2 norms and q's scale as a
    delta-rule layer's (`_gdn_mixer`); beta = sigmoid(b); the decay, one
    number a value head, token and key channel, is g = -exp(a_log[head]) x
    softplus(f w_kda_f + dt_bias), float32 (scope `decay`);
    `parallel.delta.delta_scan` computes S_t = Diag(exp(g_t)) S_{t-1}, S_t +=
    k_t (beta_t (v_t - S_t^T k_t))^T, o_t = S_t^T q_t in its chunked form;
    then RMSNorm over each head's values (`kda_norm`, a plain scale) x
    sigmoid(g w_kda_g): norm first, gate after, a sigmoid where `_gdn_mixer`
    has silu; and the out-projection. Scopes: `in_proj`, `conv`, `prep`,
    `decay`, `scan`, `gate_norm`, `out_proj`. The half is ONE function of
    the stream that the backward pass computes again, keeping of it what
    the scan names `parallel.delta.KEPT` alone (the state before each
    chunk, so the chain over the chunks runs once each way; from the
    kernels the scan's output too, so the forward kernel does); the norms'
    kernels (`_l2_normed_rows`, `_head_norm_gated`: rows in, rows out, the
    gate's product inside) keep their inputs alone and hold no
    recomputation of their own, so each runs forward twice, where the plain
    gated norm's inner `jax.checkpoint` runs it three times: the decay
    is [t, heads x key width] float32, 134 MB a layer at 8192 tokens and 32
    heads of 128, the 12288-wide row 201 MB and the scan's and the gate's
    outputs 67 MB each, and with them kept the step of two periods does not
    fit a chip (16.6 GB compiled for the v5e, 13.8 so: PERF.md section 6,
    PR 48); the in-projection is the one matrix product that runs again.
    `sp` > 1 and `tp` > 1 are refused (`_delta_layer_alone`)."""
    _delta_layer_alone(tp_axis, sp_axis)
    from ..parallel import delta, ssm
    b, t, _ = x.shape
    hv, r, w = cfg.gdn_value_heads, cfg.kda_rank, cfg.gdn_widths[0]
    f32 = jnp.float32

    def half(x, layer):
        y = _norm(cfg, x, layer, "ln1")
        with jax.named_scope("in_proj"):
            qkv = y @ layer["w_kda_in"]
            f_in, g_in, beta = jnp.split(y @ layer["w_kda_low"], (r, 2 * r),
                                         axis=-1)
        with jax.named_scope("conv"):
            q, k, v = ssm.conv_silu(qkv, layer["conv_w"], cuts=(w, 2 * w))
        with jax.named_scope("prep"):
            q, k = (_l2_normed_rows(part, cfg.gdn_key_heads, scale).reshape(
                b, t, cfg.gdn_key_heads, -1)
                for part, scale in ((q, cfg.gdn_key_dim ** -0.5), (k, 1.0)))
            v = v.reshape(b, t, hv, cfg.gdn_value_dim)
            beta = jax.nn.sigmoid(beta.astype(f32))
        with jax.named_scope("decay"):
            g = -jnp.exp(layer["a_log"])[:, None] * jax.nn.softplus(
                jnp.dot(f_in, layer["w_kda_f"], preferred_element_type=f32)
                + layer["dt_bias"]).reshape(b, t, hv, cfg.gdn_key_dim)
        with jax.named_scope("scan"):
            o = delta.delta_scan(q, k, v, g, beta, cfg.gdn_chunk)
        with jax.named_scope("gate_norm"):
            o = _head_norm_gated(cfg, o, layer["kda_norm"], "sigmoid", g_in,
                                 layer["w_kda_g"])
        with jax.named_scope("out_proj"):
            return o.reshape(b, t, -1) @ layer["w_kda_out"]
    mine = ("ln1", "ln1_b", "w_kda_in", "w_kda_low", "conv_w", "a_log",
            "dt_bias", "w_kda_f", "w_kda_g", "kda_norm", "w_kda_out")
    return jax.checkpoint(
        half, policy=jax.checkpoint_policies.save_only_these_names(
            delta.KEPT))(x, {k: layer[k] for k in mine if k in layer})


def _mamba_mixer(cfg: TransformerConfig, layer: dict, x: jnp.ndarray):
    """A Mamba-1 layer's first half: (what is added to the residual, {"memory":
    the scan's output}). One product gives x and the gate z, inner =
    ssm_expand x d_model wide each; x passes a causal depthwise convolution
    (`ssm_conv` taps, with bias) and silu; from the convolved x one product
    gives dt's low-rank form, B and C (ssm_state wide, one vector a token for
    all channels), and dt = softplus(dt_low w_ssm_dt + dt_bias) a channel; A =
    -exp(a_log) [inner, state]; `parallel.ssm.selective_scan` computes
    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c],
    y_t[c] = sum_n C_t[n] S_t[c, n] + D[c] x_t[c]; then (y x silu(z))
    w_ssm_out: no norm here. The memory is y, the scan's output with its skip
    term, BEFORE the gate. Scopes: `in_proj`, `conv`, `x_proj` (with dt's
    projection and softplus), `scan`, `gate`, `out_proj`."""
    from ..parallel import ssm      # a program without such a layer pays
    #                                 no import for it
    inner, n, r = cfg.mamba_inner, cfg.ssm_state, cfg.ssm_dt_rank
    y = _norm(cfg, x, layer, "ln1")
    with jax.named_scope("in_proj"):
        proj = y @ layer["w_ssm_in"]
        z = proj[..., inner:]
    with jax.named_scope("conv"):
        xs = ssm.conv_silu(proj, layer["conv_w"], layer["conv_b"])
    with jax.named_scope("x_proj"):
        dt, b_in, c_in = jnp.split(xs @ layer["w_ssm_x"], [r, r + n], axis=-1)
        dt = jax.nn.softplus((dt @ layer["w_ssm_dt"]).astype(jnp.float32)
                             + layer["dt_bias"])
    with jax.named_scope("scan"):
        o = ssm.selective_scan(xs, dt, -jnp.exp(layer["a_log"]), b_in, c_in,
                               layer["d_skip"], cfg.ssm_chunk)
    with jax.named_scope("gate"):
        gated = o * jax.nn.silu(z)
    with jax.named_scope("out_proj"):
        return gated @ layer["w_ssm_out"], {"memory": o}


def _gmu_mixer(cfg: TransformerConfig, layer: dict, x: jnp.ndarray,
               memory: jnp.ndarray) -> jnp.ndarray:
    """A gated memory unit: out(memory x silu(in(x))), elementwise in the
    token: a layer with no mixing over the sequence of its own. Scope `gmu`."""
    y = _norm(cfg, x, layer, "ln1")
    with jax.named_scope("gmu"):
        return (memory * jax.nn.silu(y @ layer["w_gmu_in"])) \
            @ layer["w_gmu_out"]


def lambda_init(depth):
    """Differential attention's `lambda_init` of a layer at ``depth``."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * depth)


def _diff_attn(cfg: TransformerConfig, layer: dict, x: jnp.ndarray, *,
               window: int, depth, kv: Optional[tuple] = None):
    """Differential attention without positions: (what is added to the
    residual, {"kv": the keys and values as the attention read them}). The
    n_heads query heads of `d_head` are n_heads / 2 differential heads: head
    i is the difference of two softmaxes, o_i = (softmax(q_i1 k_j1^T) - lambda
    softmax(q_i2 k_j2^T)) [v_j1 | v_j2], over the key/value PAIR j = i //
    (n_heads / n_kv_heads), its values both heads' side by side (2 x d_head
    wide); lambda = exp(lambda_q1 . lambda_k1) - exp(lambda_q2 . lambda_k2) +
    lambda_init(depth), float32; then RMSNorm over each o_i's 2 x d_head
    values (`diff_norm`) x (1 - lambda_init), and the output projection.
    Each softmax is one head of ONE `local_attention` call (the fused kernel
    where it is selected) with values wider than its scores' heads: `w_q`'s
    columns are laid out [pair j][softmax s][head r of the pair's n_heads /
    n_kv_heads] so that the kernel's grouping (query head m reads key/value
    head m // group) hands query (j, s, r) key 2 j + s; value head 2 j + s is
    the pair's [v_j1 | v_j2] for either s. The subtraction, the norm and the
    scale run in float32 under scope `diff`. ``kv``: another layer's keys and
    values in that layout (a cross layer: queries of its own, no `w_k`,
    `w_v`)."""
    b, t, _ = x.shape
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    group = h // hk

    def projected(name):
        out = y @ layer["w_" + name]
        return out + layer["b_" + name] if cfg.attn_bias else out
    y = _norm(cfg, x, layer, "ln1")
    q = _cut_heads(projected("q"), h)
    if kv is None:
        k = _cut_heads(projected("k"), hk)
        v = jnp.repeat(_cut_heads(projected("v"), hk // 2), 2, axis=1)
    else:
        k, v = kv
    fused = fused_attention_selected(q.shape, q.dtype, 0, v.shape[3])
    perfvars.note("attn_kinds", ("diff", "fused" if fused else "plain"))
    o = local_attention(q, k, v, window)            # [b, h, t, 2 dh]
    with jax.named_scope("diff"):
        f32 = jnp.float32
        start = lambda_init(depth)
        lam = jnp.exp(jnp.sum(layer["lambda_q1"].astype(f32)
                              * layer["lambda_k1"].astype(f32))) \
            - jnp.exp(jnp.sum(layer["lambda_q2"].astype(f32)
                              * layer["lambda_k2"].astype(f32))) + start
        o = o.reshape(b, hk // 2, 2, group, t, 2 * dh).astype(f32)
        o = _rms_norm(o[:, :, 0] - lam * o[:, :, 1],
                      layer["diff_norm"].astype(f32), cfg.norm_eps) \
            * (1.0 - start)
        o = o.astype(x.dtype).transpose(0, 3, 1, 2, 4).reshape(b, t, h * dh)
    out = o @ layer["w_proj"]
    return (out + layer["b_proj"] if cfg.attn_bias else out), {"kv": (k, v)}


def _whole_vector_norm(cfg: TransformerConfig, x: jnp.ndarray,
                       scale: jnp.ndarray, tp_axis: Optional[str]):
    """RMSNorm of q or k over the whole d_model-wide vector, before it is
    cut into heads: x is (b, heads_local, t, head_dim), `scale` this rank's
    columns of the learned scale, in the order [head][head_dim]."""
    h, dh = x.shape[1], x.shape[3]
    ss = jnp.sum(jnp.square(x.astype(jnp.float32)), axis=(1, 3), keepdims=True)
    if tp_axis is not None:
        ss = lax.psum(ss, tp_axis)
    x = (x * lax.rsqrt(ss / cfg.d_model + cfg.norm_eps)).astype(x.dtype)
    return x * scale.reshape(h, 1, dh)


def _norm_and_rope(cfg: TransformerConfig, x: jnp.ndarray, scale: jnp.ndarray,
                   positions: Optional[jnp.ndarray], tp_axis: Optional[str]):
    """q or k, cut into heads (b, heads_local, t, head_dim): normed by the
    model's norm of q and k (`qk_norm`: the whole vector's; `qk_norm_heads`:
    each head's) and, unless ``positions`` is None, rotated. Where the
    backend and the shape select it (heads of 128, one of the two norms, a
    whole-vector norm on one tp rank: it sums over the heads that are
    here) norm and rotation are ONE kernel each way,
    `pallas_kernels.norm_rope`: the norm's arithmetic as it stands below,
    float32 inside and rounded where it rounds; elsewhere the norm, then
    `_rope`. The kernel scales by the learned scale itself and turns the
    whole head: a scale of 1 + w (`norm_unit_offset`) and a rotated share
    (`rotary_dim`: the head's first values turned, the rest passed) take the
    plain path. The caller's scope is `qk_norm`."""
    b, h, t, dh = x.shape
    alone = tp_axis is None or lax.axis_size(tp_axis) == 1
    part = cfg.rotary_dim if 0 < cfg.rotary_dim < dh else 0
    run = choice.decide(
        choice.NORM_ROPE, b * h, t, dh, x.dtype, h if cfg.qk_norm else 0,
        also=positions is not None and cfg.qk_norm != cfg.qk_norm_heads
        and (alone or not cfg.qk_norm) and not part
        and not cfg.norm_unit_offset)
    if run:
        cos, sin = _rope_table(positions, cfg.rope_theta, dh, np.arange(dh))
        return pk.norm_rope(
            x, scale.reshape(h, dh) if cfg.qk_norm else scale, cos, sin,
            eps=cfg.norm_eps, denom=cfg.d_model if cfg.qk_norm else dh,
            interpret=run.interpret)
    if cfg.qk_norm:
        x = _whole_vector_norm(cfg, x, scale, tp_axis)
    if part and positions is not None:
        # recomputed in the backward pass, as `_gdn_mixer`'s `gate_norm` is
        def normed_and_turned(x, scale):
            x = _scaled_rms(cfg, x, scale) if cfg.qk_norm_heads else x
            return jnp.concatenate(
                [_rope(x[..., :part], positions, cfg.rope_theta),
                 x[..., part:]], axis=-1)
        return jax.checkpoint(normed_and_turned)(x, scale)
    if cfg.qk_norm_heads:
        x = _scaled_rms(cfg, x, scale)
    return x if positions is None else _rope(x, positions, cfg.rope_theta)


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


# `head_loss` cuts its tokens into blocks whose float32 logits stay about
# under this many bytes (PERF.md section 6, PR 43: what 1 to 8 blocks cost)
_HEAD_BLOCK_BYTES = 1 << 29


def _head_block(tokens: int, vocab: int) -> int:
    """The tokens of one block of `head_loss`: the fewest equal blocks whose
    float32 logits [block, vocab] each fit `_HEAD_BLOCK_BYTES`, a block
    rounded up to whole tiles of 128 tokens (so the last one may be
    shorter, and under 128 tokens there is one block)."""
    blocks = -(-tokens * vocab * 4 // _HEAD_BLOCK_BYTES)
    return min(tokens, -(-tokens // (128 * blocks)) * 128)


def head_loss(cfg: TransformerConfig, params: dict, x: jnp.ndarray,
              labels: jnp.ndarray) -> jnp.ndarray:
    """The mean cross-entropy of the model's head over `_trunk`'s stream
    ``x`` [b, t, d] against ``labels`` [b, t]: `_xent` of `_forward`'s
    logits, computed a block of tokens at a time (`_head_block`) with the
    gradients of the stream and of the head made in the same pass
    (`_blocked_xent`), so that no [tokens, vocab] array is held and no
    logits are computed a second time. A tied head is read, and its
    gradient written, in the embedding's own [vocab, d] layout."""
    with jax.named_scope("head_loss"):
        x = _norm(cfg, x, params, "ln_f").reshape(-1, cfg.d_model)
        w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        block = _head_block(x.shape[0], cfg.vocab)
        perfvars.note("head_loss_lowerings", "blocked")
        perfvars.note("head_loss_blocks", -(-x.shape[0] // block))
        # inside `shard_map` the head is the same on every data shard and
        # the stream is not: said here, so that the sum of the shards'
        # gradients is this cast's transpose, as it was the product's
        shards = tuple(jax.typeof(x).vma - jax.typeof(w).vma)
        if shards:
            w = lax.pcast(w, shards, to="varying")
        return _blocked_xent(x, w, labels.reshape(-1), cfg.tie_embeddings,
                             cfg.logits_divisor, block)


def _xent_blocks(x, w, labels, tied: bool, divisor: float, block: int,
                 grads: bool):
    """(loss, dx, dw) of `_blocked_xent`, ``dx`` and ``dw`` None unless
    ``grads``. Unrolled: a block's logits, its softmax and the logits'
    gradient live between its three products and nowhere else."""
    n = x.shape[0]
    vd = 0 if tied else 1       # where the vocabulary stands in w
    total, dxs, dw = jnp.zeros((), jnp.float32), [], None
    for lo in range(0, n, block):
        xb, lb = x[lo:lo + block], labels[lo:lo + block, None]
        z = lax.dot_general(
            xb, w, (((1,), (1 - vd,)), ((), ()))).astype(jnp.float32)
        if divisor != 1.0:
            z = z / divisor
        top = jnp.max(z, axis=-1, keepdims=True)
        e = jnp.exp(z - top)
        norm = jnp.sum(e, axis=-1, keepdims=True)
        total += jnp.sum(top + jnp.log(norm)
                         - jnp.take_along_axis(z, lb, axis=-1))
        if not grads:
            continue
        hot = lb == lax.broadcasted_iota(labels.dtype, z.shape, 1)
        dl = ((e / norm - hot) * (1.0 / (n * divisor))).astype(x.dtype)
        dxs.append(lax.dot_general(dl, w, (((1,), (vd,)), ((), ()))))
        pair = (dl, xb) if tied else (xb, dl)
        part = lax.dot_general(*pair, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
        dw = part if dw is None else dw + part
    if not grads:
        return total / n, None, None
    return total / n, jnp.concatenate(dxs), dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _blocked_xent(x, w, labels, tied: bool, divisor: float, block: int):
    """The mean over the tokens of ``x`` [n, d] of the cross-entropy of
    softmax((x @ head) / divisor) against ``labels`` [n], float32, ``block``
    tokens at a time; ``w`` is the head, [vocab, d] if ``tied`` and [d,
    vocab] if not. Differentiated, the forward pass makes both gradients
    while a block's logits are there (the loss is the last thing a step
    computes, so its cotangent is a scalar): the products take the
    operands' dtype and sum in float32, the head's gradient is summed over
    the blocks in float32 and rounded once, and the backward pass scales
    what was kept."""
    return _xent_blocks(x, w, labels, tied, divisor, block, False)[0]


def _blocked_xent_fwd(x, w, labels, tied, divisor, block):
    loss, dx, dw = _xent_blocks(x, w, labels, tied, divisor, block, True)
    return loss, (dx, dw)


def _blocked_xent_bwd(tied, divisor, block, kept, g):
    return tuple((g * d.astype(jnp.float32)).astype(d.dtype)
                 for d in kept) + (None,)


_blocked_xent.defvjp(_blocked_xent_fwd, _blocked_xent_bwd)


def transformer_train_step(cfg: TransformerConfig, mesh, lr: float = 1e-2, *,
                           dp_axis: str = "dp", tp_axis: str = "tp",
                           sp_axis: str = "sp", donate: bool = False):
    """Build the jitted DP×TP×SP train step over ``mesh``.

    Returns (step, param_specs): ``step(params, tokens, labels) -> (params,
    loss)`` where tokens/labels are global (batch, seq) arrays sharded
    (batch→dp, seq→sp) by shard_map, and params follow param_specs. With
    experts the loss is the cross-entropy plus `router_aux_coef` x the
    load-balancing loss of each data shard's own tokens. ``donate`` gives
    the step its `params` argument's buffers for the new parameters: the
    caller's old tree is gone after the call. (Compiled for a v5e chip the
    benchmark's OLMoE step holds 10.5 GB with it and 11.6 GB without:
    PERF.md section 6, PR 25.)
    """
    specs = transformer_param_specs(cfg, tp_axis)
    axis_names = set(mesh.axis_names)
    for a in (dp_axis, tp_axis, sp_axis):
        if a not in axis_names:
            raise ValueError(f"mesh is missing axis {a!r}")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if "ssm" in cfg.mixer_kinds and (sizes[tp_axis] > 1 or sizes[sp_axis] > 1):
        raise NotImplementedError(
            f"a model with state-space layers trains at tp 1 and sp 1 (this "
            f"mesh has {tp_axis} {sizes[tp_axis]}, {sp_axis} "
            f"{sizes[sp_axis]}): a layer's state would cross sequence "
            f"shards; shard its batch over {dp_axis}")
    if {"gdn", "kda"} & set(cfg.mixer_kinds) \
            and (sizes[tp_axis] > 1 or sizes[sp_axis] > 1):
        raise NotImplementedError(
            f"a model with delta-rule layers trains at tp 1 and sp 1 (this "
            f"mesh has {tp_axis} {sizes[tp_axis]}, {sp_axis} "
            f"{sizes[sp_axis]}): a layer's state would cross sequence shards "
            f"and its heads are not cut; shard its batch over {dp_axis}")
    if (cfg.diff_attn or set(cfg.mixer_kinds) & {"mamba", "gmu", "cross"}) \
            and (sizes[tp_axis] > 1 or sizes[sp_axis] > 1):
        raise NotImplementedError(
            f"a decoder-hybrid-decoder stack (Mamba-1 layers, gated memory "
            f"units, differential and cross attention) trains at tp 1 and sp "
            f"1 (this mesh has {tp_axis} {sizes[tp_axis]}, {sp_axis} "
            f"{sizes[sp_axis]}): its state and its side values are not cut; "
            f"shard its batch over {dp_axis}")
    reduce_axes = (dp_axis, sp_axis)
    choice.warm_kernel_imports()    # off the first trace's path (set-up time)

    def local_step(params, tokens, labels):
        def loss_fn(p):
            x, routed = _trunk(cfg, p, tokens, tp_axis=tp_axis,
                               sp_axis=sp_axis)
            loss = head_loss(cfg, p, x, labels)
            if routed and cfg.router_aux_coef:
                with jax.named_scope("aux_loss"):
                    loss = loss + cfg.router_aux_coef * load_balancing_loss(
                        routed, tokens.size)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # dp/sp shards saw different tokens: sum their param grads. The tp
        # direction needs no reduction — the f/g operators already produced
        # tp-correct grads (sharded params local, replicated params invariant).
        with jax.named_scope("optimizer"):
            grads = jax.tree_util.tree_map(
                lambda g: lax.psum(g, reduce_axes), grads)
            params = jax.tree_util.tree_map(
                lambda p, g: (p - lr * g).astype(p.dtype), params, grads)
        loss = lax.pmean(loss, reduce_axes)
        return params, loss

    data_spec = P(dp_axis, sp_axis)
    perfvars.note_step_fun(local_step.__name__)     # before anything is built
    step = jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, data_spec, data_spec),
        out_specs=(specs, P())), donate_argnums=(0,) if donate else ())
    return step, specs


# ---------------------------------------------------------------------------
# pipeline x expert-parallel variant: the remaining two axes of the 5-way
# parallelism matrix (SURVEY.md §2.5 rows PP and EP), composed in one step
# ---------------------------------------------------------------------------

def transformer_pp_moe_init(key, cfg: TransformerConfig, n_experts: int) -> dict:
    """Layer-stacked params for the pipelined MoE transformer: every layer
    tensor carries a leading (n_layers,) dim (sharded over 'pp'); the expert
    FFN weights add an (n_experts,) dim (sharded over 'ep')."""
    def dense(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(cfg.dtype)

    d, f, L, E = cfg.d_model, cfg.d_ff, cfg.n_layers, n_experts
    keys = jax.random.split(key, 6)
    return {
        "embed": dense(keys[0], (cfg.vocab, d), d ** -0.5),
        "ln_f": jnp.ones((d,), cfg.dtype),
        "ln1": jnp.ones((L, d), cfg.dtype),
        "w_qkv": dense(keys[1], (L, d, 3 * d), d ** -0.5),
        "w_proj": dense(keys[2], (L, d, d), (2 * d * L) ** -0.5),
        "ln2": jnp.ones((L, d), cfg.dtype),
        "w_gate": dense(keys[3], (L, d, E), d ** -0.5),
        "w_in": dense(keys[4], (L, E, d, f), d ** -0.5),
        "w_out": dense(keys[5], (L, E, f, d), (2 * f * L) ** -0.5),
    }


def transformer_pp_moe_specs(pp_axis: str, ep_axis: str) -> dict:
    """PartitionSpecs matching transformer_pp_moe_init."""
    lyr = P(pp_axis)
    return {
        "embed": P(), "ln_f": P(),
        "ln1": lyr, "w_qkv": lyr, "w_proj": lyr, "ln2": lyr,
        "w_gate": lyr,
        "w_in": P(pp_axis, ep_axis), "w_out": P(pp_axis, ep_axis),
    }


def _pp_moe_stage(cfg: TransformerConfig, n_experts: int, ep_axis: str,
                  capacity: int, stage_params: dict, x: jnp.ndarray,
                  positions: jnp.ndarray) -> jnp.ndarray:
    """One pipeline stage: this rank's block of layers, each a causal dense
    attention plus a top-1 MoE FFN routed over the 'ep' axis."""
    from ..parallel.ep import moe_dispatch_combine

    b, t, d = x.shape
    h, dh = cfg.n_heads, cfg.head_dim
    L_local = stage_params["w_qkv"].shape[0]
    for i in range(L_local):
        # -- attention (heads local: this config spends its devices on pp/ep)
        y = _rms_norm(x, stage_params["ln1"][i])
        q, k, v = _rope_heads(y @ stage_params["w_qkv"][i], positions,
                              10000.0, h, ((dh, True), (dh, True), (dh, False)))
        o = local_attention(q, k, v)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, d)
        x = x + o @ stage_params["w_proj"][i]

        # -- MoE FFN: route each token to its argmax expert over 'ep';
        # Switch-style scaling by the selected gate probability keeps the
        # router differentiable (argmax alone would never train w_gate)
        y = _rms_norm(x, stage_params["ln2"][i]).reshape(b * t, d)
        gate = jax.nn.softmax(y @ stage_params["w_gate"][i], axis=-1)
        eidx = jnp.argmax(gate, axis=-1)
        p_sel = jnp.take_along_axis(gate, eidx[:, None], axis=-1)
        w_in = stage_params["w_in"][i, 0]      # this rank's expert shard
        w_out = stage_params["w_out"][i, 0]

        def expert(tok):
            return jax.nn.gelu(tok @ w_in) @ w_out

        out = moe_dispatch_combine(y, eidx.astype(jnp.int32), expert,
                                   capacity=capacity, axis=ep_axis)
        x = x + (out * p_sel).reshape(b, t, d)
    return x


def transformer_pp_moe_host_params(params: dict, cfg: TransformerConfig,
                                   n_experts: int, stage: int,
                                   n_stages: int, expert: int) -> dict:
    """Numpy slice of one (pipeline stage, expert) shard of
    :func:`transformer_pp_moe_init` params, for the host-path inference
    engine (``tpu_mpi.infer``): the stage's slab of layer tensors plus
    ONLY this rank's expert FFN weights (w_in/w_out lose their expert
    dim). ``embed``/``ln_f`` ride along on every rank — stage 0 embeds,
    the last stage computes logits."""
    import numpy as np
    if cfg.n_layers % n_stages:
        raise ValueError(f"n_layers={cfg.n_layers} must divide over "
                         f"{n_stages} pipeline stages")
    if not (0 <= expert < n_experts):
        raise ValueError(f"expert {expert} out of range [0, {n_experts})")
    per = cfg.n_layers // n_stages
    lo, hi = stage * per, (stage + 1) * per

    def host(a):
        return np.ascontiguousarray(np.asarray(a, dtype=np.float32))

    return {
        "embed": host(params["embed"]),
        "ln_f": host(params["ln_f"]),
        "ln1": host(params["ln1"][lo:hi]),
        "w_qkv": host(params["w_qkv"][lo:hi]),
        "w_proj": host(params["w_proj"][lo:hi]),
        "ln2": host(params["ln2"][lo:hi]),
        "w_gate": host(params["w_gate"][lo:hi]),
        "w_in": host(params["w_in"][lo:hi, expert]),
        "w_out": host(params["w_out"][lo:hi, expert]),
    }


def transformer_pp_moe_train_step(cfg: TransformerConfig, mesh,
                                  n_experts: int, lr: float = 1e-2, *,
                                  dp_axis: str = "dp", pp_axis: str = "pp",
                                  ep_axis: str = "ep",
                                  microbatches: Optional[int] = None):
    """Jitted DP × PP × EP train step: batch sharded over 'dp', layers
    sharded over 'pp' (GPipe microbatch rotation via
    tpu_mpi.parallel.pp.pipeline_forward), expert FFNs sharded over 'ep'
    (padded-all_to_all routing via tpu_mpi.parallel.ep). Together with
    transformer_train_step (DP × TP × SP) this covers the full 5-axis
    parallelism matrix of SURVEY.md §2.5.

    Returns (step, param_specs); step(params, tokens, labels) -> (params,
    loss). n_experts must equal the 'ep' axis size (one expert per rank).
    """
    from ..parallel.pp import pipeline_forward

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for a in (dp_axis, pp_axis, ep_axis):
        if a not in sizes:
            raise ValueError(f"mesh is missing axis {a!r}")
    if n_experts != sizes[ep_axis]:
        raise ValueError(f"n_experts={n_experts} must equal the {ep_axis!r} "
                         f"axis size {sizes[ep_axis]}")
    if cfg.n_layers % sizes[pp_axis]:
        raise ValueError(f"n_layers={cfg.n_layers} must divide over "
                         f"{sizes[pp_axis]} pipeline stages")
    n_pp = sizes[pp_axis]
    m = microbatches or max(2, 2 * n_pp)
    specs = transformer_pp_moe_specs(pp_axis, ep_axis)

    def local_step(params, tokens, labels):
        b, t = tokens.shape
        if b % m:
            raise ValueError(f"local batch {b} must divide into {m} microbatches")
        positions = jnp.arange(t)
        capacity = max(1, 2 * (b // m) * t // n_experts)

        def loss_fn(p):
            stage = {k: p[k] for k in
                     ("ln1", "w_qkv", "w_proj", "ln2", "w_gate",
                      "w_in", "w_out")}
            e = p["embed"][tokens].reshape(m, b // m, t, cfg.d_model)

            def stage_fn(sp_, x):
                return _pp_moe_stage(cfg, n_experts, ep_axis,
                                     capacity, sp_, x, positions)

            acts = pipeline_forward(stage_fn, stage, e, axis=pp_axis)
            acts = acts.reshape(b, t, cfg.d_model)
            logits = (_rms_norm(acts, p["ln_f"])
                      @ p["embed"].T).astype(jnp.float32)
            perfvars.note("head_loss_lowerings", "whole")
            l = _xent(logits, labels)
            # only the last stage's emissions are the real model output
            last = lax.axis_index(pp_axis) == n_pp - 1
            return lax.psum(jnp.where(last, l, 0.0), pp_axis)

        loss, grads = jax.value_and_grad(loss_fn)(params)

        def reduce_leaf(path_key, g):
            if path_key in ("w_in", "w_out"):
                # ep-sharded experts: each rank owns its expert's grads, but
                # the batch is REPLICATED over ep — every replica's loss
                # back-propagates through the same expert via the all_to_all
                # transpose, so the raw grad is ep_size times the per-batch
                # gradient; normalize or experts train at an inflated lr
                return lax.psum(g, dp_axis) / sizes[ep_axis]
            if path_key in ("embed", "ln_f"):
                # fully replicated, with distinct per-stage contributions
                return lax.pmean(lax.psum(g, (dp_axis, pp_axis)), ep_axis)
            # pp-sharded, ep-replicated layer tensors
            return lax.pmean(lax.psum(g, dp_axis), ep_axis)

        grads = {k: reduce_leaf(k, g) for k, g in grads.items()}
        params = jax.tree_util.tree_map(
            lambda p_, g: (p_ - lr * g).astype(p_.dtype), params, grads)
        loss = lax.pmean(lax.pmean(loss, dp_axis), ep_axis)
        return params, loss

    data_spec = P(dp_axis, None)
    perfvars.note_step_fun(local_step.__name__)
    step = jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, data_spec, data_spec),
        out_specs=(specs, P())))
    return step, specs


# ---------------------------------------------------------------------------
# 4-axis variant: DP x TP x SP x PP in ONE step (VERDICT r3 #9). Layers are
# stacked over 'pp' (GPipe microbatch rotation), attention/FFN weights are
# Megatron-sharded over 'tp', the sequence is ring-attention-sharded over
# 'sp', and the batch over 'dp' — four simultaneously nontrivial axes.
# ---------------------------------------------------------------------------

def transformer_4d_init(key, cfg: TransformerConfig) -> dict:
    """Layer-stacked dense params: every layer tensor carries a leading
    (n_layers,) dim (sharded over 'pp'); within a layer the shapes match
    transformer_init's per-layer dicts."""
    def dense(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(cfg.dtype)

    d, f, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    keys = jax.random.split(key, 5)
    return {
        "embed": dense(keys[0], (cfg.vocab, d), d ** -0.5),
        "ln_f": jnp.ones((d,), cfg.dtype),
        "ln1": jnp.ones((L, d), cfg.dtype),
        "w_qkv": dense(keys[1], (L, d, 3 * d), d ** -0.5),
        "w_proj": dense(keys[2], (L, d, d), (2 * d * L) ** -0.5),
        "ln2": jnp.ones((L, d), cfg.dtype),
        "w_in": dense(keys[3], (L, d, f), d ** -0.5),
        "w_out": dense(keys[4], (L, f, d), (2 * f * L) ** -0.5),
    }


def transformer_4d_specs(pp_axis: str, tp_axis: str) -> dict:
    """PartitionSpecs matching transformer_4d_init: leading layer dim over
    pp; Megatron column/row sharding over tp within each layer."""
    return {
        "embed": P(), "ln_f": P(),
        "ln1": P(pp_axis), "ln2": P(pp_axis),
        "w_qkv": P(pp_axis, None, tp_axis),    # column-parallel
        "w_proj": P(pp_axis, tp_axis, None),   # row-parallel
        "w_in": P(pp_axis, None, tp_axis),
        "w_out": P(pp_axis, tp_axis, None),
    }


def transformer_4d_train_step(cfg: TransformerConfig, mesh, lr: float = 1e-2,
                              *, dp_axis: str = "dp", tp_axis: str = "tp",
                              sp_axis: str = "sp", pp_axis: str = "pp",
                              microbatches: Optional[int] = None):
    """Jitted DP x TP x SP x PP train step (the flagship on a 4-axis mesh):
    batch over dp, Megatron f/g matmuls over tp, ring attention over sp,
    GPipe stages over pp. Returns (step, param_specs); step(params, tokens,
    labels) -> (params, loss) with tokens/labels global (batch, seq) arrays
    sharded (batch->dp, seq->sp)."""
    from ..parallel.pp import pipeline_forward

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for a in (dp_axis, tp_axis, sp_axis, pp_axis):
        if a not in sizes:
            raise ValueError(f"mesh is missing axis {a!r}")
    if cfg.n_heads % sizes[tp_axis]:
        raise ValueError(f"n_heads={cfg.n_heads} must divide over tp size "
                         f"{sizes[tp_axis]}")
    if cfg.n_layers % sizes[pp_axis]:
        raise ValueError(f"n_layers={cfg.n_layers} must divide over "
                         f"{sizes[pp_axis]} pipeline stages")
    n_pp = sizes[pp_axis]
    m = microbatches or max(2, 2 * n_pp)
    specs = transformer_4d_specs(pp_axis, tp_axis)

    def local_step(params, tokens, labels):
        b, t = tokens.shape            # local (dp- and sp-sharded) block
        if b % m:
            raise ValueError(f"local batch {b} must divide into {m} "
                             f"microbatches")
        sp_idx = lax.axis_index(sp_axis)
        positions = sp_idx * t + jnp.arange(t)

        def loss_fn(p):
            stage = {k: p[k] for k in ("ln1", "w_qkv", "w_proj", "ln2",
                                       "w_in", "w_out")}
            e = p["embed"][tokens].reshape(m, b // m, t, cfg.d_model)

            def stage_fn(sp_, x):
                for i in range(sp_["w_qkv"].shape[0]):     # local layers
                    layer = {k: v[i] for k, v in sp_.items()}
                    x, _, _ = _attn_ffn_block(cfg, layer, x, positions,
                                              tp_axis=tp_axis,
                                              sp_axis=sp_axis)
                return x

            acts = pipeline_forward(stage_fn, stage, e, axis=pp_axis)
            acts = acts.reshape(b, t, cfg.d_model)
            logits = (_rms_norm(acts, p["ln_f"])
                      @ p["embed"].T).astype(jnp.float32)
            perfvars.note("head_loss_lowerings", "whole")
            l = _xent(logits, labels)
            # only the last stage's emissions are the real model output
            last = lax.axis_index(pp_axis) == n_pp - 1
            return lax.psum(jnp.where(last, l, 0.0), pp_axis)

        loss, grads = jax.value_and_grad(loss_fn)(params)

        def reduce_leaf(path_key, g):
            if path_key in ("embed", "ln_f"):
                # replicated everywhere; distinct contributions from each
                # dp/sp data shard and each pp stage (embed: the injected
                # activations on stage 0 + the logit matmul on the last)
                return lax.psum(g, (dp_axis, sp_axis, pp_axis))
            # pp-sharded layer stacks: dp/sp data shards sum; tp grads are
            # already correct from the f/g custom_vjp pair
            return lax.psum(g, (dp_axis, sp_axis))

        grads = {k: reduce_leaf(k, g) for k, g in grads.items()}
        params = jax.tree_util.tree_map(
            lambda p_, g: (p_ - lr * g).astype(p_.dtype), params, grads)
        loss = lax.pmean(loss, (dp_axis, sp_axis))
        return params, loss

    data_spec = P(dp_axis, sp_axis)
    perfvars.note_step_fun(local_step.__name__)
    step = jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, data_spec, data_spec),
        out_specs=(specs, P())))
    return step, specs
