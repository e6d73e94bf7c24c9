"""Plain reference of one rank's share of a train step of a language model
whose layers are linear attention with a delta rule whose decay is a vector,
one number a key channel (KDA), three in four, and latent attention without
positions, the first before a dense gated FFN and every other before routed
sigmoid experts beside a shared one (the Kimi Linear block, as
`yardstick/configs/kimi-linear-48b-a3b-1c.json` states it with what it
`assumed`). Straightforward `jax.numpy`, float32 and `highest` matmul
precision; no kernel, no chunk, no mesh and none of tpu_mpi. The KDA layer
is **the recurrence itself**, one token at a time (`lax.scan` over time),
the decay a full vector a head; latent attention is one full softmax over
the 192-wide concatenation [nope | the shared pe] under an explicit [t, t]
mask; the experts are a loop over the held ones.

`model` is the configuration file's published keys: `hidden_size`,
`num_hidden_layers`, `rms_norm_eps`, `linear_attn_config` (`num_heads`,
`head_dim`, `short_conv_kernel_size`, and the 1-based lists `kda_layers`
and `full_attn_layers`, of which the layers up to `num_hidden_layers` are
here), `num_attention_heads`, `kv_lora_rank`, `qk_nope_head_dim`,
`qk_rope_head_dim`, `v_head_dim`, `mla_use_nope` (true: nothing is rotated;
anything else is refused), `q_lora_rank` (null: one `q_proj`; anything else
is refused), `first_k_dense_replace` and `moe_layer_freq` (1),
`num_experts_per_token`, `moe_router_activation_func` (sigmoid),
`moe_renormalize`, `routed_scaling_factor`, `num_expert_group` and
`topk_group` (1: no group limit), and the share: `router_num_experts` score
a token, of which experts `[held_experts_first, held_experts_first +
num_experts)` are here; the vocabulary rows here are the embedding's and
the head's shapes. Parameters carry the model's names, every matrix stored
[in, out]:

  embed_tokens [V, d]   norm [d]   lm_head [d, V]   layers[i]:
    input_layernorm, post_attention_layernorm [d]
    KDA layer:  q_proj, k_proj, v_proj [d, heads x head_dim]
        q_conv1d, k_conv1d, v_conv1d [taps, heads x head_dim] (the last tap
        weighs the token itself)   A_log [heads]   dt_bias [heads x head_dim]
        f_a_proj, g_a_proj [d, head_dim]   f_b_proj, g_b_proj [head_dim,
        heads x head_dim]   b_proj [d, heads]   o_norm [head_dim]
        o_proj [heads x head_dim, d]
    MLA layer:  q_proj [d, heads x (nope + rope)], a head's [nope | rope]
        kv_a_proj_with_mqa [d, kv_lora_rank + rope]   kv_a_layernorm
        [kv_lora_rank]   kv_b_proj [kv_lora_rank, heads x (nope + v)], a
        head's [k_nope | v]   o_proj [heads x v, d]
    dense layer:   gate_proj, up_proj [d, F]   down_proj [F, d]
    sparse layer:  gate [d, router_num_experts] (the router)
                   gate_proj, up_proj [held, d, f]   down_proj [held, f, d]
                   shared_gate_proj, shared_up_proj [d, fs]
                   shared_down_proj [fs, d]

Every RMSNorm scales by a plain w. h = embed_tokens[token]; a layer, both
halves: h += half(RMSNorm(h)). KDA layer, a head of d = head_dim: q, k, v
<- silu(causal depthwise convolution, no bias) of three products; q and k
L2-normed, x / sqrt(sum x^2 + 1e-6), q x d^-0.5; the decay, one number a
head, token and key channel, g = -exp(A_log[head]) softplus((y f_a_proj)
f_b_proj + dt_bias); beta = sigmoid(y b_proj); S_t = Diag(exp(g_t)) S_{t-1};
S_t += k_t (beta_t (v_t - S_t^T k_t))^T; o_t = S_t^T q_t; out = (RMSNorm(o_t,
over a head's values, o_norm) x sigmoid((y g_a_proj) g_b_proj)) o_proj: the
norm first, the gate after. MLA layer: a head's [q_nope | q_pe] from
`q_proj`; [c | k_pe] = y kv_a_proj_with_mqa, c <- RMSNorm(c;
kv_a_layernorm), a head's [k_nope | v] from c kv_b_proj; k_pe is ONE key a
token that every head reads; nothing is rotated; causal float32 softmax of
([q_nope | q_pe] . [k_nope | k_pe]) x (nope + rope)^-0.5; out = concat(o)
o_proj. Second half, dense: down(silu(gate(y)) x up(y)); sparse: s =
sigmoid(y gate) float32, the token's experts its top `num_experts_per_token`
of all the router's, w_e = routed_scaling_factor x s_e / sum of the chosen
s; += sum over the chosen experts held here of w_e E_e(y) + S(y), E_e and S
gated silu FFNs, S ungated. Slots routed to experts that are not held add
nothing: their ranks add them. After the last layer RMSNorm and `lm_head`
over the held vocabulary rows, mean token cross-entropy. No auxiliary loss.

So that a bfloat16 model that fills the chip can be checked beside itself,
every entry works in pieces that change no value: a KDA layer runs over the
sequence `SEGMENT` tokens at a time, carrying the state and the
convolutions' last inputs, each segment recomputed in the backward pass;
attention a few heads at a time, recomputed likewise; `make_loss_from` and
`make_grads_from` apply one layer's weights at a time.

`from_system` re-lays tpu_mpi's parameter tree under the names above: a
renaming of leaves, and three of tpu_mpi's leaves cut into the model's (the
in-projection [q | k | v], the convolution's taps likewise, and the narrow
product [f_a | g_a | b]); it carries gradients as well as parameters."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from yardstick.reference.lm_gdn_train_step import l2_normed
from yardstick.reference.lm_kinds_train_step import (SCORE_BYTES, gated,
                                                     held_experts_mix,
                                                     visible)
from yardstick.reference.lm_ssm_train_step import blocks_of
from yardstick.reference.lm_train_step import _f32, rms_norm, xent

SEGMENT = 128               # tokens of a KDA layer computed at once

NAMES = {"ln1": "input_layernorm", "ln2": "post_attention_layernorm",
         "a_log": "A_log", "dt_bias": "dt_bias", "w_kda_f": "f_b_proj",
         "w_kda_g": "g_b_proj", "kda_norm": "o_norm", "w_kda_out": "o_proj",
         "w_q": "q_proj", "w_dkv": "kv_a_proj_with_mqa",
         "kv_latent_norm": "kv_a_layernorm", "w_ukv": "kv_b_proj",
         "w_proj": "o_proj", "w_router": "gate", "w_gate": "gate_proj",
         "w_in": "up_proj", "w_out": "down_proj",
         "w_shared_gate": "shared_gate_proj", "w_shared_in": "shared_up_proj",
         "w_shared_out": "shared_down_proj"}
# tpu_mpi's leaves that hold several of the model's side by side
CUT = {"w_kda_in": ("q_proj", "k_proj", "v_proj"),
       "conv_w": ("q_conv1d", "k_conv1d", "v_conv1d"),
       "w_kda_low": ("f_a_proj", "g_a_proj", "b_proj")}


def from_system(params: dict, model: dict) -> dict:
    """tpu_mpi.models.transformer's tree under the model's names; `model`
    gives the widths at which three leaves are cut."""
    lin = model["linear_attn_config"]
    wide, rank = lin["num_heads"] * lin["head_dim"], lin["head_dim"]

    def renamed(lp):
        out = {}
        for name, leaf in lp.items():
            if name in CUT:
                at = (rank, 2 * rank) if name == "w_kda_low" \
                    else (wide, 2 * wide)
                out.update(zip(CUT[name], jnp.split(leaf, at, axis=-1)))
            else:
                out[NAMES[name]] = leaf
        return out
    return {"embed_tokens": params["embed"], "norm": params["ln_f"],
            "lm_head": params["lm_head"],
            "layers": [renamed(lp) for lp in params["layers"]]}


def kinds(model: dict) -> list:
    """("kda" | "mla", sparse) a layer that is here, by the model's own
    lists (1-based)."""
    if not model["mla_use_nope"] or model["q_lora_rank"] is not None:
        raise ValueError("written down for positionless latent attention "
                         "with no query latent")
    if model["moe_layer_freq"] != 1 or model["num_expert_group"] != 1 \
            or model["topk_group"] != 1:
        raise ValueError("written down for experts in every layer after the "
                         "dense ones and a router without groups")
    if model["moe_router_activation_func"] != "sigmoid":
        raise ValueError(model["moe_router_activation_func"])
    lin = model["linear_attn_config"]
    out = []
    for i in range(1, model["num_hidden_layers"] + 1):
        if (i in lin["kda_layers"]) == (i in lin["full_attn_layers"]):
            raise ValueError(f"layer {i} is in one of the two lists")
        out.append(("kda" if i in lin["kda_layers"] else "mla",
                    i > model["first_k_dense_replace"]))
    return out


def route(model: dict, lp: dict, h):
    """h: (tokens, d). (a token's top-k experts (tokens, k), and dense
    weights (tokens, E) over all the router's experts: the chosen experts'
    weights at their experts, zero elsewhere)."""
    scores = jax.nn.sigmoid((h @ lp["gate"]).astype(jnp.float32))
    top, idx = lax.top_k(scores, model["num_experts_per_token"])
    if model["moe_renormalize"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * model["routed_scaling_factor"]
    dense = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1], dtype=jnp.float32)
                    * top[..., None], axis=1)
    return idx, dense


def ffn_half(model: dict, sparse: bool, lp: dict, x):
    """x [.., d] after the layer's second half: the dense gated FFN, or the
    held experts' part of the routed sum and the shared expert."""
    d = x.shape[-1]
    h = rms_norm(x, lp["post_attention_layernorm"],
                 model["rms_norm_eps"]).reshape(-1, d)
    if not sparse:
        out = gated(h, lp["gate_proj"], lp["up_proj"], lp["down_proj"])
    else:
        _idx, dense = route(model, lp, h)
        out = held_experts_mix(model, lp, h, dense) + gated(
            h, lp["shared_gate_proj"], lp["shared_up_proj"],
            lp["shared_down_proj"])
    return x + out.reshape(x.shape)


def kda_operands(model: dict, lp: dict, y, tail):
    """What a KDA layer's recurrence reads of a stretch of the sequence: y
    [batch, tokens, d] the normed stream, `tail` the three convolutions'
    inputs of the taps - 1 tokens before it, side by side. -> ((q, k, v
    [batch, tokens, heads, d head], g the same (one decay a head, token and
    key channel, <= 0), beta [batch, tokens, heads]), the tail after it)."""
    lin = model["linear_attn_config"]
    nh, dh, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    b, t, _ = y.shape
    seen = jnp.concatenate([tail, jnp.concatenate(
        [y @ lp[w] for w in ("q_proj", "k_proj", "v_proj")], axis=-1)], axis=1)
    taps_w = jnp.concatenate(
        [lp[w] for w in ("q_conv1d", "k_conv1d", "v_conv1d")], axis=-1)
    conv = 0.0
    for j in range(taps):                           # a loop over the taps
        conv = conv + taps_w[j] * seen[:, j:j + t]
    q, k, v = (part.reshape(b, t, nh, dh)
               for part in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    q, k = l2_normed(q) * dh ** -0.5, l2_normed(k)
    g = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(
        (y @ lp["f_a_proj"]) @ lp["f_b_proj"]
        + lp["dt_bias"]).reshape(b, t, nh, dh)      # a head, token, channel
    beta = jax.nn.sigmoid(y @ lp["b_proj"])         # [b, t, heads]
    return (q, k, v, g, beta), seen[:, t:]


def kda_recurrence(state, q, k, v, g, beta):
    """The delta rule decayed a key channel, ONE TOKEN AT A TIME: `state`
    [batch, heads, d key, d value] after the token before. -> (the state
    after the last token, o [batch, tokens, heads, d value])."""
    def token(s, at):
        q_t, k_t, v_t, g_t, b_t = at    # [b, h, d] x 4, [b, h]
        s = s * jnp.exp(g_t)[..., None]             # Diag(exp g_t) S
        told = jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + k_t[..., :, None] * (b_t[..., None] * (v_t - told))[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)
    state, o = lax.scan(token, state, tuple(
        jnp.moveaxis(part, 1, 0) for part in (q, k, v, g, beta)))
    return state, jnp.moveaxis(o, 0, 1)


def kda_segment(model: dict, lp: dict, carry, x):
    """One stretch of the sequence through a KDA layer's first half: x
    [batch, tokens, d]; `carry` = (the state after the token before it
    [batch, heads, d key, d value], the three convolutions' inputs of the
    taps - 1 tokens before it, side by side). -> (carry after it, x after
    the half)."""
    state, tail = carry
    b, t, _ = x.shape
    y = rms_norm(x, lp["input_layernorm"], model["rms_norm_eps"])
    operands, tail = kda_operands(model, lp, y, tail)
    state, o = kda_recurrence(state, *operands)
    gate = jax.nn.sigmoid((y @ lp["g_a_proj"]) @ lp["g_b_proj"])
    o = rms_norm(o, lp["o_norm"], model["rms_norm_eps"]) * gate.reshape(o.shape)
    return (state, tail), x + o.reshape(b, t, -1) @ lp["o_proj"]


def kda_start(model: dict, b: int, dtype):
    """The carry before a sequence's first token: no state, no inputs."""
    lin = model["linear_attn_config"]
    nh, dh = lin["num_heads"], lin["head_dim"]
    return (jnp.zeros((b, nh, dh, dh), dtype),
            jnp.zeros((b, lin["short_conv_kernel_size"] - 1, 3 * nh * dh),
                      dtype))


def kda_layer(model: dict, sparse: bool, lp: dict, x):
    """x [batch, seq, d] after a KDA layer, a segment at a time."""
    b, t, d = x.shape
    seg = blocks_of(t, SEGMENT)

    def segment(carry, xs):
        carry, out = kda_segment(model, lp, carry, xs)
        return carry, ffn_half(model, sparse, lp, out)
    _, out = lax.scan(jax.checkpoint(segment), kda_start(model, b, x.dtype),
                      jnp.moveaxis(x.reshape(b, t // seg, seg, d), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, d)


def attention(model: dict, lp: dict, h):
    """h: (batch, seq, d), normed. What `o_proj` is applied to."""
    nh, eps = model["num_attention_heads"], model["rms_norm_eps"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv, ckv = model["v_head_dim"], model["kv_lora_rank"]

    def one(hs):                                    # (seq, d)
        t = hs.shape[0]
        q = (hs @ lp["q_proj"]).reshape(t, nh, dn + dr).transpose(1, 0, 2)
        down = hs @ lp["kv_a_proj_with_mqa"]
        c = rms_norm(down[:, :ckv], lp["kv_a_layernorm"], eps)
        k_pe = down[:, ckv:]                        # (seq, rope): one key
        kv = (c @ lp["kv_b_proj"]).reshape(t, nh, dn + dv).transpose(1, 0, 2)
        k = jnp.concatenate(                        # every head reads k_pe
            [kv[..., :dn], jnp.broadcast_to(k_pe[None], (nh, t, dr))], -1)
        mask = visible(t, 0)
        part = max(1, min(nh, SCORE_BYTES // (4 * t * t)))
        while nh % part:
            part -= 1

        @jax.checkpoint
        def heads(ops):             # `part` heads at a time
            qs, ks, vs = ops
            s = jnp.einsum("hqd,hkd->hqk", qs, ks) * (dn + dr) ** -0.5
            s = jnp.where(mask, s, -jnp.inf)
            return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), vs)
        o = lax.map(heads, tuple(
            a.reshape((nh // part, part) + a.shape[1:])
            for a in (q, k, kv[..., dn:])))
        return o.reshape(nh, t, dv).transpose(1, 0, 2).reshape(t, nh * dv)
    return lax.map(one, h)


def layer(model: dict, kind: tuple, lp: dict, x):
    """x [batch, seq, d] after the layer."""
    mixer, sparse = kind
    if mixer == "kda":
        return kda_layer(model, sparse, lp, x)
    x = x + attention(model, lp, rms_norm(
        x, lp["input_layernorm"], model["rms_norm_eps"])) @ lp["o_proj"]
    b, t, d = x.shape
    rows = blocks_of(b * t, SEGMENT * 8)
    return lax.map(
        jax.checkpoint(functools.partial(ffn_half, model, sparse, lp)),
        x.reshape(b * t // rows, rows, d)).reshape(b, t, d)


def forward(model: dict, params: dict, tokens):
    """Logits over the held vocabulary rows [batch, seq, V]."""
    x = params["embed_tokens"][tokens]
    for kind, lp in zip(kinds(model), params["layers"]):
        x = layer(model, kind, lp, x)
    return rms_norm(x, params["norm"], model["rms_norm_eps"]) \
        @ params["lm_head"]


def loss_of(model: dict, params: dict, tokens, labels):
    return xent(forward(model, params, tokens), labels)


def chosen_experts(model: dict, params: dict, tokens) -> list:
    """Each token's experts [tokens, k] a sparse layer: the routing the
    forward pass makes, for a caller that counts it."""
    x, out = params["embed_tokens"][tokens], []
    eps = model["rms_norm_eps"]
    for (mixer, sparse), lp in zip(kinds(model), params["layers"]):
        if mixer == "kda":
            mid = kda_segment(model, lp, kda_start(model, x.shape[0], x.dtype),
                              x)[1]
        else:
            mid = x + attention(model, lp, rms_norm(
                x, lp["input_layernorm"], eps)) @ lp["o_proj"]
        if sparse:
            h = rms_norm(mid, lp["post_attention_layernorm"], eps)
            out.append(route(model, lp, h.reshape(-1, h.shape[-1]))[0])
        x = ffn_half(model, sparse, lp, mid)
    return out


def _layerwise(model: dict):
    """(embed(table, tokens), one_layer(kind, layer's weights, x)): the
    forward pass one program a layer kind, its weights taken to float32
    there."""
    @jax.jit
    def embed(table, tok):
        return table.astype(jnp.float32)[tok]

    @functools.partial(jax.jit, static_argnums=0)
    def one_layer(kind, lp, x):
        with jax.default_matmul_precision("highest"):
            return layer(model, kind, _f32(lp), x)
    return embed, one_layer


def make_loss_from(model: dict):
    """(params, tokens, labels, logits=False) -> (the loss of one batch, its
    float32 logits on the device or None) from `params` as they are (the
    names above, any dtype), one layer's weights taken to float32 at a
    time."""
    layer_kinds = kinds(model)
    embed, one_layer = _layerwise(model)
    eps = model["rms_norm_eps"]

    @jax.jit
    def head(norm, w, x, labels):
        with jax.default_matmul_precision("highest"):
            logits = rms_norm(x, norm.astype(jnp.float32), eps) \
                @ w.astype(jnp.float32)
            return xent(logits, labels), logits

    def loss_from(params, tokens, labels, logits=False):
        x = embed(params["embed_tokens"], tokens)
        for kind, lp in zip(layer_kinds, params["layers"]):
            x = one_layer(kind, lp, x)
        loss, out = head(params["norm"], params["lm_head"], x, labels)
        return float(loss), out if logits else None
    return loss_from


def make_grads_from(model: dict):
    """(params, tokens, labels) -> an iterator over the gradient of `loss_of`
    at `params` as they are (the names above, any dtype, on the device or on
    the host), in float32, one layer's weights at a time. It yields (None,
    {"norm", "lm_head"}), then (i, layer i's leaves) from the last layer
    down, then (None, {"embed_tokens"}): what it has yielded the caller may
    drop."""
    eps = model["rms_norm_eps"]
    layer_kinds = kinds(model)
    embed, one_layer = _layerwise(model)

    @jax.jit
    def head_back(norm, w, x, labels):
        with jax.default_matmul_precision("highest"):
            return jax.grad(
                lambda n, w, x: xent(rms_norm(x, n, eps) @ w, labels),
                argnums=(0, 1, 2))(norm.astype(jnp.float32),
                                   w.astype(jnp.float32), x)

    @functools.partial(jax.jit, static_argnums=0)
    def layer_back(kind, lp, x, d_out):
        with jax.default_matmul_precision("highest"):
            _, back = jax.vjp(lambda lp, x: layer(model, kind, lp, x),
                              _f32(lp), x)
            return back(d_out)

    @jax.jit
    def embed_back(table, tok, d_x):
        # the embedding is linear in its table: its gradient is taken at a
        # table of zeros, and no float32 copy of the real one is made
        _, back = jax.vjp(lambda t: t[tok],
                          jnp.zeros(table.shape, jnp.float32))
        return back(d_x)[0]

    def grads_from(params, tokens, labels):
        xs = [embed(params["embed_tokens"], tokens)]
        for kind, lp in zip(layer_kinds, params["layers"]):
            xs.append(one_layer(kind, lp, xs[-1]))
        d_norm, d_head, d_x = head_back(params["norm"], params["lm_head"],
                                        xs.pop(), labels)
        yield None, {"norm": d_norm, "lm_head": d_head}
        del d_norm, d_head
        for i in reversed(range(len(params["layers"]))):
            d_lp, d_x = layer_back(layer_kinds[i], params["layers"][i],
                                   xs.pop(), d_x)
            yield i, d_lp
            del d_lp
        yield None, {"embed_tokens": embed_back(params["embed_tokens"],
                                                tokens, d_x)}
    return grads_from
