"""Plain reference of one rank's share of a train step of a language model
whose layers are linear attention with a gated delta rule, three in four,
and gated softmax attention, each before routed experts beside a gated
shared expert (the Qwen3-Next block, as
`yardstick/configs/qwen3-next-80b-a3b-1c.json` states it with what it
`assumed`). Straightforward `jax.numpy`, float32 and `highest` matmul
precision; no kernel, no chunk, no mesh and none of tpu_mpi. The delta-rule
layer is **the recurrence itself**, one token at a time (`lax.scan` over
time); attention is one full softmax under an explicit [t, t] mask; the
experts are a loop over the held ones.

`model` is the configuration file's published keys: `hidden_size`,
`num_hidden_layers`, `full_attention_interval` (layer i is a full-attention
layer where (i + 1) % it == 0, else a delta-rule layer), `rms_norm_eps`,
`num_attention_heads`, `num_key_value_heads`, `head_dim`,
`partial_rotary_factor`, `rope_theta`, `linear_num_key_heads`,
`linear_num_value_heads`, `linear_key_head_dim`, `linear_value_head_dim`,
`linear_conv_kernel_dim`, `num_experts_per_tok`, `norm_topk_prob`,
`decoder_sparse_step` (1) and `mlp_only_layers` ([]: every layer has
experts; anything else is refused), and the share: `router_num_experts`
score a token, of which experts `[held_experts_first, held_experts_first +
num_experts)` are here; the vocabulary rows here are the embedding's and the
head's shapes. Parameters carry the model's names, every matrix stored [in,
out]:

  embed_tokens [V, d]   norm [d]   lm_head [d, V]   layers[i]:
    input_layernorm, post_attention_layernorm [d]
    delta-rule layer:  in_proj_qkvz [d, key heads x (2 dk + 2 r dv)], a key
        head's [q | k | v of its r value heads | z of them] side by side
        in_proj_ba [d, key heads x 2 r]: a key head's [b | a]
        conv1d_weight [taps, q | k | v] (the last tap weighs the token
        itself; all heads' q, then k, then v)   A_log, dt_bias [value heads]
        linear_norm [dv]   linear_out_proj [value heads x dv, d]
    attention layer:   q_proj [d, heads x 2 x head_dim], a head's [query |
        gate]   k_proj, v_proj [d, kv heads x head_dim]   q_norm, k_norm
        [head_dim]   o_proj [heads x head_dim, d]
    both:  gate [d, router_num_experts] (the router)   gate_proj, up_proj
        [held, d, f]   down_proj [held, f, d]   shared_gate_proj,
        shared_up_proj [d, fs]   shared_down_proj [fs, d]
        shared_expert_gate [d, 1]

Every RMSNorm of the stream, and q_norm and k_norm, scales by 1 + w
(`norm1p`); `linear_norm` by w. h = embed_tokens[token]; a layer, both
halves: h += half(norm1p(h)). Delta-rule layer, with r = value heads / key
heads: q, k, v <- silu(causal depthwise convolution, no bias); each head's q
and k L2-normed, x / sqrt(sum x^2 + 1e-6), q x dk^-0.5; value head j reads
key head j // r; beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias);
S_t = exp(g_t) S_{t-1}; S_t += k_t (beta_t (v_t - S_t^T k_t))^T; o_t = S_t^T
q_t; out = (RMSNorm(o_t, over a head's dv values, linear_norm) x silu(z_t))
linear_out_proj: the norm first, the gate after. Attention layer: q, k
normed a head (norm1p), the first head_dim x partial_rotary_factor values
of each rotated (RoPE, halves), the rest passed; query head j reads
key/value head j // (heads / kv heads); causal float32 softmax of q k^T x
head_dim^-0.5; out = (o x sigmoid(gate)) o_proj. Second half: p =
softmax(y gate) over all the router's experts, the token's experts its top
`num_experts_per_tok`, their weights divided by their sum; += sum over the
chosen experts held here of w_e E_e(y) + sigmoid(y shared_expert_gate)
S(y), E_e and S gated silu FFNs. Slots routed to experts that are not held
add nothing: their ranks add them. After the last layer norm1p and
`lm_head` over the held vocabulary rows, mean token cross-entropy. No
auxiliary loss.

So that a bfloat16 model that fills the chip can be checked beside itself,
every entry works in pieces that change no value: a delta-rule layer runs
over the sequence `SEGMENT` tokens at a time, carrying the state and the
convolution's last inputs, each segment recomputed in the backward pass
(8192 float32 states of 32 x 128 x 128 would be 17 GB a layer); attention
a few query heads of one key/value head at a time, recomputed likewise;
`make_loss_from` and `make_grads_from` apply one layer's weights at a time.

`from_system` re-lays tpu_mpi's parameter tree under the names above: a
renaming of leaves and, for `in_proj_qkvz`, `in_proj_ba` and `q_proj`, a
relabelling of columns (tpu_mpi lays them out [all q | all k | all v | all
z], [all b | all a] and [all queries | all gates]); it carries gradients as
well as parameters."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from yardstick.reference.lm_kinds_train_step import (gated, held_experts_mix,
                                                     visible)
from yardstick.reference.lm_ssm_train_step import blocks_of
from yardstick.reference.lm_train_step import _f32, rms_norm, rope, xent

SEGMENT = 128               # tokens of a delta-rule layer computed at once
SCORE_BYTES = 1 << 30       # float32 scores held at once by `attention`

NAMES = {"ln1": "input_layernorm", "ln2": "post_attention_layernorm",
         "w_gdn_in": "in_proj_qkvz", "w_gdn_ba": "in_proj_ba",
         "conv_w": "conv1d_weight", "a_log": "A_log", "dt_bias": "dt_bias",
         "gdn_norm": "linear_norm", "w_gdn_out": "linear_out_proj",
         "w_q": "q_proj", "w_k": "k_proj", "w_v": "v_proj",
         "w_proj": "o_proj", "q_norm": "q_norm", "k_norm": "k_norm",
         "w_router": "gate", "w_gate": "gate_proj", "w_in": "up_proj",
         "w_out": "down_proj", "w_shared_gate": "shared_gate_proj",
         "w_shared_in": "shared_up_proj", "w_shared_out": "shared_down_proj",
         "w_shared_sigmoid": "shared_expert_gate"}


def _by_head(parts: list, heads: int):
    """Column blocks [.., heads x w_i], each all heads' side by side, as one
    [.., heads x sum w_i] with every head's parts side by side."""
    lead = parts[0].shape[:-1]
    return jnp.concatenate([jnp.reshape(p, lead + (heads, -1)) for p in parts],
                           axis=-1).reshape(lead + (-1,))


def from_system(params: dict, model: dict) -> dict:
    """tpu_mpi.models.transformer's tree under the model's names; `model`
    gives the head counts by which the three projections are re-laid."""
    hk, nh = model["linear_num_key_heads"], model["num_attention_heads"]
    kw = hk * model["linear_key_head_dim"]

    def relaid(name, leaf):
        if name == "w_gdn_in":          # [q | k | v | z] -> a key head's four
            vw = (leaf.shape[-1] - 2 * kw) // 2
            return _by_head(jnp.split(leaf, [kw, 2 * kw, 2 * kw + vw], -1), hk)
        if name == "w_gdn_ba":          # [b | a] -> a key head's two
            return _by_head(jnp.split(leaf, 2, -1), hk)
        if name == "w_q":               # [queries | gates] -> a head's two
            return _by_head(jnp.split(leaf, 2, -1), nh)
        return leaf
    return {"embed_tokens": params["embed"], "norm": params["ln_f"],
            "lm_head": params["lm_head"],
            "layers": [{NAMES[k]: relaid(k, v) for k, v in lp.items()}
                       for lp in params["layers"]]}


def kinds(model: dict) -> list:
    """"linear" | "full" a layer, by the model's own rule."""
    if model["decoder_sparse_step"] != 1 or model["mlp_only_layers"]:
        raise ValueError("written down for experts in every layer")
    every = model["full_attention_interval"]
    return ["full" if (i + 1) % every == 0 else "linear"
            for i in range(model["num_hidden_layers"])]


def norm1p(x, w, eps):
    """RMSNorm with the scale 1 + w."""
    return rms_norm(x, 1.0 + w, eps)


def l2_normed(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def route(model: dict, lp: dict, h):
    """h: (tokens, d). (a token's top-k experts (tokens, k), and dense
    weights (tokens, E) over all the router's experts: the chosen experts'
    weights at their experts, zero elsewhere)."""
    probs = jax.nn.softmax((h @ lp["gate"]).astype(jnp.float32), axis=-1)
    top, idx = lax.top_k(probs, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    dense = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32)
                    * top[..., None], axis=1)
    return idx, dense


def expert_half(model: dict, lp: dict, x):
    """x [.., d] after the layer's second half: the held experts' part of
    the routed sum and the gated shared expert."""
    d = x.shape[-1]
    h = norm1p(x, lp["post_attention_layernorm"],
               model["rms_norm_eps"]).reshape(-1, d)
    _idx, dense = route(model, lp, h)
    shared = jax.nn.sigmoid(h @ lp["shared_expert_gate"]) * gated(
        h, lp["shared_gate_proj"], lp["shared_up_proj"],
        lp["shared_down_proj"])
    return x + (held_experts_mix(model, lp, h, dense) + shared).reshape(x.shape)


def linear_segment(model: dict, lp: dict, carry, x):
    """One stretch of the sequence through a delta-rule layer's first half:
    x [batch, tokens, d]; `carry` = (the state after the token before it
    [batch, value heads, dk, dv], the convolution's inputs of the taps - 1
    tokens before it). -> (carry after it, x after the half)."""
    state, tail = carry
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    taps, r = model["linear_conv_kernel_dim"], hv // hk
    b, t, _ = x.shape
    y = norm1p(x, lp["input_layernorm"], model["rms_norm_eps"])
    q, k, v, z = jnp.split(
        (y @ lp["in_proj_qkvz"]).reshape(b, t, hk, -1),
        [dk, 2 * dk, 2 * dk + r * dv], axis=-1)     # a key head's four
    beta, a = jnp.split((y @ lp["in_proj_ba"]).reshape(b, t, hk, 2 * r),
                        2, axis=-1)
    seen = jnp.concatenate([tail, jnp.concatenate(
        [part.reshape(b, t, -1) for part in (q, k, v)], axis=-1)], axis=1)
    conv = 0.0
    for j in range(taps):                           # a loop over the taps
        conv = conv + lp["conv1d_weight"][j] * seen[:, j:j + t]
    q, k, v = jnp.split(jax.nn.silu(conv), [hk * dk, 2 * hk * dk], axis=-1)
    q = l2_normed(q.reshape(b, t, hk, dk)) * dk ** -0.5
    k = l2_normed(k.reshape(b, t, hk, dk))
    q, k = (jnp.repeat(part, r, axis=2) for part in (q, k))     # j reads j // r
    v, z = v.reshape(b, t, hv, dv), z.reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(beta.reshape(b, t, hv))
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(a.reshape(b, t, hv)
                                                + lp["dt_bias"])

    def token(s, at):
        q_t, k_t, v_t, g_t, b_t = at    # [b, hv, width] x 3, [b, hv] x 2
        s = s * jnp.exp(g_t)[..., None, None]
        told = jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + k_t[..., :, None] * (b_t[..., None] * (v_t - told))[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)
    state, o = lax.scan(token, state, tuple(
        jnp.moveaxis(part, 1, 0) for part in (q, k, v, g, beta)))
    o = rms_norm(jnp.moveaxis(o, 0, 1), lp["linear_norm"],
                 model["rms_norm_eps"]) * jax.nn.silu(z)
    out = x + o.reshape(b, t, hv * dv) @ lp["linear_out_proj"]
    return (state, seen[:, t:]), out


def linear_layer(model: dict, lp: dict, x):
    """x [batch, seq, d] after a delta-rule layer, a segment at a time."""
    b, t, d = x.shape
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    seg = blocks_of(t, SEGMENT)
    start = (jnp.zeros((b, hv, dk, dv), x.dtype),
             jnp.zeros((b, model["linear_conv_kernel_dim"] - 1,
                        2 * hk * dk + hv * dv), x.dtype))

    def segment(carry, xs):
        carry, out = linear_segment(model, lp, carry, xs)
        return carry, expert_half(model, lp, out)
    _, out = lax.scan(jax.checkpoint(segment), start,
                      jnp.moveaxis(x.reshape(b, t // seg, seg, d), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, d)


def attention(model: dict, lp: dict, h):
    """h: (batch, seq, d), normed. What `o_proj` is applied to: the heads'
    outputs, each x the sigmoid of its gate."""
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    dh, eps = model["head_dim"], model["rms_norm_eps"]
    turned = int(dh * model["partial_rotary_factor"])
    theta, group = float(model["rope_theta"]), nh // nkv

    def part_rope(a):               # (heads, seq, dh): the first values turn
        return jnp.concatenate([rope(a[..., :turned], theta),
                                a[..., turned:]], axis=-1)

    def one(hs):                                    # (seq, d)
        t = hs.shape[0]
        q, gate = jnp.split((hs @ lp["q_proj"]).reshape(t, nh, 2 * dh), 2,
                            axis=-1)                # a head's [query | gate]
        q = norm1p(q, lp["q_norm"], eps)
        k = norm1p((hs @ lp["k_proj"]).reshape(t, nkv, dh), lp["k_norm"], eps)
        v = (hs @ lp["v_proj"]).reshape(t, nkv, dh)
        q, k, v = (a.transpose(1, 0, 2) for a in (q, k, v))
        q, k = part_rope(q), part_rope(k)
        mask = visible(t, 0)
        # the queries of one key/value head, `part` of them at a time
        part = max(1, min(group, SCORE_BYTES // (4 * t * t)))
        while group % part:
            part -= 1

        @jax.checkpoint
        def heads(qkv):
            qs, kh, vh = qkv                        # (part, t, dh), (t, dh) x 2
            s = jnp.einsum("hqd,kd->hqk", qs, kh) * dh ** -0.5
            s = jnp.where(mask, s, -jnp.inf)
            return jnp.einsum("hqk,kd->hqd", jax.nn.softmax(s, axis=-1), vh)
        kv_of = jnp.repeat(jnp.arange(nkv), group // part)
        o = lax.map(heads, (q.reshape(nh // part, part, t, dh),
                            k[kv_of], v[kv_of]))
        o = o.reshape(nh, t, dh).transpose(1, 0, 2) * jax.nn.sigmoid(gate)
        return o.reshape(t, nh * dh)
    return lax.map(one, h)


def layer(model: dict, kind: str, lp: dict, x):
    """x [batch, seq, d] after the layer."""
    if kind == "linear":
        return linear_layer(model, lp, x)
    x = x + attention(model, lp, norm1p(x, lp["input_layernorm"],
                                        model["rms_norm_eps"])) @ lp["o_proj"]
    b, t, d = x.shape
    rows = blocks_of(b * t, SEGMENT * 8)
    return lax.map(jax.checkpoint(functools.partial(expert_half, model, lp)),
                   x.reshape(b * t // rows, rows, d)).reshape(b, t, d)


def forward(model: dict, params: dict, tokens):
    """Logits over the held vocabulary rows [batch, seq, V]."""
    x = params["embed_tokens"][tokens]
    for kind, lp in zip(kinds(model), params["layers"]):
        x = layer(model, kind, lp, x)
    return norm1p(x, params["norm"], model["rms_norm_eps"]) @ params["lm_head"]


def loss_of(model: dict, params: dict, tokens, labels):
    return xent(forward(model, params, tokens), labels)


def chosen_experts(model: dict, params: dict, tokens) -> list:
    """Each token's experts [tokens, k] a layer: the routing the forward
    pass makes, for a caller that counts it."""
    x, out = params["embed_tokens"][tokens], []
    for kind, lp in zip(kinds(model), params["layers"]):
        if kind == "linear":
            b, t, d = x.shape
            start = (jnp.zeros((b, model["linear_num_value_heads"],
                                model["linear_key_head_dim"],
                                model["linear_value_head_dim"]), x.dtype),
                     jnp.zeros((b, model["linear_conv_kernel_dim"] - 1,
                                lp["conv1d_weight"].shape[-1]), x.dtype))
            mid = linear_segment(model, lp, start, x)[1]
        else:
            mid = x + attention(model, lp, norm1p(
                x, lp["input_layernorm"], model["rms_norm_eps"])) @ lp["o_proj"]
        h = norm1p(mid, lp["post_attention_layernorm"], model["rms_norm_eps"])
        out.append(route(model, lp, h.reshape(-1, h.shape[-1]))[0])
        x = expert_half(model, lp, mid)
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
            logits = norm1p(x, norm.astype(jnp.float32), eps) \
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
                lambda n, w, x: xent(norm1p(x, n, eps) @ w, labels),
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
