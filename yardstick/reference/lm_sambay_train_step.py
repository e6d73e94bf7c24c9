"""Plain reference of a train step of a decoder-hybrid-decoder language model
(the SambaY stack of arXiv:2507.06607 with Differential Attention,
arXiv:2410.05258, and Mamba-1 layers, arXiv:2312.00752, as
`yardstick/configs/phi-4-mini-flash-reasoning-1c.json` states it with what it
`assumed`). Straightforward `jax.numpy`, float32 and `highest` matmul
precision; no kernel, no mesh and none of tpu_mpi. The state-space layer is
**the recurrence itself**, one token at a time (`lax.scan` over time);
attention is **two full softmaxes and a subtraction**; the two values that
cross layers beside the residual stream (the memory, the shared keys and
values) are plain Python values handed down the list of layers.

`model` is the configuration file's published keys: `hidden_size`,
`intermediate_size`, `num_hidden_layers` (N, a multiple of 4),
`num_attention_heads`, `num_key_value_heads` (both even), `sliding_window`,
`layer_norm_eps`, `mb_per_layer` (2: every second layer is a mamba layer;
anything else is refused), `tie_word_embeddings` (true), and the family's
sizes the model's code fixes, which the file lists under `assumed`:
`mamba_d_state`, `mamba_d_conv`, `mamba_expand`, `mamba_dt_rank`.

Kind by index l (`kinds`): even l <= N/2 `mamba` (l = N/2 is `memory`: the
mamba layer whose scan output is the memory); odd l < N/2 `window`; l = N/2 +
1 `full` (its keys and values are the shared ones); even l >= N/2 + 2 `gmu`;
odd l >= N/2 + 3 `cross`. Parameters, every matrix stored [in, out]:

  embed_tokens [V, d]   final_layernorm_weight, final_layernorm_bias [d]
  layers[l]: input_layernorm_weight/_bias, post_attention_layernorm_weight/
    _bias [d]   gate_proj, up_proj [d, F]   down_proj [F, d]   (the model
    stores gate and up as one matrix; which half is the gate is a labelling)
    mamba, memory: in_proj [d, x | z = 2 x inner]   conv1d_weight [taps,
      inner] (the last tap weighs the token itself)   conv1d_bias   x_proj
      [inner, dt_rank + 2 x state]   dt_proj [dt_rank, inner]   dt_bias, D
      [inner]   A_log [inner, state]   out_proj [inner, d]
    gmu: gmu_in_proj [d, inner]   gmu_out_proj [inner, d]
    window, full: q_proj [d, heads x 64], q_bias   k_proj, v_proj [d, kv
      heads x 64], k_bias, v_bias   o_proj [heads x 64, d], o_bias
      lambda_q1, lambda_k1, lambda_q2, lambda_k2 [64]   subln [128]
    cross: as window without k_proj, v_proj and their biases

h = embed_tokens[token]. A layer, both halves: h += half(LayerNorm(h)),
LayerNorm with mean, variance, scale and bias. mamba: x | z = y in_proj; x <-
silu(bias + sum over taps j of conv1d_weight[j] x[t - (taps - 1) + j]); dt_low
| B | C = x x_proj; dt = softplus(dt_low dt_proj + dt_bias); A = -exp(A_log);
S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]; y_t[c]
= sum_n C_t[n] S_t[c, n] + D[c] x_t[c]; out = (y x silu(z)) out_proj. The
memory is y of layer N/2, before the gate. gmu: (memory x silu(y
gmu_in_proj)) gmu_out_proj. Attention, no positional embedding: differential
head i is query heads (2i, 2i + 1) over the key/value pair j = i // (heads /
kv heads), heads (2j, 2j + 1): o_i = (softmax(q_2i k_2j^T / 8) - lambda
softmax(q_2i+1 k_2j+1^T / 8)) [v_2j | v_2j+1] under the causal mask (window:
a query sees itself and the sliding_window - 1 before it), lambda =
exp(lambda_q1 . lambda_k1) - exp(lambda_q2 . lambda_k2) + lambda_init(l),
lambda_init(l) = 0.8 - 0.6 exp(-0.3 l); RMSNorm of o_i's 128 values (subln) x
(1 - lambda_init(l)); o_proj with bias. cross: its own queries over layer
N/2 + 1's keys and values. Second half: down(silu(gate(y)) x up(y)). Logits
= LayerNorm_f(h) embed_tokens^T; mean token cross-entropy.

So that a bfloat16 model that fills the chip can be checked beside itself,
every entry works in pieces that change no value: a mamba layer runs over the
sequence `SEGMENT` tokens at a time, carrying the state and the convolution's
last inputs, each segment recomputed in the backward pass; attention one
differential head at a time, recomputed likewise; an FFN `TOKENS` and the
head `HEAD_TOKENS` tokens at a time; `make_loss_from` and `make_grads_from`
apply one layer's weights at a time, and the gradient of a side value is
summed over its readers on the way down and handed to its writer.

`from_system` re-lays tpu_mpi's parameter tree under the names above: a
renaming of leaves and, for `q_proj` and `q_bias`, a relabelling of columns
(tpu_mpi lays a pair's query heads out [softmax][head], the model
[head][softmax]); it carries gradients as well as parameters."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from yardstick.reference.lm_ssm_train_step import blocks_of, gated
from yardstick.reference.lm_train_step import _f32, rms_norm

SEGMENT = 128       # tokens of a mamba layer computed (and kept) at once
TOKENS = 1024       # tokens of an FFN at once
HEAD_TOKENS = 256   # tokens of the head at once

NAMES = {"ln1": "input_layernorm_weight", "ln1_b": "input_layernorm_bias",
         "ln2": "post_attention_layernorm_weight",
         "ln2_b": "post_attention_layernorm_bias",
         "w_gate": "gate_proj", "w_in": "up_proj", "w_out": "down_proj",
         "w_ssm_in": "in_proj", "conv_w": "conv1d_weight",
         "conv_b": "conv1d_bias", "w_ssm_x": "x_proj", "w_ssm_dt": "dt_proj",
         "dt_bias": "dt_bias", "a_log": "A_log", "d_skip": "D",
         "w_ssm_out": "out_proj", "w_gmu_in": "gmu_in_proj",
         "w_gmu_out": "gmu_out_proj", "w_q": "q_proj", "b_q": "q_bias",
         "w_k": "k_proj", "b_k": "k_bias", "w_v": "v_proj", "b_v": "v_bias",
         "w_proj": "o_proj", "b_proj": "o_bias", "lambda_q1": "lambda_q1",
         "lambda_k1": "lambda_k1", "lambda_q2": "lambda_q2",
         "lambda_k2": "lambda_k2", "diff_norm": "subln"}


def from_system(params: dict) -> dict:
    """tpu_mpi.models.transformer's tree under the names above."""
    widths = [(p["lambda_q1"].shape[0], p["w_k"].shape[-1])
              for p in params["layers"] if "w_k" in p]

    def relaid(name, leaf):
        if name not in ("w_q", "b_q"):
            return leaf
        dh, kv = widths[0]
        pairs, group = kv // dh // 2, leaf.shape[-1] // kv
        lead = leaf.shape[:-1]          # [pair][softmax][head] -> [pair][head][softmax]
        return leaf.reshape(*lead, pairs, 2, group, dh).swapaxes(
            -3, -2).reshape(*lead, -1)
    return {"embed_tokens": params["embed"],
            "final_layernorm_weight": params["ln_f"],
            "final_layernorm_bias": params["ln_f_b"],
            "layers": [{NAMES[k]: relaid(k, v) for k, v in p.items()}
                       for p in params["layers"]]}


def kinds(model: dict) -> list:
    """The kind of each layer, by the model's own rule."""
    n = model["num_hidden_layers"]
    if n % 4 or model["mb_per_layer"] != 2 or not model["tie_word_embeddings"]:
        raise ValueError("written down for a multiple of 4 layers, a mamba "
                         "layer every second one and a tied head")
    if model["num_attention_heads"] % 2 or model["num_key_value_heads"] % 2:
        raise ValueError("differential attention pairs its heads: even counts")
    half = n // 2

    def kind(l):
        if l % 2 == 0:
            return "memory" if l == half else "mamba" if l < half else "gmu"
        return "window" if l < half else "full" if l == half + 1 else "cross"
    return [kind(l) for l in range(n)]


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * weight + bias


def normed(model: dict, lp: dict, which: str, x):
    return layer_norm(x, lp[which + "_weight"], lp[which + "_bias"],
                      model["layer_norm_eps"])


def ffn_half(model: dict, lp: dict, x):
    """x [.., d] after the layer's second half."""
    return x + gated(normed(model, lp, "post_attention_layernorm", x),
                     lp["gate_proj"], lp["up_proj"], lp["down_proj"])


def ffn_in_blocks(model: dict, lp: dict, x):
    b, t, d = x.shape
    rows = blocks_of(b * t, TOKENS)
    return lax.map(jax.checkpoint(functools.partial(ffn_half, model, lp)),
                   x.reshape(b * t // rows, rows, d)).reshape(b, t, d)


def lambda_init(depth):
    return 0.8 - 0.6 * jnp.exp(-0.3 * depth)


def mamba_segment(model: dict, lp: dict, carry, x):
    """One stretch of the sequence through a whole mamba layer: x [batch,
    tokens, d]; `carry` = (the state after the token before it [batch, state,
    inner], the convolution's inputs of the taps - 1 tokens before it). ->
    (carry after it, (x after the layer, the scan's output y))."""
    state, tail = carry
    n, taps, r = (model["mamba_d_state"], model["mamba_d_conv"],
                  model["mamba_dt_rank"])
    inner = model["mamba_expand"] * model["hidden_size"]
    t = x.shape[1]
    xs, z = jnp.split(normed(model, lp, "input_layernorm", x) @ lp["in_proj"],
                      [inner], axis=-1)
    seen = jnp.concatenate([tail, xs], axis=1)      # the taps - 1 before it
    conv = lp["conv1d_bias"]
    for j in range(taps):                           # a loop over the taps
        conv = conv + lp["conv1d_weight"][j] * seen[:, j:j + t]
    xs = jax.nn.silu(conv)
    dt, b_in, c_in = jnp.split(xs @ lp["x_proj"], [r, r + n], axis=-1)
    dt = jax.nn.softplus(dt @ lp["dt_proj"] + lp["dt_bias"])
    a = -jnp.exp(lp["A_log"]).T     # [state, inner]: the state is kept so

    def token(s, at):
        x_t, dt_t, b_t, c_t = at        # [b, inner] x 2, [b, state] x 2
        s = jnp.exp(dt_t[:, None, :] * a) * s \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)
    state, y = lax.scan(token, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (xs, dt, b_in, c_in)))
    y = jnp.moveaxis(y, 0, 1) + lp["D"] * xs
    out = x + (y * jax.nn.silu(z)) @ lp["out_proj"]
    return (state, seen[:, t:]), (ffn_half(model, lp, out), y)


def mamba_layer(model: dict, lp: dict, x):
    """(x [batch, seq, d] after a mamba layer, its scan's output [batch, seq,
    inner]), a segment at a time."""
    b, t, d = x.shape
    inner = model["mamba_expand"] * d
    seg = blocks_of(t, SEGMENT)
    start = (jnp.zeros((b, model["mamba_d_state"], inner), x.dtype),
             jnp.zeros((b, model["mamba_d_conv"] - 1, inner), x.dtype))
    _, (out, y) = lax.scan(
        jax.checkpoint(functools.partial(mamba_segment, model, lp)), start,
        jnp.moveaxis(x.reshape(b, t // seg, seg, d), 1, 0))
    return (jnp.moveaxis(out, 0, 1).reshape(b, t, d),
            jnp.moveaxis(y, 0, 1).reshape(b, t, inner))


def gmu_layer(model: dict, lp: dict, x, memory):
    y = normed(model, lp, "input_layernorm", x)
    x = x + (memory * jax.nn.silu(y @ lp["gmu_in_proj"])) @ lp["gmu_out_proj"]
    return ffn_in_blocks(model, lp, x)


def attention_layer(model: dict, lp: dict, x, depth, window: int, kv=None):
    """(x [batch, seq, d] after a differential attention layer, its keys and
    values ([batch, seq, pairs, 2, 64], [batch, seq, pairs, 128])): one
    sequence and one differential head at a time, recomputed in the backward
    pass. ``kv``: another layer's, for a cross layer."""
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    b, t, d = x.shape
    dh = d // nh
    per_pair = (nh // 2) // (nkv // 2)
    h = normed(model, lp, "input_layernorm", x)
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = cols <= rows
    if window:
        seen = jnp.logical_and(seen, rows - cols < window)
    start = lambda_init(depth)
    lam = jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"])) \
        - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + start

    def softmax(q, k):                                      # (t, dh) each
        s = (q @ k.T) / jnp.sqrt(jnp.float32(dh))
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)

    @jax.checkpoint
    def head(qkv):
        q, k, v = qkv               # (2, t, dh), (2, t, dh), (t, 2 dh)
        o = (softmax(q[0], k[0]) - lam * softmax(q[1], k[1])) @ v
        return rms_norm(o, lp["subln"], model["layer_norm_eps"]) \
            * (1.0 - start)

    if kv is None:
        k = (h @ lp["k_proj"] + lp["k_bias"]).reshape(b, t, nkv // 2, 2, dh)
        v = (h @ lp["v_proj"] + lp["v_bias"]).reshape(b, t, nkv // 2, 2 * dh)
    else:
        k, v = kv

    def one(at):                                            # one sequence
        hs, ks, vs = at
        q = (hs @ lp["q_proj"] + lp["q_bias"]).reshape(t, nh // 2, 2, dh)
        pair_of = jnp.arange(nh // 2) // per_pair
        o = lax.map(head, (q.transpose(1, 2, 0, 3),
                           ks.transpose(1, 2, 0, 3)[pair_of],
                           vs.transpose(1, 0, 2)[pair_of]))
        return o.transpose(1, 0, 2).reshape(t, nh * dh) @ lp["o_proj"] \
            + lp["o_bias"]
    x = x + lax.map(one, (h, k, v))
    return ffn_in_blocks(model, lp, x), (k, v)


def layer(model: dict, kind: str, lp: dict, x, depth, side: dict):
    """(x after the layer, what it writes beside the stream): `side` holds
    what the layer reads there ("memory" for a gmu, "kv" for a cross)."""
    if kind in ("mamba", "memory"):
        x, y = mamba_layer(model, lp, x)
        return x, ({"memory": y} if kind == "memory" else {})
    if kind == "gmu":
        return gmu_layer(model, lp, x, side["memory"]), {}
    x, kv = attention_layer(
        model, lp, x, depth, model["sliding_window"] if kind == "window" else 0,
        side.get("kv"))
    return x, ({"kv": kv} if kind == "full" else {})


READS = {"gmu": ("memory",), "cross": ("kv",)}


def read_by(kind: str, side: dict) -> dict:
    return {name: side[name] for name in READS.get(kind, ())}


def hidden(model: dict, params: dict, tokens):
    """The residual stream after the last layer."""
    x = params["embed_tokens"][tokens]
    side = {}
    for l, (kind, lp) in enumerate(zip(kinds(model), params["layers"])):
        x, wrote = layer(model, kind, lp, x, jnp.float32(l),
                         read_by(kind, side))
        side.update(wrote)
    return x


def logits_of(model: dict, weight, bias, embed, x):
    return layer_norm(x, weight, bias, model["layer_norm_eps"]) @ embed.T


def head_loss(model: dict, weight, bias, embed, x, labels):
    """Mean token cross-entropy of the tied head, `HEAD_TOKENS` tokens at a
    time."""
    d = x.shape[-1]
    rows = blocks_of(labels.size, HEAD_TOKENS)

    @jax.checkpoint
    def block(at):
        xs, ls = at
        logp = jax.nn.log_softmax(
            logits_of(model, weight, bias, embed, xs), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, ls[:, None], axis=-1))
    return jnp.sum(lax.map(block, (x.reshape(-1, rows, d),
                                   labels.reshape(-1, rows)))) / labels.size


def forward(model: dict, params: dict, tokens):
    """Logits [batch, seq, V]."""
    return logits_of(model, params["final_layernorm_weight"],
                     params["final_layernorm_bias"], params["embed_tokens"],
                     hidden(model, params, tokens))


def loss_of(model: dict, params: dict, tokens, labels):
    return head_loss(model, params["final_layernorm_weight"],
                     params["final_layernorm_bias"], params["embed_tokens"],
                     hidden(model, params, tokens), labels)


def _layerwise(model: dict):
    """(embed(table, tokens), one_layer(kind, layer's weights, x, depth,
    side)): the forward pass one program a layer kind, its weights taken to
    float32 there."""
    @jax.jit
    def embed(table, tok):
        return table.astype(jnp.float32)[tok]

    @functools.partial(jax.jit, static_argnums=0)
    def one_layer(kind, lp, x, depth, side):
        with jax.default_matmul_precision("highest"):
            return layer(model, kind, _f32(lp), x, depth, side)
    return embed, one_layer


def make_loss_from(model: dict):
    """(params, tokens, labels, logits=False) -> (the loss of one batch, its
    float32 logits on the device or None) from `params` as they are (the
    names above, any dtype), one layer's weights taken to float32 at a
    time."""
    layer_kinds = kinds(model)
    embed, one_layer = _layerwise(model)

    @jax.jit
    def head(weight, bias, table, x, labels):
        with jax.default_matmul_precision("highest"):
            return head_loss(model, *_f32((weight, bias, table)), x, labels)

    @jax.jit
    def head_logits(weight, bias, table, x):
        with jax.default_matmul_precision("highest"):
            return logits_of(model, *_f32((weight, bias, table)), x)

    def loss_from(params, tokens, labels, logits=False):
        x = embed(params["embed_tokens"], tokens)
        side = {}
        for l, (kind, lp) in enumerate(zip(layer_kinds, params["layers"])):
            x, wrote = one_layer(kind, lp, x, jnp.float32(l),
                                 read_by(kind, side))
            side.update(wrote)
        del side
        top = (params["final_layernorm_weight"],
               params["final_layernorm_bias"], params["embed_tokens"])
        loss = float(head(*top, x, labels))
        return loss, head_logits(*top, x) if logits else None
    return loss_from


def make_grads_from(model: dict):
    """(params, tokens, labels) -> an iterator over the gradient of `loss_of`
    at `params` as they are (the names above, any dtype, on the device or on
    the host), in float32, one layer's weights at a time. It yields (None,
    the final norm's two leaves), then (l, layer l's leaves) from the last
    layer down, then (None, {"embed_tokens"}): the tied table's gradient is
    the head's part and the embedding's together. On the way down a side
    value's cotangent is the sum over the layers that read it, and the layer
    that wrote it takes that sum beside the stream's. What it has yielded the
    caller may drop."""
    layer_kinds = kinds(model)
    embed, one_layer = _layerwise(model)

    @jax.jit
    def head_back(weight, bias, table, x, labels):
        with jax.default_matmul_precision("highest"):
            return jax.grad(functools.partial(head_loss, model),
                            argnums=(0, 1, 2, 3))(
                *_f32((weight, bias, table)), x, labels)

    @functools.partial(jax.jit, static_argnums=0)
    def layer_back(kind, lp, x, depth, side, d_out, d_wrote):
        with jax.default_matmul_precision("highest"):
            _, back = jax.vjp(
                lambda lp, x, side: layer(model, kind, lp, x, depth, side),
                _f32(lp), x, side)
            return back((d_out, d_wrote))

    @functools.partial(jax.jit, donate_argnums=0)
    def embed_back(d_table, tok, d_x):
        # the embedding is linear in its table: its gradient is taken at a
        # table of zeros, and no float32 copy of the real one is made
        _, back = jax.vjp(lambda t: t[tok], jnp.zeros_like(d_table))
        return d_table + back(d_x)[0]

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    def grads_from(params, tokens, labels):
        xs, sides, side = [embed(params["embed_tokens"], tokens)], [], {}
        for l, (kind, lp) in enumerate(zip(layer_kinds, params["layers"])):
            sides.append(read_by(kind, side))
            x, wrote = one_layer(kind, lp, xs[-1], jnp.float32(l), sides[-1])
            xs.append(x)
            side.update(wrote)
        d_weight, d_bias, d_table, d_x = head_back(
            params["final_layernorm_weight"], params["final_layernorm_bias"],
            params["embed_tokens"], xs.pop(), labels)
        yield None, {"final_layernorm_weight": d_weight,
                     "final_layernorm_bias": d_bias}
        del d_weight, d_bias
        d_side = {}         # a side value's cotangent, summed over its readers
        for l in reversed(range(len(params["layers"]))):
            kind = layer_kinds[l]
            writes = {"memory": ("memory",), "full": ("kv",)}.get(kind, ())
            d_wrote = {name: d_side.pop(name) if name in d_side else
                       jax.tree.map(jnp.zeros_like, side[name])
                       for name in writes}
            d_lp, d_x, d_read = layer_back(
                kind, params["layers"][l], xs.pop(), jnp.float32(l),
                sides.pop(), d_x, d_wrote)
            for name, d in d_read.items():
                d_side[name] = add(d_side[name], d) if name in d_side else d
            yield l, d_lp
            del d_lp, d_wrote, d_read
        yield None, {"embed_tokens": embed_back(d_table, tokens, d_x)}
    return grads_from
