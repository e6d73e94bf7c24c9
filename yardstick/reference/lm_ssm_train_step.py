"""Plain reference of a train step of a language model whose layers are
state-space (Mamba-2) ones with a grouped-query attention layer among them
(the Granite-4.0-H family's block, as
`yardstick/configs/granite-4.0-h-micro-1c.json` states it with what it
`assumed`). Straightforward `jax.numpy`, float32 and `highest` matmul
precision; no kernel, no mesh and none of tpu_mpi. The state-space layer is
**the recurrence itself**, one token at a time (`lax.scan` over time), not
the chunked algebra the program computes, which is what is under test. The
norm is the one `reference/lm_train_step.py` wrote down, imported.

`model` is the configuration file's published keys: `hidden_size`,
`layer_types` and `num_hidden_layers` (the list's first that many entries
are the layers: `mamba` or `attention`), `mamba_n_heads`, `mamba_d_head`,
`mamba_d_state`, `mamba_d_conv`, `mamba_n_groups` (1; anything else is
refused), `mamba_expand`, `num_attention_heads`, `num_key_value_heads`,
`attention_multiplier`, `embedding_multiplier`, `residual_multiplier`,
`logits_scaling`, `rms_norm_eps`, `position_embedding_type` (`nope`:
nothing is rotated; anything else is refused), `tie_word_embeddings`
(true). `mamba_chunk_size` is not read: a chunk is the program's business.
Parameters carry the family's names, every matrix stored [in, out]:

  embed_tokens [V, d]   norm [d]   layers[i]:
    input_layernorm, post_attention_layernorm [d]
    gate_proj, up_proj [d, F]   down_proj [F, d]     (the family's shared_mlp;
        it stores gate and up as one `input_linear`)
    mamba layer:      in_proj [d, inner + (inner + 2 x state) + heads]
                      conv1d_weight [taps, inner + 2 x state] (the last tap
                      weighs the token itself)   conv1d_bias
                      dt_bias, A_log, D [heads]   mamba_norm [inner]
                      out_proj [inner, d]
    attention layer:  q_proj [d, heads x 64]   k_proj, v_proj [d, kv heads x
                      64]   o_proj [heads x 64, d]

x = embedding_multiplier x embed_tokens[token]. A layer, both halves: x +=
residual_multiplier x half(RMSNorm(x)). A mamba layer's first half: z | xBC |
dt = h in_proj; xBC <- silu(bias + sum over taps j of conv1d_weight[j] x
xBC[t - (taps - 1) + j]) (zeros before the sequence); x | B | C = xBC, x cut
into heads; dt <- softplus(dt + dt_bias); A = -exp(A_log); a head's state S
[head width, state]: S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t
+ D x_t; out = RMSNorm(y x silu(z)) out_proj (gate first, then the norm,
over the whole inner width). An attention layer's: q, k, v from h, nothing
rotated, query head j reads key/value head j // (heads / kv heads), scores x
attention_multiplier, causal, float32 softmax, o_proj. Second half: down(
silu(gate(h)) x up(h)). Logits = RMSNorm(x) embed_tokens^T / logits_scaling;
mean token cross-entropy. No auxiliary loss.

So that a bfloat16 model that fills the chip can be checked beside itself,
every entry here works in pieces that change no value: a mamba layer runs
over the sequence `SEGMENT` tokens at a time, carrying the state and the
convolution's last inputs from segment to segment (everything else in the
layer is a token's own), each segment recomputed in the backward pass, so
that the time steps' states of one segment are kept and not the sequence's
(8192 of them would be 17 GB); attention runs one query head at a time, an
attention layer's FFN `TOKENS` and the head `HEAD_TOKENS` tokens at a time;
`make_loss_from` and `make_grads_from` apply one layer's weights at a time.

`from_system` re-lays tpu_mpi's parameter tree under the names above: a
renaming of leaves, so it carries gradients as well as parameters."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from yardstick.reference.lm_train_step import _f32, rms_norm

SEGMENT = 128       # tokens of a mamba layer computed (and kept) at once
TOKENS = 1024       # tokens of an FFN at once
HEAD_TOKENS = 256   # tokens of the head at once: 0.1 GB of float32 logits

NAMES = {"ln1": "input_layernorm", "ln2": "post_attention_layernorm",
         "w_gate": "gate_proj", "w_in": "up_proj", "w_out": "down_proj",
         "w_ssm_in": "in_proj", "conv_w": "conv1d_weight",
         "conv_b": "conv1d_bias", "dt_bias": "dt_bias", "a_log": "A_log",
         "d_skip": "D", "ssm_norm": "mamba_norm", "w_ssm_out": "out_proj",
         "w_q": "q_proj", "w_k": "k_proj", "w_v": "v_proj", "w_proj": "o_proj"}


def from_system(params: dict) -> dict:
    """tpu_mpi.models.transformer's tree under the family's names."""
    return {"embed_tokens": params["embed"], "norm": params["ln_f"],
            "layers": [{NAMES[k]: v for k, v in p.items()}
                       for p in params["layers"]]}


def kinds(model: dict) -> list:
    """"mamba" | "attention" of the layers that are here."""
    if model["mamba_n_groups"] != 1:
        raise ValueError("B and C of more than one group are not written "
                         "down here")
    if model["position_embedding_type"] != "nope" \
            or not model["tie_word_embeddings"]:
        raise ValueError("written down for no positional embedding and a "
                         "tied head")
    return list(model["layer_types"][:model["num_hidden_layers"]])


def blocks_of(t: int, most: int) -> int:
    """The largest divisor of t that is at most `most`."""
    return next(n for n in range(min(t, most), 0, -1) if t % n == 0)


def gated(h, gate_w, up_w, down_w):
    return (jax.nn.silu(h @ gate_w) * (h @ up_w)) @ down_w


def ffn_half(model: dict, lp: dict, x):
    """x [.., d] after the layer's second half."""
    h = rms_norm(x, lp["post_attention_layernorm"], model["rms_norm_eps"])
    return x + model["residual_multiplier"] * gated(
        h, lp["gate_proj"], lp["up_proj"], lp["down_proj"])


def mamba_segment(model: dict, lp: dict, carry, x):
    """One stretch of the sequence through a whole mamba layer: x [batch,
    tokens, d]; `carry` = (the state after the token before it [batch, heads,
    head width, state], the convolution's inputs of the taps - 1 tokens
    before it). -> (carry after it, x after the layer)."""
    state, tail = carry
    eps, mult = model["rms_norm_eps"], model["residual_multiplier"]
    nh, p, n = (model["mamba_n_heads"], model["mamba_d_head"],
                model["mamba_d_state"])
    inner, taps = nh * p, model["mamba_d_conv"]
    b, t, _ = x.shape
    z, xbc, dt = jnp.split(
        rms_norm(x, lp["input_layernorm"], eps) @ lp["in_proj"],
        [inner, 2 * inner + 2 * n], axis=-1)
    seen = jnp.concatenate([tail, xbc], axis=1)     # the taps - 1 before it
    conv = lp["conv1d_bias"]
    for j in range(taps):                           # a loop over the taps
        conv = conv + lp["conv1d_weight"][j] * seen[:, j:j + t]
    xs, b_in, c_in = jnp.split(jax.nn.silu(conv), [inner, inner + n], axis=-1)
    xs = xs.reshape(b, t, nh, p)
    dt = jax.nn.softplus(dt + lp["dt_bias"])        # [batch, tokens, heads]
    a = -jnp.exp(lp["A_log"])

    def token(s, at):
        x_t, dt_t, b_t, c_t = at    # [b, heads, p], [b, heads], [b, n] x 2
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return s, jnp.sum(s * c_t[:, None, None, :], axis=-1)
    state, y = lax.scan(token, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (xs, dt, b_in, c_in)))
    y = jnp.moveaxis(y, 0, 1) + lp["D"][:, None] * xs
    y = rms_norm(y.reshape(b, t, inner) * jax.nn.silu(z), lp["mamba_norm"],
                 eps) @ lp["out_proj"]
    return (state, seen[:, t:]), ffn_half(model, lp, x + mult * y)


def mamba_layer(model: dict, lp: dict, x):
    """x [batch, seq, d] after a mamba layer, a segment at a time."""
    b, t, d = x.shape
    nh, p, n = (model["mamba_n_heads"], model["mamba_d_head"],
                model["mamba_d_state"])
    if nh * p != model["mamba_expand"] * d:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x "
                         "hidden_size")
    seg = blocks_of(t, SEGMENT)
    start = (jnp.zeros((b, nh, p, n), x.dtype),
             jnp.zeros((b, model["mamba_d_conv"] - 1, nh * p + 2 * n),
                       x.dtype))
    _, out = lax.scan(
        jax.checkpoint(functools.partial(mamba_segment, model, lp)), start,
        jnp.moveaxis(x.reshape(b, t // seg, seg, d), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, d)


def attention_layer(model: dict, lp: dict, x):
    """x [batch, seq, d] after an attention layer: one sequence and one
    query head at a time, recomputed in the backward pass; the FFN `TOKENS`
    tokens at a time."""
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    b, t, d = x.shape
    dh = d // nh
    h = rms_norm(x, lp["input_layernorm"], model["rms_norm_eps"])
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]     # key <= query

    @jax.checkpoint
    def head(qkv):
        q, k, v = qkv                                           # (t, dh) each
        s = (q @ k.T) * model["attention_multiplier"]
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v

    def one(hs):                                                # (t, d)
        q = (hs @ lp["q_proj"]).reshape(t, nh, dh).transpose(1, 0, 2)
        k, v = ((hs @ lp[w]).reshape(t, nkv, dh).transpose(1, 0, 2)
                for w in ("k_proj", "v_proj"))
        kv_of = jnp.arange(nh) // (nh // nkv)
        o = lax.map(head, (q, k[kv_of], v[kv_of]))
        return o.transpose(1, 0, 2).reshape(t, nh * dh) @ lp["o_proj"]
    x = x + model["residual_multiplier"] * lax.map(one, h)
    rows = blocks_of(b * t, TOKENS)
    return lax.map(jax.checkpoint(functools.partial(ffn_half, model, lp)),
                   x.reshape(b * t // rows, rows, d)).reshape(b, t, d)


def layer(model: dict, kind: str, lp: dict, x):
    return (mamba_layer if kind == "mamba" else attention_layer)(model, lp, x)


def hidden(model: dict, params: dict, tokens):
    """The residual stream after the last layer."""
    x = model["embedding_multiplier"] * params["embed_tokens"][tokens]
    for kind, lp in zip(kinds(model), params["layers"]):
        x = layer(model, kind, lp, x)
    return x


def logits_of(model: dict, norm, embed, x):
    return rms_norm(x, norm, model["rms_norm_eps"]) @ embed.T \
        / model["logits_scaling"]


def head_loss(model: dict, norm, embed, x, labels):
    """Mean token cross-entropy of the tied head, `HEAD_TOKENS` tokens at a
    time (their float32 logits are 0.1 GB at the published vocabulary, the
    sequence's 3.3)."""
    d = x.shape[-1]
    rows = blocks_of(labels.size, HEAD_TOKENS)

    @jax.checkpoint
    def block(at):
        xs, ls = at
        logp = jax.nn.log_softmax(logits_of(model, norm, embed, xs), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, ls[:, None], axis=-1))
    return jnp.sum(lax.map(block, (x.reshape(-1, rows, d),
                                   labels.reshape(-1, rows)))) / labels.size


def forward(model: dict, params: dict, tokens):
    """Logits [batch, seq, V]."""
    return logits_of(model, params["norm"], params["embed_tokens"],
                     hidden(model, params, tokens))


def loss_of(model: dict, params: dict, tokens, labels):
    return head_loss(model, params["norm"], params["embed_tokens"],
                     hidden(model, params, tokens), labels)


def _layerwise(model: dict):
    """(embed(table, tokens), one_layer(kind, layer's weights, x)): the
    forward pass one program a layer kind, its weights taken to float32
    there."""
    @jax.jit
    def embed(table, tok):
        return model["embedding_multiplier"] * table.astype(jnp.float32)[tok]

    @functools.partial(jax.jit, static_argnums=0)
    def one_layer(kind, lp, x):
        with jax.default_matmul_precision("highest"):
            return layer(model, kind, _f32(lp), x)
    return embed, one_layer


def make_loss_from(model: dict):
    """(params, tokens, labels, logits=False) -> (the loss of one batch, its
    float32 logits on the device or None) from `params` as they are (the
    family's names, any dtype), one layer's weights taken to float32 at a
    time."""
    layer_kinds = kinds(model)
    embed, one_layer = _layerwise(model)

    @jax.jit
    def head(norm, table, x, labels):
        with jax.default_matmul_precision("highest"):
            return head_loss(model, norm.astype(jnp.float32),
                             table.astype(jnp.float32), x, labels)

    @jax.jit
    def head_logits(norm, table, x):
        with jax.default_matmul_precision("highest"):
            return logits_of(model, norm.astype(jnp.float32),
                             table.astype(jnp.float32), x)

    def loss_from(params, tokens, labels, logits=False):
        x = embed(params["embed_tokens"], tokens)
        for kind, lp in zip(layer_kinds, params["layers"]):
            x = one_layer(kind, lp, x)
        loss = float(head(params["norm"], params["embed_tokens"], x, labels))
        return loss, head_logits(params["norm"], params["embed_tokens"],
                                 x) if logits else None
    return loss_from


def make_grads_from(model: dict):
    """(params, tokens, labels) -> an iterator over the gradient of `loss_of`
    at `params` as they are (the family's names, any dtype, on the device or
    on the host), in float32, one layer's weights at a time. It yields
    (None, {"norm"}), then (i, layer i's leaves) from the last layer down,
    then (None, {"embed_tokens"}): the tied table's gradient is the head's
    part and the embedding's together. What it has yielded the caller may
    drop."""
    layer_kinds = kinds(model)
    embed, one_layer = _layerwise(model)

    @jax.jit
    def head_back(norm, table, x, labels):
        with jax.default_matmul_precision("highest"):
            return jax.grad(functools.partial(head_loss, model),
                            argnums=(0, 1, 2))(
                norm.astype(jnp.float32), table.astype(jnp.float32), x, labels)

    @functools.partial(jax.jit, static_argnums=0)
    def layer_back(kind, lp, x, d_out):
        with jax.default_matmul_precision("highest"):
            _, back = jax.vjp(functools.partial(layer, model, kind),
                              _f32(lp), x)
            return back(d_out)

    @functools.partial(jax.jit, donate_argnums=0)
    def embed_back(d_table, tok, d_x):
        # the embedding is linear in its table: its gradient is taken at a
        # table of zeros, and no float32 copy of the real one is made
        _, back = jax.vjp(lambda t: model["embedding_multiplier"] * t[tok],
                          jnp.zeros_like(d_table))
        return d_table + back(d_x)[0]

    def grads_from(params, tokens, labels):
        xs = [embed(params["embed_tokens"], tokens)]
        for kind, lp in zip(layer_kinds, params["layers"]):
            xs.append(one_layer(kind, lp, xs[-1]))
        d_norm, d_table, d_x = head_back(params["norm"],
                                         params["embed_tokens"], xs.pop(),
                                         labels)
        yield None, {"norm": d_norm}
        del d_norm
        for i in reversed(range(len(params["layers"]))):
            d_lp, d_x = layer_back(layer_kinds[i], params["layers"][i],
                                   xs.pop(), d_x)
            yield i, d_lp
            del d_lp
        yield None, {"embed_tokens": embed_back(d_table, tokens, d_x)}
    return grads_from
