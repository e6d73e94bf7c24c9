"""Plain reference of one rank's share of a train step of a language model
whose layers differ in kind: window or full grouped-query attention, a dense
gated FFN or routed experts beside a shared one (the EXAONE-MoE family's
block, as `yardstick/configs/k-exaone-236b-a23b-1c.json` states it with what
it `assumed`). Straightforward `jax.numpy`, float32 and `highest` matmul
precision, an explicit [t, t] mask per layer kind, key/value heads repeated,
a loop over the held experts; no kernel, no sort, no grouped multiplication,
no mesh and none of tpu_mpi. The norm, RoPE and cross-entropy are the ones
`reference/lm_train_step.py` wrote down, imported.

`model` is the configuration file's published keys: `hidden_size`,
`num_attention_heads`, `num_key_value_heads`, `head_dim`, `layer_types`,
`sliding_window`, `mlp_layer_types`, `num_hidden_layers` (the lists' first
that many entries are the layers), `rope_parameters`, `rms_norm_eps`,
`scoring_func`, `num_experts_per_tok`, `norm_topk_prob`,
`routed_scaling_factor`, `n_group`/`topk_group` (1: no group limit; anything
else is refused), and the share: `router_num_experts` scores a token, of
which experts `[held_experts_first, held_experts_first + num_experts)` are
here; the vocabulary rows here are the embedding's and the head's shapes.
Parameters carry the family's names, every matrix stored [in, out]:

  embed_tokens [V, d]   norm [d]   lm_head [d, V]   layers[i]:
    input_layernorm, post_attention_layernorm [d]   q_norm, k_norm [head_dim]
    q_proj [d, heads x head_dim]   k_proj, v_proj [d, kv heads x head_dim]
    o_proj [heads x head_dim, d]
    dense layer:   gate_proj, up_proj [d, F]   down_proj [F, d]
    sparse layer:  gate [d, router_num_experts] (the router)
                   gate_proj, up_proj [held, d, f]   down_proj [held, f, d]
                   shared_gate_proj, shared_up_proj [d, fs]
                   shared_down_proj [fs, d]

A layer: h = RMSNorm(x); q, k, v = h q_proj, h k_proj, h v_proj cut into
heads; q and k get an RMSNorm over each head's values (q_norm, k_norm); a
`sliding_attention` layer rotates q and k (RoPE, theta of `rope_parameters`,
halves rotated), a `full_attention` layer does not; query head j reads
key/value head j // (heads / kv heads); scores x head_dim**-0.5; query p sees
keys p - sliding_window + 1 .. p in a sliding layer, 0 .. p in a full one;
float32 softmax; x += concat(o) o_proj. Then y = RMSNorm(x); dense: x +=
down(silu(gate(y)) x up(y)); sparse: s = sigmoid(y gate) (float32), the
token's experts its top `num_experts_per_tok` of s, w_e = routed_scaling_factor
x s_e / sum of the chosen s; x += sum over chosen e that are held here of
w_e E_e(y) + S(y), E_e and the shared expert S gated silu FFNs. Slots routed
to experts that are not held add nothing: their ranks add them. After the
last layer RMSNorm and `lm_head` over the held vocabulary rows, mean token
cross-entropy over them. No auxiliary loss.

As `reference/lm_train_step.py`, one more entry applies one layer's weights
at a time (`make_loss_from`, `make_grads_from`) so that a bfloat16 model
that fills the chip can be checked beside itself. Attention runs one
sequence and a few query heads of one key/value head at a time (`lax.map`),
recomputed in the backward pass, which changes no value and keeps a
gigabyte of float32 scores, not 64 heads of them.

`from_system` re-lays tpu_mpi's parameter tree under the names above: a
renaming of leaves, so it carries gradients as well as parameters."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from yardstick.reference.lm_train_step import _f32, rms_norm, rope, xent

SCORE_BYTES = 1 << 30       # float32 scores held at once by `attention`

NAMES = {"ln1": "input_layernorm", "ln2": "post_attention_layernorm",
         "w_q": "q_proj", "w_k": "k_proj", "w_v": "v_proj",
         "w_proj": "o_proj", "q_norm": "q_norm", "k_norm": "k_norm",
         "w_router": "gate", "w_gate": "gate_proj", "w_in": "up_proj",
         "w_out": "down_proj", "w_shared_gate": "shared_gate_proj",
         "w_shared_in": "shared_up_proj", "w_shared_out": "shared_down_proj"}


def from_system(params: dict, n_heads: int = 0) -> dict:
    """tpu_mpi.models.transformer's tree under the family's names."""
    return {"embed_tokens": params["embed"], "norm": params["ln_f"],
            "lm_head": params["lm_head"],
            "layers": [{NAMES[k]: v for k, v in p.items()}
                       for p in params["layers"]]}


def kinds(model: dict) -> list:
    """[(window or 0, sparse)] of the layers that are here."""
    if model.get("n_group", 1) != 1 or model.get("topk_group", 1) != 1:
        raise ValueError("a group-limited router is not written down here")
    n = model["num_hidden_layers"]
    return [(model["sliding_window"] if a == "sliding_attention" else 0,
             m == "sparse")
            for a, m in zip(model["layer_types"][:n],
                            model["mlp_layer_types"][:n])]


def visible(t: int, window: int):
    """[t, t] bool: query p (row) sees key c (column)."""
    p, c = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = c <= p
    return jnp.logical_and(seen, p - c < window) if window else seen


def attention(model: dict, lp: dict, h, window: int):
    """h: (batch, seq, d), normed. What `o_proj` is applied to."""
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    dh, eps = model["head_dim"], model["rms_norm_eps"]
    theta = float(model["rope_parameters"]["rope_theta"])
    group = nh // nkv

    def one(hs):                                    # (seq, d)
        t = hs.shape[0]
        q = rms_norm((hs @ lp["q_proj"]).reshape(t, nh, dh), lp["q_norm"], eps)
        k = rms_norm((hs @ lp["k_proj"]).reshape(t, nkv, dh), lp["k_norm"], eps)
        v = (hs @ lp["v_proj"]).reshape(t, nkv, dh)
        q, k, v = (a.transpose(1, 0, 2) for a in (q, k, v))
        if window:                  # a full layer rotates nothing
            q, k = rope(q, theta), rope(k, theta)
        mask = visible(t, window)
        # the queries of one key/value head, `part` of them at a time
        part = max(1, min(group, SCORE_BYTES // (4 * t * t)))
        while group % part:
            part -= 1

        @jax.checkpoint
        def heads(qkv):
            qs, kh, vh = qkv                        # (part, t, dh), (t, dh) x 2
            ks, vs = (jnp.repeat(a[None], part, axis=0) for a in (kh, vh))
            s = jnp.einsum("hqd,hkd->hqk", qs, ks) * dh ** -0.5
            s = jnp.where(mask, s, -jnp.inf)
            return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), vs)
        kv_of = jnp.repeat(jnp.arange(nkv), group // part)
        o = lax.map(heads, (q.reshape(nh // part, part, t, dh),
                            k[kv_of], v[kv_of]))
        return o.reshape(nh, t, dh).transpose(1, 0, 2).reshape(t, nh * dh)
    return lax.map(one, h)


def route(model: dict, lp: dict, h):
    """h: (tokens, d). (scores (tokens, E) over all the router's experts, a
    token's top-k experts (tokens, k), and dense weights (tokens, E): the
    chosen experts' weights at their experts, zero elsewhere)."""
    logits = (h @ lp["gate"]).astype(jnp.float32)
    if model["scoring_func"] != "sigmoid":
        raise ValueError(f"scoring_func {model['scoring_func']!r}")
    scores = jax.nn.sigmoid(logits)
    top, idx = lax.top_k(scores, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * model["routed_scaling_factor"]
    dense = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1], dtype=jnp.float32)
                    * top[..., None], axis=1)
    return scores, idx, dense


def gated(h, gate_w, up_w, down_w):
    return (jax.nn.silu(h @ gate_w) * (h @ up_w)) @ down_w


def held_experts_mix(model: dict, lp: dict, h, dense):
    """sum over the experts held here of dense[:, e] x E_e(h), every held
    expert applied to every token (a token that did not choose it weighs
    it by zero)."""
    first = model["held_experts_first"]
    held = lp["gate_proj"].shape[0]

    @jax.checkpoint
    def one(gate_w, up_w, down_w, w):
        return w[:, None] * gated(h, gate_w, up_w, down_w)

    def add(acc, e):
        return acc + one(*e), None
    out, _ = lax.scan(add, jnp.zeros_like(h),
                      (lp["gate_proj"], lp["up_proj"], lp["down_proj"],
                       dense[:, first:first + held].T))
    return out


def layer(model: dict, kind: tuple, lp: dict, x):
    """(x after the layer, each token's experts or None)."""
    window, sparse = kind
    eps = model["rms_norm_eps"]
    b, t, d = x.shape
    x = x + attention(model, lp, rms_norm(x, lp["input_layernorm"], eps),
                      window) @ lp["o_proj"]
    h = rms_norm(x, lp["post_attention_layernorm"], eps).reshape(b * t, d)
    if not sparse:
        out = jax.checkpoint(gated)(h, lp["gate_proj"], lp["up_proj"],
                                    lp["down_proj"])
        return x + out.reshape(b, t, d), None
    _scores, idx, dense = route(model, lp, h)
    out = held_experts_mix(model, lp, h, dense) + gated(
        h, lp["shared_gate_proj"], lp["shared_up_proj"],
        lp["shared_down_proj"])
    return x + out.reshape(b, t, d), idx


def forward(model: dict, params: dict, tokens):
    """(logits over the held vocabulary rows, [each token's experts per
    sparse layer])."""
    x = params["embed_tokens"][tokens]
    chosen = []
    for kind, lp in zip(kinds(model), params["layers"]):
        x, idx = layer(model, kind, lp, x)
        if idx is not None:
            chosen.append(idx)
    x = rms_norm(x, params["norm"], model["rms_norm_eps"])
    return x @ params["lm_head"], chosen


def loss_of(model: dict, params: dict, tokens, labels):
    return xent(forward(model, params, tokens)[0], labels)


def slots_per_expert(model: dict, chosen) -> jnp.ndarray:
    """[router_num_experts] token-slots a layer's router sent each expert."""
    return jnp.sum(jax.nn.one_hot(chosen, model["router_num_experts"],
                                  dtype=jnp.int32), axis=(0, 1))


def make_step(model: dict, lr: float):
    """jit(params, tokens, labels) -> (params, loss): one SGD step, which
    overwrites the parameters it is given."""
    def step(params, tokens, labels):
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(
                lambda p: loss_of(model, p, tokens, labels))(params)
        return jax.tree.map(lambda p, g: p - lr * g, params, grads), loss
    return jax.jit(step, donate_argnums=0)


def losses(model: dict, lr: float, params: dict, batches: list) -> list:
    """The loss before each of len(batches) chained SGD steps, from `params`
    (the family's names, any dtype; taken to float32): the reference's own
    updates."""
    step = make_step(model, lr)
    p, out = _f32(params), []
    for tokens, labels in batches:
        p, loss = step(p, tokens, labels)
        out.append(float(loss))
    return out


def _layerwise(model: dict):
    """(embed(table, tokens), one_layer(kind, layer's weights, x)): the
    forward pass one program a layer, its weights taken to float32 there."""
    @jax.jit
    def embed(table, tok):
        return table.astype(jnp.float32)[tok]

    @functools.partial(jax.jit, static_argnums=0)
    def one_layer(kind, lp, x):
        with jax.default_matmul_precision("highest"):
            return layer(model, kind, _f32(lp), x)[0]
    return embed, one_layer


def make_loss_from(model: dict):
    """(params, tokens, labels) -> (the loss of one batch, its logits on the
    device) from `params` as they are (the family's names, any dtype), one
    layer's weights taken to float32 at a time."""
    layer_kinds = kinds(model)

    embed, one_layer = _layerwise(model)

    @jax.jit
    def head(norm, w, x, labels):
        with jax.default_matmul_precision("highest"):
            x = rms_norm(x, norm.astype(jnp.float32), model["rms_norm_eps"])
            logits = x @ w.astype(jnp.float32)
            return xent(logits, labels), logits

    def loss_from(params, tokens, labels):
        x = embed(params["embed_tokens"], tokens)
        for kind, lp in zip(layer_kinds, params["layers"]):
            x = one_layer(kind, lp, x)
        loss, logits = head(params["norm"], params["lm_head"], x, labels)
        return float(loss), logits
    return loss_from


def make_grads_from(model: dict):
    """(params, tokens, labels) -> an iterator over the gradient of `loss_of`
    at `params` as they are (the family's names, any dtype, on the device or
    on the host), in float32, one layer's weights at a time. It yields
    (None, {"norm", "lm_head"}), then (i, layer i's leaves) from the last
    layer down, then (None, {"embed_tokens"}): what it has yielded the
    caller may drop."""
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
            _, back = jax.vjp(lambda lp, x: layer(model, kind, lp, x)[0],
                              _f32(lp), x)
            return back(d_out)

    @jax.jit
    def embed_back(table, tok, d_x):
        _, back = jax.vjp(lambda t: t[tok], table.astype(jnp.float32))
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
