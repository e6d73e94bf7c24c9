"""Plain reference of a sparse-expert language model's train step: OLMoE's
block as `transformers`' `modeling_olmoe.py` computes it, written down in
straightforward `jax.numpy`, float32 and `highest` matmul precision, with no
kernel, no sort, no grouped multiplication, no mesh and none of tpu_mpi.

`model` is the configuration file's published keys (`num_attention_heads`,
`num_experts_per_tok`, `norm_topk_prob`, `rms_norm_eps`, `rope_theta`,
`router_aux_loss_coef`). Parameters carry the model's own names; every
matrix is stored [in, out] and applied as `x @ w`:

  embed_tokens [V, d]   norm [d]   lm_head [d, V]   layers[i]:
    input_layernorm, post_attention_layernorm, q_norm, k_norm [d]
    q_proj, k_proj, v_proj, o_proj [d, d]      gate [d, E] (the router)
    gate_proj, up_proj [E, d, f]               down_proj [E, f, d]

A layer: x += o_proj(attention(rope(q_norm(q_proj(h))), rope(k_norm(
k_proj(h))), v_proj(h))) with h = RMSNorm(x), q_norm and k_norm RMSNorms
over the whole d-wide vector before it is cut into heads, RoPE rotating the
halves of each head, causal softmax attention scaled by head_dim**-0.5; then
x += sum over a token's top-k experts e of p_e x down_e(silu(gate_e(h)) x
up_e(h)) with h = RMSNorm(x), p = softmax over ALL experts of the router's
logits in float32, the top-k p not renormalised (`norm_topk_prob` false;
true divides them by their sum). No token is dropped. Then the final
RMSNorm and an untied `lm_head`. Loss: mean token cross-entropy +
`router_aux_loss_coef` x `load_balancing_loss_func` over all layers' router
probabilities together: E x sum over (k-th choice, expert) of (share of
layer-tokens whose k-th choice is that expert) x (that expert's mean
probability). The shares of one expert over the k choices add up to
(token-slots routed there) / (layer-tokens); balanced routing gives k.

Departures from `modeling_olmoe.py`, each deliberate: float32 throughout
(the model runs in bfloat16 with a float32 router softmax; the comparison's
tolerance is for that); matrices stored [in, out]; the experts are a loop
over all E of them, each applied to every token and weighted by the token's
probability for it or by zero (the model gathers each expert's tokens; the
sum is the same); attention runs one sequence at a time (`lax.map`) and it
and each expert are recomputed in the backward pass (`jax.checkpoint`),
which changes no value and keeps float32 [heads, seq, seq] scores of one
sequence, not of the batch, in memory; no attention mask beyond the causal
one, no dropout, no KV cache, no `clip_qkv` (null in the config); the
paper's router z-loss is left out, as it is in that code. One more entry
applies one layer's weights at a time so that a bfloat16 model which fills
the chip can be checked beside itself (`make_loss_from`), and `make_grads_from`
takes that loss's gradient the same way: the layers forward with each
layer's input kept (one [batch, seq, d] array a layer), then `jax.vjp` of
one layer at a time from the last down, so that no more than one layer's
float32 weights and gradients are on the device at once. It is the gradient
of `loss_of`, leaf for leaf (tests/test_moe_layer.py).

`from_system` re-lays tpu_mpi's parameter tree (its packed `w_qkv`, its
names) under the names above; it is a permutation of leaves and columns, so
it carries gradients as well as parameters."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def from_system(params: dict, n_heads: int) -> dict:
    """tpu_mpi.models.transformer's tree under the model's own names.
    `w_qkv`'s columns are packed [head][q|k|v][head_dim]."""
    def layer(p):
        d = p["w_qkv"].shape[0]
        qkv = p["w_qkv"].reshape(d, n_heads, 3, d // n_heads)
        q, k, v = (qkv[:, :, i, :].reshape(d, d) for i in range(3))
        return {"input_layernorm": p["ln1"], "q_proj": q, "k_proj": k,
                "v_proj": v, "q_norm": p["q_norm"], "k_norm": p["k_norm"],
                "o_proj": p["w_proj"], "post_attention_layernorm": p["ln2"],
                "gate": p["w_router"], "gate_proj": p["w_gate"],
                "up_proj": p["w_in"], "down_proj": p["w_out"]}
    return {"embed_tokens": params["embed"], "norm": params["ln_f"],
            "lm_head": params["lm_head"],
            "layers": [layer(p) for p in params["layers"]]}


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (heads, seq, head_dim); position p rotates pair (i, i + half) by
    p / theta**(i / half)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(model: dict, lp: dict, h):
    """h: (batch, seq, d), normed. What `o_proj` is applied to."""
    nh, eps = model["num_attention_heads"], model["rms_norm_eps"]
    theta = float(model["rope_theta"])

    @jax.checkpoint
    def one(hs):                                    # (seq, d)
        t, d = hs.shape
        q = rms_norm(hs @ lp["q_proj"], lp["q_norm"], eps)
        k = rms_norm(hs @ lp["k_proj"], lp["k_norm"], eps)
        v = hs @ lp["v_proj"]
        q, k, v = (a.reshape(t, nh, d // nh).transpose(1, 0, 2)
                   for a in (q, k, v))
        s = jnp.einsum("hqd,hkd->hqk", rope(q, theta), rope(k, theta)) \
            * (d // nh) ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v)
        return o.transpose(1, 0, 2).reshape(t, d)
    return lax.map(one, h)


def route(model: dict, lp: dict, h):
    """h: (tokens, d). (probs (tokens, E) over all experts, a token's top-k
    experts (tokens, k), and dense weights (tokens, E): the top-k
    probabilities at their experts, zero elsewhere)."""
    dtype = jnp.dtype(model.get("router_softmax_dtype", "float32"))
    probs = jax.nn.softmax((h @ lp["gate"]).astype(dtype), axis=-1)
    top, idx = lax.top_k(probs, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    dense = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32)
                    * top[..., None].astype(jnp.float32), axis=1)
    return probs.astype(jnp.float32), idx, dense


def experts_mix(lp: dict, h, dense):
    """sum over experts e of dense[:, e] x down_e(silu(gate_e(h)) x up_e(h)),
    every expert applied to every token."""
    @jax.checkpoint
    def one(gate_w, up_w, down_w, w):
        return w[:, None] * ((jax.nn.silu(h @ gate_w) * (h @ up_w)) @ down_w)

    def add(acc, e):
        return acc + one(*e), None
    out, _ = lax.scan(add, jnp.zeros_like(h),
                      (lp["gate_proj"], lp["up_proj"], lp["down_proj"],
                       dense.T))
    return out


def layer(model: dict, lp: dict, x):
    """(x after the layer, the router's probs, each token's experts)."""
    eps = model["rms_norm_eps"]
    b, t, d = x.shape
    x = x + attention(model, lp, rms_norm(x, lp["input_layernorm"], eps)) \
        @ lp["o_proj"]
    h = rms_norm(x, lp["post_attention_layernorm"], eps).reshape(b * t, d)
    probs, idx, dense = route(model, lp, h)
    return x + experts_mix(lp, h, dense).reshape(b, t, d), probs, idx


def load_balancing_loss(probs: list, chosen: list):
    """`load_balancing_loss_func`, line for line: all layers' tokens
    concatenated, the k-th choices told apart and summed at the end."""
    p = jnp.concatenate(probs, axis=0)              # (layers x tokens, E)
    idx = jnp.concatenate(chosen, axis=0)           # (layers x tokens, k)
    n_experts = p.shape[-1]
    expert_mask = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)
    tokens_per_expert = jnp.mean(expert_mask, axis=0)           # (k, E)
    router_prob_per_expert = jnp.mean(p, axis=0)                # (E,)
    return n_experts * jnp.sum(tokens_per_expert
                               * router_prob_per_expert[None, :])


def xent(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def forward(model: dict, params: dict, tokens):
    """(logits, [probs per layer], [each token's experts per layer])."""
    x = params["embed_tokens"][tokens]
    probs, chosen = [], []
    for lp in params["layers"]:
        x, p, idx = layer(model, lp, x)
        probs.append(p)
        chosen.append(idx)
    x = rms_norm(x, params["norm"], model["rms_norm_eps"])
    return x @ params["lm_head"], probs, chosen


def loss_of(model: dict, params: dict, tokens, labels):
    logits, probs, chosen = forward(model, params, tokens)
    return xent(logits, labels) + model["router_aux_loss_coef"] * \
        load_balancing_loss(probs, chosen)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.array(a, jnp.float32), tree)   # a copy


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
    (the model's names, any dtype; taken to float32) over `batches` =
    [(tokens, labels)]: the reference's own updates."""
    step = make_step(model, lr)
    p, out = _f32(params), []
    for tokens, labels in batches:
        p, loss = step(p, tokens, labels)
        out.append(float(loss))
    return out


def make_loss_from(model: dict):
    """(params, tokens, labels) -> (the loss of one batch, its logits on the
    device) from `params` as they are (the model's names, any dtype), one
    layer's weights taken to float32 at a time."""
    @jax.jit
    def embed(table, tok):
        return table.astype(jnp.float32)[tok]

    @jax.jit
    def one_layer(lp, x):
        with jax.default_matmul_precision("highest"):
            return layer(model, _f32(lp), x)

    @jax.jit
    def head(norm, w, x, probs, chosen, labels):
        with jax.default_matmul_precision("highest"):
            x = rms_norm(x, norm.astype(jnp.float32), model["rms_norm_eps"])
            logits = x @ w.astype(jnp.float32)
            return xent(logits, labels) + model["router_aux_loss_coef"] * \
                load_balancing_loss(probs, chosen), logits

    def loss_from(params, tokens, labels):
        x = embed(params["embed_tokens"], tokens)
        probs, chosen = [], []
        for lp in params["layers"]:
            x, p, idx = one_layer(lp, x)
            probs.append(p)
            chosen.append(idx)
        loss, logits = head(params["norm"], params["lm_head"], x, probs,
                            chosen, labels)
        return float(loss), logits
    return loss_from


def make_grads_from(model: dict):
    """(params, tokens, labels) -> an iterator over the gradient of `loss_of`
    at `params` as they are (the model's names, any dtype, on the device or
    on the host), in float32, one layer's weights at a time. It yields
    (None, {"norm", "lm_head"}), then (i, layer i's leaves) from the last
    layer down, then (None, {"embed_tokens"}): what it has yielded the
    caller may drop."""
    eps, coef = model["rms_norm_eps"], model["router_aux_loss_coef"]

    @jax.jit
    def embed(table, tok):
        return table.astype(jnp.float32)[tok]

    @jax.jit
    def one_layer(lp, x):
        with jax.default_matmul_precision("highest"):
            return layer(model, _f32(lp), x)

    @jax.jit
    def head_back(norm, w, x, labels):
        with jax.default_matmul_precision("highest"):
            return jax.grad(
                lambda n, w, x: xent(rms_norm(x, n, eps) @ w, labels),
                argnums=(0, 1, 2))(norm.astype(jnp.float32),
                                   w.astype(jnp.float32), x)

    @jax.jit
    def aux_back(probs, chosen):
        return jax.grad(lambda p: coef * load_balancing_loss(p, chosen))(probs)

    @jax.jit
    def layer_back(lp, x, d_out, d_probs):
        with jax.default_matmul_precision("highest"):
            _, back = jax.vjp(lambda lp, x: layer(model, lp, x)[:2],
                              _f32(lp), x)
            return back((d_out, d_probs))

    @jax.jit
    def embed_back(table, tok, d_x):
        _, back = jax.vjp(lambda t: t[tok], table.astype(jnp.float32))
        return back(d_x)[0]

    def grads_from(params, tokens, labels):
        xs, probs, chosen = [embed(params["embed_tokens"], tokens)], [], []
        for lp in params["layers"]:
            x, p, idx = one_layer(lp, xs[-1])
            xs.append(x)
            probs.append(p)
            chosen.append(idx)
        d_probs = aux_back(probs, chosen)
        d_norm, d_head, d_x = head_back(params["norm"], params["lm_head"],
                                        xs.pop(), labels)
        yield None, {"norm": d_norm, "lm_head": d_head}
        del d_norm, d_head
        for i in reversed(range(len(params["layers"]))):
            d_lp, d_x = layer_back(params["layers"][i], xs.pop(), d_x,
                                   d_probs.pop())
            yield i, d_lp
            del d_lp
        yield None, {"embed_tokens": embed_back(params["embed_tokens"],
                                                tokens, d_x)}
    return grads_from
