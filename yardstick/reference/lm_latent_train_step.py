"""Plain reference of one rank's share of a train step of a language model
with latent attention in sandwich-normed blocks (the DeepSeek-V2/V3 attention
whose keys openPangu-Ultra-MoE's config carries, in Pangu Ultra's block, as
`yardstick/configs/openpangu-ultra-moe-718b-1c.json` states it with what it
`assumed`). Straightforward `jax.numpy`, float32 and `highest` matmul
precision, an explicit [t, t] mask, the one rotary key broadcast to the
heads by hand, a loop over the held experts; no kernel, no sort, no grouped
multiplication, no mesh and none of tpu_mpi. The norm, RoPE and cross-entropy
are the ones `reference/lm_train_step.py` wrote down, and the router, the
gated FFN and the held experts' loop `reference/lm_kinds_train_step.py`'s,
imported.

`model` is the configuration file's published keys: `hidden_size`,
`q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`,
`v_head_dim`, `rope_theta`, `rms_norm_eps`, `sandwich_norm`,
`num_hidden_layers` of which the first `first_k_dense_replace` are dense,
`scoring_func`, `num_experts_per_tok`, `norm_topk_prob`,
`routed_scaling_factor`, and the share: `num_attention_heads` heads are here
(which of the model's is the weights' business: a head's part of the output
projection's sum does not know its number), `router_num_experts` score a
token, of which experts `[held_experts_first, held_experts_first +
n_routed_experts)` are here; the vocabulary rows here are the embedding's and
the head's shapes. Parameters carry the family's names, every matrix stored
[in, out]:

  embed_tokens [V, d]   norm [d]   lm_head [d, V]   layers[i]:
    input_layernorm, post_attention_layernorm, pre_mlp_layernorm,
    post_mlp_layernorm [d]
    q_a_proj [d, q_lora_rank]   q_a_layernorm [q_lora_rank]
    q_b_proj [q_lora_rank, heads x (nope + rope)]
    kv_a_proj_with_mqa [d, kv_lora_rank + rope]   kv_a_layernorm [kv_lora_rank]
    kv_b_proj [kv_lora_rank, heads x (nope + v)]   o_proj [heads x v, d]
    dense layer:   gate_proj, up_proj [d, F]   down_proj [F, d]
    sparse layer:  gate [d, router_num_experts] (the router)
                   gate_proj, up_proj [held, d, f]   down_proj [held, f, d]
                   shared_gate_proj, shared_up_proj [d, fs]
                   shared_down_proj [fs, d]

A layer: h = RMSNorm(x; input_layernorm). c_q = RMSNorm(h q_a_proj;
q_a_layernorm); head j's [q_nope | q_rope] = its columns of c_q q_b_proj.
[c_kv | k_r] = h kv_a_proj_with_mqa; c_kv <- RMSNorm(c_kv; kv_a_layernorm);
head j's [k_nope | v] = its columns of c_kv kv_b_proj. RoPE (halves rotated)
turns every head's q_rope and the ONE k_r; s_j = (q_nope_j k_nope_j^T +
q_rope_j k_r^T) x (nope + rope)**-0.5, query p sees keys 0 .. p, float32
softmax, o_j = softmax(s_j) v_j; a = concat_j(o_j) o_proj over the heads
here; x += RMSNorm(a; post_attention_layernorm). Then y = RMSNorm(x;
pre_mlp_layernorm); dense: f = down(silu(gate(y)) x up(y)); sparse: s =
sigmoid(y gate) (float32), the token's experts its top `num_experts_per_tok`
of s, w_e = routed_scaling_factor x s_e / sum of the chosen s; f = sum over
chosen e that are held here of w_e E_e(y) + S(y); x += RMSNorm(f;
post_mlp_layernorm). A partial a (a share of the heads) and a partial f (a
share of the experts) are normed as they stand. After the last layer RMSNorm
and `lm_head` over the held vocabulary rows, mean token cross-entropy over
them. No auxiliary loss.

As the other two references, one more entry applies one layer's weights at a
time (`make_loss_from`, `make_grads_from`). The gradient of a layer is taken
a few leaves at a time (`leaf_groups`): the dense layer's float32 gradient
whole is 2.1 GB beside as much of float32 weights, and the step it is
checked beside fills the chip.

`from_system` re-lays tpu_mpi's parameter tree under the names above: a
renaming of leaves, so it carries gradients as well as parameters."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from yardstick.reference.lm_kinds_train_step import (SCORE_BYTES, gated,
                                                     held_experts_mix, route,
                                                     visible)
from yardstick.reference.lm_train_step import _f32, rms_norm, rope, xent

GROUP_ELEMENTS = 150_000_000    # a leaf with more has its gradient alone

NAMES = {"ln1": "input_layernorm", "ln1_out": "post_attention_layernorm",
         "ln2": "pre_mlp_layernorm", "ln2_out": "post_mlp_layernorm",
         "w_dq": "q_a_proj", "q_latent_norm": "q_a_layernorm",
         "w_uq": "q_b_proj", "w_dkv": "kv_a_proj_with_mqa",
         "kv_latent_norm": "kv_a_layernorm", "w_ukv": "kv_b_proj",
         "w_proj": "o_proj", "w_router": "gate", "w_gate": "gate_proj",
         "w_in": "up_proj", "w_out": "down_proj",
         "w_shared_gate": "shared_gate_proj", "w_shared_in": "shared_up_proj",
         "w_shared_out": "shared_down_proj"}


def from_system(params: dict, n_heads: int = 0) -> dict:
    """tpu_mpi.models.transformer's tree under the family's names."""
    return {"embed_tokens": params["embed"], "norm": params["ln_f"],
            "lm_head": params["lm_head"],
            "layers": [{NAMES[k]: v for k, v in p.items()}
                       for p in params["layers"]]}


def kinds(model: dict) -> list:
    """[sparse] of the layers that are here."""
    if not model.get("sandwich_norm"):
        raise ValueError("a block without the sandwich norm is not written "
                         "down here")
    if model.get("n_group", 1) != 1 or model.get("topk_group", 1) != 1:
        raise ValueError("a group-limited router is not written down here")
    dense = model["first_k_dense_replace"]
    return [i >= dense for i in range(model["num_hidden_layers"])]


def attention(model: dict, lp: dict, h):
    """h: (batch, seq, d), normed. What `o_proj` is applied to."""
    nh, eps = model["num_attention_heads"], model["rms_norm_eps"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv, ckv = model["v_head_dim"], model["kv_lora_rank"]
    theta = float(model["rope_theta"])

    def one(hs):                                    # (seq, d)
        t = hs.shape[0]
        c_q = rms_norm(hs @ lp["q_a_proj"], lp["q_a_layernorm"], eps)
        q = (c_q @ lp["q_b_proj"]).reshape(t, nh, dn + dr).transpose(1, 0, 2)
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:], theta)
        down = hs @ lp["kv_a_proj_with_mqa"]
        c_kv = rms_norm(down[:, :ckv], lp["kv_a_layernorm"], eps)
        k_r = rope(down[None, :, ckv:], theta)[0]   # (seq, rope): one key
        kv = (c_kv @ lp["kv_b_proj"]).reshape(t, nh, dn + dv)
        kv = kv.transpose(1, 0, 2)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        mask = visible(t, 0)
        part = max(1, min(nh, SCORE_BYTES // (4 * t * t)))
        while nh % part:
            part -= 1

        @jax.checkpoint
        def heads(ops):             # `part` heads at a time
            qn, qr, kn, vs = ops
            kr = jnp.broadcast_to(k_r[None], (part,) + k_r.shape)
            s = (jnp.einsum("hqd,hkd->hqk", qn, kn)
                 + jnp.einsum("hqd,hkd->hqk", qr, kr)) * (dn + dr) ** -0.5
            s = jnp.where(mask, s, -jnp.inf)
            return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), vs)
        o = lax.map(heads, tuple(
            a.reshape((nh // part, part) + a.shape[1:])
            for a in (q_nope, q_rope, k_nope, v)))
        return o.reshape(nh, t, dv).transpose(1, 0, 2).reshape(t, nh * dv)
    return lax.map(one, h)


def layer(model: dict, sparse: bool, lp: dict, x):
    """(x after the layer, each token's experts or None)."""
    eps = model["rms_norm_eps"]
    b, t, d = x.shape
    a = attention(model, lp, rms_norm(x, lp["input_layernorm"], eps)) \
        @ lp["o_proj"]
    x = x + rms_norm(a, lp["post_attention_layernorm"], eps)
    h = rms_norm(x, lp["pre_mlp_layernorm"], eps).reshape(b * t, d)
    idx = None
    if sparse:
        _scores, idx, dense = route(model, lp, h)
        f = held_experts_mix(model, lp, h, dense) + gated(
            h, lp["shared_gate_proj"], lp["shared_up_proj"],
            lp["shared_down_proj"])
    else:
        f = jax.checkpoint(gated)(h, lp["gate_proj"], lp["up_proj"],
                                  lp["down_proj"])
    f = rms_norm(f.reshape(b, t, d), lp["post_mlp_layernorm"], eps)
    return x + f, idx


def forward(model: dict, params: dict, tokens):
    """(logits over the held vocabulary rows, [each token's experts per
    sparse layer])."""
    x = params["embed_tokens"][tokens]
    chosen = []
    for sparse, lp in zip(kinds(model), params["layers"]):
        x, idx = layer(model, sparse, lp, x)
        if idx is not None:
            chosen.append(idx)
    x = rms_norm(x, params["norm"], model["rms_norm_eps"])
    return x @ params["lm_head"], chosen


def loss_of(model: dict, params: dict, tokens, labels):
    return xent(forward(model, params, tokens)[0], labels)


def _layerwise(model: dict):
    """(embed(table, tokens), one_layer(sparse, layer's weights, x)): the
    forward pass one program a layer, its weights taken to float32 there."""
    @jax.jit
    def embed(table, tok):
        return table.astype(jnp.float32)[tok]

    @functools.partial(jax.jit, static_argnums=0)
    def one_layer(sparse, lp, x):
        with jax.default_matmul_precision("highest"):
            return layer(model, sparse, _f32(lp), x)[0]
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
        for sparse, lp in zip(layer_kinds, params["layers"]):
            x = one_layer(sparse, lp, x)
        loss, logits = head(params["norm"], params["lm_head"], x, labels)
        return float(loss), logits
    return loss_from


def leaf_groups(lp: dict) -> list:
    """The layer's leaf names in groups whose float32 gradients are taken
    together: a leaf of more than `GROUP_ELEMENTS` alone, the rest as one."""
    big = sorted(k for k, v in lp.items() if v.size > GROUP_ELEMENTS)
    rest = tuple(sorted(k for k in lp if k not in big))
    return [rest] + [(k,) for k in big]


def make_grads_from(model: dict):
    """(params, tokens, labels) -> an iterator over the gradient of `loss_of`
    at `params` as they are (the family's names, any dtype, on the device or
    on the host), in float32, a few of one layer's leaves at a time
    (`leaf_groups`). It yields (None, {"norm", "lm_head"}), then (i, some of
    layer i's leaves) from the last layer down, each layer's groups one
    after the other, then (None, {"embed_tokens"}): what it has yielded the
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

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def layer_back(sparse, names, lp, x, d_out):
        """d of layer's output wrt the leaves `names`, or wrt its input
        (`names` empty)."""
        with jax.default_matmul_precision("highest"):
            lp = _f32(lp)
            if not names:
                return jax.vjp(lambda x: layer(model, sparse, lp, x)[0],
                               x)[1](d_out)[0]
            rest = {k: v for k, v in lp.items() if k not in names}
            return jax.vjp(
                lambda some: layer(model, sparse, {**rest, **some}, x)[0],
                {k: lp[k] for k in names})[1](d_out)[0]

    @jax.jit
    def embed_back(table, tok, d_x):
        _, back = jax.vjp(lambda t: t[tok], table.astype(jnp.float32))
        return back(d_x)[0]

    def grads_from(params, tokens, labels):
        xs = [embed(params["embed_tokens"], tokens)]
        for sparse, lp in zip(layer_kinds, params["layers"]):
            xs.append(one_layer(sparse, lp, xs[-1]))
        d_norm, d_head, d_x = head_back(params["norm"], params["lm_head"],
                                        xs.pop(), labels)
        yield None, {"norm": d_norm, "lm_head": d_head}
        del d_norm, d_head
        for i in reversed(range(len(params["layers"]))):
            lp, x = jax.device_put(params["layers"][i]), xs.pop()
            for names in leaf_groups(lp):
                yield i, layer_back(layer_kinds[i], names, lp, x, d_x)
            d_x = layer_back(layer_kinds[i], (), lp, x, d_x)
            del lp, x
        yield None, {"embed_tokens": embed_back(params["embed_tokens"],
                                                tokens, d_x)}
    return grads_from
