"""Plain reference of the flagship's train step: the architecture's forward
pass, loss, gradients and SGD update in straightforward `jax.numpy`, float32
and `highest` matmul precision, with no kernel, no mesh, no shard_map and
none of tpu_mpi. It follows tpu_mpi/models/transformer.py's description of
itself: pre-norm RMSNorm (eps 1e-6), rotary embeddings on q and k (base
10000, halves rotated), causal multi-head attention with scores scaled by
head_dim**-0.5, a GELU (tanh form) MLP, a final RMSNorm and a head tied to
the embedding; the loss is the mean token cross-entropy.

Departures, each deliberate: everything is float32 (the system computes in
bfloat16 and rounds its weights to bfloat16 after every update, which is
what the comparison's tolerance is for); the batch is folded in
micro-batches of `micro` sequences with `lax.scan`, which is the same mean
and keeps the float32 activations inside one chip's memory."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def rms_norm(x, scale):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * scale


def rope(x):
    """x: (batch, heads, seq, head_dim); position p rotates pair (i, i+half)
    by p / 10000**(i/half)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(n_heads: int, params: dict, tokens):
    b, t = tokens.shape
    x = params["embed"][tokens]
    d = x.shape[-1]
    dh = d // n_heads
    causal = jnp.tril(jnp.ones((t, t), bool))
    for layer in params["layers"]:
        y = rms_norm(x, layer["ln1"])
        # w_qkv's columns are packed [head][q|k|v][head_dim]
        qkv = (y @ layer["w_qkv"]).reshape(b, t, n_heads, 3, dh)
        q, k, v = (qkv[:, :, :, i, :].transpose(0, 2, 1, 3) for i in range(3))
        s = jnp.einsum("bhqd,bhkd->bhqk", rope(q), rope(k)) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
        x = x + o.transpose(0, 2, 1, 3).reshape(b, t, d) @ layer["w_proj"]
        y = rms_norm(x, layer["ln2"])
        x = x + gelu(y @ layer["w_in"]) @ layer["w_out"]
    return rms_norm(x, params["ln_f"]) @ params["embed"].T


def loss_of(n_heads: int, params: dict, tokens, labels):
    logp = jax.nn.log_softmax(forward(n_heads, params, tokens), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def make_step(n_heads: int, lr: float, micro: int):
    """jit(params, tokens, labels) -> (params, loss): one SGD step on the
    whole batch, the gradient summed over micro-batches of `micro` rows."""
    def step(params, tokens, labels):
        nb = tokens.shape[0] // micro
        tok = tokens.reshape(nb, micro, -1)
        lab = labels.reshape(nb, micro, -1)

        def fold(acc, tl):
            loss, grads = jax.value_and_grad(
                lambda p: loss_of(n_heads, p, *tl))(params)
            return jax.tree.map(jnp.add, acc, (loss, grads)), None
        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
        (loss, grads), _ = lax.scan(fold, zero, (tok, lab))
        new = jax.tree.map(lambda p, g: p - lr * g / nb, params, grads)
        return new, loss / nb

    def with_precision(params, tokens, labels):
        with jax.default_matmul_precision("highest"):
            return step(params, tokens, labels)
    return jax.jit(with_precision)


def losses(n_heads: int, lr: float, params: dict, batches: list,
           micro: int) -> list:
    """Loss before each of the first len(batches) steps, from `params`
    (any dtype; taken to float32) over `batches` = [(tokens, labels)]."""
    step = make_step(n_heads, lr, micro)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    out = []
    for tokens, labels in batches:
        p, loss = step(p, tokens, labels)
        out.append(float(loss))
    return out
