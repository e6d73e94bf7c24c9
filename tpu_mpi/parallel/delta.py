"""Linear attention with a gated delta rule: the scan of one block.

The recurrence, a value head, with a state ``S`` of [key width, value width]
(float32), a decay ``g_t`` <= 0 and a write strength ``beta_t`` in (0, 1),
one of each a value head and token:

    S_t = exp(g_t) S_{t-1}                            the state decays,
    S_t <- S_t + k_t (beta_t (v_t - S_t^T k_t))^T     is corrected by what it
                                                      already says of this key,
    o_t = S_t^T q_t                                   and is read.

Value head h reads key head h // (value heads / key heads). Unlike the
state-space scans of `parallel/ssm.py` (a decay times the state plus an outer
product) the written value depends on the state, so a chunk of tokens is no
masked product of its inputs alone. :func:`delta_recurrence` is the
recurrence as it stands, a token at a time: the form the tests hold the
chunks to. :func:`delta_scan` is the one "delta-rule scan of a block" the
model calls; it computes the recurrence ``chunk`` tokens at a time. With
``gamma_i`` the sum of the chunk's ``g`` up to token i and ``S`` the state
before the chunk, the values a chunk writes, ``u_i = beta_i (v_i - (exp(g_i)
S_{i-1})^T k_i)``, solve

    (I + A) U = beta V - (beta exp(gamma) K) S,
    A_ij = beta_i exp(gamma_i - gamma_j) (k_i . k_j)  for j < i, else 0,

a unit lower-triangular system a head and chunk. ``T = (I + A)^-1``
(:func:`_unit_lower_inverse`) gives ``U0 = T (beta V)`` and ``W = T (beta
exp(gamma) K)`` from the chunk's inputs alone, for all chunks at once; then

    U = U0 - W S                  S' = exp(gamma_last) S + (exp(gamma_last -
    O = (exp(gamma) Q) S + (Q K^T o decay, j <= i) U        gamma) K)^T U

and only ``S -> S'`` runs in order, one step a chunk (:func:`_state_chain`,
two products a step, its backward pass written out: two more and the
cotangents' three). The decay sums, their exponentials, ``A``, ``T`` and the
state are float32 (``T``'s products at `HIGHEST` precision: the MXU's
default would round its float32 operands to bfloat16); every other product
takes operands of the input's type and accumulates in float32; the output
is rounded once. For the backward pass the inputs and the state before each
chunk are kept ([chunks, batch, value heads, key width, value width]
float32) and every [chunk x chunk] array is computed again: `jax.checkpoint`
with a policy that saves the states alone.

The decay may also be a vector, one number a value head, token and KEY
CHANNEL (``g`` [batch, t, value heads, key width]: S_t = Diag(exp(g_t))
S_{t-1}; counted ``perfvars.snapshot()["delta_decays"]``: ``head`` or
``channel``). Everything above holds with gamma a vector and the decayed
[chunk x chunk] forms sum_d k_id k_jd exp(gamma_id - gamma_jd), which are no
product of K K^T with a matrix: :func:`_decayed_products` computes them by
halves so that no exponential of a positive number is ever formed; the
state's carry a chunk is a vector. With every channel alike it is the scalar
recurrence, to rounding.

The form is chosen from the shapes, never by trying, and counted where it is
chosen (``perfvars.snapshot()["delta_lowerings"]``): ``chunked`` where the
sequence is a multiple of the chunk, ``padded`` where it is not: the
sequence is filled up to the next multiple with tokens of ``g`` = 0,
``beta`` = 0 and ``k`` = 0, which decay nothing and write nothing, so the
result is exact, and their outputs are cut off.

Who computes it is chosen by `xla.choice`'s rule and counted beside the form
(``perfvars.snapshot()["delta_kernel_lowerings"]``): ``kernel`` where a
kernel backend is there (a TPU; the tests' word) and the contract
`xla.delta_kernels.delta_scan_selected` takes the operands. The contract has
two rows, both with heads of 128, a chunk of 64 and float32 or bfloat16:

    a decay a head      two value heads a key head   (Qwen3-Next's layers)
    a decay a channel   a key head a value head,     (Kimi-Linear's KDA)
                        the heads in twos

each one Pallas kernel each way that keeps a chunk's arrays and the state in
VMEM, the padded form filled with the same zero tokens in front of it. The
two are two bodies behind the one contract that share the inverse, ``W``,
``U0``, the written values, the outputs and the state's step: inside a
chunk one ``K K^T`` serves two value heads under a scalar outer difference
in the first, and two independent heads go through
:func:`_decayed_products`' six rounds in the second. ``plain`` everywhere
else (the CPU, the tests, other shapes, a decay a channel with two value
heads a key head): :func:`_chunked`, plain XLA, which is also what the tests
hold the kernels to. What a recomputed function around the scan may keep
of it is named :data:`KEPT` (`jax.checkpoint` with
``save_only_these_names(KEPT)``: `models.transformer._kda_mixer`'s half), and
what that is differs by path: on the plain path the state before each chunk
alone (:func:`_state_chain_fwd`: the chain over the chunks then runs once
each way and every other array is computed again); from the kernels of a
decay a channel those states AND the scan's output o (67 MB a layer more at
8192 tokens and 32 heads of 128), because o is what the caller's backward
pass reads and with it kept the forward kernel runs once a step, not twice;
the kernels of a decay a head name nothing (their one caller, `_gdn_mixer`,
recomputes nothing around the scan).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .. import perfvars
from ..xla import choice, delta_kernels

KEPT = delta_kernels.KEPT     # what is kept of the scan: the docstring's end
_EXACT = lax.Precision.HIGHEST


def delta_recurrence(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     g: jnp.ndarray, beta: jnp.ndarray) -> jnp.ndarray:
    """o [batch, t, value heads, value width] float32 of the recurrence
    above, one token at a time, everything float32: q and k [batch, t, key
    heads, key width], v [batch, t, value heads, value width], beta [batch,
    t, value heads], g the same (a decay a head) or [batch, t, value heads,
    key width] (a decay a key channel: S_t = Diag(exp(g_t)) S_{t-1})."""
    f32 = jnp.float32
    rep = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(a.astype(f32), rep, axis=2) for a in (q, k))

    def token(s, at):
        q_t, k_t, v_t, g_t, b_t = at    # [b, h, width] x 3, [b, h(, dk)], [b, h]
        s = s * jnp.exp(g_t if g.ndim == 4 else g_t[..., None])[..., None]
        seen = jnp.einsum("bhde,bhd->bhe", s, k_t, precision=_EXACT)
        s = s + k_t[..., :, None] * (b_t[..., None] * (v_t - seen))[..., None, :]
        return s, jnp.einsum("bhde,bhd->bhe", s, q_t, precision=_EXACT)
    start = jnp.zeros(v.shape[:1] + v.shape[2:3] + (k.shape[3], v.shape[3]),
                      f32)
    _, o = lax.scan(token, start, tuple(
        jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def delta_scan(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               g: jnp.ndarray, beta: jnp.ndarray,
               chunk: int = 64) -> jnp.ndarray:
    """o [batch, t, value heads, value width], of v's type, of the
    recurrence above in its chunked form: q and k [batch, t, key heads, key
    width] (the caller's norms and scale applied), v [batch, t, value heads,
    value width], beta [batch, t, value heads] and g (<= 0) the same or
    [batch, t, value heads, key width], float32; ``chunk`` a power of two.
    Each call built into a traced program counts in
    ``perfvars.snapshot()["delta_lowerings"]`` as ``chunked`` or ``padded``,
    in ``["delta_kernel_lowerings"]`` as ``kernel`` or ``plain`` and in
    ``["delta_decays"]`` as ``head`` or ``channel``. The result does not
    depend on the chunk."""
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk}: the triangular system is inverted "
                         f"by halves, so a chunk is a power of two")
    t = q.shape[1]
    pad = -t % chunk
    perfvars.note("delta_lowerings", "padded" if pad else "chunked")
    perfvars.note("delta_decays", "channel" if g.ndim == 4 else "head")

    def filled(a):      # up to the next multiple, with tokens of zeros
        widths = ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)
        return jnp.pad(a, widths) if pad else a
    operands = tuple(filled(a) for a in (q, k, v, g, beta))
    run = choice.decide(choice.DELTA_SCAN, v.shape[2], k.shape[2],
                        k.shape[3], v.shape[3], chunk, v.dtype,
                        g.shape[3] if g.ndim == 4 else 1,
                        also=q.dtype == k.dtype == v.dtype)
    if run:
        return delta_kernels.delta_scan(*operands,
                                        interpret=run.interpret)[:, :t]
    return _chunked(*operands, chunk)[:, :t]


def _same_block(length: int, size: int):
    """[length, length] bool: places i and j lie in one block of ``size``."""
    at = jnp.arange(length) // size
    return at[:, None] == at[None, :]


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` for a strictly lower-triangular ``a`` [..., n, n]
    float32, n a power of two, by halves: with T_s the inverse of the
    diagonal blocks of size s alone (T_1 = I) and a_s what ``a`` holds inside
    a block of 2 s and outside its two blocks of s, T_2s = T_s - T_s a_s T_s
    (the inverse of [[A, 0], [C, B]] is [[A', 0], [-B' C A', B']]). log2 n
    rounds of two [n x n] products, each as exact as float32 is: no power
    of ``a`` is ever formed, so keys that repeat (``a`` near all ones) cost
    no digits, where the series (I - a)(I + a^2)(I + a^4).. would cancel
    binomials of 1e17 at n = 64. The gradient is the inverse's own:
    -T^T dT T^T."""
    n = a.shape[-1]
    t = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), a.shape)
    size = 1
    while size < n:
        between = jnp.logical_and(_same_block(n, 2 * size),
                                  jnp.logical_not(_same_block(n, size)))
        t = t - jnp.matmul(t, jnp.matmul(jnp.where(between, a, 0.0), t,
                                         precision=_EXACT), precision=_EXACT)
        size *= 2
    return t


def _unit_lower_inverse_bwd(t, d):
    t_t = jnp.swapaxes(t, -1, -2)
    return (-jnp.matmul(t_t, jnp.matmul(d, t_t, precision=_EXACT),
                        precision=_EXACT),)


_unit_lower_inverse.defvjp(lambda a: (_unit_lower_inverse(a),) * 2,
                           _unit_lower_inverse_bwd)


def _chain_step(s, at, dtype):
    """One chunk of the state's recurrence: (the state after it, the values
    it wrote)."""
    carry, kd, w, u0 = at
    u = u0 - jnp.einsum("bhld,bhde->bhle", w, s.astype(dtype),
                        preferred_element_type=jnp.float32)
    after = carry[..., None] * s + jnp.einsum(
        "bhld,bhle->bhde", kd, u.astype(dtype),
        preferred_element_type=jnp.float32)
    return after, u


@jax.custom_vjp
def _state_chain(carry, kd, w, u0):
    """The state BEFORE each chunk, [chunks, batch, heads, key width, value
    width] float32, of S' = Diag(carry) S + kd^T (u0 - w S) from S = 0:
    ``carry`` [chunks, batch, heads, 1 | key width] float32 (a chunk's whole
    decay, one number a head or one a key channel), ``kd`` and ``w``
    [chunks, batch, heads, chunk, key width] of the input's type (the keys
    decayed to the chunk's end; ``W`` above), ``u0`` [chunks, batch, heads,
    chunk, value width] float32. The one part of the scan that runs in
    order. Its backward pass is the same chain run backwards over the kept
    states: no step of the forward one runs again."""
    start = jnp.zeros(u0.shape[1:3] + (kd.shape[-1], u0.shape[-1]),
                      jnp.float32)
    varies = tuple(sorted(set().union(*(jax.typeof(a).vma
                                        for a in (carry, kd, w, u0)))))
    if varies:      # under `shard_map` the carry varies as the operands do
        start = lax.pcast(start, varies, to="varying")

    def step(s, at):
        return _chain_step(s, at, kd.dtype)[0], s
    return lax.scan(step, start, (carry, kd, w, u0))[1]


def _state_chain_fwd(carry, kd, w, u0):
    states = checkpoint_name(_state_chain(carry, kd, w, u0), KEPT)
    return states, (carry, kd, w, u0, states)


def _state_chain_bwd(kept, d_states):
    carry, kd, w, u0, states = kept
    f32, dtype = jnp.float32, kd.dtype

    def step(d_after, at):
        c, kd_c, w_c, u0_c, s, d_s = at
        u = _chain_step(s, (c, kd_c, w_c, u0_c), dtype)[1].astype(dtype)
        after = d_after.astype(dtype)
        d_u = jnp.einsum("bhld,bhde->bhle", kd_c, after,
                         preferred_element_type=f32)
        d_kd = jnp.einsum("bhle,bhde->bhld", u, after,
                          preferred_element_type=f32)
        d_w = -jnp.einsum("bhle,bhde->bhld", d_u.astype(dtype),
                          s.astype(dtype), preferred_element_type=f32)
        d_before = c[..., None] * d_after + d_s - jnp.einsum(
            "bhld,bhle->bhde", w_c, d_u.astype(dtype),
            preferred_element_type=f32)
        d_c = jnp.sum(s * d_after, axis=-1)     # a key channel's
        if c.shape[-1] == 1:                    # one number a head: all of them
            d_c = jnp.sum(d_c, axis=-1, keepdims=True)
        return d_before, (d_c, d_kd.astype(dtype), d_w.astype(w.dtype), d_u)
    _, grads = lax.scan(step, jnp.zeros_like(d_states[0]),
                        (carry, kd, w, u0, states, d_states), reverse=True)
    return grads


_state_chain.defvjp(_state_chain_fwd, _state_chain_bwd)


def _for_values(a, hv: int):
    """A key head's [b, c, hk, x, y] for each of its value heads."""
    hk = a.shape[2]
    return jnp.broadcast_to(
        a[:, :, :, None], a.shape[:3] + (hv // hk,) + a.shape[3:]).reshape(
            a.shape[:2] + (hv,) + a.shape[3:])


def _chunk_parts(q, k, v, g, beta):
    """What the chunks give the state's chain and the outputs, each from
    its inputs alone ([batch, chunks, heads, chunk, width]; beta [batch,
    chunks, value heads, chunk] and g the same or with the key width behind,
    float32): (the whole decay a chunk [batch, chunks, value heads, 1 | key
    width] float32; the keys decayed to the chunk's end, ``W`` and the
    queries decayed from its start, of the input's type; ``U0`` float32; the
    masked, decayed scores of the input's type). Every [chunk x chunk] array
    lives and dies here."""
    hv, length = v.shape[2], k.shape[3]
    f32, dtype = jnp.float32, v.dtype
    gamma = jnp.cumsum(g, axis=3)       # [b, c, hv, l] or [b, c, hv, l, dk]
    seen = jnp.tril(jnp.ones((length, length), dtype=bool))
    k_v, q_v = (_for_values(a, hv).astype(f32) for a in (k, q))
    if g.ndim == 5:
        kk, scores = _decayed_products(k_v, q_v, gamma, dtype)
        told = beta[..., None] * kk
    else:
        decay = jnp.exp(jnp.where(
            seen, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
        kk, qk = (_for_values(jnp.einsum("bchld,bchsd->bchls", a, k,
                                         preferred_element_type=f32), hv)
                  for a in (k, q))            # [b, c, hv, l, s], s <= l
        told, scores = beta[..., None] * decay * kk, qk * decay
        gamma = gamma[..., None]        # one number for every key channel
    last = gamma[..., -1:, :]
    inverse = _unit_lower_inverse(
        jnp.where(jnp.tril(seen, -1), told, 0.0)).astype(dtype)
    w = jnp.einsum("bchls,bchsd->bchld", inverse,
                   (k_v * (beta[..., None] * jnp.exp(gamma))).astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    u0 = jnp.einsum("bchls,bchse->bchle", inverse,
                    (v.astype(f32) * beta[..., None]).astype(dtype),
                    preferred_element_type=f32)
    kd = (k_v * jnp.exp(last - gamma)).astype(dtype)
    return (jnp.exp(last[..., 0, :]), kd, w, u0,
            (q_v * jnp.exp(gamma)).astype(dtype), scores.astype(dtype))


def _decayed_products(k, q, gamma, dtype):
    """(sum_d k_id k_jd exp(gamma_id - gamma_jd), the same of q_i), [batch,
    chunks, heads, chunk, chunk] float32, for j <= i and zero above, for a
    decay a key channel (k, q and gamma [batch, chunks, heads, chunk, key
    width] float32). That is no product of K K^T with anything, and its
    factors (k_i o exp(gamma_i)) . (k_j o exp(-gamma_j)) overflow float32
    inside one chunk (gamma reaches -100 at a model's own initial values).
    No exponential of a positive number is formed here. By halves, as the
    inverse above: inside a block of 2 s tokens, the rows of its second half
    against the columns of its first go through the rows' first token n,
    exp(gamma_i - gamma_n) x exp(gamma_n - gamma_j) with j < n <= i, both
    factors <= 1, as one product of the two scaled operands (of ``dtype``,
    accumulated in float32: :func:`_crossed`); the halves are blocks of s,
    and a block of one token is a pair with itself, exponent 0. log2(chunk)
    rounds over arrays no larger than the operands: no [r, r, key width]
    form of a pair and channel exists (written as a masked exponential
    inside a sum over the channels, the compiler keeps it in HBM: 2.1 GB a
    layer at 8192 tokens and 32 heads of 128)."""
    length = k.shape[-2]
    a = jnp.stack([k, q])                       # both forms at once
    out = jnp.sum(a * k, axis=-1)[..., None, None]      # [2, .., l, 1, 1]
    size = 1
    while size < length:
        cross = _crossed(a, k, gamma, size, dtype)
        own = out.reshape(cross.shape[:-2] + (2, size, size))
        out = jnp.concatenate([
            jnp.concatenate([own[..., 0, :, :], jnp.zeros_like(cross)], -1),
            jnp.concatenate([cross, own[..., 1, :, :]], -1)], -2)
        size *= 2
    return out[0, ..., 0, :, :], out[1, ..., 0, :, :]


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _crossed(a, k, gamma, size: int, dtype):
    """One round of :func:`_decayed_products`: [2, batch, chunks, heads,
    blocks of 2 ``size``, ``size``, ``size``] float32, the rows of each
    block's second half against the columns of its first. The scaled
    operands are as large as half the chunk's inputs and there is a pair a
    round: each is computed again where the backward pass wants it, and
    none is kept."""
    def halves(x):      # [.., l, dk] -> its blocks' ([.., n, size, dk],) x 2
        x = x.reshape(x.shape[:-2] + (-1, 2, size, x.shape[-1]))
        return x[..., 0, :, :], x[..., 1, :, :]
    g_cols, g_rows = halves(gamma)
    first = g_rows[..., :1, :]                  # the rows' first token
    return jnp.einsum(
        "a...rd,...sd->a...rs",
        (halves(a)[1] * jnp.exp(g_rows - first)).astype(dtype),
        (halves(k)[0] * jnp.exp(first - g_cols)).astype(dtype),
        preferred_element_type=jnp.float32)


@functools.partial(
    jax.checkpoint, static_argnums=(5,),
    policy=jax.checkpoint_policies.save_only_these_names(KEPT))
def _chunked(q, k, v, g, beta, length: int):
    """The recurrence's outputs [batch, t, value heads, value width], of
    v's type (rounded here, so that what the caller's backward pass keeps
    of them is that wide), t a multiple of ``length``."""
    bsz, t, hk, _dk = k.shape
    hv, dv = v.shape[2:]
    nc = t // length
    f32, dtype = jnp.float32, v.dtype

    def by_chunk(a, heads):     # [b, t, h, w] -> [b, chunks, h, length, w]
        return a.reshape(bsz, nc, length, heads, -1).transpose(0, 1, 3, 2, 4)
    by_channel = g.ndim == 4
    g = by_chunk(g.astype(f32), hv)
    carry, kd, w, u0, q_from_start, scores = _chunk_parts(
        by_chunk(q, hk), by_chunk(k, hk), by_chunk(v, hv),
        g if by_channel else g[..., 0],
        by_chunk(beta.astype(f32), hv)[..., 0])
    states = jnp.moveaxis(_state_chain(*(
        jnp.moveaxis(a, 1, 0) for a in (carry, kd, w, u0))), 0, 1)
    before = states.astype(dtype)                   # [b, c, hv, dk, dv]
    u = u0 - jnp.einsum("bchld,bchde->bchle", w, before,
                        preferred_element_type=f32)
    o = jnp.einsum("bchld,bchde->bchle", q_from_start, before,
                   preferred_element_type=f32) \
        + jnp.einsum("bchls,bchse->bchle", scores, u.astype(dtype),
                     preferred_element_type=f32)
    return o.astype(dtype).transpose(0, 1, 3, 2, 4).reshape(bsz, t, hv, dv)
