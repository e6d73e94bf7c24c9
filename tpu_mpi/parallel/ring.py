"""Ring attention: exact attention over sequence shards with a ppermute ring.

Reference primitives: the periodic Cart_shift + Sendrecv! ring machinery
(SURVEY.md §5 long-context; /root/reference/test/test_sendrecv.jl:100-115,
src/topology.jl:155-164). TPU realization: each rank holds a sequence block of
Q/K/V; K/V blocks rotate around the 'sp' mesh axis with ``lax.ppermute`` while
a flash-style online softmax accumulates — n_ring steps of compute overlapped
with neighbor DMA on the ICI ring, memory O(block²) instead of O(seq²).

A ring of one is :func:`local_attention`, the attention of a block with
itself: on a TPU, at a shape inside its contract, the fused Pallas kernel
``xla.pallas_kernels.causal_attention`` (blockwise, forward and backward, no
[b, h, t, t] tensor in HBM); everywhere else the plain einsum / softmax /
einsum. Both take a window, fewer key/value heads than query heads, and
scores that are the sum of two products (a head's unrotated part beside a
rotated one whose key all heads may share) with values of a width of their
own. A ring of n > 1 keeps its XLA online softmax per step.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..xla import choice
from ..xla import pallas_kernels as pk

NEG_INF = -1e30


def fused_attention_selected(shape: tuple, dtype, rope_dim: int = 0,
                             value_dim: int = 0) -> bool:
    """Whether :func:`local_attention` runs the fused kernel for (batch,
    heads, t, head_dim) queries of ``dtype``: `xla.choice`'s rule over the
    kernel's contract, ``pallas_kernels.causal_attention_blocks``. (A
    window, and how many heads the keys and values have, are not part of
    the contract: any window, any divisor of the queries' heads. The width
    of a second term of the scores, ``rope_dim``, and of the values where
    it is their own, ``value_dim``, are.)"""
    return choice.fit(choice.ATTENTION, shape[2], shape[3], rope_dim,
                      value_dim, dtype) is not None


def local_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    window: int = 0, rope: tuple = ()) -> jnp.ndarray:
    """Causal attention of a (batch, heads, t, head_dim) block with itself,
    scaled by head_dim ** -0.5. ``window`` > 0: a query sees its last
    ``window`` keys, itself included. k and v may hold fewer heads than q:
    query head j reads key/value head j // (heads / key-value heads).

    ``rope`` = (q_rope, k_rope): the scores are (q k^T + q_rope k_rope^T) x
    (head_dim + rope width) ** -0.5, a head's unrotated part beside its
    rotated one (latent attention); `k_rope` may hold one head that every
    query head reads, and v a width of its own. Each call built into a
    traced program counts in ``perfvars.snapshot()["attn_lowerings"]`` as
    ``fused`` or ``plain``, and by its kind (``full``, ``window``,
    ``latent``) in ``["attn_kinds"]``."""
    rope_dim = rope[0].shape[3] if rope else 0
    run = choice.decide(
        choice.ATTENTION, q.shape[2], q.shape[3], rope_dim, v.shape[3],
        q.dtype, of="latent" if rope else "window" if window else "full")
    if run:
        return pk.causal_attention(q, k, v, window=window, rope=rope,
                                   interpret=run.interpret)
    return plain_attention(q, k, v, window, rope)


def plain_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    window: int = 0, rope: tuple = ()) -> jnp.ndarray:
    """:func:`local_attention`'s meaning as einsum / softmax / einsum: what
    runs off the kernel's backend and contract, and what the kernel is held
    against (tests, `chip_smoke.py`). Writes [b, h, t, t] scores."""
    t, dh = q.shape[2:]
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    if rope:        # one product over the two parts side by side
        q2, k2 = rope
        dh += q2.shape[3]
        q = jnp.concatenate([q, q2], axis=-1)
        k = jnp.concatenate(
            [k, jnp.repeat(k2, q.shape[1] // k2.shape[1], axis=1)], axis=-1)
    # stays in the input dtype: an f32 upcast here runs the attention
    # matmuls on the slow MXU path and cost 13% of a full bf16 train step
    # (benchmarks/flagship_probe)
    s = jnp.einsum("bhqd,bhkd->bhqk", q * dh ** -0.5, k)
    mask = jnp.tril(jnp.ones((t, t), dtype=bool))
    if window:
        mask = jnp.logical_and(mask, jnp.triu(jnp.ones((t, t), dtype=bool),
                                              1 - window))
    s = jnp.where(mask, s, NEG_INF)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   axis: str = "sp", causal: bool = True,
                   scale: Optional[float] = None,
                   window: int = 0, rope: tuple = ()) -> jnp.ndarray:
    """Blockwise-exact attention over a sequence-sharded axis.

    q, k, v: (batch, heads, block_len, head_dim) — the local sequence block.
    Block b of the global sequence lives on rank b of ``axis``. Returns the
    local attention output block (same shape as q). A ``window``, fewer
    key/value heads than query heads or a second term of the scores
    (``rope``) are :func:`local_attention`'s, a ring of one; a longer ring
    has none of them yet.
    """
    b, h, t, d = q.shape
    n = lax.axis_size(axis)
    my = lax.axis_index(axis)
    if n == 1 and causal and scale is None:
        return local_attention(q, k, v, window, rope)
    if window or k.shape[1] != h or rope:
        raise NotImplementedError(
            "ring attention over more than one sequence shard takes no "
            "window, as many key/value heads as query heads and scores of "
            "one product")
    scale = (d ** -0.5) if scale is None else scale
    q = q * scale

    acc = jnp.zeros_like(q, dtype=jnp.float32)
    m = jnp.full((b, h, t, 1), NEG_INF, dtype=jnp.float32)   # running max
    l = jnp.zeros((b, h, t, 1), dtype=jnp.float32)           # running denom

    k_cur, v_cur = k, v
    perm = [(i, (i + 1) % n) for i in range(n)]
    for step in range(n):
        src_block = (my - step) % n          # which global block k_cur holds
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_cur).astype(jnp.float32)
        if causal:
            # block-granular mask: future blocks fully masked, own block
            # triangular, past blocks unmasked.
            qi = jnp.arange(t)[:, None]
            ki = jnp.arange(t)[None, :]
            tri = jnp.where(qi >= ki, 0.0, NEG_INF)
            s = s + jnp.where(src_block == my, tri,
                              jnp.where(src_block > my, NEG_INF, 0.0))
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        # zero masked entries explicitly: when a whole row is masked both s
        # and m_new are NEG_INF and exp(s - m_new) would wrongly be 1.
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        correction = jnp.exp(jnp.maximum(m - m_new, NEG_INF))
        l = l * correction + p.sum(axis=-1, keepdims=True)
        acc = acc * correction + jnp.einsum("bhqk,bhkd->bhqd", p,
                                            v_cur.astype(jnp.float32))
        m = m_new
        if step != n - 1:
            k_cur = lax.ppermute(k_cur, axis, perm)
            v_cur = lax.ppermute(v_cur, axis, perm)

    out = acc / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)
