"""Ring attention: exact attention over sequence shards with a ppermute ring.

Reference primitives: the periodic Cart_shift + Sendrecv! ring machinery
(SURVEY.md §5 long-context; /root/reference/test/test_sendrecv.jl:100-115,
src/topology.jl:155-164). TPU realization: each rank holds a sequence block of
Q/K/V; K/V blocks rotate around the 'sp' mesh axis with ``lax.ppermute`` while
a flash-style online softmax accumulates — n_ring steps of compute overlapped
with neighbor DMA on the ICI ring, memory O(block²) instead of O(seq²).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   axis: str = "sp", causal: bool = True,
                   scale: Optional[float] = None) -> jnp.ndarray:
    """Blockwise-exact attention over a sequence-sharded axis.

    q, k, v: (batch, heads, block_len, head_dim) — the local sequence block.
    Block b of the global sequence lives on rank b of ``axis``. Returns the
    local attention output block (same shape as q).
    """
    b, h, t, d = q.shape
    n = lax.axis_size(axis)
    my = lax.axis_index(axis)
    scale = (d ** -0.5) if scale is None else scale
    q = q * scale

    if n == 1:
        # ring of one = plain local attention: skip the online-softmax
        # machinery so XLA fuses the whole block, and stay in the input
        # dtype (an f32 upcast here runs the attention matmuls on the slow
        # MXU path and cost 13% of a full bf16 train step, measured by
        # benchmarks/flagship_probe)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
        if causal:
            qi = jnp.arange(t)[:, None]
            ki = jnp.arange(t)[None, :]
            s = jnp.where(qi >= ki, s, jnp.asarray(NEG_INF, s.dtype))
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v).astype(q.dtype)

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
