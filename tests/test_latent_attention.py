"""The fused attention kernel with scores that are the sum of two products
(latent attention: a head's unrotated part beside a rotated one whose key all
heads share) and values of a width of their own, on the Pallas interpret
machine against `parallel.ring.plain_attention`; its selection behind
`local_attention`, the counter's third kind, and that a program without a
latent layer traces none of it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_mpi import perfvars
from tpu_mpi.parallel import ring
from tpu_mpi.xla import pallas_kernels as pk

NAMES = ("o", "dq", "dk_nope", "dv", "dq_rope", "dk_rope")


def operands(batch, heads, t, dh, dr, dv, dtype, seed=0, rope_heads=1):
    """(q, k, v, q_rope, k_rope, do): `k_rope` of `rope_heads` heads."""
    shapes = [(heads, dh), (heads, dh), (heads, dv), (heads, dr),
              (rope_heads, dr), (heads, dv)]
    keys = jax.random.split(jax.random.key(seed), len(shapes))
    return tuple(jax.random.normal(key, (batch, h, t, w), jnp.float32)
                 .astype(dtype) for key, (h, w) in zip(keys, shapes))


def out_and_grads(attend, q, k, v, q2, k2, do):
    """One jitted program, waited for: the interpret machine's callbacks and
    an eager dispatch from this thread can wait on each other for good."""
    def both(q, k, v, q2, k2, do):
        o, vjp = jax.vjp(attend, q, k, v, q2, k2)
        return (o,) + vjp(do.astype(o.dtype))
    return jax.block_until_ready(jax.jit(both)(q, k, v, q2, k2, do))


def by_hand(q, k, v, q2, k2):
    """The equations, written out: k2 broadcast by hand, a [t, t] mask."""
    t = q.shape[2]
    k2 = jnp.broadcast_to(k2, q2.shape[:1] + (q.shape[1],) + k2.shape[2:]) \
        if k2.shape[1] == 1 else k2
    s = (jnp.einsum("bhqd,bhkd->bhqk", q, k)
         + jnp.einsum("bhqd,bhkd->bhqk", q2, k2)) \
        * (q.shape[3] + q2.shape[3]) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), dtype=bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def close(got, want, tol):
    for name, g, w in zip(NAMES, got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert g.shape == w.shape, name
        assert np.isfinite(g).all(), name
        assert np.abs(g - w).max() <= tol * np.abs(w).max(), name


def test_plain_path_is_the_equations():
    """`plain_attention` with a second term is the sum of two products over
    a shared key, scaled by the two widths together: what the kernel and
    the model are held against."""
    ops = operands(2, 4, 32, 16, 8, 24, jnp.float32)
    want = out_and_grads(by_hand, *ops)
    got = out_and_grads(
        lambda q, k, v, q2, k2: ring.plain_attention(q, k, v, 0, (q2, k2)),
        *ops)
    close(got, want, 1e-5)


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("blocks", [1, 2])
def test_kernel_matches_the_plain_path_forward_and_backward(batch, dtype,
                                                            blocks):
    """o, dq, dk_nope, dv, dq_rope and dk_rope (the sum over all heads of
    the one shared key's gradient) at the published triple, 128 + 64 wide
    scores beside 128-wide values: one block is the diagonal pair alone,
    two add a pair that is skipped and one that runs unmasked."""
    t = 256
    ops = operands(batch, 4, t, 128, 64, 128, jnp.dtype(dtype), seed=batch)
    block = t // blocks
    got = out_and_grads(
        lambda q, k, v, q2, k2: pk.causal_attention(
            q, k, v, rope=(q2, k2), block_q=block, block_k=block,
            interpret=True), *ops)
    for g, a in zip(got[1:], ops):
        assert g.dtype == a.dtype and g.shape == a.shape
    want = out_and_grads(
        lambda q, k, v, q2, k2: ring.plain_attention(q, k, v, 0, (q2, k2)),
        *(a.astype(jnp.float32) for a in ops))
    close(got, want, 2e-5 if dtype == "float32" else 2e-2)


def test_value_width_of_its_own_and_a_rotary_key_a_head():
    """Values 256 wide beside 128-wide scores' first term, and a second
    term whose keys have as many heads as its queries (no sum outside)."""
    ops = operands(1, 2, 256, 128, 64, 256, jnp.float32, seed=5, rope_heads=2)
    got = out_and_grads(
        lambda q, k, v, q2, k2: pk.causal_attention(
            q, k, v, rope=(q2, k2), block_q=128, block_k=128, interpret=True),
        *ops)
    want = out_and_grads(by_hand, *ops)
    close(got, want, 2e-5)


def test_contract_knows_the_triple():
    assert pk.causal_attention_blocks(4096, 128, 64, 128) == (512, 512)
    assert pk.causal_attention_blocks(4096, 128, 64) == (512, 512)
    assert pk.causal_attention_blocks(256, 128, 128, 256) == (256, 256)
    for rope, dv in [(32, 128), (96, 128), (64, 96), (64, 32)]:
        assert pk.causal_attention_blocks(4096, 128, rope, dv) is None
    # the one-term contract is what it was
    assert pk.causal_attention_blocks(4096, 128) == (512, 512)
    assert pk.causal_attention_blocks(256, 96) is None
    q, k, v, q2, k2, _ = operands(1, 2, 256, 128, 64, 128, jnp.float32)
    with pytest.raises(ValueError, match="second term"):
        pk.causal_attention(q, k, v, rope=(q2[:, :1], k2), interpret=True)
    with pytest.raises(ValueError, match="contract"):
        pk.causal_attention(q, k, v, rope=(q2[..., :32], k2[..., :32]),
                            interpret=True)


def test_selection_and_the_counters_third_kind(kernel_backend):
    """Off the kernel's backend a latent call is plain; with the interpret
    machine asked for, the triple inside the contract is fused, one outside
    it (a 32-wide rotated part) plain: `attn_kinds["latent"]` says which."""
    perfvars.reset()
    q, k, v, q2, k2, _ = operands(1, 2, 256, 128, 64, 128, jnp.float32)
    assert not ring.fused_attention_selected(q.shape, q.dtype, 64, 128)
    ring.local_attention(q, k, v, rope=(q2, k2))
    assert perfvars.snapshot()["attn_kinds"] == {"latent": "plain"}

    kernel_backend("interpret")
    perfvars.reset()
    assert ring.fused_attention_selected(q.shape, q.dtype, 64, 128)
    assert not ring.fused_attention_selected(q.shape, q.dtype, 32, 128)
    got = jax.block_until_ready(jax.jit(
        lambda *a: ring.local_attention(*a[:3], rope=a[3:]))(q, k, v, q2, k2))
    np.testing.assert_allclose(got, by_hand(q, k, v, q2, k2), atol=2e-6)
    snap = perfvars.snapshot()
    assert snap["attn_kinds"] == {"latent": "fused"}
    assert snap["attn_lowerings"] == {"fused": 1, "plain": 0}
    ring.local_attention(q, k, v, rope=(q2[..., :32], k2[..., :32]))
    ring.local_attention(q, k, v)
    snap = perfvars.snapshot()
    assert snap["attn_kinds"] == {"full": "fused", "latent": "mixed"}
    assert snap["attn_lowerings"] == {"fused": 2, "plain": 1}


def kernel_operands(jaxpr) -> list:
    """Sorted operand counts of every `pallas_call` in a closed jaxpr."""
    found = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(len(eqn.invars))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    return sorted(found)


def test_one_term_program_traces_the_one_term_body():
    """A program without a latent layer builds the kernels it built before:
    three operands forward and six backward, no ref of a second term; the
    two-term function is another, with two more each way."""
    q, k, v, q2, k2, do = operands(1, 2, 256, 128, 64, 128, jnp.float32)

    def loss(q, k, v, *rope):
        return jnp.sum(pk.causal_attention(q, k, v, rope=rope,
                                           interpret=True) * do)
    one = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert kernel_operands(one) == [3, 6]
    two = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
        q, k, v, q2, k2)
    assert kernel_operands(two) == [5, 8]
