"""The fused causal attention kernel (xla/pallas_kernels.causal_attention) on
the Pallas interpret machine against the plain einsum / softmax / einsum
path, and its selection behind `parallel.ring.local_attention`: what is
chosen from the backend and the kernel's contract, what the counter says,
and what `remat_attn` wraps."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_mpi import perfvars, xla
from tpu_mpi.models import transformer as tf
from tpu_mpi.models.transformer import (TransformerConfig, transformer_init,
                                        transformer_train_step)
from tpu_mpi.parallel import ring
from tpu_mpi.xla import pallas_kernels as pk


def plain(q, k, v):
    """`local_attention`'s plain path, whatever is selected."""
    t, dh = q.shape[2:]
    s = jnp.einsum("bhqd,bhkd->bhqk", q * dh ** -0.5, k)
    s = jnp.where(jnp.tril(jnp.ones((t, t), dtype=bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def operands(t, dh, dtype, seed=0, heads=2, gain=1.0):
    keys = jax.random.split(jax.random.key(seed), 4)
    q, k, v, do = (jax.random.normal(key, (1, heads, t, dh), jnp.float32)
                   for key in keys)
    return tuple(a.astype(dtype) for a in (q * gain, k, v, do))


def out_and_grads(attend, q, k, v, do):
    o, vjp = jax.vjp(attend, q, k, v)
    return (o,) + vjp(do.astype(o.dtype))


def close(got, want, tol):
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all(), name
        assert np.abs(g - w).max() <= tol * np.abs(w).max(), name


@pytest.mark.parametrize("key_blocks", [1, 2, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t, dh", [(256, 64), (512, 128)])
def test_kernel_matches_the_plain_path_forward_and_backward(t, dh, dtype,
                                                            key_blocks):
    """o, dq, dk, dv against the plain path's `jax.vjp` in float32. One key
    block is the diagonal pair alone; two and four add the pairs that are
    skipped (above the diagonal) and the ones that run unmasked (below)."""
    q, k, v, do = operands(t, dh, jnp.dtype(dtype))
    block = t // key_blocks
    got = out_and_grads(
        lambda *a: pk.causal_attention(*a, block_q=block, block_k=block,
                                       interpret=True), q, k, v, do)
    assert all(g.dtype == q.dtype and g.shape == q.shape for g in got)
    want = out_and_grads(plain, *(a.astype(jnp.float32) for a in (q, k, v, do)))
    # bfloat16: the probabilities and ds are rounded to 8 bits as operands
    close(got, want, 2e-5 if dtype == "float32" else 2e-2)


@pytest.mark.parametrize("block_q, block_k", [(256, 64), (64, 256), (128, 64)])
def test_rows_that_see_only_their_first_key_block(block_q, block_k):
    """Query blocks wider than key blocks (and the reverse): a pair on the
    diagonal then holds rows whose keys in it are all masked, so what those
    rows had from the first key block must survive untouched. Scores are
    large (q x 12), so a masked score that leaked would dominate."""
    q, k, v, do = operands(256, 64, jnp.float32, seed=3, gain=12.0)
    got = out_and_grads(
        lambda *a: pk.causal_attention(*a, block_q=block_q, block_k=block_k,
                                       interpret=True), q, k, v, do)
    want = out_and_grads(plain, q, k, v, do)
    close(got, want, 5e-5)
    # row 0 sees key 0 alone: its output is v[0] exactly
    np.testing.assert_allclose(got[0][:, :, 0], v[:, :, 0], rtol=1e-6)


def test_blocks_and_contract():
    assert pk.causal_attention_blocks(4096, 128) == (512, 512)
    assert pk.causal_attention_blocks(1024, 64) == (512, 512)
    assert pk.causal_attention_blocks(384, 64) == (128, 128)
    assert pk.causal_attention_blocks(256, 256) == (256, 256)
    for t, dh in [(16, 64), (200, 64), (256, 32), (256, 96), (2 ** 20, 128)]:
        assert pk.causal_attention_blocks(t, dh) is None, (t, dh)
    q, k, v, _ = operands(32, 64, jnp.float32)
    with pytest.raises(ValueError, match="contract"):
        pk.causal_attention(q, k, v, interpret=True)
    q, k, v, _ = operands(256, 64, jnp.float32)
    with pytest.raises(ValueError, match="do not divide"):
        pk.causal_attention(q, k, v, block_q=96, block_k=128, interpret=True)


def lowerings():
    return dict(perfvars.snapshot()["attn_lowerings"])


def test_selection_follows_the_backend_and_the_contract(kernel_backend):
    """On the CPU backend nothing is fused. With the interpret machine asked
    for (a test's patch, never a setting) an eligible shape takes the kernel
    and everything else the plain path; each call counts."""
    perfvars.reset()
    q, k, v, _ = operands(256, 64, jnp.float32)
    assert not ring.fused_attention_selected(q.shape, q.dtype)
    ring.local_attention(q, k, v)
    assert lowerings() == {"fused": 0, "plain": 1}

    kernel_backend("interpret")
    assert ring.fused_attention_selected(q.shape, q.dtype)
    got = ring.local_attention(q, k, v)
    assert lowerings() == {"fused": 1, "plain": 1}
    np.testing.assert_allclose(got, plain(q, k, v), atol=2e-6)
    for shape, dtype in [((1, 2, 16, 64), jnp.float32),     # the CPU tests' t
                         ((1, 2, 256, 32), jnp.float32),    # half a head
                         ((1, 2, 256, 64), jnp.float16),
                         ((1, 2, 256, 64), jnp.float64)]:
        assert not ring.fused_attention_selected(shape, dtype), (shape, dtype)
    small = tuple(a[:, :, :16] for a in (q, k, v))
    np.testing.assert_allclose(ring.local_attention(*small), plain(*small),
                               atol=2e-6)
    assert lowerings() == {"fused": 1, "plain": 2}
    perfvars.reset()
    assert lowerings() == {"fused": 0, "plain": 0}


def test_a_selected_kernel_that_cannot_lower_raises(kernel_backend):
    """Selected as on a TPU while the backend is the CPU: Mosaic cannot
    lower there, and that is an error, not a quiet plain path."""
    kernel_backend("mosaic")
    q, k, v, _ = operands(256, 64, jnp.float32)
    before = lowerings()
    with pytest.raises(ValueError, match="Only interpret mode is supported"):
        jax.block_until_ready(jax.jit(ring.local_attention)(q, k, v))
    assert lowerings()["fused"] == before["fused"] + 1


def test_a_ring_of_one_is_the_local_attention(kernel_backend):
    kernel_backend("interpret")
    mesh = xla.make_mesh({"sp": 1}, devices=jax.devices()[:1])
    q, k, v, _ = operands(128, 64, jnp.float32, seed=5)
    perfvars.reset()
    got = jax.jit(jax.shard_map(
        lambda *a: ring.ring_attention(*a, axis="sp", causal=True), mesh=mesh,
        in_specs=jax.P(), out_specs=jax.P()))(q, k, v)
    assert lowerings() == {"fused": 1, "plain": 0}
    np.testing.assert_allclose(got, plain(q, k, v), atol=2e-6)


TOY = dict(vocab=64, d_model=128, n_heads=2, n_layers=2, d_ff=64, max_seq=128)


def one_step(cfg, seed=11):
    mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1}, devices=jax.devices()[:1])
    step, _ = transformer_train_step(cfg, mesh, lr=0.1)
    params = transformer_init(jax.random.key(seed), cfg)
    tokens = jax.random.randint(jax.random.key(seed + 1), (2, 128), 0, cfg.vocab)
    return step(params, tokens, jnp.roll(tokens, -1, axis=1))


@pytest.mark.parametrize("model", ["flagship", "experts"])
def test_one_train_step_through_the_kernel_is_the_plain_step(kernel_backend,
                                                             model):
    """`transformer_train_step` at a toy shape inside the kernel's contract
    (seq 128, head 64), the selection patched to the interpret machine: the
    loss and every updated leaf against the plain path's, with and without
    QK-norm, experts and `remat_attn`."""
    extra = {} if model == "flagship" else dict(
        qk_norm=True, n_experts=4, experts_per_tok=2, router_aux_coef=0.01,
        tie_embeddings=False, remat_attn=True)
    cfg = TransformerConfig(dtype=jnp.float32, **TOY, **extra)
    # the layers of a program share one trace (`_block_traced_once`), and
    # the choice counts where it is made: once a program, whatever the depth
    jax.clear_caches()
    perfvars.reset()
    want_params, want_loss = one_step(cfg)
    assert lowerings() == {"fused": 0, "plain": 1}
    kernel_backend("interpret")
    got_params, got_loss = one_step(cfg)
    assert lowerings() == {"fused": 1, "plain": 1}
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    moved = 0.0
    for g, w, p0 in zip(jax.tree.leaves(got_params),
                        jax.tree.leaves(want_params),
                        jax.tree.leaves(transformer_init(jax.random.key(11),
                                                         cfg))):
        np.testing.assert_allclose(g, w, atol=2e-6)
        moved = max(moved, float(jnp.abs(w - p0).max()))
    assert moved > 1e-3                     # the step did move the leaves


def test_remat_attn_wraps_the_plain_path_and_not_the_kernel(kernel_backend):
    """`remat_attn` promises that no [b, h, s, s] scores are kept for the
    backward pass: the plain path is recomputed there, the kernel keeps
    none by construction and is not wrapped."""
    cfg = TransformerConfig(dtype=jnp.float32, remat_attn=True, **TOY)
    params = transformer_init(jax.random.key(0), cfg)
    tokens = jnp.zeros((1, 256), jnp.int32)

    def traced():
        return str(jax.make_jaxpr(jax.grad(lambda p: tf._xent(
            tf._forward(cfg, p, tokens)[0], tokens)))(params))
    text = traced()
    # jax.checkpoint, in the one backward trace that the layers share
    # (`_block_traced_once`), which every layer calls
    assert text.count("remat2[") == 1
    assert text.count("jaxpr=block") == 2 * cfg.n_layers
    assert "[1,2,256,256]" in text
    assert "pallas_call" not in text
    kernel_backend("interpret")
    text = traced()
    assert "remat2[" not in text
    assert "[1,2,256,256]" not in text      # no [b, h, s, s] value
    assert text.count("pallas_call") >= 2
