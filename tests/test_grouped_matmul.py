"""The grouped matrix multiplication kernels (xla/pallas_kernels.grouped_matmul:
rows x their group's matrix, and in the backward pass the rows' and the
weights' gradients) on the Pallas interpret machine against `lax.ragged_dot`,
and their selection behind `parallel.ep.grouped_products`: what is chosen from
the backend and the kernels' contract, what the counter says, and that an
expert layer inside the contract equals the plain path."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tpu_mpi import perfvars, xla
from tpu_mpi.models import transformer as tf
from tpu_mpi.models.transformer import TransformerConfig, transformer_init
from tpu_mpi.parallel import ep, ring
from tpu_mpi.xla import pallas_kernels as pk

M, K, N, TILE = 1024, 128, 256, 128
# group sizes over M rows in tiles of TILE; the names say what each is for
SPLITS = {
    "balanced": [256, 256, 256, 256],
    "uneven": [896, 40, 24, 64],                # one group at 7 x the mean
    "an_empty_group": [300, 0, 424, 300],
    "an_empty_group_at_a_tile_edge": [256, 0, 512, 256],
    "empty_first_and_last": [0, 600, 424, 0],
    "a_group_smaller_than_a_tile": [500, 30, 494],
    "a_tile_shared_by_three_groups": [130, 20, 30, 844],
    "rows_past_the_sum": [200, 100, 250, 90],   # 640 of 1024, 384 left over
    "a_whole_tile_past_the_sum": [128, 128, 0, 256],
    "no_rows_at_all": [0, 0, 0],
    "all_rows_in_one_group": [0, 1024, 0, 0],
}


def operands(sizes, dtype, m=M, k=K, n=N, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    lhs = jax.random.normal(keys[0], (m, k), jnp.float32)
    rhs = jax.random.normal(keys[1], (len(sizes), k, n), jnp.float32) * k ** -0.5
    dout = jax.random.normal(keys[2], (m, n), jnp.float32)
    return (lhs.astype(dtype), rhs.astype(dtype), dout.astype(dtype),
            jnp.asarray(sizes, jnp.int32))


def out_and_grads(product, lhs, rhs, dout, sizes):
    """(out, d lhs, d rhs) as ONE jitted program, waited for: while an
    interpreted kernel's callbacks run, this thread must not dispatch
    another computation of its own (the two can wait on each other for
    good on a loaded host)."""
    def run(lhs, rhs, dout):
        out, vjp = jax.vjp(lambda l, r: product(l, r, sizes), lhs, rhs)
        return (out,) + vjp(dout.astype(out.dtype))
    return jax.block_until_ready(jax.jit(run)(lhs, rhs, dout))


def kernel(tile=TILE, cols=None):
    return lambda l, r, s: pk.grouped_matmul(l, r, s, block_m=tile,
                                             block_c=cols, interpret=True)


def close(got, want, tol):
    for name, g, w in zip(("out", "d lhs", "d rhs"), got, want):
        assert g.shape == w.shape, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all(), name
        assert np.abs(g - w).max() <= tol * max(np.abs(w).max(), 1.0), name


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("split", sorted(SPLITS))
def test_kernel_matches_ragged_dot_forward_and_backward(split, dtype):
    """out, d lhs and d rhs against `lax.ragged_dot`'s own in float32, over
    the splits a sorted batch of token-slots can have. Weights that widen
    the rows (k < n: an expert's gate and in); the ones that narrow them
    are the next test's."""
    check_against_ragged_dot(split, dtype, K, N)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("split", ["uneven", "a_tile_shared_by_three_groups",
                                   "rows_past_the_sum", "an_empty_group"])
def test_weights_that_narrow_the_rows(split, dtype):
    """k > n, an expert's out projection: the rows' gradient is then the
    wider product and the weights' gradient is cut along its other side."""
    check_against_ragged_dot(split, dtype, N, K)


def check_against_ragged_dot(split, dtype, k, n):
    lhs, rhs, dout, sizes = operands(SPLITS[split], jnp.dtype(dtype), k=k,
                                     n=n)
    got = out_and_grads(kernel(), lhs, rhs, dout, sizes)
    assert all(g.dtype == lhs.dtype for g in got)
    want = out_and_grads(lax.ragged_dot, *(a.astype(jnp.float32)
                                           for a in (lhs, rhs, dout)), sizes)
    # bfloat16: one rounding of a float32 sum, half a unit in the last of 8
    # bits of values up to the largest
    close(got, want, 1e-5 if dtype == "float32" else 6e-3)
    past = int(sizes.sum())
    if past < M:
        # what `ep._over_expert_ranks` hands in: those rows are zero, and
        # so are their gradient and their share of the weights' gradient
        assert not np.asarray(got[0][past:], np.float32).any()
        assert not np.asarray(got[1][past:], np.float32).any()
    empty = np.asarray(sizes) == 0
    assert not np.asarray(got[2], np.float32)[empty].any()


@pytest.mark.parametrize("tile, cols", [(128, 128), (256, 128), (512, 256),
                                        (1024, 2048)])
def test_every_tiling_gives_the_same_product(tile, cols):
    """Row tiles of 128 to the whole operand, the columns cut (the
    weights' gradient in 128-wide blocks of both k and n) or whole."""
    lhs, rhs, dout, sizes = operands(SPLITS["a_tile_shared_by_three_groups"],
                                     jnp.float32, k=256, seed=2)
    got = out_and_grads(kernel(tile, cols), lhs, rhs, dout, sizes)
    close(got, out_and_grads(lax.ragged_dot, lhs, rhs, dout, sizes), 1e-5)


def test_rows_past_the_sum_may_hold_anything():
    """Padding rows that are not zero on the way in (nor in d out) reach
    neither the product nor a gradient."""
    lhs, rhs, dout, sizes = operands(SPLITS["rows_past_the_sum"], jnp.float32)
    past = int(sizes.sum())
    got = out_and_grads(kernel(), lhs.at[past:].set(1e30), rhs,
                        dout.at[past:].set(-1e30), sizes)
    want = out_and_grads(lax.ragged_dot, lhs.at[past:].set(0.0), rhs,
                         dout.at[past:].set(0.0), sizes)
    close(got, want, 1e-5)


def test_the_walk_over_groups_and_tiles():
    """`grouped_matmul_visits`: every (group, tile) pair that holds a row, in order,
    an empty group once, the rows past the sum as group g, then padding."""
    def walk(sizes):
        return tuple(np.asarray(a).tolist() for a in pk.grouped_matmul_visits(
            jnp.asarray(sizes, jnp.int32), 1024, 128))
    offsets, group, tile, matrix, visits = walk([130, 0, 20, 300])
    assert offsets == [0, 130, 130, 150, 450, 1024]
    assert visits == [12] and len(group) == len(tile) == 1024 // 128 + 4
    assert group == [0, 0, 1, 2, 3, 3, 3, 4, 4, 4, 4, 4]
    assert tile == [0, 1, 1, 1, 1, 2, 3, 3, 4, 5, 6, 7]
    # the matrix a product needs: never an empty group's, never group g's
    assert matrix == [0, 0, 0, 2, 3, 3, 3, 3, 3, 3, 3, 3]
    # groups that end at tile edges leave the static length unused: padding
    offsets, group, tile, matrix, visits = walk([128, 0, 128, 768])
    assert visits == [9]
    assert group == [0, 1, 2, 3, 3, 3, 3, 3, 3, 4, 4, 4]
    assert tile == [0, 1, 1, 2, 3, 4, 5, 6, 7, 7, 7, 7]
    assert matrix == [0, 0, 2, 3, 3, 3, 3, 3, 3, 3, 3, 3]
    walked = pk.grouped_matmul_visits(jnp.asarray([1, 2], jnp.int32), 128, 128)
    assert all(np.asarray(a).dtype == np.int32 for a in walked)


@pytest.mark.parametrize("m, k, n, itemsize, want", [
    (65536, 2048, 1024, 2, (512, 2048)),        # the OLMoE cell's gate / in
    (65536, 1024, 2048, 2, (512, 2048)),        # and its out projection
    (384, 128, 256, 4, (128, 2048)),
    (65536, 2048, 1024, 4, (512, 2048)),        # float32: at the limit, whole
    (65536, 4096, 4096, 2, (512, 1024)),        # VMEM cuts the columns
    (65536, 4096, 4096, 4, (512, 512)),
    (256, 64, 32, 4, None),                     # the rehearse size
    (65536, 2048, 1000, 2, None),               # n not a multiple of 128
    (65536, 2000, 1024, 2, None),               # k neither
    (1000, 2048, 1024, 2, None),                # no row tile divides m
    (65536, 16384, 128, 4, None),               # the blocks are over VMEM
])
def test_blocks_and_contract(m, k, n, itemsize, want):
    assert pk.grouped_matmul_blocks(m, k, n, itemsize) == want
    if want is not None:
        assert 2 * pk._grouped_vmem(want[0], k, n, want[1], itemsize) \
            <= pk.VMEM_LIMIT_BYTES


def test_a_shape_outside_the_contract_raises():
    lhs, rhs, _, sizes = operands([16, 16], jnp.float32, m=32, k=64, n=32)
    with pytest.raises(ValueError, match="contract"):
        pk.grouped_matmul(lhs, rhs, sizes, interpret=True)
    lhs, rhs, _, sizes = operands(SPLITS["balanced"], jnp.float32)
    with pytest.raises(ValueError, match="do not divide"):
        pk.grouped_matmul(lhs, rhs, sizes, block_m=96, interpret=True)
    with pytest.raises(ValueError, match="one dtype"):
        pk.grouped_matmul(lhs, rhs.astype(jnp.bfloat16), sizes, interpret=True)


def lowerings():
    return dict(perfvars.snapshot()["gmm_lowerings"])


def test_selection_follows_the_backend_and_the_contract(kernel_backend):
    """On the CPU backend every product is `lax.ragged_dot`. With the
    interpret machine asked for (a test's patch of the one rule both kernels
    share, never a setting) a shape inside the contract takes the kernel and
    everything else the plain path; each call counts."""
    perfvars.reset()
    lhs, rhs, _, sizes = operands(SPLITS["uneven"], jnp.float32)
    want = lax.ragged_dot(lhs, rhs, sizes)
    assert not ep.grouped_matmul_selected(lhs.shape, rhs.shape, lhs.dtype)
    np.testing.assert_array_equal(ep.grouped_products(sizes)(lhs, rhs), want)
    assert lowerings() == {"kernel": 0, "ragged_dot": 1}

    kernel_backend("interpret")
    q = jnp.zeros((1, 2, 256, 64), jnp.float32)
    assert ring.fused_attention_selected(q.shape, q.dtype)   # one patch, both
    assert ep.grouped_matmul_selected(lhs.shape, rhs.shape, lhs.dtype)
    np.testing.assert_allclose(
        jax.block_until_ready(ep.grouped_products(sizes)(lhs, rhs)), want,
        atol=1e-5)
    assert lowerings() == {"kernel": 1, "ragged_dot": 1}
    for rows, weights, dtype in [
            ((256, 64), (8, 64, 32), jnp.float32),      # the rehearse size
            ((1024, 128), (4, 128, 200), jnp.float32),
            ((1000, 128), (4, 128, 256), jnp.float32),
            ((1024, 128), (4, 128, 256), jnp.float16),
            ((1024, 128), (4, 128, 256), jnp.float64)]:
        assert not ep.grouped_matmul_selected(rows, weights, dtype), rows
    small = operands([100, 156], jnp.float32, m=256, k=64, n=32)
    np.testing.assert_array_equal(
        ep.grouped_products(small[3])(small[0], small[1]),
        lax.ragged_dot(small[0], small[1], small[3]))
    # operands of two dtypes: `ragged_dot`'s business
    ep.grouped_products(sizes)(lhs.astype(jnp.bfloat16), rhs)
    assert lowerings() == {"kernel": 1, "ragged_dot": 3}
    perfvars.reset()
    assert lowerings() == {"kernel": 0, "ragged_dot": 0}


def test_a_selected_kernel_that_cannot_lower_raises(kernel_backend):
    """Selected as on a TPU while the backend is the CPU: Mosaic cannot
    lower there, and that is an error, not a quiet `ragged_dot`."""
    kernel_backend("mosaic")
    lhs, rhs, _, sizes = operands(SPLITS["balanced"], jnp.float32)
    with pytest.raises(ValueError, match="Only interpret mode is supported"):
        jax.block_until_ready(ep.grouped_products(sizes)(lhs, rhs))


# -- an expert layer inside the contract ---------------------------------------

LAYER = TransformerConfig(
    vocab=64, d_model=128, n_heads=2, n_layers=1, d_ff=128, max_seq=64,
    dtype=jnp.bfloat16, norm_eps=1e-5, qk_norm=True, n_experts=4,
    experts_per_tok=2, router_aux_coef=0.01, tie_embeddings=False)


def layer_out_and_grads(dtype):
    """`_expert_ffn` of 4 x 64 tokens (512 token-slots, tiles of 512, 256 or
    128 by the contract) and its gradient with respect to the tokens and the
    layer's weights."""
    cfg = LAYER if dtype == jnp.bfloat16 else \
        TransformerConfig(**{**LAYER.__dict__, "dtype": dtype})
    layer = transformer_init(jax.random.key(3), cfg)["layers"][0]
    y = jax.random.normal(jax.random.key(4), (4, 64, cfg.d_model),
                          jnp.float32).astype(dtype)

    def summed(layer, y):
        out, (_probs, slots) = tf._expert_ffn(cfg, layer, y)
        return jnp.sum(out.astype(jnp.float32) ** 2), (out, slots)
    # one jitted program, waited for (see `out_and_grads`)
    (_loss, (out, slots)), grads = jax.block_until_ready(jax.jit(
        jax.value_and_grad(summed, argnums=(0, 1), has_aux=True))(layer, y))
    return out, slots, grads


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_an_expert_layer_equals_the_plain_path(kernel_backend, dtype):
    """With the kernel selected (the interpret machine) one layer's output,
    its token-slots per expert and every gradient equal the `ragged_dot`
    path's within the operand dtype's rounding; all three products count."""
    dtype = jnp.dtype(dtype)
    perfvars.reset()
    want = layer_out_and_grads(dtype)
    assert lowerings() == {"kernel": 0, "ragged_dot": 3}
    kernel_backend("interpret")
    got = layer_out_and_grads(dtype)
    assert lowerings() == {"kernel": 3, "ragged_dot": 3}
    np.testing.assert_array_equal(got[1], want[1])
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for g, w in zip(jax.tree.leaves((got[0], got[2])),
                    jax.tree.leaves((want[0], want[2]))):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all()
        assert np.abs(g - w).max() <= tol * max(np.abs(w).max(), 1e-6)


def test_one_train_step_through_the_kernels_is_the_plain_step(kernel_backend):
    """`transformer_train_step` on a 1 x 1 x 1 mesh at a toy shape inside
    both kernels' contracts, the selection patched to the interpret machine:
    the loss and every updated leaf against the plain step's. Under
    `shard_map` the rows vary over dp and sp and the weights do not: the
    kernel's operands are made to vary together, and the cast's transpose
    sums the weights' gradient as XLA's own product's would be.

    (`moe_dropless(..., axis="ep")` is not run through the kernel here: the
    interpret machine holds the devices of a mesh to one another with a
    barrier per kernel, which a loaded test host can stall. What that path
    needs of the kernel, rows past the groups' sum that hold anything and
    come out zero, is tested above on one device.)"""
    from tpu_mpi.models.transformer import transformer_train_step
    cfg = TransformerConfig(**{**LAYER.__dict__, "dtype": jnp.float32,
                               "max_seq": 128, "n_layers": 2})

    def one_step():
        mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1},
                             devices=jax.devices()[:1])
        step, _ = transformer_train_step(cfg, mesh, lr=0.1)
        params = transformer_init(jax.random.key(11), cfg)
        tokens = jax.random.randint(jax.random.key(12), (2, 128), 0, cfg.vocab)
        return jax.block_until_ready(
            step(params, tokens, jnp.roll(tokens, -1, axis=1)))

    # the layers of a program share one trace (`_block_traced_once`): the
    # three products count once a program, where the choice is made
    jax.clear_caches()
    perfvars.reset()
    want_params, want_loss = one_step()
    assert lowerings() == {"kernel": 0, "ragged_dot": 3}
    kernel_backend("interpret")
    got_params, got_loss = one_step()
    assert lowerings() == {"kernel": 3, "ragged_dot": 3}
    assert perfvars.snapshot()["attn_lowerings"]["fused"] == 1
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    start = transformer_init(jax.random.key(11), cfg)
    moved = 0.0
    for g, w, p0 in zip(*(jax.tree.leaves(t) for t in
                          (got_params, want_params, start))):
        np.testing.assert_allclose(g, w, atol=5e-6)
        moved = max(moved, float(jnp.abs(w - p0).max()))
    assert moved > 1e-3                     # the step did move the leaves


# -- what the kernels cost a step's set-up --------------------------------------

def test_a_program_traces_each_kernel_and_the_walk_once(monkeypatch, kernel_backend):
    """The set-up guard (PERF.md, Set-up): tracing a kernel's body and
    lowering it is what a kernel costs before the first step, so a step of
    four layers traces each distinct kernel once and the walk over the groups
    once, whatever its depth: 3 kinds x 2 weight shapes (an expert's gate and
    in share one) = 6 bodies for the 36 products, in 4 jitted functions that
    the lowered module defines once and calls. A second program over the
    same model, the forward pass alone, traces only its own two (its rows do
    not vary over a mesh as the step's do), and a third of the same shapes
    (the expert counts) nothing."""
    from tpu_mpi.models.transformer import (transformer_expert_counts,
                                            transformer_forward,
                                            transformer_train_step)
    kernel_backend("interpret")
    cfg = TransformerConfig(**{**LAYER.__dict__, "dtype": jnp.float32,
                               "d_ff": 256, "max_seq": 128, "n_layers": 4})
    traced = {"rows x matrix": 0, "weights' gradient": 0, "walk": 0}

    def counted(what, fn):
        def body(*args, **kwargs):
            traced[what] += 1
            return fn(*args, **kwargs)
        return body
    monkeypatch.setattr(pk, "_gmm_kernel",
                        counted("rows x matrix", pk._gmm_kernel))
    monkeypatch.setattr(pk, "_tgmm_kernel",
                        counted("weights' gradient", pk._tgmm_kernel))
    monkeypatch.setattr(pk, "_visits_of", counted("walk", pk._visits_of))
    for cached in (pk._grouped_matmul_fn, pk._group_visits_fn):
        cached.cache_clear()    # jitted before the patches: traced afresh
    jax.clear_caches()
    perfvars.reset()

    mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1}, devices=jax.devices()[:1])
    step, _ = transformer_train_step(cfg, mesh, lr=0.1)
    params = jax.eval_shape(lambda k: transformer_init(k, cfg),
                            jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = step.lower(params, tokens, tokens).as_text()
    # forward at 2 shapes, the rows' gradient at 2, the weights' at 2
    assert traced == {"rows x matrix": 4, "weights' gradient": 2, "walk": 1}
    assert lowerings() == {"kernel": 3, "ragged_dot": 0}
    import re
    defined = re.findall(r"func\.func private @(forward|backward)\w*\(", text)
    assert sorted(defined) == ["backward"] * 2 + ["forward"] * 2, defined
    calls = re.findall(r"call @(forward|backward)\w*\(", text)
    assert sorted(calls) == ["backward"] * 3 + ["forward"] * 3, calls
    blocks = re.findall(r"call @(block\w*)\(", text)  # the layers, 2 directions
    assert len(blocks) == 2 * cfg.n_layers and len(set(blocks)) == 2, blocks

    before = dict(traced)
    jax.jit(lambda p, t: transformer_forward(cfg, p, t)).lower(params, tokens)
    assert traced == {"rows x matrix": before["rows x matrix"] + 2,
                      "weights' gradient": 2, "walk": 2}
    before = dict(traced)
    jax.jit(lambda p, t: transformer_expert_counts(cfg, p, t)).lower(
        params, tokens)
    assert traced == before
    for cached in (pk._grouped_matmul_fn, pk._group_visits_fn):
        cached.cache_clear()    # they hold the counting bodies
