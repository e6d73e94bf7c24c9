"""Rotary embeddings as one pass each way (PR 36): `tf._rope` (x cos2 +
swap(x) sin2 with the inverse rotation for a backward) and `tf._rope_heads`
(the rotation on a token-major row and the cut into heads: the kernel
`pallas_kernels.rope_heads` where it is selected, here on the Pallas
interpret machine) and `tf._norm_and_rope` (the norm of q and k in the
rotation's pass, `pallas_kernels.norm_rope`) against the plain statement
they replaced: the cut into heads first, the norm, then two half-width
products, concatenated, differentiated by autodiff."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tpu_mpi import perfvars, xla                               # noqa: E402
from tpu_mpi.models import transformer as tf                    # noqa: E402
from tpu_mpi.xla import pallas_kernels as pk                    # noqa: E402

F32 = jnp.float32


# -- the plain statement -------------------------------------------------------

def halves(x, positions, theta=10000.0):
    """RoPE of x [..., t, width] as it stood before PR 36."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = positions[:, None].astype(F32) * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def cut_then_halves(row, positions, theta, heads, parts):
    """[b, t, heads x period] -> one [b, heads, t, width] a part: the cut
    into heads and parts by slices, then `halves` on the rotated ones."""
    b, t, _ = row.shape
    x = row.reshape(b, t, heads, -1).transpose(0, 2, 1, 3)
    out, at = [], 0
    for width, turned in parts:
        part = x[..., at:at + width]
        out.append(halves(part, positions, theta) if turned else part)
        at += width
    return tuple(out)


def weighed(outs):
    """A scalar whose gradient differs at every entry of every part."""
    return sum(jnp.sum(jnp.sin(o.astype(F32)) * (i + 1.0))
               for i, o in enumerate(outs))


def ulps(got, want):
    """The largest difference in bfloat16 units of the last place of want's
    largest entry (a rotation's output is no larger than its pair, so that
    is the place its products and their sum are rounded at)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    ulp = 2.0 ** np.floor(np.log2(np.abs(want).max())) \
        * float(jnp.finfo(jnp.bfloat16).eps)
    return float(np.abs(got - want).max() / ulp)


# -- `_rope`: one head on the last axis -----------------------------------------

HEAD_MAJOR = {
    "b,h,t,64": ((2, 3, 16, 64), 0),
    "b,h,t,128": ((2, 2, 16, 128), 0),
    "one shared rotary head": ((2, 1, 16, 64), 0),      # `_latent_attn`'s k_rope
    "a sequence shard's positions": ((1, 2, 16, 64), 48),
    "theta 1e6": ((1, 2, 16, 128), 0),
}


@pytest.mark.parametrize("what", sorted(HEAD_MAJOR))
def test_rope_is_the_halves_form_in_value_and_gradient(what):
    shape, first = HEAD_MAJOR[what]
    theta = 1e6 if "theta" in what else 1e4
    positions = first + jnp.arange(shape[2])
    x = jax.random.normal(jax.random.key(3), shape, F32)
    # op by op the two forms round alike: x1 cos - x2 sin is x1 cos +
    # x2 (-sin), and a sum does not care for its order
    np.testing.assert_array_equal(tf._rope(x, positions, theta),
                                  halves(x, positions, theta))
    got = jax.grad(lambda x: weighed([tf._rope(x, positions, theta)]))(x)
    want = jax.grad(lambda x: weighed([halves(x, positions, theta)]))(x)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    # compiled, a backend may contract a product and a sum into one fused
    # multiply-add, and not the same one in both forms: float32 round-off
    # (compiled against compiled: the angles' own rounding differs between
    # a compiled and an op-by-op `theta ** ..`, times the position)
    np.testing.assert_allclose(
        jax.jit(tf._rope, static_argnums=2)(x, positions, theta),
        jax.jit(halves, static_argnums=2)(x, positions, theta),
        rtol=0, atol=2e-6)


@pytest.mark.parametrize("width", [64, 128])
def test_rope_in_bfloat16_is_within_an_ulp_of_the_halves_form(width):
    positions = jnp.arange(5, 37)
    x = jax.random.normal(jax.random.key(4), (2, 2, 32, width), jnp.bfloat16)
    assert ulps(tf._rope(x, positions), halves(x, positions)) <= 1.0
    assert ulps(jax.jit(tf._rope)(x, positions),
                jax.jit(halves)(x, positions)) <= 1.0
    got = jax.grad(lambda x: weighed([tf._rope(x, positions)]))(x)
    want = jax.grad(lambda x: weighed([halves(x, positions)]))(x)
    # the halves form rounds each cotangent half before it sums the two
    # (and the sum again); the inverse rotation rounds once
    assert got.dtype == jnp.bfloat16 and ulps(got, want) <= 2.0


NORMS = {       # TransformerConfig fields, x's shape, the scale's
    "a norm a head": (dict(qk_norm_heads=True, d_head=128), (2, 4, 128, 128),
                      (128,)),
    "a norm of the whole vector": (dict(qk_norm=True), (2, 4, 128, 128),
                                   (512,)),
    "grouped keys' whole vector": (dict(qk_norm=True), (1, 2, 256, 128),
                                   (256,)),
    "grouped keys' norm a head": (dict(qk_norm_heads=True, d_head=128),
                                  (1, 3, 256, 128), (128,)),
    "one head": (dict(qk_norm_heads=True, d_head=128), (1, 1, 128, 128),
                 (128,)),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("what", sorted(NORMS))
def test_the_norm_of_q_and_k_rides_the_rotations_pass(what, dtype,
                                                      monkeypatch,
                                                      kernel_backend):
    """`_norm_and_rope` with the kernel selected (one pass: the norm, the
    scale, the rotation; one pass back, which computes the inverse rms
    again and sums the scale's gradient) against the same function's plain
    path: the model's norm as XLA runs it, then the halves form."""
    fields, shape, scale_shape = NORMS[what]
    dt = jnp.dtype(dtype)
    cfg = tf.TransformerConfig(vocab=64, d_model=512, n_heads=4, n_layers=1,
                               d_ff=64, max_seq=256, dtype=dt, norm_eps=1e-5,
                               **fields)
    positions = 3 + jnp.arange(shape[2])
    x = (3.0 * jax.random.normal(jax.random.key(9), shape, F32)).astype(dt)
    scale = (1.0 + 0.1 * jax.random.normal(jax.random.key(10), scale_shape,
                                           F32)).astype(dt)

    def both():         # a function of its own each time: traced each time
        def out(x, scale):
            return tf._norm_and_rope(cfg, x, scale, positions, None)
        return jax.jit(lambda x, scale: (out(x, scale), jax.grad(
            lambda x, s: weighed([out(x, s)]), (0, 1))(x, scale)))
    monkeypatch.setattr(tf, "_rope", halves)
    want, (dx_want, ds_want) = both()(x, scale)
    kernel_backend("interpret")
    perfvars.reset()
    got, (dx, ds) = jax.block_until_ready(both()(x, scale))
    built = perfvars.snapshot()
    assert set(built["build"]["kernels"]) == {"norm_rope_fwd", "norm_rope_bwd"}
    assert built["rope_forms"]["halves"] == 0 < built["rope_forms"]["dense"]
    assert (got.dtype, dx.dtype, ds.dtype) == (dt, dt, dt)
    assert ds.shape == scale.shape
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
        np.testing.assert_allclose(dx, dx_want, rtol=0, atol=1e-5)
        np.testing.assert_allclose(ds, ds_want, rtol=2e-5, atol=1e-4)
    else:       # the plain path rounds its cotangents at every step's edge
        assert ulps(got, want) <= 1.0 and ulps(dx, dx_want) <= 2.0
        assert ulps(ds, ds_want) <= 8.0     # a sum over every token


def test_without_a_rotation_or_off_the_contract_the_norm_is_the_plain_one(
        kernel_backend):
    """A layer that rotates nothing (positions None), heads under 128, or a
    whole-vector norm whose heads are cut over tp: the norm as it was."""
    kernel_backend("interpret")
    cfg = tf.TransformerConfig(vocab=64, d_model=512, n_heads=4, n_layers=1,
                               d_ff=64, max_seq=128, dtype=F32,
                               qk_norm_heads=True, d_head=128)
    x = jax.random.normal(jax.random.key(11), (1, 4, 128, 128), F32)
    scale = jnp.ones((128,), F32)
    perfvars.reset()
    np.testing.assert_array_equal(
        tf._norm_and_rope(cfg, x, scale, None, None),
        tf._rms_norm(x, scale, cfg.norm_eps))
    small = tf.TransformerConfig(vocab=64, d_model=256, n_heads=4, n_layers=1,
                                 d_ff=64, max_seq=128, dtype=F32,
                                 qk_norm_heads=True)
    y = x[..., :64]
    np.testing.assert_array_equal(
        tf._norm_and_rope(small, y, scale[:64], jnp.arange(128), None),
        tf._rope(tf._rms_norm(y, scale[:64], small.norm_eps), jnp.arange(128)))
    assert perfvars.snapshot()["build"].get("kernels", {}) == {}


def test_a_head_of_odd_width_takes_the_halves_form_and_its_last_value_passes():
    perfvars.reset()
    positions = jnp.arange(8)
    x = jax.random.normal(jax.random.key(5), (1, 2, 8, 7), F32)
    out = tf._rope(x, positions)
    np.testing.assert_array_equal(out[..., :6], halves(x[..., :6], positions))
    np.testing.assert_array_equal(out[..., 6], x[..., 6])
    assert perfvars.snapshot()["rope_forms"] == {"dense": 0, "halves": 1}


# -- `_rope_heads`: the rotation on the row, then the cut -------------------------
#
# The four train cells' patterns: the flagship's packed [head][q|k|v] row of
# 64-wide heads, a grouped-query row of 128-wide heads (K-EXAONE's q and k,
# and OLMoE's once its norm has taken them out of the packing), the latent
# query's [128 unrotated | 64 rotated], and the packed row at 128.

PATTERNS = {
    "packed q|k|v of 64": (4, ((64, True), (64, True), (64, False))),
    "packed q|k|v of 128": (2, ((128, True), (128, True), (128, False))),
    "a row of 128-wide heads": (3, ((128, True),)),
    "a row of 64-wide heads": (6, ((64, True),)),
    "latent 128 unrotated | 64 rotated": (4, ((128, False), (64, True))),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("backend", ["plain", "kernel"])
@pytest.mark.parametrize("what", sorted(PATTERNS))
def test_rope_heads_is_the_cut_then_the_halves_form(what, backend, dtype,
                                                    kernel_backend):
    heads, parts = PATTERNS[what]
    if backend == "kernel":
        kernel_backend("interpret")
    positions = 128 + jnp.arange(128)       # a shard that does not start at 0
    row = jax.random.normal(
        jax.random.key(6), (2, 128, heads * sum(w for w, _t in parts)),
        jnp.dtype(dtype))

    def new(row):
        return tf._rope_heads(row, positions, 1e4, heads, parts)

    def old(row):
        return cut_then_halves(row, positions, 1e4, heads, parts)
    perfvars.reset()
    # one jitted program, waited for, before anything else is dispatched
    # (the interpret machine's callbacks: .claude/skills/verify)
    got, d_got = jax.block_until_ready(jax.jit(
        lambda row: (new(row), jax.grad(lambda r: weighed(new(r)))(row)))(row))
    built = perfvars.snapshot()
    assert built["rope_forms"]["halves"] == 0
    assert built["rope_forms"]["dense"] >= sum(t for _w, t in parts)
    assert ("rope_heads_fwd" in built["build"].get("kernels", {})) == \
        (backend == "kernel")
    want, d_want = jax.jit(
        lambda row: (old(row), jax.grad(lambda r: weighed(old(r)))(row)))(row)
    for (width, turned), a, b in zip(parts, got, want):
        assert a.shape == (2, heads, 128, width) and a.dtype == row.dtype
        if not turned:                      # v, the unrotated part: bit for bit
            np.testing.assert_array_equal(a, b)
        elif dtype == "float32":
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-6)
        else:
            assert ulps(a, b) <= 1.0
    assert d_got.shape == row.shape and d_got.dtype == row.dtype
    if dtype == "float32":
        np.testing.assert_allclose(d_got, d_want, rtol=0, atol=4e-6)
    else:       # the halves form's three roundings against one
        assert ulps(d_got, d_want) <= 2.0


def test_the_kernel_is_selected_by_the_pattern_alone(kernel_backend):
    """Widths of 64 or multiples of 128, one rotary width of 64 or 128 with
    a 128 on a tile of its own, tokens a multiple of 128, whole groups of
    heads: everything else takes the plain path, and asking the kernel for
    it raises."""
    assert pk.rope_heads_blocks(
        1024, 16, ((64, True),) * 2 + ((64, False),)) == (512, 4)
    assert pk.rope_heads_blocks(8192, 64, ((128, True),)) == (512, 8)
    assert pk.rope_heads_blocks(4096, 64, ((128, False), (64, True))) \
        == (512, 4)
    for t, heads, parts in (
            (100, 4, ((64, True),)),                # tokens
            (128, 3, ((64, True),)),                # half a group
            (128, 4, ((32, True),)),                # a width under 64
            (128, 4, ((192, True),)),               # a rotary width over 128
            (128, 4, ((64, False), (128, True))),   # a 128 across two tiles
            (128, 4, ((64, True), (128, True))),    # two rotary widths
            (128, 4, ((128, False),))):             # nothing to rotate
        assert pk.rope_heads_blocks(t, heads, parts) is None
    kernel_backend("interpret")
    perfvars.reset()
    row = jnp.ones((1, 128, 4 * 32), F32)
    (out,) = tf._rope_heads(row, jnp.arange(128), 1e4, 4, ((32, True),))
    assert out.shape == (1, 4, 128, 32)
    assert perfvars.snapshot()["build"].get("kernels", {}) == {}
    with pytest.raises(ValueError, match="outside the kernel's contract"):
        pk.rope_heads(row, jnp.ones((128, 128)), jnp.ones((128, 128)), 4,
                      ((32, True),), interpret=True)


# -- in a traced step -----------------------------------------------------------

def _equations(jaxpr, inside=""):
    """(where, equation) of every equation of a jaxpr and of the jaxprs
    inside it: `where` is the path of names down to it, the functions
    called (a `jit`'s, a `custom_vjp`'s) and the named scopes. A kernel's
    body is not entered: what it joins, it joins in VMEM."""
    for eqn in jaxpr.eqns:
        here = f"{inside}/{eqn.source_info.name_stack}"
        yield here, eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, f"{here}/{sub.debug_info.func_name}")


LATENT = dict(
    vocab=64, d_model=64, n_heads=4, n_layers=2, d_ff=64, max_seq=128,
    dtype=F32, tie_embeddings=False, d_head=128, kv_latent=32, q_latent=48,
    d_rope=64, d_value=128)
STEPS = {
    "the flagship's packed row": dict(
        vocab=64, d_model=256, n_heads=4, n_layers=2, d_ff=64, max_seq=128,
        dtype=F32),
    "a grouped-query row with a norm a head": dict(
        vocab=64, d_model=64, n_heads=4, n_layers=2, d_ff=64, max_seq=128,
        dtype=F32, d_head=128, n_kv_heads=2, qk_norm_heads=True,
        tie_embeddings=False),
    "a latent query row": LATENT,
}


@pytest.mark.parametrize("backend", ["plain", "kernel"])
@pytest.mark.parametrize("what", sorted(STEPS))
def test_the_backward_of_a_traced_step_pads_and_adds_nothing_for_rope(
        what, backend, monkeypatch, kernel_backend):
    """Autodiff's transpose of the halves form was two cotangent halves,
    each padded back to full width (`pad`), summed (`add_any`), beside the
    forward's `concatenate`. The step's gradient, read as its jaxpr, holds
    none of the three under the `rope` name (the latent layer's scope) or
    inside the rotation's own functions; the halves form, put back, does."""
    if backend == "kernel":
        kernel_backend("interpret")
    cfg = tf.TransformerConfig(**STEPS[what])
    params = jax.eval_shape(lambda k: tf.transformer_init(k, cfg),
                            jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32)

    def ropes_own():
        tf._block_traced_once.cache_clear()
        grad = jax.make_jaxpr(jax.grad(lambda p, t: tf._xent(
            tf._forward(cfg, p, t)[0], t)))(params, tokens)
        return [eqn.primitive.name for where, eqn in _equations(grad.jaxpr)
                if any(name in where.split("/") for name in
                       ("rope", "_turn", "turn_and_cut", "turn",
                        "norm_and_turn"))]
    prims = ropes_own()
    assert prims and not {"pad", "add_any"} & set(prims), prims
    if backend == "kernel":     # the cut is the kernel's: nothing is joined
        assert prims.count("pallas_call") >= 2
        assert "concatenate" not in prims
    if what == "a latent query row":        # the scope names what is RoPE's
        monkeypatch.setattr(tf, "_rope", lambda x, positions, theta:
                            halves(x, positions, theta))
        kernel_backend(None)
        assert {"pad", "add_any", "concatenate"} <= set(ropes_own())
    tf._block_traced_once.cache_clear()


def _rehearsed(cell):
    """(model, tokens' shape) of a cell of BENCHMARK.json at the tiny sizes
    of its files' `rehearse` blocks."""
    import types
    from yardstick import harness
    c = harness.Cell(harness.load_json(os.path.join(ROOT, "BENCHMARK.json")),
                     cell, rehearse=True)
    cfg, tr = c.config, c.traffic
    if hasattr(c.generator(), "build"):
        model = c.generator().build(types.SimpleNamespace(
            config=cfg, traffic=tr, devices=jax.devices()[:1], cell=c))[0]
    else:
        model = tf.TransformerConfig(
            vocab=cfg["vocab"], d_model=cfg["d_model"], n_heads=cfg["n_heads"],
            n_layers=cfg["n_layers"], d_ff=cfg["d_ff"],
            max_seq=int(tr["seq"]), dtype=jnp.dtype(cfg["dtype"]))
    return model, (int(tr["batch"]), int(tr["seq"]))


@pytest.mark.parametrize("cell", [
    "flagship-d1024-1c.step-b8s1024", "olmoe-1b-7b-1c.lm-step-b2s4096",
    "k-exaone-236b-a23b-1c.lm-step-b1s8192",
    "openpangu-ultra-moe-718b-1c.lm-step-b1s4096"])
def test_the_counter_reads_dense_at_a_cells_rehearse_shapes(cell):
    model, shape = _rehearsed(cell)
    mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1},
                         devices=jax.devices()[:1])
    step, _specs = tf.transformer_train_step(model, mesh, lr=0.01)
    params = jax.eval_shape(lambda k: tf.transformer_init(k, model),
                            jax.random.key(0))
    tokens = jax.ShapeDtypeStruct(shape, jnp.int32)
    tf._block_traced_once.cache_clear()
    perfvars.reset()
    step.lower(params, tokens, tokens)
    forms = perfvars.snapshot()["rope_forms"]
    assert forms["dense"] >= 2 and forms["halves"] == 0, forms
    tf._block_traced_once.cache_clear()
