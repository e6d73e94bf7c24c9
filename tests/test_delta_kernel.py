"""The delta-rule scan's Pallas kernel pair (`tpu_mpi/xla/delta_kernels.py`)
on the interpret machine against `parallel/delta.py:_chunked`, the plain path
it stands in for, and against the recurrence one token at a time: values and
all five gradients, float32 and bfloat16, one chunk, several, a batch of two,
two key heads (four value heads) and the `padded` form; keys that repeat;
what the backward pass keeps; which shapes take the kernel and which the
plain path; the two counters; one train step. Small shapes (a key head of
128 with its two value heads of 128, chunks of 64): each case is one jitted
program, waited for before anything else is dispatched
(.claude/skills/verify: the interpret machine's callbacks)."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tpu_mpi import perfvars                                    # noqa: E402
from tpu_mpi.models import transformer as tf                    # noqa: E402
from tpu_mpi.parallel import delta                              # noqa: E402
from tpu_mpi.xla import choice, delta_kernels                   # noqa: E402

W, CHUNK = 128, 64
F32, BF16 = "float32", "bfloat16"
NAMES = ("q", "k", "v", "g", "beta")
# (dtype, batch, tokens, key heads): the scan's form follows from the tokens
CASES = {
    "one-chunk": (F32, 1, 64, 1),
    "three-chunks": (F32, 1, 192, 1),
    "batch-of-two": (F32, 2, 128, 1),
    "two-key-heads": (F32, 1, 128, 2),
    "padded": (F32, 1, 100, 1),
    "bf16": (BF16, 1, 128, 1),
    "bf16-padded-batch-of-two": (BF16, 2, 100, 1),
}
GRADIENTS = ("batch-of-two", "two-key-heads", "padded", "bf16")


def operands(dtype, bsz, t, hk=1, width=W, pair=2):
    """(q, k, v, g, beta) as the model hands them over (q and k normed, q
    scaled; decays from slow to fast over the heads), and a weight for o."""
    keys = jax.random.split(jax.random.key(t + bsz + hk), 6)
    hv = pair * hk
    q, k = (tf._l2_normed(jax.random.normal(key, (bsz, t, hk, width)))
            for key in keys[:2])
    v = jax.random.normal(keys[2], (bsz, t, hv, width))
    g = -jax.random.uniform(keys[3], (bsz, t, hv)) \
        * jnp.linspace(0.05, 2.0, hv)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (bsz, t, hv)))
    args = tuple(a.astype(dtype) for a in (q * width ** -0.5, k, v)) \
        + (g.astype(jnp.float32), beta.astype(jnp.float32))
    return args, jax.random.normal(keys[5], v.shape).astype(dtype)


def _scanned(kernel_backend, case: str, grads: bool = False):
    """(kernel's, `_chunked`'s, the recurrence's in float32) values, or the
    three's gradients of sum(o w), for a case; each one jitted program."""
    dtype, bsz, t, hk = CASES[case]
    args, w = operands(dtype, bsz, t, hk)
    f32 = jnp.float32

    def of(fun):
        def loss(*a):
            return jnp.sum(fun(*a).astype(f32) * w.astype(f32))
        return jax.jit(jax.grad(loss, argnums=tuple(range(5))) if grads
                       else fun)

    def chunked(*a):
        return delta.delta_scan(*a, CHUNK)
    out = []
    for name in ("interpret", None):
        with kernel_backend(name):
            out.append(jax.block_until_ready(of(chunked)(*args)))
    out.append(jax.block_until_ready(of(delta.delta_recurrence)(
        *(a.astype(f32) for a in args))))
    return out


_SCANNED = {}    # a case's three, computed once for the tests that read it


@pytest.fixture
def scanned(kernel_backend):
    """`_scanned` of a case, from `_SCANNED` after its first call."""
    def cached(*case):
        if case not in _SCANNED:
            _SCANNED[case] = _scanned(kernel_backend, *case)
        return _SCANNED[case]
    return cached


def off_by(got, want) -> float:
    """max |got - want| over max |want|."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_chunked_scan_and_the_recurrence(case, scanned):
    dtype, bsz, t, hk = CASES[case]
    kernel, plain, token_by_token = scanned(case)
    assert kernel.shape == (bsz, t, 2 * hk, W)
    assert kernel.dtype == jnp.dtype(dtype)
    assert bool(jnp.isfinite(kernel.astype(jnp.float32)).all())
    # bfloat16: o is rounded once (2^-9 of its size); the two round the
    # same products, summed in another order
    assert off_by(kernel, plain) < (1e-5 if dtype == F32 else 8e-3)
    assert off_by(kernel, token_by_token) < (1e-5 if dtype == F32 else 3e-2)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case", GRADIENTS)
def test_the_kernels_gradient_is_the_chunked_scans(case, name, scanned):
    """q, k, v, g, beta: against `jax.grad` of `_chunked` and of the
    recurrence. In bfloat16 each lies as near the float32 recurrence as
    `_chunked`'s does (both round the operands of the same products)."""
    dtype = CASES[case][0]
    at = NAMES.index(name)
    kernel, plain, token_by_token = (g[at] for g in scanned(case, True))
    assert kernel.shape == plain.shape and kernel.dtype == plain.dtype
    if dtype == F32:
        assert off_by(kernel, plain) < 2e-5
        assert off_by(kernel, token_by_token) < 2e-5
    else:
        assert off_by(kernel, plain) < 3e-2
        assert off_by(kernel, token_by_token) < max(
            2e-2, 2.0 * off_by(plain, token_by_token))


def test_keys_that_repeat_cost_the_kernels_inverse_no_digits(kernel_backend):
    """Every key the same, no decay, beta one: `A` is all ones under the
    diagonal, whose powers reach 1e17 at a chunk of 64 while its inverse
    has entries of one; the kernel inverts by halves, as the plain path
    does, and forms no power: values and gradients stay the recurrence's."""
    (q, k, v, g, beta), w = operands(F32, 1, 128)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    args = (q, k, v, jnp.zeros_like(g), jnp.ones_like(beta))

    def both(fun):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fun(*a) * w), argnums=(0, 1, 2, 3, 4)))(*args)
    with kernel_backend("interpret"):
        perfvars.reset()
        got, got_grads = jax.block_until_ready(
            both(lambda *a: delta.delta_scan(*a, CHUNK)))
        assert perfvars.snapshot()["delta_kernel_lowerings"]["kernel"] == 1
    want, want_grads = jax.block_until_ready(both(delta.delta_recurrence))
    assert abs(float(got - want)) < 5e-5 * abs(float(want)) + 5e-5
    for name, a, b in zip(NAMES, got_grads, want_grads):
        # (g's gradient is nothing but rounding here, 1e-7 in both)
        assert float(jnp.abs(a - b).max()) < 2e-5 * max(
            1.0, float(jnp.abs(b).max())), name
    perfvars.reset()


def test_the_backward_keeps_the_operands_and_the_states_alone(
        kernel_backend):
    """What the backward pass is handed: q, k and v as rows, the state
    before each chunk, and the scalars a head and token (beta, the
    exponentials, the kernel's tile of them: each [batch, t, value heads]
    float32 or eight times that); nothing of [.., chunk, chunk] and nothing
    as wide as `W`, `U0` or the decayed keys, which the kernel computes
    again."""
    from jax._src.ad_checkpoint import saved_residuals
    bsz, t, hk = 1, 192, 1
    args, _w = operands(F32, bsz, t, hk)
    with kernel_backend("interpret"):
        kept = saved_residuals(lambda *a: delta.delta_scan(*a, CHUNK), *args)
    shapes = [tuple(aval.shape) for aval, _why in kept]
    nc, hv = t // CHUNK, 2 * hk
    assert (bsz, nc, hv, W, W) in shapes                    # the states
    assert not [s for s in shapes if s[-2:] == (CHUNK, CHUNK)]
    wide = sorted(s for s in shapes if s[-1] >= W)
    assert wide == sorted([
        (bsz, t, hk * W), (bsz, t, hk * W), (bsz, t, hv * W),
        (bsz, hk, nc * 8, 128), (bsz, hk, nc, 1, 128),
        (bsz, nc, hv, W, W)]), shapes
    for s in shapes:        # and the rest are scalars a head and token
        size = 1
        for n in s:
            size *= n
        assert s in wide or size <= bsz * t * hv, s


@pytest.mark.parametrize("what, heads, widths, chunk, dtype, taken", [
    ("the cell's", (32, 16), (128, 128), 64, BF16, True),
    ("float32", (2, 1), (128, 128), 64, F32, True),
    ("four value heads", (4, 2), (128, 128), 64, BF16, True),
    ("one value head a key head", (2, 2), (128, 128), 64, BF16, False),
    ("four value heads a key head", (4, 1), (128, 128), 64, BF16, False),
    ("a key head of 64", (2, 1), (64, 128), 64, F32, False),
    ("a value head of 256", (2, 1), (128, 256), 64, F32, False),
    ("a chunk of 32", (2, 1), (128, 128), 32, BF16, False),
    ("a chunk of 128", (2, 1), (128, 128), 128, BF16, False),
    ("float16", (2, 1), (128, 128), 64, "float16", False),
])
def test_which_shapes_take_the_kernel(what, heads, widths, chunk, dtype,
                                      taken, kernel_backend):
    asked = (choice.DELTA_SCAN, *heads, *widths, chunk, dtype)
    with kernel_backend("interpret"):
        assert (choice.fit(*asked) is not None) is taken
    with kernel_backend(None):     # the CPU: nothing does
        assert choice.fit(*asked) is None
    if not taken and chunk == CHUNK:    # (the kernel's chunk is its own)
        (hv, hk), (dk, dv) = heads, widths
        t = 2 * chunk
        with pytest.raises(ValueError, match="outside the kernel's contract"):
            delta_kernels.delta_scan(
                jnp.zeros((1, t, hk, dk), dtype),
                jnp.zeros((1, t, hk, dk), dtype),
                jnp.zeros((1, t, hv, dv), dtype), jnp.zeros((1, t, hv)),
                jnp.ones((1, t, hv)), interpret=True)


@pytest.mark.parametrize("width, pair, chunk, t, form", [
    (64, 2, 64, 128, "chunked"), (128, 1, 64, 128, "chunked"),
    (128, 2, 32, 100, "padded")])
def test_a_shape_the_kernel_does_not_take_goes_the_plain_way(
        width, pair, chunk, t, form, kernel_backend):
    """With the kernels selectable, heads of 64, one value head a key head
    or a chunk of 32 computes what it computed and counts `plain`."""
    args, _w = operands(F32, 1, t, width=width, pair=pair)
    perfvars.reset()
    with kernel_backend("interpret"):
        got = jax.block_until_ready(
            jax.jit(lambda *a: delta.delta_scan(*a, chunk))(*args))
    counted = perfvars.snapshot()
    assert counted["delta_kernel_lowerings"] == {"kernel": 0, "plain": 1}
    assert counted["delta_lowerings"][form] == 1
    assert sum(counted["delta_lowerings"].values()) == 1
    assert off_by(got, jax.jit(delta.delta_recurrence)(*args)) < 1e-5
    perfvars.reset()


@pytest.mark.parametrize("name, t, form, who", [
    ("interpret", 128, "chunked", "kernel"),
    ("interpret", 100, "padded", "kernel"),
    (None, 128, "chunked", "plain"), (None, 100, "padded", "plain")])
def test_the_counters_count_once_a_traced_scan(name, t, form, who,
                                               kernel_backend):
    """`delta_kernel_lowerings` says who computes a traced scan,
    `delta_lowerings` its form, as it did, whoever computes it; one count
    each a trace, none for a second call of the traced program, both
    zeroed by `reset`."""
    args, _w = operands(F32, 1, t)
    perfvars.reset()
    with kernel_backend(name):
        scan = jax.jit(lambda *a: delta.delta_scan(*a, CHUNK))
        jax.block_until_ready(scan.lower(*args))
        counted = perfvars.snapshot()
        assert counted["delta_kernel_lowerings"] == {
            "kernel": int(who == "kernel"), "plain": int(who == "plain")}
        assert counted["delta_lowerings"] == {
            "chunked": int(form == "chunked"), "padded": int(form == "padded")}
        scan.lower(*args)       # traced once: counted once
        assert perfvars.snapshot()["delta_kernel_lowerings"] == \
            counted["delta_kernel_lowerings"]
    perfvars.reset()
    assert perfvars.snapshot()["delta_kernel_lowerings"] == {
        "kernel": 0, "plain": 0}


def test_one_train_step_through_the_kernels_is_the_plain_step(kernel_backend):
    """`transformer_train_step` on a 1 x 1 x 1 mesh at a toy shape inside
    the kernels' contract (two delta-rule layers of one key head and two
    value heads of 128, 128 tokens in chunks of 64), the selection patched
    to the interpret machine: the loss and every updated leaf against the
    plain step's. Under `shard_map` every operand varies over dp; the
    kernel's operands are made to vary together all the same."""
    import numpy as np
    from tpu_mpi import xla
    cfg = tf.TransformerConfig(
        vocab=64, d_model=64, n_heads=4, n_layers=2, d_ff=64, max_seq=128,
        dtype=jnp.float32, rope_full_layers=False, dense_gated=True,
        mixer_kinds=["gdn", "gdn"], gdn_key_heads=1, gdn_key_dim=W,
        gdn_value_heads=2, gdn_value_dim=W, gdn_conv=4, gdn_chunk=CHUNK)

    def one_step():
        mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1},
                             devices=jax.devices()[:1])
        tf._block_traced_once.cache_clear()
        step, _ = tf.transformer_train_step(cfg, mesh, lr=0.1)
        params = tf.transformer_init(jax.random.key(11), cfg)
        tokens = jax.random.randint(jax.random.key(12), (2, 128), 0, cfg.vocab)
        return jax.block_until_ready(
            step(params, tokens, jnp.roll(tokens, -1, axis=1)))

    perfvars.reset()
    want_params, want_loss = one_step()
    assert perfvars.snapshot()["delta_kernel_lowerings"] == {
        "kernel": 0, "plain": 1}        # two layers of a kind: one trace
    with kernel_backend("interpret"):
        got_params, got_loss = one_step()
    counted = perfvars.snapshot()
    assert counted["delta_kernel_lowerings"] == {"kernel": 1, "plain": 1}
    assert counted["delta_lowerings"] == {"chunked": 2, "padded": 0}
    tf._block_traced_once.cache_clear()
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    start = tf.transformer_init(jax.random.key(11), cfg)
    moved = 0.0
    for g, w, p0 in zip(*(jax.tree.leaves(t) for t in
                          (got_params, want_params, start))):
        np.testing.assert_allclose(g, w, atol=5e-6)
        moved = max(moved, float(jnp.abs(w - p0).max()))
    assert moved > 1e-3                     # the step did move the leaves
    perfvars.reset()
