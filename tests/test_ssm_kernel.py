"""The state-space scan's Pallas kernel pair (`tpu_mpi/xla/ssm_kernels.py`)
on the interpret machine against `parallel/ssm.py:_chunked`, the plain path
it stands in for, and against the recurrence one token at a time: values and
all six gradients, float32 and bfloat16, one chunk, several, a chunk of two
diagonal tiles, a batch of two, and the `padded` form; what the backward pass
keeps; which shapes take the kernel and which the plain path; the two
counters. Small shapes (8 heads of 64 over a state of 128): each case is one
jitted program, waited for before anything else is dispatched
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
from tpu_mpi.parallel import ssm                                # noqa: E402
from tpu_mpi.xla import ssm_kernels                             # noqa: E402
from test_ssm_layer import SCAN_ARGS, recurrence                # noqa: E402

H, P, N = 8, 64, 128
F32, BF16 = "float32", "bfloat16"
# (dtype, batch, tokens, chunk): the scan's form follows from the last two
CASES = {
    "one-chunk": (F32, 1, 128, 128),
    "three-chunks": (F32, 1, 384, 128),
    "two-tiles-a-chunk": (F32, 1, 512, 256),
    "batch-of-two": (F32, 2, 256, 128),
    "padded": (F32, 1, 200, 128),
    "shorter-than-a-chunk": (F32, 1, 128, 256),
    "bf16": (BF16, 1, 256, 128),
    "bf16-padded-batch-of-two": (BF16, 2, 200, 128),
}
GRADIENTS = ("batch-of-two", "two-tiles-a-chunk", "padded", "bf16")


def operands(dtype, bsz, t, heads=H, width=P, state=N):
    keys = jax.random.split(jax.random.key(t + bsz), 5)
    f32 = jnp.float32
    args = (jax.random.normal(keys[0], (bsz, t, heads, width), f32),
            jax.nn.softplus(jax.random.normal(keys[1], (bsz, t, heads), f32)
                            - 2.0),
            -jnp.linspace(0.5, 8.0, heads, dtype=f32),
            0.3 * jax.random.normal(keys[2], (bsz, t, state), f32),
            0.3 * jax.random.normal(keys[3], (bsz, t, state), f32),
            jnp.linspace(0.5, 1.5, heads, dtype=f32))
    cast = [0, 3, 4]        # x, B and C are the model's type; the rest float32
    args = tuple(v.astype(dtype) if i in cast else v
                 for i, v in enumerate(args))
    w = jax.random.normal(keys[4], (bsz, t, heads, width), f32).astype(dtype)
    return args, w


def _scanned(kernel_backend, case: str, grads: bool = False):
    """(kernel's, `_chunked`'s, the recurrence's in float32) values, or the
    three's gradients of sum(y w), for a case; each one jitted program."""
    dtype, bsz, t, chunk = CASES[case]
    args, w = operands(dtype, bsz, t)
    f32 = jnp.float32

    def of(fun):
        def loss(*a):
            return jnp.sum(fun(*a).astype(f32) * w.astype(f32))
        return jax.jit(jax.grad(loss, argnums=tuple(range(6))) if grads
                       else fun)

    def chunked(*a):
        return ssm.scan(*a, chunk)
    out = []
    for name in ("interpret", None):
        with kernel_backend(name):
            out.append(jax.block_until_ready(of(chunked)(*args)))
    with jax.default_matmul_precision("highest"):
        out.append(jax.block_until_ready(of(recurrence)(
            *(v.astype(f32) for v in args))))
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
    dtype, bsz, t, _chunk = CASES[case]
    kernel, plain, token_by_token = scanned(case)
    assert kernel.shape == (bsz, t, H, P) and kernel.dtype == jnp.dtype(dtype)
    assert bool(jnp.isfinite(kernel.astype(jnp.float32)).all())
    # bfloat16: y is rounded once (2^-9 of its size); the two round the
    # same products, summed in another order
    limit = 1e-5 if dtype == F32 else 6e-3
    assert off_by(kernel, plain) < limit
    assert off_by(kernel, token_by_token) < (1e-5 if dtype == F32 else 2e-2)


@pytest.mark.parametrize("name", SCAN_ARGS)
@pytest.mark.parametrize("case", GRADIENTS)
def test_the_kernels_gradient_is_the_chunked_scans(case, name, scanned):
    """x, dt, A, B, C, D: against `jax.grad` of `_chunked` and of the
    recurrence. In bfloat16 each lies as near the float32 recurrence as
    `_chunked`'s does (both round the operands of the same products)."""
    dtype = CASES[case][0]
    at = SCAN_ARGS.index(name)
    kernel, plain, token_by_token = (g[at] for g in scanned(case, True))
    assert kernel.shape == plain.shape and kernel.dtype == plain.dtype
    if dtype == F32:
        assert off_by(kernel, plain) < 2e-5
        assert off_by(kernel, token_by_token) < 2e-5
    else:
        assert off_by(kernel, plain) < 3e-2
        assert off_by(kernel, token_by_token) < max(
            2e-2, 2.0 * off_by(plain, token_by_token))


def test_the_backward_keeps_the_states_and_nothing_of_the_decay_matrix(
        kernel_backend):
    """What the backward kernel is handed: the operands (x as rows, dt and
    the sums head-major, B, C, D over the lanes) and the state before each
    chunk; nothing of [.., chunk, chunk], which it computes again."""
    from jax._src.ad_checkpoint import saved_residuals
    bsz, t, chunk = 1, 256, 128
    args, _w = operands(F32, bsz, t)
    with kernel_backend("interpret"):
        kept = saved_residuals(lambda *a: ssm.scan(*a, chunk), *args)
    shapes = [tuple(aval.shape) for aval, _why in kept]
    assert (bsz, t // chunk, N, H * P) in shapes            # the states
    assert not [s for s in shapes if s[-2:] == (chunk, chunk)]
    largest = max(bsz * t * H * P, bsz * (t // chunk) * N * H * P)
    for s in shapes:
        size = 1
        for n in s:
            size *= n
        assert size <= largest, s


@pytest.mark.parametrize("what, shape, state, chunk, dtype, taken", [
    ("the cell's", (1, 8192, 64, 64), 128, 256, BF16, True),
    ("float32", (2, 256, 8, 64), 128, 128, F32, True),
    ("a wider state", (1, 256, 8, 64), 256, 128, BF16, True),
    ("a head of 48", (1, 256, 8, 48), 128, 128, F32, False),
    ("a head of 128", (1, 256, 8, 128), 128, 128, F32, False),
    ("a chunk of 96", (1, 192, 8, 64), 128, 96, F32, False),
    ("a chunk of 8", (1, 256, 8, 64), 128, 8, BF16, False),
    ("six heads", (1, 256, 6, 64), 128, 128, F32, False),
    ("a state of 16", (1, 256, 8, 64), 16, 128, F32, False),
    ("a state of 384", (1, 256, 8, 64), 384, 128, BF16, False),
    ("a chunk of 512", (1, 512, 8, 64), 128, 512, BF16, False),
    ("float16", (1, 256, 8, 64), 128, 128, "float16", False),
])
def test_which_shapes_take_the_kernel(what, shape, state, chunk, dtype, taken,
                                      kernel_backend):
    with kernel_backend("interpret"):
        assert ssm.scan_kernel_selected(shape, dtype, state, chunk) is taken
    with kernel_backend(None):     # the CPU: nothing does
        assert not ssm.scan_kernel_selected(shape, dtype, state, chunk)
    if not taken:
        x = jnp.zeros(shape, dtype)
        with pytest.raises(ValueError, match="outside the kernel's contract"):
            ssm_kernels.ssm_scan(
                x, jnp.ones(shape[:3]), -jnp.ones(shape[2]),
                jnp.zeros(shape[:2] + (state,), dtype),
                jnp.zeros(shape[:2] + (state,), dtype), jnp.ones(shape[2]),
                length=chunk, interpret=True)


@pytest.mark.parametrize("width, chunk, t, form", [
    (48, 128, 256, "chunked"), (64, 96, 192, "chunked"),
    (48, 96, 200, "padded")])
def test_a_shape_the_kernel_does_not_take_goes_the_plain_way(
        width, chunk, t, form, kernel_backend):
    """With the kernels selectable, a head of 48 or a chunk of 96 computes
    what it computed and counts `plain`."""
    args, _w = operands(F32, 1, t, width=width)
    perfvars.reset()
    with kernel_backend("interpret"):
        got = jax.block_until_ready(
            jax.jit(lambda *a: ssm.scan(*a, chunk))(*args))
    counted = perfvars.snapshot()
    assert counted["scan_kernel_lowerings"] == {"kernel": 0, "plain": 1}
    assert counted["scan_lowerings"][form] == 1
    assert sum(counted["scan_lowerings"].values()) == 1
    with jax.default_matmul_precision("highest"):
        want = recurrence(*args)
    assert off_by(got, want) < 1e-5


@pytest.mark.parametrize("name, t, form, who", [
    ("interpret", 256, "chunked", "kernel"),
    ("interpret", 200, "padded", "kernel"),
    (None, 256, "chunked", "plain"), (None, 200, "padded", "plain")])
def test_the_counters_count_once_a_traced_scan(name, t, form, who,
                                               kernel_backend):
    """`scan_kernel_lowerings` says who computes a traced scan,
    `scan_lowerings` its form, as it did; one count each a trace, none for
    a second call of the traced program, both zeroed by `reset`."""
    args, _w = operands(F32, 1, t)
    perfvars.reset()
    with kernel_backend(name):
        scan = jax.jit(lambda *a: ssm.scan(*a, 128))
        jax.block_until_ready(scan.lower(*args))
        counted = perfvars.snapshot()
        assert counted["scan_kernel_lowerings"] == {
            "kernel": int(who == "kernel"), "plain": int(who == "plain")}
        assert counted["scan_lowerings"] == {
            "chunked": int(form == "chunked"), "padded": int(form == "padded")}
        scan.lower(*args)       # traced once: counted once
        assert perfvars.snapshot()["scan_kernel_lowerings"] == \
            counted["scan_kernel_lowerings"]
    perfvars.reset()
    assert perfvars.snapshot()["scan_kernel_lowerings"] == {
        "kernel": 0, "plain": 0}


def test_one_train_step_through_the_kernels_is_the_plain_step(kernel_backend):
    """`transformer_train_step` on a 1 x 1 x 1 mesh at a toy shape inside
    the kernels' contract (two state-space layers of 8 heads of 64 over a
    state of 128, 256 tokens in chunks of 128), the selection patched to
    the interpret machine: the loss and every updated leaf against the
    plain step's. Under `shard_map` x, dt, B and C vary over dp and A and
    D do not: the kernel's operands are made to vary together, and the
    cast's transpose sums their gradients as XLA's own product's would."""
    import numpy as np
    from tpu_mpi import xla
    from tpu_mpi.models import transformer as tf
    cfg = tf.TransformerConfig(
        vocab=64, d_model=256, n_heads=4, n_layers=2, d_ff=128, max_seq=256,
        dtype=jnp.float32, rope_full_layers=False, dense_gated=True,
        mixer_kinds=["ssm", "ssm"], ssm_expand=2, ssm_heads=H,
        ssm_head_dim=P, ssm_state=N, ssm_conv=4, ssm_chunk=128)

    def one_step():
        mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1},
                             devices=jax.devices()[:1])
        tf._block_traced_once.cache_clear()
        step, _ = tf.transformer_train_step(cfg, mesh, lr=0.1)
        params = tf.transformer_init(jax.random.key(11), cfg)
        tokens = jax.random.randint(jax.random.key(12), (2, 256), 0, cfg.vocab)
        return jax.block_until_ready(
            step(params, tokens, jnp.roll(tokens, -1, axis=1)))

    perfvars.reset()
    want_params, want_loss = one_step()
    assert perfvars.snapshot()["scan_kernel_lowerings"] == {
        "kernel": 0, "plain": 1}        # two layers of a kind: one trace
    with kernel_backend("interpret"):
        got_params, got_loss = one_step()
    assert perfvars.snapshot()["scan_kernel_lowerings"] == {
        "kernel": 1, "plain": 1}
    tf._block_traced_once.cache_clear()
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    start = tf.transformer_init(jax.random.key(11), cfg)
    moved = 0.0
    for g, w, p0 in zip(*(jax.tree.leaves(t) for t in
                          (got_params, want_params, start))):
        np.testing.assert_allclose(g, w, atol=5e-6)
        moved = max(moved, float(jnp.abs(w - p0).max()))
    assert moved > 1e-3                     # the step did move the leaves
