"""The selective (Mamba-1) scan's Pallas kernel pair
(`tpu_mpi/xla/sel_scan_kernels.py`) on the interpret machine against
`parallel/ssm.py:_selective_chunks`, the plain path it stands in for, and
against the recurrence one token at a time (`test_sambay_layer.recurrence`):
values and all six gradients, float32 and bfloat16, a batch of one and of
two, one block of tokens, three (the carried state and its cotangent), a
block of 256, the `padded` form, two tiles of channels; a float32 case held
as tightly as the plain form is, so that a kernel with a bfloat16 state or
decay fails here; what the backward pass keeps; which shapes take the kernel
and which the plain path; the counters; one train step of a small
decoder-hybrid-decoder model. Each case is one jitted program, waited for
before anything else is dispatched (.claude/skills/verify: the interpret
machine's callbacks)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tpu_mpi import perfvars                                    # noqa: E402
from tpu_mpi.parallel import ssm                                # noqa: E402
from tpu_mpi.xla import sel_scan_kernels                        # noqa: E402
from test_sambay_layer import CFG, SCAN_ARGS, recurrence        # noqa: E402

N = sel_scan_kernels.SEL_STATE
TILE = sel_scan_kernels.SEL_TILE
F32, BF16 = "float32", "bfloat16"
# (dtype, batch, tokens, channels, chunk): the form follows from tokens and
# chunk, the kernel's blocks from the tokens alone (filled up to 128s)
CASES = {
    "three-blocks-two-tiles": (F32, 1, 384, 2 * TILE, 64),
    "padded-batch-of-two": (F32, 2, 100, TILE, 64),
    "bf16-one-block-of-256": (BF16, 1, 256, TILE, 64),
    "bf16-padded-batch-of-two-two-tiles": (BF16, 2, 200, 2 * TILE, 64),
}


def operands(dtype, bsz, t, ch, state=N):
    """A selective scan's operands with a decay of its own a channel and
    state index, and a cotangent."""
    keys = jax.random.split(jax.random.key(t + bsz), 6)
    f32 = jnp.float32
    args = (jax.random.normal(keys[0], (bsz, t, ch), f32),
            jax.nn.softplus(jax.random.normal(keys[1], (bsz, t, ch), f32)
                            - 2.0),
            -jnp.exp(jax.random.normal(keys[2], (ch, state), f32)),
            jax.random.normal(keys[3], (bsz, t, state), f32),
            jax.random.normal(keys[4], (bsz, t, state), f32),
            jnp.linspace(0.5, 1.5, ch, dtype=f32))
    cast = [0, 3, 4]        # x, B and C are the model's type; the rest float32
    args = tuple(v.astype(dtype) if i in cast else v
                 for i, v in enumerate(args))
    w = jax.random.normal(keys[5], (bsz, t, ch), f32).astype(dtype)
    return args, w


def _scanned(kernel_backend, case: str):
    """(kernel's, `_selective_chunks`', the recurrence's in float32), each
    (y, the six gradients of sum(y w)) from one jitted program."""
    dtype, bsz, t, ch, chunk = CASES[case]
    args, w = operands(dtype, bsz, t, ch)
    f32 = jnp.float32

    def of(fun):
        def loss(*a):
            y = fun(*a)
            return jnp.sum(y.astype(f32) * w.astype(f32)), y
        both = jax.value_and_grad(loss, argnums=tuple(range(6)),
                                  has_aux=True)

        def run(*a):
            (_loss, y), grads = both(*a)
            return y, grads
        return jax.jit(run)

    def scan(*a):
        return ssm.selective_scan(*a, chunk=chunk)
    out = []
    for name in ("interpret", None):
        with kernel_backend(name):
            out.append(jax.block_until_ready(of(scan)(*args)))
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
def test_the_kernel_is_the_plain_scan_and_the_recurrence(case, scanned):
    dtype, bsz, t, ch, _chunk = CASES[case]
    (kernel, _), (plain, _), (token_by_token, _) = scanned(case)
    assert kernel.shape == (bsz, t, ch) and kernel.dtype == jnp.dtype(dtype)
    assert bool(jnp.isfinite(kernel.astype(jnp.float32)).all())
    if dtype == F32:    # as `test_the_selective_scan_is_the_recurrence`
        np.testing.assert_allclose(kernel, token_by_token, rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(kernel, plain, rtol=2e-5, atol=2e-5)
    else:   # y is rounded once (2^-9 of its size); the two round the same
        #     float32 sums, taken in another order
        assert off_by(kernel, plain) < 4e-3
        assert off_by(kernel, token_by_token) < 4e-3


@pytest.mark.parametrize("name", SCAN_ARGS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_gradient_is_the_plain_scans(case, name, scanned):
    """x, dt, A, B, C, D: against `jax.grad` of `_selective_chunks` and of
    the recurrence. In float32 as tightly as the plain form is held; in
    bfloat16 each lies as near the float32 recurrence as the plain form's
    does (both round the same float32 gradient once)."""
    dtype = CASES[case][0]
    at = SCAN_ARGS.index(name)
    kernel, plain, token_by_token = (g[at] for _y, g in scanned(case))
    assert kernel.shape == plain.shape and kernel.dtype == plain.dtype
    if dtype == F32:
        size = float(jnp.abs(token_by_token).max())
        for want in (token_by_token, plain):
            np.testing.assert_allclose(kernel, want, rtol=1e-4,
                                       atol=1e-4 * max(1.0, size))
        assert off_by(kernel, token_by_token) < 2e-5
    else:
        assert off_by(kernel, plain) < 1e-2
        assert off_by(kernel, token_by_token) < max(
            1e-2, 2.0 * off_by(plain, token_by_token))


def test_the_backward_keeps_the_blocks_states_and_no_token_states(
        kernel_backend):
    """What the backward kernel is handed: the operands (A, B and C with the
    state index in front, D as a row) and the state before each block of
    tokens; nothing [t, state, channels] wide, which it computes again in
    VMEM."""
    from jax._src.ad_checkpoint import saved_residuals
    bsz, t, ch = 1, 384, TILE
    args, _w = operands(F32, bsz, t, ch)
    with kernel_backend("interpret"):
        kept = saved_residuals(lambda *a: ssm.selective_scan(*a, chunk=64),
                               *args)
    shapes = [tuple(aval.shape) for aval, _why in kept]
    assert (bsz, 3, N, ch) in shapes            # the states, a block of 128
    largest = bsz * t * ch
    for s in shapes:
        assert int(np.prod(s)) <= largest, s
        assert not (len(s) >= 3 and s[-3:] == (t, N, ch)), s


@pytest.mark.parametrize("what, shape, state, dtype, taken", [
    ("the cell's", (1, 8192, 5120), 16, BF16, True),
    ("float32", (2, 100, 512), 16, F32, True),
    ("one token", (1, 1, 1024), 16, BF16, True),
    ("12 channels", (2, 50, 12), 4, F32, False),
    ("640 channels", (1, 256, 640), 16, BF16, False),
    ("a state of 8", (1, 256, 512), 8, F32, False),
    ("a state of 32", (1, 256, 512), 32, BF16, False),
    ("float16", (1, 256, 512), 16, "float16", False),
])
def test_which_shapes_take_the_kernel(what, shape, state, dtype, taken,
                                      kernel_backend):
    with kernel_backend("interpret"):
        assert ssm.sel_scan_kernel_selected(shape, dtype, state) is taken
    with kernel_backend(None):     # the CPU: nothing does
        assert not ssm.sel_scan_kernel_selected(shape, dtype, state)
    if not taken:
        ch = shape[2]
        with pytest.raises(ValueError, match="outside the kernel's contract"):
            sel_scan_kernels.sel_scan(
                jnp.zeros(shape, dtype), jnp.ones(shape), -jnp.ones((ch, state)),
                jnp.zeros(shape[:2] + (state,), dtype),
                jnp.zeros(shape[:2] + (state,), dtype), jnp.ones(ch),
                interpret=True)


@pytest.mark.parametrize("ch, state, t, chunk, form", [
    (12, 4, 50, 16, "padded"), (640, 16, 128, 64, "chunked")])
def test_a_shape_the_kernel_does_not_take_goes_the_plain_way(
        ch, state, t, chunk, form, kernel_backend):
    """With the kernels selectable, 12 channels or 640 compute what they
    computed and count `plain`."""
    args, _w = operands(F32, 1, t, ch, state)
    perfvars.reset()
    with kernel_backend("interpret"):
        got = jax.block_until_ready(
            jax.jit(lambda *a: ssm.selective_scan(*a, chunk=chunk))(*args))
    counted = perfvars.snapshot()
    assert counted["sel_scan_kernel_lowerings"] == {"kernel": 0, "plain": 1}
    assert counted["sel_scan_lowerings"][form] == 1
    assert sum(counted["sel_scan_lowerings"].values()) == 1
    np.testing.assert_allclose(got, recurrence(*args), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name, t, form, who", [
    ("interpret", 256, "chunked", "kernel"),
    ("interpret", 200, "padded", "kernel"),
    (None, 256, "chunked", "plain"), (None, 200, "padded", "plain")])
def test_the_counters_count_once_a_traced_scan(name, t, form, who,
                                               kernel_backend):
    """`sel_scan_kernel_lowerings` says who computes a traced scan,
    `sel_scan_lowerings` its form, as it did; one count each a trace, none
    for a second call of the traced program, both zeroed by `reset`."""
    args, _w = operands(F32, 1, t, TILE)
    perfvars.reset()
    with kernel_backend(name):
        scan = jax.jit(lambda *a: ssm.selective_scan(*a, chunk=64))
        scan.lower(*args)
        counted = perfvars.snapshot()
        assert counted["sel_scan_kernel_lowerings"] == {
            "kernel": int(who == "kernel"), "plain": int(who == "plain")}
        assert counted["sel_scan_lowerings"] == {
            "chunked": int(form == "chunked"), "padded": int(form == "padded")}
        scan.lower(*args)       # traced once: counted once
        assert perfvars.snapshot()["sel_scan_kernel_lowerings"] == \
            counted["sel_scan_kernel_lowerings"]
    assert perfvars.snapshot()["scan_kernel_lowerings"] == {
        "kernel": 0, "plain": 0}        # the other scan's pair is its own
    perfvars.reset()
    assert perfvars.snapshot()["sel_scan_kernel_lowerings"] == {
        "kernel": 0, "plain": 0}


def test_one_train_step_through_the_kernels_is_the_plain_step(kernel_backend):
    """`transformer_train_step` on a 1 x 1 x 1 mesh: `test_sambay_layer`'s
    eight-layer decoder-hybrid-decoder stack made wide enough for the
    kernels' contract (three mamba layers of 512 channels over a state of
    16), the selection patched to the interpret machine: the loss and every
    updated leaf against the plain step's. Under `shard_map` x, dt, B and C
    vary over dp and A and D do not: the kernel's operands are made to vary
    together, and the cast's transpose sums their gradients as XLA's own
    product's would."""
    from tpu_mpi import xla
    from tpu_mpi.models import transformer as tf
    cfg = dataclasses.replace(CFG, d_model=TILE // CFG.ssm_expand,
                              ssm_state=N, ssm_dt_rank=4)
    assert cfg.mamba_inner == TILE

    def one_step():
        mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1},
                             devices=jax.devices()[:1])
        tf._block_traced_once.cache_clear()
        step, _ = tf.transformer_train_step(cfg, mesh, lr=0.05)
        params = tf.transformer_init(jax.random.key(11), cfg)
        tokens = jax.random.randint(jax.random.key(12), (2, cfg.max_seq), 0,
                                    cfg.vocab)
        return jax.block_until_ready(
            step(params, tokens, jnp.roll(tokens, -1, axis=1)))

    perfvars.reset()
    want_params, want_loss = one_step()
    traced = perfvars.snapshot()["sel_scan_kernel_lowerings"]["plain"]
    assert 1 <= traced < 3      # three mamba layers share their traces
    with kernel_backend("interpret"):
        got_params, got_loss = one_step()
    assert perfvars.snapshot()["sel_scan_kernel_lowerings"] == {
        "kernel": traced, "plain": traced}
    tf._block_traced_once.cache_clear()
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    start = tf.transformer_init(jax.random.key(11), cfg)
    moved = 0.0
    for g, w, p0 in zip(*(jax.tree.leaves(t) for t in
                          (got_params, want_params, start))):
        np.testing.assert_allclose(g, w, atol=5e-6)
        moved = max(moved, float(jnp.abs(w - p0).max()))
    assert moved > 1e-3                     # the step did move the leaves
