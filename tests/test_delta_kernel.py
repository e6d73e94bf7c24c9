"""The delta-rule scan's two Pallas kernel pairs
(`tpu_mpi/xla/delta_kernels.py`: a decay a head with two value heads a key
head, and a decay a key CHANNEL with a key head a value head) on the
interpret machine against `parallel/delta.py:_chunked`, the plain path they
stand in for, and against the recurrence one token at a time: values and
all five gradients, float32 and bfloat16, one chunk, several, a batch of two,
two key heads (four value heads) and the `padded` form; keys that repeat;
decay sums of -100 inside a chunk; what the backward pass keeps; which shapes
take a kernel and which the plain path; the counters; one train step of
either kind of layer. Small shapes (heads of 128, chunks of 64): each case is
one jitted program, waited for before anything else is dispatched
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
# (dtype, batch, tokens, key heads): the scan's form follows from the tokens.
# A case named `channel-..` has a decay a key channel and a key head a value
# head; the others a decay a head and two value heads a key head.
CASES = {
    "one-chunk": (F32, 1, 64, 1),
    "three-chunks": (F32, 1, 192, 1),
    "batch-of-two": (F32, 2, 128, 1),
    "two-key-heads": (F32, 1, 128, 2),
    "padded": (F32, 1, 100, 1),
    "bf16": (BF16, 1, 128, 1),
    "bf16-padded-batch-of-two": (BF16, 2, 100, 1),
    "channel-one-chunk": (F32, 1, 64, 2),
    "channel-three-chunks": (F32, 1, 192, 2),
    "channel-batch-of-two": (F32, 2, 128, 2),
    "channel-four-heads": (F32, 1, 128, 4),
    "channel-padded": (F32, 1, 100, 2),
    "channel-bf16": (BF16, 1, 128, 2),
    "channel-bf16-padded-batch-of-two": (BF16, 2, 100, 2),
}
GRADIENTS = ("batch-of-two", "two-key-heads", "padded", "bf16",
             "channel-batch-of-two", "channel-four-heads", "channel-padded",
             "channel-bf16")
KINDS = ("head", "channel")     # what a number of the decay belongs to
CHANNEL = dict(pair=1, channel=True)    # `operands` with a decay a channel


def by_channel(case: str) -> bool:
    return case.startswith("channel")


def operands(dtype, bsz, t, hk=1, width=W, pair=2, channel=False):
    """(q, k, v, g, beta) as the model hands them over (q and k normed, q
    scaled; decays from slow to fast over the heads, ``channel``: a number a
    key channel, from a twentieth of a head's rate to all of it), and a
    weight for o."""
    keys = jax.random.split(jax.random.key(t + bsz + hk), 6)
    hv = pair * hk
    q, k = (tf._l2_normed(jax.random.normal(key, (bsz, t, hk, width)))
            for key in keys[:2])
    v = jax.random.normal(keys[2], (bsz, t, hv, width))
    g = -jax.random.uniform(keys[3], (bsz, t, hv)) \
        * jnp.linspace(0.05, 2.0, hv)
    if channel:
        g = g[..., None] * jax.random.uniform(
            jax.random.fold_in(keys[3], 1), (bsz, t, hv, width), minval=0.05)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (bsz, t, hv)))
    args = tuple(a.astype(dtype) for a in (q * width ** -0.5, k, v)) \
        + (g.astype(jnp.float32), beta.astype(jnp.float32))
    return args, jax.random.normal(keys[5], v.shape).astype(dtype)


def _scanned(kernel_backend, case: str, grads: bool = False):
    """(kernel's, `_chunked`'s, the recurrence's in float32) values, or the
    three's gradients of sum(o w), for a case; each one jitted program."""
    dtype, bsz, t, hk = CASES[case]
    args, w = operands(dtype, bsz, t, hk,
                       **(CHANNEL if by_channel(case) else {}))
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
    assert kernel.shape == (bsz, t, (1 if by_channel(case) else 2) * hk, W)
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


@pytest.mark.parametrize("kind", KINDS)
def test_keys_that_repeat_cost_the_kernels_inverse_no_digits(kind,
                                                             kernel_backend):
    """Every key the same, no decay, beta one: `A` is all ones under the
    diagonal, whose powers reach 1e17 at a chunk of 64 while its inverse
    has entries of one; the kernel inverts by halves, as the plain path
    does, and forms no power: values and gradients stay the recurrence's."""
    (q, k, v, g, beta), w = operands(F32, 1, 128, **(
        dict(CHANNEL, hk=2) if kind == "channel" else {}))
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


@pytest.mark.parametrize("dtype", [F32, BF16])
def test_decay_sums_of_minus_a_hundred_inside_a_chunk_stay_finite_and_equal(
        dtype, kernel_backend):
    """g = -1.6 a token on every other channel, the fastest a model's own
    initial values give: the sums reach -100 inside a chunk, where the naive
    factoring (k_i o exp(gamma_i)) . (k_j o exp(-gamma_j)) is 0 x inf in
    float32. The kernel scales a round's rows to the first token of their
    half and its columns from it, as the plain path does, and forms no
    exponential of a positive number: values and every gradient are finite
    and the plain path's, and in float32 the recurrence's."""
    (q, k, v, g, beta), w = operands(dtype, 1, 128, hk=2, **CHANNEL)
    g = g.at[..., ::2].set(-1.6)
    args = (q, k, v, g, beta)
    gamma = jnp.cumsum(g[:, :CHUNK], axis=1)
    assert float(gamma.min()) < -100.0
    assert not bool(jnp.isfinite(jnp.exp(-gamma)).all())    # the naive factor
    f32 = jnp.float32

    def both(fun, args):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fun(*a).astype(f32) * w.astype(f32)),
            argnums=(0, 1, 2, 3, 4)))(*args)
    with kernel_backend("interpret"):
        got, got_grads = jax.block_until_ready(
            both(lambda *a: delta.delta_scan(*a, CHUNK), args))
    plain, plain_grads = jax.block_until_ready(
        both(lambda *a: delta.delta_scan(*a, CHUNK), args))
    exact, exact_grads = jax.block_until_ready(both(
        delta.delta_recurrence, tuple(a.astype(f32) for a in args)))
    assert bool(jnp.isfinite(got))
    near = 2e-5 if dtype == F32 else 3e-2
    assert abs(float(got - plain)) < near * abs(float(plain))
    for name, a, b, c in zip(NAMES, got_grads, plain_grads, exact_grads):
        assert bool(jnp.isfinite(a.astype(f32)).all()), name
        assert off_by(a, b) < near, name
        assert off_by(a, c) < (2e-5 if dtype == F32 else max(
            2e-2, 2.0 * off_by(b, c))), name


@pytest.mark.parametrize("kind", KINDS)
def test_the_backward_keeps_the_operands_and_the_states_alone(
        kind, kernel_backend):
    """What the backward pass is handed: q, k and v as rows, the state
    before each chunk, and with a decay a head the scalars a head and token
    (beta, the exponentials, the kernel's tile of them: each [batch, t,
    value heads] float32 or eight times that), with a decay a channel the
    decay as it came, as rows, and beta's tile; nothing of [.., chunk,
    chunk] and nothing as wide as `W`, `U0`, the decay sums or the decayed
    keys, which the kernel computes again."""
    from jax._src.ad_checkpoint import saved_residuals
    channel = kind == "channel"
    bsz, t, hk = 1, 192, 2 if channel else 1
    args, _w = operands(F32, bsz, t, hk, **(CHANNEL if channel else {}))
    with kernel_backend("interpret"):
        kept = saved_residuals(lambda *a: delta.delta_scan(*a, CHUNK), *args)
    shapes = [tuple(aval.shape) for aval, _why in kept]
    nc, hv = t // CHUNK, hk if channel else 2 * hk
    assert (bsz, nc, hv, W, W) in shapes                    # the states
    assert not [s for s in shapes if s[-2:] == (CHUNK, CHUNK)]
    wide = sorted(s for s in shapes if s and s[-1] >= W)
    if channel:     # q, k, v and g as rows, beta's tile, the states
        assert wide == sorted(
            [(bsz, t, hv * W)] * 4
            + [(bsz, hv // 2, nc * 8, 128), (bsz, nc, hv, W, W)]), shapes
    else:
        assert wide == sorted([
            (bsz, t, hk * W), (bsz, t, hk * W), (bsz, t, hv * W),
            (bsz, hk, nc * 8, 128), (bsz, hk, nc, 1, 128),
            (bsz, nc, hv, W, W)]), shapes
    for s in shapes:        # and the rest are scalars a head and token
        size = 1
        for n in s:
            size *= n
        assert s in wide or size <= bsz * t * hv, s


@pytest.mark.parametrize("what, heads, widths, chunk, dtype, decay, taken", [
    ("the cell's", (32, 16), (128, 128), 64, BF16, 1, True),
    ("float32", (2, 1), (128, 128), 64, F32, 1, True),
    ("four value heads", (4, 2), (128, 128), 64, BF16, 1, True),
    ("one value head a key head", (2, 2), (128, 128), 64, BF16, 1, False),
    ("four value heads a key head", (4, 1), (128, 128), 64, BF16, 1, False),
    ("a key head of 64", (2, 1), (64, 128), 64, F32, 1, False),
    ("a value head of 256", (2, 1), (128, 256), 64, F32, 1, False),
    ("a chunk of 32", (2, 1), (128, 128), 32, BF16, 1, False),
    ("a chunk of 128", (2, 1), (128, 128), 128, BF16, 1, False),
    ("float16", (2, 1), (128, 128), 64, "float16", 1, False),
    ("a decay a channel: the Kimi cell's", (32, 32), (128, 128), 64, BF16,
     128, True),
    ("a decay a channel, float32", (2, 2), (128, 128), 64, F32, 128, True),
    ("a decay a channel, two value heads a key head", (2, 1), (128, 128), 64,
     BF16, 128, False),
    ("a decay a channel, an odd number of heads", (3, 3), (128, 128), 64,
     BF16, 128, False),
    ("a decay a channel, heads of 64", (2, 2), (64, 64), 64, BF16, 64,
     False),
    ("a decay half the channels", (2, 2), (128, 128), 64, BF16, 64, False),
    ("a decay a channel, a chunk of 32", (2, 2), (128, 128), 32, BF16, 128,
     False),
    ("a decay a channel, float16", (2, 2), (128, 128), 64, "float16", 128,
     False),
])
def test_which_shapes_take_the_kernel(what, heads, widths, chunk, dtype,
                                      decay, taken, kernel_backend):
    asked = (choice.DELTA_SCAN, *heads, *widths, chunk, dtype, decay)
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
                jnp.zeros((1, t, hv, dv), dtype),
                jnp.zeros((1, t, hv) + ((decay,) if decay > 1 else ())),
                jnp.ones((1, t, hv)), interpret=True)


@pytest.mark.parametrize("width, pair, chunk, t, form, kind", [
    (64, 2, 64, 128, "chunked", "head"), (128, 1, 64, 128, "chunked", "head"),
    (128, 2, 32, 100, "padded", "head"),
    (128, 2, 64, 128, "chunked", "channel"),
    (64, 1, 64, 128, "chunked", "channel"),
    (128, 1, 32, 100, "padded", "channel")])
def test_a_shape_the_kernel_does_not_take_goes_the_plain_way(
        width, pair, chunk, t, form, kind, kernel_backend):
    """With the kernels selectable, heads of 64, one value head a key head
    or a chunk of 32 computes what it computed and counts `plain`; with a
    decay a channel, two value heads a key head, heads of 64 or a chunk of
    32 likewise."""
    args, _w = operands(F32, 1, t, hk=2 if kind == "channel" else 1,
                        width=width, pair=pair, channel=kind == "channel")
    perfvars.reset()
    with kernel_backend("interpret"):
        got = jax.block_until_ready(
            jax.jit(lambda *a: delta.delta_scan(*a, chunk))(*args))
    counted = perfvars.snapshot()
    assert counted["delta_kernel_lowerings"] == {"kernel": 0, "plain": 1}
    assert counted["delta_decays"] == {
        "head": int(kind == "head"), "channel": int(kind == "channel")}
    assert counted["delta_lowerings"][form] == 1
    assert sum(counted["delta_lowerings"].values()) == 1
    assert off_by(got, jax.jit(delta.delta_recurrence)(*args)) < 1e-5
    perfvars.reset()


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("name, t, form, who", [
    ("interpret", 128, "chunked", "kernel"),
    ("interpret", 100, "padded", "kernel"),
    (None, 128, "chunked", "plain"), (None, 100, "padded", "plain")])
def test_the_counters_count_once_a_traced_scan(name, t, form, who, kind,
                                               kernel_backend):
    """`delta_kernel_lowerings` says who computes a traced scan,
    `delta_lowerings` its form, as it did, whoever computes it,
    `delta_decays` what a number of its decay belongs to; one count each a
    trace, none for a second call of the traced program, all zeroed by
    `reset`; a kernel's trace is noted under its own name."""
    channel = kind == "channel"
    args, _w = operands(F32, 1, t,
                        **(dict(CHANNEL, hk=2) if channel else {}))
    names = ["delta_channel_scan_fwd" if channel else "delta_scan_fwd"]
    perfvars.reset()
    delta_kernels._delta_scan_fn.cache_clear()      # (traced once a process)
    delta_kernels._channel_scan_fn.cache_clear()
    with kernel_backend(name):
        scan = jax.jit(lambda *a: delta.delta_scan(*a, CHUNK))
        jax.block_until_ready(scan.lower(*args))
        counted = perfvars.snapshot()
        assert counted["delta_kernel_lowerings"] == {
            "kernel": int(who == "kernel"), "plain": int(who == "plain")}
        assert counted["delta_lowerings"] == {
            "chunked": int(form == "chunked"), "padded": int(form == "padded")}
        assert counted["delta_decays"] == {
            "head": int(not channel), "channel": int(channel)}
        built = counted.get("build", {}).get("kernels", {})
        assert sorted(built) == (names if who == "kernel" else []), built
        scan.lower(*args)       # traced once: counted once
        assert perfvars.snapshot()["delta_kernel_lowerings"] == \
            counted["delta_kernel_lowerings"]
    perfvars.reset()
    assert perfvars.snapshot()["delta_kernel_lowerings"] == {
        "kernel": 0, "plain": 0}


@pytest.mark.parametrize("mixer", ["gdn", "kda"])
def test_one_train_step_through_the_kernels_is_the_plain_step(mixer,
                                                              kernel_backend):
    """`transformer_train_step` on a 1 x 1 x 1 mesh at a toy shape inside
    the kernels' contract (two delta-rule layers of one key head and two
    value heads of 128, or two KDA layers of two heads of 128 whose half is
    one recomputed function; 128 tokens in chunks of 64), the selection
    patched to the interpret machine: the loss and every updated leaf
    against the plain step's. Under `shard_map` every operand varies over
    dp; the kernel's operands are made to vary together all the same."""
    import numpy as np
    from tpu_mpi import xla
    cfg = tf.TransformerConfig(
        vocab=64, d_model=64, n_heads=4, n_layers=2, d_ff=64, max_seq=128,
        dtype=jnp.float32, rope_full_layers=False, dense_gated=True,
        mixer_kinds=[mixer, mixer], gdn_key_heads=1 if mixer == "gdn" else 2,
        gdn_key_dim=W, gdn_value_heads=2, gdn_value_dim=W, gdn_conv=4,
        gdn_chunk=CHUNK, kda_rank=8 if mixer == "kda" else 0)

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
    assert counted["delta_decays"] == {
        "head": 2 * (mixer == "gdn"), "channel": 2 * (mixer == "kda")}
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
