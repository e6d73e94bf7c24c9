"""The causal convolution's Pallas kernel pair with its silu
(`tpu_mpi/xla/conv_kernels.py`) on the interpret machine against
`jax.nn.silu(parallel/ssm.py:causal_conv(...))`, the plain path it stands in
for: the value and all three gradients (dx, dw, dbias) in float32 (tight) and
bfloat16 (one rounding) at the three cells' channel counts cut to a few
blocks of tokens, with and without a bias, read where the channels stand in a
wider row and cut into parts, for 2, 3 and 4 taps; a batch of two (the
second sequence's first tokens see zeros, not the first one's last); what
the backward pass keeps; which shapes take the kernel, and that the others go
the plain way, give the same numbers and count `plain`; the counter. Each
case is one jitted program, waited for before anything else is dispatched
(.claude/skills/verify: the interpret machine's callbacks)."""

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
from tpu_mpi.xla import conv_kernels                            # noqa: E402

F32, BF16 = "float32", "bfloat16"
# (dtype, batch, tokens, columns of the row, first column, channels, taps,
# bias, cuts): 384 tokens are three blocks of 128 (a tap reaches into the
# block before at two edges, and into the inner loop's group before at
# three), 256 one block
CASES = {
    "granite-4352-of-a-wider-row-cut-in-three": (
        F32, 2, 384, 4352 + 256 + 64, 256, 4352, 4, True, (4096, 4224)),
    "phi-5120-the-rows-first-half-bf16": (
        BF16, 1, 384, 5120 + 256, 0, 5120, 4, True, ()),
    "qwen3-next-8192-no-bias-cut-in-three-bf16": (
        BF16, 2, 256, 8192, 0, 8192, 4, False, (2048, 4096)),
    "two-taps": (F32, 1, 384, 256, 0, 256, 2, True, ()),
    "three-taps-bf16-batch-of-two": (BF16, 2, 384, 384, 128, 256, 3, True,
                                     (128,)),
    "four-taps-no-bias": (F32, 2, 384, 128, 0, 128, 4, False, ()),
}
GRADS = ("x", "w", "bias")


def operands(dtype, bsz, t, columns, channels, taps):
    """A row, taps, a bias and a cotangent, of the model's type."""
    keys = jax.random.split(jax.random.key(t + channels + taps), 4)
    normal = jax.random.normal
    return (normal(keys[0], (bsz, t, columns), jnp.float32).astype(dtype),
            (normal(keys[1], (taps, channels), jnp.float32)
             * taps ** -0.5).astype(dtype),
            normal(keys[2], (channels,), jnp.float32).astype(dtype),
            normal(keys[3], (bsz, t, channels), jnp.float32).astype(dtype))


def plain(x, w, bias, start, cuts):
    """The plain path, written out: what the kernel is held to."""
    out = jax.nn.silu(ssm.causal_conv(x[..., start:start + w.shape[1]], w,
                                      bias))
    return jnp.split(out, cuts, axis=-1) if cuts else out


def _convolved(kernel_backend, case: str):
    """(the kernel's, the plain path's at the operands' type, the plain
    path's in float32), each (y, the three gradients of sum(y ct)) from one
    jitted program; y the parts side by side."""
    dtype, bsz, t, columns, start, channels, taps, biased, cuts = CASES[case]
    x, w, bias, ct = operands(dtype, bsz, t, columns, channels, taps)
    f32 = jnp.float32

    def of(fun):
        def loss(x, w, bias):
            y = fun(x, w, bias if biased else None)
            y = jnp.concatenate(y, axis=-1) if cuts else y
            return jnp.sum(y.astype(f32) * ct.astype(f32)), y

        def run(*a):
            (_loss, y), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(*a)
            return y, grads
        return jax.jit(run)

    def conv(x, w, bias):
        return ssm.conv_silu(x, w, bias, start=start, cuts=cuts)

    def written_out(x, w, bias):
        return plain(x, w, jnp.zeros((), f32) if bias is None else bias,
                     start, cuts)
    out = []
    perfvars.reset()
    with kernel_backend("interpret"):
        out.append(jax.block_until_ready(of(conv)(x, w, bias)))
    assert perfvars.snapshot()["conv_kernel_lowerings"] == {
        "kernel": 1, "plain": 0}
    out.append(jax.block_until_ready(of(written_out)(x, w, bias)))
    out.append(jax.block_until_ready(of(written_out)(
        *(v.astype(f32) for v in (x, w, bias)))))
    return out


_CONVOLVED = {}     # a case's three, computed once for the tests that read it


@pytest.fixture
def convolved(kernel_backend):
    """`_convolved` of a case, from `_CONVOLVED` after its first call."""
    def cached(case):
        if case not in _CONVOLVED:
            _CONVOLVED[case] = _convolved(kernel_backend, case)
        return _CONVOLVED[case]
    return cached


def off_by(got, want) -> float:
    """max |got - want| over max |want|."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_silu_of_the_plain_convolution(case, convolved):
    dtype, bsz, t, _columns, _start, channels = CASES[case][:6]
    (kernel, _), (same_type, _), (in_f32, _) = convolved(case)
    assert kernel.shape == (bsz, t, channels)
    assert kernel.dtype == jnp.dtype(dtype)
    assert bool(jnp.isfinite(kernel.astype(jnp.float32)).all())
    if dtype == F32:    # the same float32 sums, taken in another order
        np.testing.assert_allclose(kernel, same_type, rtol=2e-6, atol=2e-6)
    else:   # one rounding of the float32 silu (2^-9 of its size), where the
        #     plain path rounds the sum and then the silu
        assert off_by(kernel, in_f32) < 2.0 ** -8
        assert off_by(kernel, in_f32) <= off_by(same_type, in_f32)


@pytest.mark.parametrize("name", GRADS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_gradient_is_the_plain_paths(case, name, convolved):
    """dx, dw and dbias against `jax.grad` of the plain path: in float32
    tightly (dw and dbias are sums over every token: held against their own
    size); in bfloat16 each lies as near the float32 gradient as the plain
    path's does (the kernel rounds the float32 gradient once)."""
    dtype, biased = CASES[case][0], CASES[case][7]
    at = GRADS.index(name)
    kernel, same_type, in_f32 = (g[at] for _y, g in convolved(case))
    assert kernel.shape == same_type.shape
    assert kernel.dtype == same_type.dtype
    if name == "bias" and not biased:
        assert not kernel.any() and not same_type.any()
    elif dtype == F32:
        size = float(jnp.abs(in_f32).max())
        np.testing.assert_allclose(kernel, same_type, rtol=1e-5,
                                   atol=2e-6 * max(1.0, size))
    else:
        assert off_by(kernel, in_f32) < 2.0 ** -7
        assert off_by(kernel, in_f32) <= 1.5 * off_by(same_type, in_f32)


@pytest.mark.parametrize("dtype", [F32, BF16])
def test_a_sequence_sees_zeros_before_it_not_the_one_before(dtype,
                                                            kernel_backend):
    """In a batch of two, over three blocks of tokens: each sequence's result
    and gradient are what it gives alone, to the bit."""
    x, w, bias, ct = operands(dtype, 2, 384, 256, 256, 4)

    def both(x, ct):
        y, back = jax.vjp(lambda x: ssm.conv_silu(x, w, bias), x)
        return y, back(ct)[0]
    with kernel_backend("interpret"):
        y, dx = jax.block_until_ready(jax.jit(both)(x, ct))
        for i in range(2):
            alone = jax.block_until_ready(jax.jit(both)(x[i:i + 1],
                                                        ct[i:i + 1]))
            np.testing.assert_array_equal(y[i:i + 1], alone[0])
            np.testing.assert_array_equal(dx[i:i + 1], alone[1])
    assert bool((y[1, :3] != y[0, :3]).any())


def test_the_backward_keeps_the_row_the_taps_and_the_bias_alone(
        kernel_backend):
    """What the backward kernels are handed: x as it stands (the whole row,
    once), each part's taps and bias; nothing float32 of [tokens, channels],
    and no second copy of the row or of a part of it."""
    from jax._src.ad_checkpoint import saved_residuals
    bsz, t, columns, start, channels, cuts = 1, 256, 640, 128, 384, (256,)
    x, w, bias, _ct = operands(BF16, bsz, t, columns, channels, 4)
    with kernel_backend("interpret"):
        kept = saved_residuals(
            lambda *a: ssm.conv_silu(*a, start=start, cuts=cuts), x, w, bias)
    shapes = sorted((tuple(aval.shape), str(aval.dtype))
                    for aval, _why in kept)
    assert ((bsz, t, columns), BF16) in shapes
    wide = [s for s, _d in shapes if len(s) == 3]
    assert wide == [(bsz, t, columns)], shapes
    assert all(int(np.prod(s)) <= 4 * channels
               for s, _d in shapes if len(s) < 3), shapes


@pytest.mark.parametrize(
    "what, t, channels, taps, dtype, start, cuts, taken", [
        ("granite's", 8192, 4352, 4, BF16, 4096, (4096, 4224), True),
        ("phi's", 8192, 5120, 4, BF16, 0, (), True),
        ("qwen3-next's", 8192, 8192, 4, BF16, 0, (2048, 4096), True),
        ("float32, two taps", 128, 128, 2, F32, 0, (), True),
        ("three blocks of 128", 384, 256, 3, F32, 128, (128,), True),
        ("100 tokens", 100, 256, 4, F32, 0, (), False),
        ("192 tokens", 192, 256, 4, BF16, 0, (), False),
        ("12 channels", 256, 12, 4, F32, 0, (), False),
        ("200 channels", 256, 200, 4, BF16, 0, (), False),
        ("one tap", 256, 256, 1, F32, 0, (), False),
        ("five taps", 256, 256, 5, BF16, 0, (), False),
        ("from column 64", 256, 256, 4, F32, 64, (), False),
        ("cut at 200", 256, 256, 4, F32, 0, (200,), False),
        ("float16", 256, 256, 4, "float16", 0, (), False),
    ])
def test_which_shapes_take_the_kernel(what, t, channels, taps, dtype, start,
                                      cuts, taken, kernel_backend):
    from tpu_mpi.xla import choice
    asked = (t, channels, taps, dtype, start, cuts)
    with kernel_backend("mosaic"):
        parts = choice.fit(choice.CONV, *asked)
    assert (parts is not None) is taken
    assert choice.fit(choice.CONV, *asked) is None  # the CPU: nothing does
    if taken:       # a part a cut, each with a block of tokens that divides
        #             the sequence and a tile that divides its place
        assert len(parts) == len(cuts) + 1
        assert sum(n for _at, n, _tokens, _tile in parts) == channels
        for at, n, tokens, tile in parts:
            assert t % tokens == 0 and n % tile == 0 and at % tile == 0
    else:
        with pytest.raises(ValueError, match="outside the kernel's contract"):
            conv_kernels.conv_silu(
                jnp.zeros((1, t, start + channels), dtype),
                jnp.zeros((taps, channels), dtype), start=start, cuts=cuts,
                interpret=True)


@pytest.mark.parametrize("t, channels, taps, start, cuts", [
    (100, 256, 4, 0, ()), (256, 200, 4, 0, ()), (256, 256, 5, 0, ()),
    (256, 256, 4, 64, (128,))])
def test_a_shape_the_kernel_does_not_take_goes_the_plain_way(
        t, channels, taps, start, cuts, kernel_backend):
    """With the kernel selectable, a sequence no block divides, channels
    not in 128s, five taps and an odd first column compute what they
    computed and count `plain`."""
    x, w, bias, _ct = operands(F32, 2, t, start + channels + 8, channels,
                               taps)
    perfvars.reset()
    with kernel_backend("interpret"):
        got = jax.block_until_ready(jax.jit(
            lambda *a: ssm.conv_silu(*a, start=start, cuts=cuts))(x, w, bias))
    assert perfvars.snapshot()["conv_kernel_lowerings"] == {
        "kernel": 0, "plain": 1}
    want = plain(x, w, bias, start, cuts)     # (not jitted: the same sums,
    for g, p in zip(got if cuts else [got],   # fused in another way)
                    want if cuts else [want]):
        np.testing.assert_allclose(g, p, rtol=1e-5, atol=2e-6)


@pytest.mark.parametrize("word, who", [("interpret", "kernel"),
                                       (None, "plain")])
def test_the_counter_counts_once_a_traced_call(word, who, kernel_backend):
    """`conv_kernel_lowerings` says who computes a traced convolution: one
    count a call however many parts it is cut into, none for a second call
    of the traced program, zeroed by `reset`."""
    x, w, bias, _ct = operands(F32, 1, 128, 384, 384, 4)
    perfvars.reset()
    with kernel_backend(word):
        conv = jax.jit(lambda *a: ssm.conv_silu(*a, cuts=(128, 256)))
        conv.lower(x, w, bias)
        counted = perfvars.snapshot()["conv_kernel_lowerings"]
        assert counted == {"kernel": int(who == "kernel"),
                           "plain": int(who == "plain")}
        conv.lower(x, w, bias)      # traced once: counted once
        assert perfvars.snapshot()["conv_kernel_lowerings"] == counted
    perfvars.reset()
    assert perfvars.snapshot()["conv_kernel_lowerings"] == {
        "kernel": 0, "plain": 0}


def traced_primitives(jaxpr, above=""):
    """(name stack, primitive) of every equation of a traced program, a
    `pallas_call` by its kernel's name, the kernels' bodies not entered; an
    inner program (a jitted kernel's among them) once a call site."""
    for eqn in jaxpr.eqns:
        stack = f"{above}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            yield stack, str(eqn.params["name"])
            continue
        inner = list(jax.core.jaxprs_in_params(eqn.params))
        for sub in inner:
            yield from traced_primitives(sub, stack)
        if not inner:
            yield stack, eqn.primitive.name


def conv_scope_primitives(cfg, word, kernel_backend):
    """(forward, backward): the primitives under a one-layer model's
    `mixer/conv` scope in the traced gradient of its trunk, with the kernels
    chosen under ``word``. For the mixers' tests (`test_ssm_layer`,
    `test_gdn_layer`, `test_sambay_layer`)."""
    from tpu_mpi.models import transformer as tf
    params = tf.transformer_init(jax.random.key(0), cfg)
    tokens = jnp.zeros((1, cfg.max_seq), jnp.int32)
    tf._block_traced_once.cache_clear()
    with kernel_backend(word):
        traced = jax.make_jaxpr(jax.grad(
            lambda p: tf._trunk(cfg, p, tokens)[0].astype(jnp.float32).sum()
        ))(params)
    tf._block_traced_once.cache_clear()
    found = [(s, p) for s, p in traced_primitives(traced.jaxpr)
             if "mixer" in s and "/conv" in s]
    return ({p for s, p in found if "transpose(" not in s},
            {p for s, p in found if "transpose(" in s})


def check_the_conv_scope(cfg, kernel_backend):
    """Selected, a mixer's `conv` scope holds the kernel each way and none
    of the plain path's arithmetic (the row filled up in front, the shifted
    slices multiplied and summed, the silu); on the CPU's word it holds
    that and no kernel."""
    chain = {"mul", "add", "logistic"}
    # (traced as a TPU would: nothing is lowered, and the interpret
    # machine's callbacks are effects that `jax.checkpoint` refuses)
    forward, backward = conv_scope_primitives(cfg, "mosaic", kernel_backend)
    assert "conv_silu_fwd" in forward and "conv_silu_bwd" in backward
    assert not (forward | backward) & chain, (forward, backward)
    assert "pad" not in forward
    forward, backward = conv_scope_primitives(cfg, None, kernel_backend)
    assert chain | {"pad"} <= forward and "mul" in backward
    assert not [p for p in forward | backward if p.startswith("conv_silu")]
    perfvars.reset()
