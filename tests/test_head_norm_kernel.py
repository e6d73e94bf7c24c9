"""A delta-rule mixer's per-head norms as a Pallas kernel pair
(`tpu_mpi/xla/head_norm_kernels.py`) on the interpret machine against the
plain helpers of `models/transformer.py` they stand in for (`_l2_normed`
over the four-dimensional form with q's scale and the rounding;
`_rms_norm` x the gate of `_head_norm_gated`): both uses, the gate from rows
(silu(z), Qwen3-Next) and from the product taken in the kernel (sigmoid(g_in
w), Kimi), float32 (tight) and bfloat16 (one rounding where the plain path
has three), the two cells' widths cut to a test's size, the value and every
gradient (x, the scale leaf, z or g_in and w); a batch of two; what the
backward pass keeps; which shapes take the kernel, and that the others go the
plain way, give the same numbers and count `plain`; the counter; one trace a
direction. Each case is one jitted program, waited for before anything else
is dispatched (.claude/skills/verify: the interpret machine's callbacks)."""

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
from tpu_mpi.models import transformer as tf                    # noqa: E402
from tpu_mpi.xla import head_norm_kernels as hk                 # noqa: E402

F32, BF16 = "float32", "bfloat16"
EPS = 1e-5
# (use, dtype, batch, tokens, heads, rank of the gate's product): 256 tokens
# are two blocks of 128 where a block of 256 would pass 2 MB (float32 rows of
# 4096), one block else; 384 three of 128
CASES = {
    "l2-kimi-4096-wide-bf16": ("l2", BF16, 1, 128, 32, 0),
    "l2-qwen3-next-2048-wide-bf16-batch-of-two": ("l2", BF16, 2, 128, 16, 0),
    "l2-three-blocks-f32": ("l2", F32, 1, 384, 3, 0),
    "silu-rows-qwen3-next-4096-wide-bf16": ("silu", BF16, 1, 128, 32, 0),
    "silu-rows-three-blocks-f32-batch-of-two": ("silu", F32, 2, 384, 2, 0),
    "sigmoid-product-kimi-4096-wide-bf16": ("sigmoid", BF16, 1, 256, 32, 128),
    "sigmoid-product-two-blocks-f32-batch-of-two": ("sigmoid", F32, 2, 256, 3,
                                                    128),
    "sigmoid-product-rank-256-bf16": ("sigmoid", BF16, 1, 128, 2, 256),
}
WIDTH = hk.HEAD_WIDTH
SCALE = WIDTH ** -0.5       # q's


class Cfg:                  # what `_head_norm_gated` reads of a configuration
    norm_eps = EPS


def operands(case: str):
    """(x as rows, the scale leaf and the gate's operands, a cotangent), of
    the model's type."""
    use, dtype, bsz, t, heads, rank = CASES[case]
    keys = jax.random.split(jax.random.key(t + heads + rank), 6)
    normal = jax.random.normal
    rows = (bsz, t, heads * WIDTH)
    x = normal(keys[0], rows, jnp.float32).astype(dtype)
    ct = normal(keys[1], rows, jnp.float32).astype(dtype)
    scale = (1.0 + 0.1 * normal(keys[2], (WIDTH,), jnp.float32)).astype(dtype)
    if use == "l2":
        return (x,), ct
    if use == "silu":
        return (x, scale, normal(keys[3], rows, jnp.float32).astype(dtype)), ct
    return (x, scale,
            normal(keys[4], (bsz, t, rank), jnp.float32).astype(dtype),
            (normal(keys[5], (rank, heads * WIDTH), jnp.float32)
             * rank ** -0.5).astype(dtype)), ct


def helpers(case: str):
    """x and the rest -> rows: the model's helper (which decides between the
    kernel and the plain path) as the mixers call it."""
    use, _dtype, _bsz, _t, heads, _rank = CASES[case]

    def l2(x):
        return tf._l2_normed_rows(x, heads, SCALE).reshape(x.shape)

    def gated(x, scale, *gate_from):
        o = x.reshape(*x.shape[:2], heads, WIDTH)
        return tf._head_norm_gated(Cfg, o, scale, use,
                                   *gate_from).reshape(x.shape)
    return l2 if use == "l2" else gated


def _normed(kernel_backend, case: str):
    """(the kernel's, the plain path's at the operands' type, the plain
    path's in float32), each (y, the gradients of sum(y ct)) from one jitted
    program."""
    args, ct = operands(case)
    fun = helpers(case)
    f32 = jnp.float32

    def of(ct):
        def loss(*a):
            y = fun(*a)
            return jnp.sum(y.astype(f32) * ct.astype(f32)), y

        def run(*a):
            (_loss, y), grads = jax.value_and_grad(
                loss, argnums=tuple(range(len(a))), has_aux=True)(*a)
            return y, grads
        return jax.jit(run)
    out = []
    perfvars.reset()
    with kernel_backend("interpret"):
        out.append(jax.block_until_ready(of(ct)(*args)))
    assert perfvars.snapshot()["head_norm_lowerings"] == {
        "kernel": 1, "plain": 0}
    out.append(jax.block_until_ready(of(ct)(*args)))
    assert perfvars.snapshot()["head_norm_lowerings"] == {
        "kernel": 1, "plain": 1}
    out.append(jax.block_until_ready(of(ct.astype(f32))(
        *(v.astype(f32) for v in args))))
    return out


_NORMED = {}        # a case's three, computed once for the tests that read it


@pytest.fixture
def normed(kernel_backend):
    """`_normed` of a case, from `_NORMED` after its first call."""
    def cached(case):
        if case not in _NORMED:
            _NORMED[case] = _normed(kernel_backend, case)
        return _NORMED[case]
    return cached


def off_by(got, want) -> float:
    """max |got - want| over max |want|."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_plain_helpers_norm(case, normed):
    use, dtype, bsz, t, heads, _rank = CASES[case]
    (kernel, _), (same_type, _), (in_f32, _) = normed(case)
    assert kernel.shape == (bsz, t, heads * WIDTH)
    assert kernel.dtype == jnp.dtype(dtype)
    assert bool(jnp.isfinite(kernel.astype(jnp.float32)).all())
    if dtype == F32:    # the same float32 sums, taken in another order
        np.testing.assert_allclose(kernel, same_type, rtol=1e-5, atol=2e-6)
    else:   # ONE rounding of the float32 result (2^-9 of its size), where
        #     the plain gated norm rounds the norm, its scale and the gate
        assert off_by(kernel, in_f32) < 2.0 ** -8
        assert off_by(kernel, in_f32) <= off_by(same_type, in_f32)
    if use == "l2":     # a head's squares sum to the scale's: 1 / 128
        sums = jnp.sum(jnp.square(kernel.astype(jnp.float32)).reshape(
            bsz, t, heads, WIDTH), axis=-1)
        np.testing.assert_allclose(sums, SCALE ** 2, rtol=2e-2)


@pytest.mark.parametrize("case, at", [
    (case, at) for case in sorted(CASES)
    for at in range({"l2": 1, "silu": 3, "sigmoid": 4}[CASES[case][0]])])
def test_the_kernels_gradient_is_the_plain_paths(case, at, normed):
    """dx, d scale and dz, or d g_in and d w, against `jax.grad` of the
    plain path: in float32 tightly (d scale and d w are sums over every
    token: held against their own size); in bfloat16 each lies as near the
    float32 gradient as the plain path's does."""
    dtype = CASES[case][1]
    kernel, same_type, in_f32 = (g[at] for _y, g in normed(case))
    assert kernel.shape == same_type.shape
    assert kernel.dtype == same_type.dtype
    if dtype == F32:
        size = float(jnp.abs(in_f32).max())
        np.testing.assert_allclose(kernel, same_type, rtol=2e-5,
                                   atol=4e-6 * max(1.0, size))
    else:
        assert off_by(kernel, in_f32) < 2.0 ** -6
        assert off_by(kernel, in_f32) <= 1.5 * off_by(same_type, in_f32) \
            + 2.0 ** -9


def test_a_sequence_of_a_batch_is_what_it_gives_alone(kernel_backend):
    """In a batch of two over two blocks of tokens: each sequence's result
    and gradients are what it gives alone, to the bit, the leaves' the sum."""
    (x, scale, g_in, w), ct = operands(
        "sigmoid-product-two-blocks-f32-batch-of-two")

    def both(x, g_in, ct):
        y, back = jax.vjp(lambda *a: hk.gated_rms_norm(
            *a, act="sigmoid", eps=EPS), x, scale, g_in, w)
        return y, back(ct)
    with kernel_backend("interpret"):
        y, grads = jax.block_until_ready(jax.jit(both)(x, g_in, ct))
        alone = [jax.block_until_ready(jax.jit(both)(
            x[i:i + 1], g_in[i:i + 1], ct[i:i + 1])) for i in range(2)]
    for i in range(2):
        np.testing.assert_array_equal(y[i:i + 1], alone[i][0])
        for at in (0, 2):       # dx, d g_in
            np.testing.assert_array_equal(grads[at][i:i + 1],
                                          alone[i][1][at])
    for at in (1, 3):           # d scale, d w
        np.testing.assert_allclose(grads[at], alone[0][1][at]
                                   + alone[1][1][at], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", [
    "l2-kimi-4096-wide-bf16", "silu-rows-qwen3-next-4096-wide-bf16",
    "sigmoid-product-kimi-4096-wide-bf16"])
def test_the_backward_keeps_the_kernels_inputs_alone(case, kernel_backend):
    """What the backward kernel is handed: x, the scale and the gate's
    operands as they stand; nothing float32 of [tokens, channels], no
    second copy of the rows."""
    from jax._src.ad_checkpoint import saved_residuals
    args, _ct = operands(case)
    with kernel_backend("interpret"):
        kept = saved_residuals(helpers(case), *args)
    shapes = sorted((tuple(aval.shape), str(aval.dtype))
                    for aval, _why in kept)
    rows = [s for s in shapes if s[0] == args[0].shape]
    assert rows == [(args[0].shape, BF16)] * (
        2 if CASES[case][0] == "silu" else 1), shapes
    assert not [s for s in shapes if s[1] == F32
                and int(np.prod(s[0])) > WIDTH], shapes


@pytest.mark.parametrize("what, t, width, head, dtype, rank, taken", [
    ("kimi's q and k", 8192, 4096, 128, BF16, 0, 256),
    ("kimi's gated norm", 8192, 4096, 128, BF16, 128, 256),
    ("qwen3-next's q and k", 8192, 2048, 128, BF16, 0, 512),
    ("qwen3-next's gated norm", 8192, 4096, 128, BF16, 0, 256),
    ("float32, one head", 128, 128, 128, F32, 0, 128),
    ("three blocks of 128", 384, 256, 128, F32, 0, 128),
    ("a head of 64 lanes", 256, 256, 64, BF16, 0, None),
    ("a head of 256 lanes", 256, 512, 256, BF16, 0, None),
    ("100 tokens", 100, 256, 128, F32, 0, None),
    ("192 tokens", 192, 256, 128, BF16, 0, None),
    ("200 channels", 256, 200, 128, BF16, 0, None),
    ("a product over 96", 256, 256, 128, BF16, 96, None),
    ("rows no block of 2 MB holds", 256, 16384, 128, F32, 0, None),
    ("float16", 256, 256, 128, "float16", 0, None),
])
def test_which_shapes_take_the_kernel(what, t, width, head, dtype, rank,
                                      taken, kernel_backend):
    from tpu_mpi.xla import choice
    asked = (t, width, head, dtype, rank)
    with kernel_backend("mosaic"):
        assert choice.fit(choice.HEAD_NORM, *asked) == taken
    assert choice.fit(choice.HEAD_NORM, *asked) is None  # the CPU: none does
    if taken is None and head == hk.HEAD_WIDTH:
        # (a head of another width has no rows the kernel's entry could read)
        gate_from = () if not rank else (
            jnp.zeros((1, t, rank), dtype), jnp.zeros((rank, width), dtype))
        with pytest.raises(ValueError, match="outside the kernel's contract"):
            if rank:
                hk.gated_rms_norm(jnp.zeros((1, t, width), dtype),
                                  jnp.ones((head,), dtype), *gate_from,
                                  act="sigmoid", eps=EPS, interpret=True)
            else:
                hk.l2_norm(jnp.zeros((1, t, width), dtype), interpret=True)


@pytest.mark.parametrize("use, t, heads, width", [
    ("l2", 100, 2, 128), ("l2", 256, 4, 64), ("silu", 128, 1, 256),
    ("sigmoid", 192, 2, 128)])
def test_a_shape_the_kernel_does_not_take_goes_the_plain_way(
        use, t, heads, width, kernel_backend):
    """With the kernel selectable, a sequence no block divides and heads of
    64 and 256 lanes compute what they computed and count `plain`."""
    keys = jax.random.split(jax.random.key(t), 4)
    x = jax.random.normal(keys[0], (2, t, heads * width))
    scale = 1.0 + 0.1 * jax.random.normal(keys[1], (width,))
    gate_from = {"l2": (), "silu": (jax.random.normal(keys[2], x.shape),),
                 "sigmoid": (jax.random.normal(keys[2], (2, t, 128)),
                             jax.random.normal(keys[3], (128, heads * width))
                             * 0.1)}[use]

    def fun(x, scale, *gate_from):
        if use == "l2":
            return tf._l2_normed_rows(x, heads, SCALE)
        return tf._head_norm_gated(Cfg, x.reshape(2, t, heads, width), scale,
                                   use, *gate_from)
    perfvars.reset()
    with kernel_backend("interpret"):
        got = jax.block_until_ready(jax.jit(fun)(x, scale, *gate_from))
    assert perfvars.snapshot()["head_norm_lowerings"] == {
        "kernel": 0, "plain": 1}
    o = x.reshape(2, t, heads, width)
    if use == "l2":
        want = o * jax.lax.rsqrt(jnp.sum(o * o, -1, keepdims=True)
                                 + 1e-6) * SCALE
    else:
        pre = gate_from[0] if use == "silu" else gate_from[0] @ gate_from[1]
        gate = jax.nn.silu(pre) if use == "silu" else jax.nn.sigmoid(pre)
        want = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                 + EPS) * scale * gate.reshape(o.shape)
    np.testing.assert_allclose(got.reshape(o.shape), want, rtol=2e-5,
                               atol=2e-6)


@pytest.mark.parametrize("word, who", [("interpret", "kernel"),
                                       (None, "plain")])
def test_the_counter_counts_once_a_traced_call(word, who, kernel_backend):
    """`head_norm_lowerings` says who computes a traced norm: one count a
    call, none for a second call of the traced program, zeroed by `reset`."""
    (x, scale, z), _ct = operands("silu-rows-qwen3-next-4096-wide-bf16")
    perfvars.reset()
    with kernel_backend(word):
        norm = jax.jit(lambda *a: tf._head_norm_gated(
            Cfg, a[0].reshape(1, 128, 32, WIDTH), *a[1:]),
            static_argnums=2)
        norm.lower(x, scale, "silu", z)
        counted = perfvars.snapshot()["head_norm_lowerings"]
        assert counted == {"kernel": int(who == "kernel"),
                           "plain": int(who == "plain")}
        norm.lower(x, scale, "silu", z)     # traced once: counted once
        assert perfvars.snapshot()["head_norm_lowerings"] == counted
    perfvars.reset()
    assert perfvars.snapshot()["head_norm_lowerings"] == {
        "kernel": 0, "plain": 0}


def test_a_differentiated_norm_builds_each_kernel_once(kernel_backend):
    """The `custom_vjp`'s primal and its forward rule share the jitted
    forward's one trace (ROADMAP S11 (h)), and q and k, whose scales differ,
    share a kernel: the scale is an operand. Traced as a TPU would."""
    hk._head_norm_fn.cache_clear()
    x = jnp.zeros((1, 256, 512), jnp.bfloat16)

    def loss(q, k):
        return sum(tf._l2_normed_rows(part, 4, s).astype(jnp.float32).sum()
                   for part, s in ((q, SCALE), (k, 1.0)))
    perfvars.reset()
    with kernel_backend("mosaic"):
        jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, x)
        jax.make_jaxpr(loss)(x, x)      # a forward program beside it
    built = perfvars.build_snapshot()["kernels"]
    perfvars.reset()
    hk._head_norm_fn.cache_clear()
    assert built == {"head_l2_norm_fwd": 1, "head_l2_norm_bwd": 1}, built


def mixer_scope_calls(cfg, word, kernel_backend):
    """(calls, lowered text): every primitive under a one-layer model's
    `mixer` scope in the traced gradient of its trunk
    (`test_conv_kernel.traced_primitives`: a kernel once a CALL); and, where
    the kernels are chosen as a TPU would, the module lowered for one.
    For the mixers' tests (`test_gdn_layer`, `test_kda_layer`)."""
    from test_conv_kernel import traced_primitives
    params = tf.transformer_init(jax.random.key(0), cfg)
    tokens = jnp.zeros((1, cfg.max_seq), jnp.int32)

    def loss(p):
        return tf._trunk(cfg, p, tokens)[0].astype(jnp.float32).sum()
    tf._block_traced_once.cache_clear()
    text = ""
    with kernel_backend(word):
        traced = jax.make_jaxpr(jax.grad(loss))(params)
        if word == "mosaic":    # (as the program runs: without the tests'
            #                     x64, under which the scan's kernels do not
            #                     lower at these small shapes)
            with jax.enable_x64(False):
                text = jax.jit(jax.grad(loss)).trace(params).lower(
                    lowering_platforms=("tpu",)).as_text()
    tf._block_traced_once.cache_clear()
    perfvars.reset()
    return [(s, p) for s, p in traced_primitives(traced.jaxpr)
            if "mixer" in s], text


def check_the_norm_scopes(cfg, kernel_backend, forwards: dict):
    """Selected, a delta-rule mixer's `prep` scope holds the L2 kernel, q's
    and k's, ``forwards["head_l2_norm_fwd"]`` times forward (with what a
    recomputed half runs again) and twice backward, its `gate_norm` scope
    the gated kernel likewise and once backward, and neither the plain
    path's reciprocal root; the lowered module holds a backward kernel's
    body once and a forward one's at most twice (a recomputed half lowers
    its own), and turns no float32 [batch, tokens, heads, 128] array; on
    the CPU's word the arithmetic and no kernel."""
    import collections
    import re
    calls, text = mixer_scope_calls(cfg, "mosaic", kernel_backend)
    in_scope = {scope: collections.Counter(
        p for s, p in calls if f"/{scope}" in s)
        for scope in ("prep", "gate_norm")}
    kernels = {p: n for c in in_scope.values() for p, n in c.items()
               if p.startswith("head_")}
    assert kernels == {**forwards, "head_l2_norm_bwd": 2,
                       "head_gated_norm_bwd": 1}, kernels
    assert {p for p in in_scope["prep"] if p.startswith("head_")} == {
        "head_l2_norm_fwd", "head_l2_norm_bwd"}
    for c in in_scope.values():
        assert "rsqrt" not in c, c
    for name in kernels:    # a body a trace, however many calls
        bodies = len(re.findall(rf'kernel_name = "{name}"', text))
        assert 1 <= bodies <= 1 + name.endswith("_fwd"), (name, bodies)
    heads = f"1x{cfg.max_seq}x\\d+x{WIDTH}xf32"
    assert not re.findall(
        rf"stablehlo\.transpose.*tensor<{heads}>", text)
    calls, _text = mixer_scope_calls(cfg, None, kernel_backend)
    for scope in ("prep", "gate_norm"):
        found = {p for s, p in calls if f"/{scope}" in s}
        assert {"reduce_sum", "rsqrt"} <= found, (scope, found)
        assert "pallas_call" not in found
        assert not [p for p in found if p.startswith("head_")]
