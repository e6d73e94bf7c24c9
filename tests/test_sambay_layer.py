"""A decoder-hybrid-decoder stack through the program's normal path: the
selective (Mamba-1) scan of `parallel.ssm` against the recurrence one token
at a time, values and gradients, at several chunk lengths and at a length
that is no multiple of the chunk; differential attention against two plain
softmaxes, full and windowed, two query pairs to a key/value pair; LayerNorm
with a bias; the two side values (the memory is layer N/2's scan output
before its gate, the shared keys and values are layer N/2 + 1's, and no other
layer's) and their gradients; the model at N = 8 against the plain reference
(yardstick/reference/lm_sambay_train_step.py) on seeded random weights,
float32: loss, logits, and the update leaf by leaf; one trace a kind at N =
16; a program without the new kinds traces what it traced and imports none
of it; what the configuration and the step refuse; the counters."""

import dataclasses
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tpu_mpi import perfvars, xla                               # noqa: E402
from tpu_mpi.models import transformer as tf                    # noqa: E402
from tpu_mpi.models.transformer import (TransformerConfig,      # noqa: E402
                                        transformer_forward,
                                        transformer_init,
                                        transformer_train_step)
from tpu_mpi.parallel import ssm                                # noqa: E402
from yardstick.reference import lm_sambay_train_step as ref     # noqa: E402

V, T, LR, WINDOW = 128, 24, 0.05, 8


def layout(n: int, window: int = WINDOW) -> dict:
    """The model's own rule, as the fields of `TransformerConfig`."""
    kinds, windows = [], []
    for l in range(n):
        if l % 2 == 0:
            kinds.append("mamba" if l <= n // 2 else "gmu")
        else:
            kinds.append("attention" if l <= n // 2 + 1 else "cross")
        windows.append(window if l % 2 and l < n // 2 else 0)
    return dict(n_layers=n, mixer_kinds=kinds, attn_windows=windows,
                memory_from=n // 2, kv_from=n // 2 + 1)


CFG = TransformerConfig(
    vocab=V, d_model=32, n_heads=8, n_kv_heads=4, d_head=4, d_ff=64,
    max_seq=T, dtype=jnp.float32, norm_eps=1e-5, dense_gated=True,
    diff_attn=True, attn_bias=True, norm_kind="layer",
    ssm_state=4, ssm_conv=4, ssm_dt_rank=2, ssm_chunk=8,
    remat_layers=["ffn", "", "", "ffn", "", "", "", ""], **layout(8))
MODEL = dict(hidden_size=32, intermediate_size=64, num_hidden_layers=8,
             num_attention_heads=8, num_key_value_heads=4,
             sliding_window=WINDOW, layer_norm_eps=1e-5, mb_per_layer=2,
             tie_word_embeddings=True, vocab_size=V, mamba_d_state=4,
             mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=2)


def off_by(got, want) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(got - want))
                          / jnp.sum(jnp.square(want))))


# -- the selective scan against the recurrence ---------------------------------

def recurrence(x, dt, a, b, c, d):
    """S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c],
    y_t[c] = sum_n C_t[n] S_t[c, n] + D[c] x_t[c], one token at a time."""
    def token(s, at):
        x_t, dt_t, b_t, c_t = at
        s = jnp.exp(dt_t[..., None] * a) * s \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return s, jnp.einsum("bcn,bn->bc", s, c_t) + d * x_t
    _, ys = lax.scan(token, jnp.zeros(x.shape[:1] + a.shape, x.dtype),
                     tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1)


SCAN_ARGS = ("x", "dt", "a", "b", "c", "d")


@pytest.fixture(scope="module")
def scanned():
    """Operands of a selective scan (2 sequences of 50 tokens, 12 channels,
    state 4, a decay of its own a channel and state index), a cotangent, and
    the recurrence's output and gradients."""
    keys = jax.random.split(jax.random.key(0), 6)
    bsz, t, ch, n = 2, 50, 12, 4
    args = (jax.random.normal(keys[0], (bsz, t, ch)),
            jax.nn.softplus(jax.random.normal(keys[1], (bsz, t, ch)) - 1.0),
            -jnp.exp(jax.random.normal(keys[2], (ch, n))),
            jax.random.normal(keys[3], (bsz, t, n)),
            jax.random.normal(keys[4], (bsz, t, n)),
            jnp.linspace(0.5, 1.5, ch))
    w = jax.random.normal(keys[5], (bsz, t, ch))
    want = recurrence(*args)
    grads = jax.grad(lambda *a: jnp.sum(recurrence(*a) * w),
                     argnums=tuple(range(6)))(*args)
    return args, w, want, grads


@pytest.mark.parametrize("chunk, form", [
    (5, "chunked"), (10, "chunked"), (25, "chunked"), (50, "chunked"),
    (64, "chunked"), (16, "padded"), (7, "padded")])
def test_the_selective_scan_is_the_recurrence(scanned, chunk, form):
    """Values and all six gradients, float32, whatever the chunk: a divisor
    of the length, the length, more than the length (one chunk), and two
    that leave a last chunk to be filled up."""
    args, w, want, grads = scanned
    perfvars.reset()
    got = ssm.selective_scan(*args, chunk=chunk)
    assert perfvars.snapshot()["sel_scan_lowerings"][form] == 1
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    got_grads = jax.grad(
        lambda *a: jnp.sum(ssm.selective_scan(*a, chunk=chunk) * w),
        argnums=tuple(range(6)))(*args)
    for name, g, wg in zip(SCAN_ARGS, got_grads, grads):
        np.testing.assert_allclose(g, wg, rtol=1e-4, atol=1e-4, err_msg=name)
    perfvars.reset()


@pytest.mark.parametrize("who, ch, n", [("plain", 16, 4),
                                        ("kernel", 512, 16)])
def test_the_selective_scan_rounds_its_output_once(who, ch, n,
                                                   kernel_backend):
    """bfloat16 operands: the decays and the state stay float32, whoever
    computes the scan: the plain form, or the Pallas kernel pair on the
    interpret machine at a shape inside its contract (tier-1 is the only
    guard of that precision). Slow decays (dt x A of a few hundredths, so a
    state remembers the whole sequence): one rounding of y is 1.7e-3 of its
    norm; a state rounded to bfloat16 at every token reads 5.8e-3 to
    7.1e-3 here and a bfloat16 decay 3.6e-3 to 5.1e-3 (PERF.md section 6,
    PR 42: at PR 41's faster decays a bfloat16 state read 2.1e-3 and
    passed)."""
    keys = jax.random.split(jax.random.key(1), 5)
    bsz, t = 1, 96
    x, b, c = (jax.random.normal(k, s).astype(jnp.bfloat16) for k, s in
               zip(keys, ((bsz, t, ch), (bsz, t, n), (bsz, t, n))))
    dt = jax.nn.softplus(jax.random.normal(keys[3], (bsz, t, ch)) - 1.0)
    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1) * 0.2 / n, (ch, n))
    d = jnp.ones((ch,))
    perfvars.reset()
    with kernel_backend("interpret" if who == "kernel" else None):
        # one jitted program, waited for (the interpret machine's rule)
        got = jax.block_until_ready(jax.jit(
            lambda *v: ssm.selective_scan(*v, chunk=32))(x, dt, a, b, c, d))
    assert perfvars.snapshot()["sel_scan_kernel_lowerings"][who] == 1
    perfvars.reset()
    assert got.dtype == jnp.bfloat16
    want = recurrence(*(v.astype(jnp.float32) for v in (x, dt, a, b, c, d)))
    assert off_by(got.astype(jnp.float32), want) < 2.5e-3    # one rounding


# -- LayerNorm with a bias ------------------------------------------------------

def test_layer_norm_takes_the_mean_off_and_adds_a_bias():
    x = jax.random.normal(jax.random.key(2), (3, 5, 16)) * 3.0 + 1.5
    scale = jnp.linspace(0.5, 1.5, 16)
    bias = jnp.linspace(-1.0, 1.0, 16)
    got = tf._layer_norm(x, scale, bias, 1e-5)
    xn = np.asarray(x, np.float64)
    want = (xn - xn.mean(-1, keepdims=True)) / np.sqrt(
        xn.var(-1, keepdims=True) + 1e-5) * np.asarray(scale) + np.asarray(bias)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the model's norm is this one where the configuration says so, and the
    # one it was where it does not
    leaves = {"n": scale, "n_b": bias}
    np.testing.assert_allclose(tf._norm(CFG, x, leaves, "n"), got)
    np.testing.assert_allclose(
        tf._norm(TransformerConfig(), x, leaves, "n"),
        tf._rms_norm(x, scale, 1e-6))


# -- differential attention against two plain softmaxes -------------------------

def two_softmaxes(cfg, layer, x, window, depth):
    """Differential attention as the model's paper writes it, head by head,
    in the published layout: query heads (2i, 2i + 1) are differential head
    i, which reads the key/value pair i // (pairs of queries a pair of keys)."""
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    relaid = ref.from_system({"embed": 0, "ln_f": 0, "ln_f_b": 0,
                              "layers": [layer]})["layers"][0]
    b, t, _ = x.shape
    y = tf._layer_norm(x, layer["ln1"], layer["ln1_b"], cfg.norm_eps)
    q = (y @ relaid["q_proj"] + relaid["q_bias"]).reshape(b, t, h, dh)
    k = (y @ layer["w_k"] + layer["b_k"]).reshape(b, t, hk, dh)
    v = (y @ layer["w_v"] + layer["b_v"]).reshape(b, t, hk, dh)
    rows, cols = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = cols <= rows
    if window:
        seen = seen & (rows - cols < window)
    start = 0.8 - 0.6 * np.exp(-0.3 * depth)
    lam = float(jnp.exp(jnp.sum(layer["lambda_q1"] * layer["lambda_k1"]))
                - jnp.exp(jnp.sum(layer["lambda_q2"] * layer["lambda_k2"]))) \
        + start

    def softmax(qh, kh):
        s = jnp.einsum("btd,bsd->bts", qh, kh) / np.sqrt(dh)
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    out = []
    for i in range(h // 2):
        j = i // ((h // 2) // (hk // 2))
        values = jnp.concatenate([v[:, :, 2 * j], v[:, :, 2 * j + 1]], -1)
        o = (softmax(q[:, :, 2 * i], k[:, :, 2 * j])
             - lam * softmax(q[:, :, 2 * i + 1], k[:, :, 2 * j + 1])) @ values
        o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps) \
            * layer["diff_norm"] * (1.0 - start)
        out.append(o)
    return jnp.concatenate(out, -1) @ layer["w_proj"] + layer["b_proj"]


@pytest.mark.parametrize("window", [0, 8, 3], ids=["full", "window-8",
                                                   "window-3"])
@pytest.mark.parametrize("heads", [(8, 4), (8, 8), (12, 2)],
                         ids=["2-pairs-to-1", "1-to-1", "3-pairs-to-1"])
def test_differential_attention_is_two_softmaxes_and_a_subtraction(window,
                                                                   heads):
    cfg = dataclasses.replace(CFG, n_heads=heads[0], n_kv_heads=heads[1],
                              d_head=4)
    layer = transformer_init(jax.random.key(3), cfg)["layers"][1]
    layer["diff_norm"] = jnp.linspace(0.5, 1.5, 8)
    x = jax.random.normal(jax.random.key(4), (2, T, 32))
    depth = 5.0
    with jax.default_matmul_precision("highest"):
        got, wrote = tf._diff_attn(cfg, layer, x, window=window,
                                   depth=jnp.float32(depth))
        want = two_softmaxes(cfg, layer, x, window, depth)
    assert off_by(got, want) < 1e-5
    k, v = wrote["kv"]          # as the attention read them
    assert k.shape == (2, heads[1], T, 4) and v.shape == (2, heads[1], T, 8)
    np.testing.assert_array_equal(v[:, 0], v[:, 1])     # a pair's values


def test_a_cross_layer_attends_over_the_keys_and_values_it_is_given():
    """Queries of its own, no `w_k`, `w_v`: with the writer's keys and
    values and the writer's other leaves it is the writer's attention."""
    params = transformer_init(jax.random.key(3), CFG)
    full, cross = params["layers"][5], params["layers"][7]
    assert "w_k" not in cross and "w_v" not in cross and "b_k" not in cross
    x = jax.random.normal(jax.random.key(4), (2, T, 32))
    want, wrote = tf._diff_attn(CFG, full, x, window=0,
                                depth=jnp.float32(5.0))
    as_cross = {k: v for k, v in full.items()
                if k not in ("w_k", "w_v", "b_k", "b_v")}
    got, again = tf._diff_attn(CFG, as_cross, x, window=0,
                               depth=jnp.float32(5.0), kv=wrote["kv"])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert again["kv"][0] is wrote["kv"][0]


def test_the_attention_kernel_takes_values_wider_than_its_scores_heads():
    """The fused kernel on the interpret machine with 64-wide scores and
    128-wide values and no second term, four query heads to two key/value
    heads, with and without a window: values and gradients against the plain
    path."""
    from tpu_mpi.parallel import ring
    from tpu_mpi.xla import pallas_kernels as pk
    keys = jax.random.split(jax.random.key(5), 4)
    q = jax.random.normal(keys[0], (1, 4, 128, 64))
    k = jax.random.normal(keys[1], (1, 2, 128, 64))
    v = jax.random.normal(keys[2], (1, 2, 128, 128))
    w = jax.random.normal(keys[3], (1, 4, 128, 128))
    assert pk.causal_attention_blocks(8192, 64, 0, 128) is not None

    def both(window):
        def kernel(q, k, v):
            return jnp.sum(pk.causal_attention(q, k, v, window=window,
                                               interpret=True) * w)

        def plain(q, k, v):
            return jnp.sum(ring.plain_attention(q, k, v, window) * w)
        with jax.default_matmul_precision("highest"):
            return (jax.value_and_grad(kernel, (0, 1, 2))(q, k, v),
                    jax.value_and_grad(plain, (0, 1, 2))(q, k, v))
    for window in (0, 40):
        got, want = jax.block_until_ready(jax.jit(
            both, static_argnums=0)(window))
        np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
        for g, wg in zip(got[1], want[1]):
            np.testing.assert_allclose(g, wg, rtol=2e-4, atol=2e-4)


# -- the model against the plain reference -------------------------------------

@pytest.fixture(scope="module")
def both():
    """Seeded weights and tokens at N = 8, the reference's loss, logits and
    gradient (whole, by `jax.grad` of its loss), and the program's."""
    params = transformer_init(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (2, T), 0, V)
    labels = jnp.roll(tokens, -1, axis=1)
    relaid = ref.from_system(params)
    tf._block_traced_once.cache_clear()
    with jax.default_matmul_precision("highest"):
        want_logits = jax.jit(lambda p: ref.forward(MODEL, p, tokens))(relaid)
        want_loss, want_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss_of(MODEL, p, tokens, labels)))(relaid)
        logits = jax.jit(lambda p: transformer_forward(CFG, p, tokens))(params)
        loss, grads = jax.jit(jax.value_and_grad(lambda p: tf._xent(
            transformer_forward(CFG, p, tokens), labels)))(params)
    tf._block_traced_once.cache_clear()
    return dict(params=params, tokens=tokens, labels=labels, relaid=relaid,
                logits=(logits, want_logits), loss=(loss, want_loss),
                grads=(ref.from_system(grads), want_grads))


def test_the_model_agrees_with_the_reference_in_loss_and_logits(both):
    assert abs(float(both["loss"][0]) - float(both["loss"][1])) < 1e-5
    assert off_by(*both["logits"]) < 1e-5


def leaves_of(tree):
    for name, leaf in tree.items():
        if name != "layers":
            yield (None, name), leaf
    for i, layer in enumerate(tree["layers"]):
        for name, leaf in layer.items():
            yield (i, name), leaf


def test_the_gradient_agrees_leaf_by_leaf_and_crosses_layers(both):
    """Every leaf's gradient, the reference's names; among them the leaves
    that only a side value's gradient reaches in full: the memory layer's
    (summed over the gated memory units) and the full layer's key and value
    projections (summed over the cross layers and itself)."""
    got, want = (dict(leaves_of(g)) for g in both["grads"])
    assert set(got) == set(want)
    for key, w in want.items():
        scale = float(jnp.max(jnp.abs(w)))
        if key[1] == "k_bias":      # a softmax does not see it: exactly 0
            assert scale < 1e-6 and float(jnp.max(jnp.abs(got[key]))) < 1e-6
            continue
        np.testing.assert_allclose(got[key], w, rtol=2e-4, atol=2e-4 * scale,
                                   err_msg=str(key))
    for key in ((4, "in_proj"), (4, "A_log"), (5, "k_proj"), (5, "v_proj")):
        assert float(jnp.max(jnp.abs(want[key]))) > 1e-5, key


def test_the_references_layerwise_gradient_is_its_whole_gradient(both):
    """`make_grads_from` (what the benchmark holds the step's update to)
    yields, a layer at a time with the side values' cotangents summed over
    their readers, what `jax.grad` of the whole loss gives."""
    want = dict(leaves_of(both["grads"][1]))
    seen = set()
    with jax.default_matmul_precision("highest"):
        for i, grads in ref.make_grads_from(MODEL)(
                both["relaid"], both["tokens"], both["labels"]):
            for name, g in grads.items():
                w = want[(i, name)]
                seen.add((i, name))
                if name == "k_bias":        # exactly 0 but for rounding
                    assert float(jnp.max(jnp.abs(g))) < 1e-6
                    continue
                np.testing.assert_allclose(
                    g, w, rtol=2e-4, atol=2e-4 * float(jnp.max(jnp.abs(w)))
                    + 1e-8, err_msg=f"{i} {name}")
    assert seen == set(want)


def test_the_first_update_agrees_leaf_by_leaf(both):
    """One step of the program's train step against before - lr x the
    reference's gradient."""
    mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1},
                         devices=jax.devices()[:1])
    tf._block_traced_once.cache_clear()
    with jax.default_matmul_precision("highest"):
        step, _specs = transformer_train_step(CFG, mesh, lr=LR)
        after, loss = step(both["params"], both["tokens"], both["labels"])
    tf._block_traced_once.cache_clear()
    assert abs(float(loss) - float(both["loss"][1])) < 1e-5
    before = dict(leaves_of(both["relaid"]))
    grads = dict(leaves_of(both["grads"][1]))
    for key, a in leaves_of(ref.from_system(after)):
        want = before[key] - LR * grads[key]
        moved = float(jnp.sum(jnp.square(want - before[key])))
        missed = float(jnp.sum(jnp.square(a - want)))
        assert missed <= 1e-6 * moved + 1e-12, key


# -- which layer's values the readers read --------------------------------------

def still(params, layer: int, leaves: tuple) -> dict:
    """`params` with layer ``layer`` taken off the stream: its out-projections
    (and their biases) zeroed, so what it computes reaches later layers
    through a side value or not at all."""
    out = dict(params, layers=list(params["layers"]))
    out["layers"][layer] = {
        k: jnp.zeros_like(v) if k in leaves + ("w_out",) else v
        for k, v in params["layers"][layer].items()}
    return out


def perturbed(params, layer: int, leaf: str, cols=slice(None)) -> dict:
    out = dict(params, layers=list(params["layers"]))
    moved = params["layers"][layer][leaf]
    moved = moved.at[..., cols].add(0.5 * jax.random.normal(
        jax.random.key(7), moved[..., cols].shape, moved.dtype))
    out["layers"][layer] = dict(params["layers"][layer], **{leaf: moved})
    return out


@pytest.fixture(scope="module")
def forward():
    tokens = jax.random.randint(jax.random.key(1), (2, T), 0, V)
    tf._block_traced_once.cache_clear()
    yield jax.jit(lambda p: transformer_forward(CFG, p, tokens))
    tf._block_traced_once.cache_clear()


def test_the_memory_is_layer_n_half_s_scan_output_before_its_gate(forward):
    """Layers 0, 2 and 4 are mamba layers; taken off the stream, a change of
    layer 2's scan moves nothing (no one reads ITS output), a change of layer
    4's moves the logits (the gated memory unit reads it), and a change of
    layer 4's gate z alone moves nothing: the memory is taken before it."""
    params = transformer_init(jax.random.key(0), CFG)
    inner = CFG.mamba_inner
    quiet = still(still(params, 2, ("w_ssm_out",)), 4, ("w_ssm_out",))
    base = forward(quiet)
    other = forward(perturbed(quiet, 2, "conv_b"))
    np.testing.assert_array_equal(other, base)
    gate = forward(perturbed(quiet, 4, "w_ssm_in", slice(inner, None)))
    np.testing.assert_array_equal(gate, base)
    scan = forward(perturbed(quiet, 4, "conv_b"))
    assert off_by(scan, base) > 1e-3


def test_cross_attention_reads_layer_n_half_plus_one_s_keys_and_values(
        forward):
    """Layers 1 and 3 are window layers, 5 the full one; taken off the stream,
    a change of a window layer's keys and values moves nothing, a change of
    layer 5's moves the logits through the cross layer."""
    params = transformer_init(jax.random.key(0), CFG)
    off = ("w_proj", "b_proj")
    quiet = still(still(still(params, 1, off), 3, off), 5, off)
    base = forward(quiet)
    for layer in (1, 3):
        for leaf in ("w_k", "w_v", "b_v"):
            np.testing.assert_array_equal(
                forward(perturbed(quiet, layer, leaf)), base)
    for leaf in ("w_k", "w_v", "b_v"):
        assert off_by(forward(perturbed(quiet, 5, leaf)), base) > 1e-4, leaf


# -- one trace a kind, the counters, and what is refused ------------------------

def test_sixteen_layers_are_five_traces_and_counted():
    """N = 16 (5 mamba, 4 window, 1 full, 3 gated memory units, 3 cross):
    five kinds, one trace of the block each; the counters say which kinds,
    how the scan and the attention were lowered, and how many layers read
    each side value; the named scopes cover each mixer."""
    cfg = dataclasses.replace(CFG, remat_layers=(), **layout(16))
    perfvars.reset()
    tf._block_traced_once.cache_clear()
    params = transformer_init(jax.random.key(0), cfg)
    tokens = jnp.zeros((1, T), jnp.int32)
    text = jax.jit(lambda p: transformer_forward(cfg, p, tokens)).lower(
        params).compile().as_text()
    assert tf._block_traced_once.cache_info().currsize == 5
    snap = perfvars.snapshot()
    assert snap["mixer_kinds"] == {"attention": 2, "ssm": 0, "mamba": 1,
                                   "gmu": 1, "cross": 1}
    assert snap["sel_scan_lowerings"] == {"chunked": 1, "padded": 0}
    assert snap["side_values"] == {"memory": 3, "kv": 3}
    assert snap["attn_kinds"] == {"diff": "plain", "full": "plain",
                                  "window": "plain"}
    assert snap["attn_lowerings"] == {"fused": 0, "plain": 3}
    assert snap["scan_lowerings"] == {"chunked": 0, "padded": 0}
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("in_proj", "conv", "x_proj", "scan", "gate", "out_proj"):
        assert [n for n in names if "layer_8" in n
                and f"/mixer/{scope}/" in n], scope
    assert [n for n in names if "layer_10" in n and "/mixer/gmu/" in n]
    for i in (1, 9, 11):
        assert [n for n in names if f"layer_{i}/" in n.replace(")", "")
                and "/attn/diff/" in n], i
    assert not [n for n in names if "layer_0/" in n.replace(")", "")
                and "/attn/" in n]
    tf._block_traced_once.cache_clear()
    perfvars.reset()
    assert perfvars.snapshot()["mixer_kinds"] == {"attention": 0, "ssm": 0}
    assert perfvars.snapshot()["side_values"] == {"memory": 0, "kv": 0}
    assert perfvars.snapshot()["sel_scan_lowerings"] == {"chunked": 0,
                                                         "padded": 0}


def test_a_mamba_layers_conv_scope_holds_the_kernel_where_selected(
        kernel_backend):
    """Wide enough for the contract (the first 128 of the in-projection's
    256 columns, 128 tokens), the traced gradient holds `conv_silu_fwd` and
    `conv_silu_bwd` under `mixer/conv` and no pad or shifted-slice chain;
    on the CPU the chain and no kernel."""
    from test_conv_kernel import check_the_conv_scope
    cfg = dataclasses.replace(CFG, d_model=64, max_seq=128, remat_layers=())
    assert cfg.mamba_inner == 128
    check_the_conv_scope(cfg, kernel_backend)


def test_a_model_without_the_new_kinds_traces_what_it_traced():
    """The flagship and a grouped-query stack, their new fields at their
    defaults or named: the same jaxpr, equation for equation, and the same
    leaves; each new field adds its equations when set."""
    base = TransformerConfig(vocab=V, d_model=64, n_heads=8, n_layers=2,
                             d_ff=96, max_seq=T, dtype=jnp.float32,
                             n_kv_heads=4)
    named = dataclasses.replace(
        base, mixer_kinds=["attention"] * 2, norm_kind="rms", diff_attn=False,
        attn_bias=False, memory_from=-1, kv_from=-1)
    params = transformer_init(jax.random.key(0), base)
    tokens = jnp.zeros((1, T), jnp.int32)

    def jaxpr(cfg, params=params):
        tf._block_traced_once.cache_clear()
        try:
            return str(jax.make_jaxpr(
                lambda p: transformer_forward(cfg, p, tokens))(params))
        finally:
            tf._block_traced_once.cache_clear()
    want = jaxpr(base)
    assert jaxpr(named) == want
    assert jax.tree.structure(transformer_init(jax.random.key(0), named)) \
        == jax.tree.structure(params)
    for fields in (dict(norm_kind="layer"), dict(diff_attn=True),
                   dict(diff_attn=True, attn_bias=True)):
        cfg = dataclasses.replace(base, **fields)
        assert jaxpr(cfg, transformer_init(jax.random.key(0), cfg)) != want


def test_a_program_without_the_new_kinds_never_imports_the_scan():
    """Set-up of the other programs pays nothing for the new layer kinds: a
    fresh process that traces the flagship's forward pass has not imported
    `tpu_mpi.parallel.ssm`, where the selective scan lives; one that traces a
    mamba layer has."""
    code = """
import sys, jax, jax.numpy as jnp
sys.path.insert(0, {root!r})
from tpu_mpi.models.transformer import (TransformerConfig, transformer_init,
                                        transformer_forward)
cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                        max_seq=16, dtype=jnp.float32, {extra})
params = jax.eval_shape(lambda k: transformer_init(k, cfg), jax.random.key(0))
jax.eval_shape(lambda p: transformer_forward(cfg, p, jnp.zeros((1, 16),
               jnp.int32)), params)
print("tpu_mpi.parallel.ssm" in sys.modules)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for extra, want in (("", "False"), (
            "mixer_kinds=['mamba', 'gmu'], memory_from=0, ssm_state=4, "
            "ssm_dt_rank=2, ssm_chunk=8", "True")):
        out = subprocess.run(
            [sys.executable, "-c", code.format(root=ROOT, extra=extra)],
            env=env, capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().splitlines()[-1] == want, extra


@pytest.mark.parametrize("axes", [{"dp": 1, "tp": 2, "sp": 1},
                                  {"dp": 1, "tp": 1, "sp": 2},
                                  {"dp": 2, "tp": 2, "sp": 2}])
def test_the_step_refuses_the_stack_under_tp_or_sp(axes):
    n = axes["dp"] * axes["tp"] * axes["sp"]
    mesh = xla.make_mesh(axes, devices=jax.devices()[:n])
    with pytest.raises(NotImplementedError, match="tp 1 and sp 1"):
        transformer_train_step(CFG, mesh, lr=LR)


def test_the_step_takes_the_stack_under_dp():
    """Two sequences over two ranks of `dp`: the loss is the mean of the
    two, as on one rank."""
    mesh = xla.make_mesh({"dp": 2, "tp": 1, "sp": 1},
                         devices=jax.devices()[:2])
    step, _specs = transformer_train_step(CFG, mesh, lr=LR)
    one = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1},
                        devices=jax.devices()[:1])
    step_one, _specs = transformer_train_step(CFG, one, lr=LR)
    params = transformer_init(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (2, T), 0, V)
    labels = jnp.roll(tokens, -1, axis=1)
    _, loss = step(params, tokens, labels)
    _, want = step_one(params, tokens, labels)
    assert abs(float(loss) - float(want)) < 1e-5


def moved(field: str, layer: int, value) -> dict:
    values = list(getattr(CFG, field))
    values[layer] = value
    return {field: values}


@pytest.mark.parametrize("fields, match", [
    (dict(n_heads=6, n_kv_heads=3), "even"),
    (dict(n_kv_heads=0), "even"),
    (dict(qk_norm_heads=True), "no norm of q and k"),
    (dict(ssm_dt_rank=0), "ssm_dt_rank"),
    (dict(ssm_state=0), "ssm_state"),
    (dict(memory_from=-1), "'gmu' layer reads"),
    (dict(memory_from=6), "'gmu' layer reads"),        # not before its reader
    (dict(memory_from=1), "'mamba' layer"),             # an attention layer
    (dict(kv_from=-1), "'cross' layer reads"),
    (dict(kv_from=4), "'attention' layer"),
    (dict(kv_from=3), "full"),                          # a window layer
    (dict(diff_attn=False, attn_bias=False), "differential attention over"),
    (dict(diff_attn=False), "attn_bias"),       # biases nothing would add
    (moved("mixer_kinds", 0, "mamba2"), "mixer"),
    (dict(norm_kind="batch"), "norm_kind"),
])
def test_what_the_configuration_refuses(fields, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **fields)


@pytest.mark.parametrize("n", [2, 6, 10, 18])
def test_the_reference_refuses_a_depth_that_is_no_multiple_of_four(n):
    with pytest.raises(ValueError, match="multiple of 4"):
        ref.kinds(dict(MODEL, num_hidden_layers=n))


def test_the_models_rule_gives_the_published_counts():
    """At N = 32: 9 mamba (the memory layer among them), 8 window, 1 full, 7
    gated memory units, 7 cross; at N = 16: 5 / 4 / 1 / 3 / 3; the program's
    fields say the same layers."""
    for n, counts in ((32, (9, 8, 1, 7, 7)), (16, (5, 4, 1, 3, 3))):
        kinds = ref.kinds(dict(MODEL, num_hidden_layers=n))
        assert (kinds.count("mamba") + kinds.count("memory"),
                kinds.count("window"), kinds.count("full"),
                kinds.count("gmu"), kinds.count("cross")) == counts
        fields = layout(n)
        assert kinds.index("memory") == fields["memory_from"] == n // 2
        assert kinds.index("full") == fields["kv_from"] == n // 2 + 1
        for kind, mixer, window in zip(kinds, fields["mixer_kinds"],
                                       fields["attn_windows"]):
            assert mixer == {"memory": "mamba", "window": "attention",
                             "full": "attention"}.get(kind, kind)
            assert bool(window) == (kind == "window")


def test_the_new_layers_leaves_and_the_others_defaults():
    """A mamba layer has the mixer's nine leaves in attention's place (the
    recurrence's three in float32), a gated memory unit two, a cross layer no
    key or value projection; every layer two LayerNorms with a bias; the
    published widths give the published count."""
    params = transformer_init(jax.random.key(0), dataclasses.replace(
        CFG, dtype=jnp.bfloat16))
    mamba, window, gmu, cross = (params["layers"][i] for i in (0, 1, 6, 7))
    norms = ["ln1", "ln1_b", "ln2", "ln2_b", "w_in", "w_gate", "w_out"]
    diff = ["lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "diff_norm"]
    assert sorted(mamba) == sorted(norms + [
        "w_ssm_in", "conv_w", "conv_b", "w_ssm_x", "w_ssm_dt", "dt_bias",
        "a_log", "d_skip", "w_ssm_out"])
    assert sorted(gmu) == sorted(norms + ["w_gmu_in", "w_gmu_out"])
    assert sorted(window) == sorted(norms + diff + [
        "w_q", "w_k", "w_v", "w_proj", "b_q", "b_k", "b_v", "b_proj"])
    assert sorted(cross) == sorted(norms + diff + [
        "w_q", "w_proj", "b_q", "b_proj"])
    assert mamba["w_ssm_in"].shape == (32, 128)
    assert mamba["w_ssm_x"].shape == (64, 2 + 2 * 4)
    assert mamba["w_ssm_dt"].shape == (2, 64)
    assert mamba["a_log"].shape == (64, 4) and mamba["conv_w"].shape == (4, 64)
    for name in ("dt_bias", "a_log", "d_skip"):
        assert mamba[name].dtype == jnp.float32
    np.testing.assert_allclose(np.exp(np.asarray(mamba["a_log"])),
                               np.broadcast_to(np.arange(1, 5), (64, 4)),
                               rtol=1e-6)
    dt = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 1e-1 * 1.001).all()
    assert float(jnp.max(jnp.abs(mamba["w_ssm_dt"].astype(jnp.float32)))) \
        <= 2 ** -0.5
    assert "ln_f_b" in params
    specs = tf.transformer_param_specs(CFG, "tp")
    assert jax.tree.structure(specs) == jax.tree.structure(
        jax.tree.map(lambda a: 0, params))
    layer = transformer_init(jax.random.key(0),
                             TransformerConfig())["layers"][0]
    assert sorted(layer) == ["ln1", "ln2", "w_in", "w_out", "w_proj", "w_qkv"]
    published = TransformerConfig(
        vocab=66688, d_model=2560, n_heads=40, n_kv_heads=20, d_head=64,
        d_ff=10240, dtype=jnp.bfloat16, dense_gated=True, diff_attn=True,
        attn_bias=True, norm_kind="layer", ssm_state=16,
        ssm_dt_rank=160, ssm_chunk=64, **layout(16, 512))
    shapes = jax.eval_shape(lambda k: transformer_init(k, published),
                            jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 1_851_715_072
