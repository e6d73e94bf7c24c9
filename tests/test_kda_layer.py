"""A delta rule whose decay is a vector, one number a key channel (KDA), and
latent attention without positions, through the program's normal path: the
chunked scan of `parallel.delta` with a decay a channel against the
recurrence one token at a time, values and every gradient, at a multiple of
the chunk and padded, at decays under which the naive factoring of a chunk
overflows, against the scalar path where every channel decays alike, and its
float32 inside bfloat16 operands; a KDA layer's first half against the
layer's equations written out here, and against each way of getting them
wrong; the latent layer likewise; the expert layer's shares at the model's
router adding up to the uncut layer with the shared expert counted once; the
model at two periods against the plain reference
(yardstick/reference/lm_kda_train_step.py) on seeded random weights,
float32: loss, logits, and the update leaf by leaf; what the configuration
and the step refuse; the counters."""

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

from tpu_mpi import perfvars, xla                               # noqa: E402
from tpu_mpi.models import transformer as tf                    # noqa: E402
from tpu_mpi.models.transformer import (TransformerConfig,      # noqa: E402
                                        transformer_forward,
                                        transformer_init,
                                        transformer_train_step)
from tpu_mpi.parallel import delta                              # noqa: E402
from yardstick.reference import lm_kda_train_step as ref        # noqa: E402

V, T, LR, D = 96, 32, 0.05, 32
H, DH, RANK = 4, 8, 8               # KDA heads, their width, the maps' rank
NOPE, ROPE, CKV = 8, 4, 16          # latent attention's widths

CFG = TransformerConfig(
    vocab=V, d_model=D, n_heads=4, d_head=NOPE, d_rope=ROPE, d_value=8,
    kv_latent=CKV, rope_full_layers=False, d_ff=16, n_layers=8, max_seq=T,
    dtype=jnp.float32, norm_eps=1e-5, tie_embeddings=False,
    n_experts=16, experts_per_tok=4, router_score="sigmoid",
    router_renorm=True, router_scale=2.446, n_shared_experts=1,
    experts_held=(4, 4), ffn_kinds=("dense",) + ("sparse",) * 7,
    d_ff_dense=48, dense_gated=True,
    mixer_kinds=("kda", "kda", "kda", "attention") * 2,
    remat_layers=("ffn", "", "", "ffn", "", "", "", ""),
    gdn_key_heads=H, gdn_key_dim=DH, gdn_value_heads=H, gdn_value_dim=DH,
    gdn_conv=4, gdn_chunk=8, kda_rank=RANK)
MODEL = dict(
    hidden_size=D, num_hidden_layers=8, rms_norm_eps=1e-5,
    linear_attn_config=dict(num_heads=H, head_dim=DH, short_conv_kernel_size=4,
                            kda_layers=[1, 2, 3, 5, 6, 7],
                            full_attn_layers=[4, 8]),
    num_attention_heads=4, kv_lora_rank=CKV, qk_nope_head_dim=NOPE,
    qk_rope_head_dim=ROPE, v_head_dim=8, mla_use_nope=True, q_lora_rank=None,
    first_k_dense_replace=1, moe_layer_freq=1, num_experts_per_token=4,
    moe_router_activation_func="sigmoid", moe_renormalize=True,
    routed_scaling_factor=2.446, num_expert_group=1, topk_group=1,
    router_num_experts=16, held_experts_first=4, num_experts=4,
    vocab_size=V)


def off_by(got, want) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(got - want))
                          / jnp.sum(jnp.square(want))))


# -- the chunked scan with a decay a channel, against the recurrence ------------

def scan_inputs(t: int, dtype=jnp.float32, strong: bool = False,
                hk: int = H):
    keys = jax.random.split(jax.random.key(3), 5)
    q, k = (tf._l2_normed(jax.random.normal(key, (2, t, hk, DH)))
            for key in keys[:2])
    v = jax.random.normal(keys[2], (2, t, H, DH))
    g = -jax.random.uniform(keys[3], (2, t, H, DH)) * 2.0
    if strong:      # half the channels lose 5 a token: -320 over a chunk of 64
        g = g.at[..., ::2].set(-5.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (2, t, H)))
    return tuple(a.astype(dtype) for a in (q * DH ** -0.5, k, v)) \
        + (g.astype(jnp.float32), beta.astype(jnp.float32))


def values_and_grads(scan, args, weigh):
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(scan(*a) * weigh), argnums=range(5)))(*args)


@pytest.mark.parametrize("t, chunk, form", [
    (32, 8, "chunked"), (64, 16, "chunked"), (64, 64, "chunked"),
    (128, 32, "chunked"), (20, 8, "padded"), (70, 64, "padded"),
    (5, 16, "padded")])
def test_the_chunked_scan_with_a_vector_decay_is_the_recurrence(
        t, chunk, form):
    """Values and the gradient of each of the five operands, float32, the
    decay's among them a number a channel; the form and the decay's kind
    are counted where they are chosen, and the scan is the plain path's
    (off a kernel backend; `test_with_the_tests_word_..` has the kernels')."""
    args = scan_inputs(t)
    weigh = jax.random.normal(jax.random.key(4), (2, t, H, DH))
    perfvars.reset()
    got, got_grads = values_and_grads(
        lambda *a: delta.delta_scan(*a, chunk), args, weigh)
    snap = perfvars.snapshot()
    assert snap["delta_lowerings"][form] == 1 == sum(
        snap["delta_lowerings"].values())
    assert snap["delta_decays"] == {"head": 0, "channel": 1}
    assert snap["delta_kernel_lowerings"] == {"kernel": 0, "plain": 1}
    perfvars.reset()
    want, want_grads = values_and_grads(delta.delta_recurrence, args, weigh)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    np.testing.assert_allclose(
        jax.jit(lambda *a: delta.delta_scan(*a, chunk))(*args),
        jax.jit(delta.delta_recurrence)(*args), atol=2e-6)
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("t, form", [(128, "chunked"), (70, "padded")])
def test_with_the_tests_word_for_a_kernel_backend_the_scan_is_the_kernels(
        t, form, kernel_backend):
    """The same scan at the kernels' shape (heads of 128 in twos, chunks of
    64) with the tests' word for a kernel backend: counted `kernel`, and the
    recurrence's values and gradients all the same."""
    keys = jax.random.split(jax.random.key(3), 6)
    wide = (1, t, 2, 128)
    q, k = (tf._l2_normed(jax.random.normal(key, wide)) for key in keys[:2])
    args = tuple(a.astype(jnp.float32) for a in (
        q * 128 ** -0.5, k, jax.random.normal(keys[2], wide),
        -jax.random.uniform(keys[3], wide) * 2.0,
        jax.nn.sigmoid(jax.random.normal(keys[4], wide[:3]))))
    weigh = jax.random.normal(keys[5], wide).astype(jnp.float32)
    perfvars.reset()
    with kernel_backend("interpret"):
        got, got_grads = jax.block_until_ready(values_and_grads(
            lambda *a: delta.delta_scan(*a, 64), args, weigh))
    snap = perfvars.snapshot()
    assert snap["delta_lowerings"][form] == 1 == sum(
        snap["delta_lowerings"].values())
    assert snap["delta_decays"] == {"head": 0, "channel": 1}
    assert snap["delta_kernel_lowerings"] == {"kernel": 1, "plain": 0}
    perfvars.reset()
    want, want_grads = values_and_grads(delta.delta_recurrence, args, weigh)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("chunk", [16, 64])
def test_strong_decays_stay_finite_and_equal(chunk):
    """g = -5 a token on half the channels: the decay sums reach -320 inside
    a chunk of 64, where (k_i o exp(gamma_i)) . (k_j o exp(-gamma_j)) is
    inf x 0 in float32. No exponential of a positive number is formed, so
    values and every gradient are finite and the recurrence's."""
    args = scan_inputs(128, strong=True)
    gamma = jnp.cumsum(args[3][:, :64], axis=1)
    assert not jnp.isfinite(jnp.exp(-gamma)).all()      # the naive factor
    weigh = jax.random.normal(jax.random.key(4), (2, 128, H, DH))
    got, got_grads = values_and_grads(
        lambda *a: delta.delta_scan(*a, chunk), args, weigh)
    want, want_grads = values_and_grads(delta.delta_recurrence, args, weigh)
    assert jnp.isfinite(got)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        assert jnp.isfinite(a).all(), name
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)


def test_the_result_does_not_depend_on_the_chunk():
    args = scan_inputs(128, strong=True)
    outs = [delta.delta_scan(*args, chunk) for chunk in (8, 16, 32, 64, 128)]
    for other in outs[1:]:
        np.testing.assert_allclose(other, outs[0], atol=3e-6)


@pytest.mark.parametrize("hk", [H, H // 2])
def test_one_decay_in_every_channel_is_the_scalar_path(hk):
    """With g the same number in all of a head's channels the recurrence is
    today's (one module: the decay's rank is data), values and gradients,
    with as many key heads as value heads and with fewer."""
    q, k, v, g, beta = scan_inputs(40, hk=hk)
    alike = jnp.broadcast_to(g[..., :1], g.shape)
    weigh = jax.random.normal(jax.random.key(4), v.shape)
    got, got_grads = values_and_grads(
        lambda *a: delta.delta_scan(*a, 16), (q, k, v, alike, beta), weigh)
    want, want_grads = values_and_grads(
        lambda *a: delta.delta_scan(*a, 16), (q, k, v, g[..., 0], beta),
        weigh)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        if name == "g":         # a channel's share sums to the head's
            a = a.sum(-1)
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
    np.testing.assert_allclose(
        delta.delta_recurrence(q, k, v, alike, beta),
        delta.delta_recurrence(q, k, v, g[..., 0], beta), atol=1e-6)


def test_the_backward_pass_runs_the_state_chain_once_each_way():
    """Two `scan`s in the gradient's program, the forward chain and the
    backward one, with a decay a channel as with one a head: the carry is a
    vector and its cotangent a sum over the value axis alone."""
    args = scan_inputs(64)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(delta.delta_scan(*a, 16)), argnums=range(5)))(
            *args))
    assert text.count("scan[") == 2, text.count("scan[")


def rounding_inputs(t: int, decay: float, write: float):
    """bfloat16 operands whose decays are at most ``decay`` a token and whose
    write strengths lie around sigmoid(``write``)."""
    q, k, v, g, beta = scan_inputs(t, jnp.bfloat16)
    return q, k, v, g * (decay / 2.0), jax.nn.sigmoid(
        jax.scipy.special.logit(beta) + write)


@pytest.mark.parametrize("broken, t, chunk, decay, write", [
    ("state", 1024, 8, 0.0, -5.5),      # 128 chunks of faint writes, kept
    ("decay", 96, 32, 2.0, 0.0),        # a chunk's decays sum to dozens
])
def test_the_state_and_the_decay_sums_are_float32_under_bfloat16(
        broken, t, chunk, decay, write, monkeypatch):
    """bfloat16 operands: the decay sums and the state stay float32, the
    products' operands are rounded and the output once, and the output is
    within half a percent (rms) of the float32 recurrence; with the state
    after each chunk, or a chunk's summed decays, rounded to bfloat16 it
    reads twice that and more."""
    def rounded(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    args = rounding_inputs(t, decay, write)
    want = delta.delta_recurrence(*args)
    jax.clear_caches()      # `jax.checkpoint` keeps `_chunked`'s trace
    got = delta.delta_scan(*args, chunk)
    assert got.dtype == jnp.bfloat16
    sound = off_by(got.astype(jnp.float32), want)
    if broken == "state":
        chain = delta._chain_step
        monkeypatch.setattr(delta, "_chain_step", lambda s, at, dtype: tuple(
            map(rounded, chain(s, at, dtype))))
    else:
        cumsum = jnp.cumsum
        monkeypatch.setattr(delta.jnp, "cumsum",
                            lambda *a, **k: rounded(cumsum(*a, **k)))
    jax.clear_caches()
    read = off_by(delta.delta_scan(*args, chunk).astype(jnp.float32), want)
    jax.clear_caches()
    assert sound < 5e-3 and read > 2 * sound, (sound, read)


def test_the_decays_exponentials_and_the_system_are_float32_under_bfloat16():
    """In the program of the scan under bfloat16 operands every exponential
    and every cumulative sum is float32, and so is the inverse's input."""
    args = rounding_inputs(64, 1.0, 0.0)
    text = str(jax.make_jaxpr(lambda *a: delta._chunked.__wrapped__(*a, 32))(
        *args))
    exps = [line for line in text.splitlines() if " exp " in line]
    assert exps and all(":f32[" in line.split("=")[0] for line in exps)
    sums = [line for line in text.splitlines() if "= cumsum[" in line]
    assert sums and all(":f32[" in line.split("=")[0] for line in sums)


# -- a layer's halves against their equations ------------------------------------

def silu(x):
    return x / (1.0 + np.exp(-x))


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def rms(x, scale, eps=1e-5):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * scale


KDA_SLIPS = [
    "the decay averaged to one number a head", "dt_bias dropped",
    "A_log read a channel", "a silu gate", "gate before norm",
    "norm over the whole width", "beta left out of the correction",
    "no l2 norm", "q unscaled"]


def plain_kda_half(lp: dict, x, wrong=None):
    """ISSUE 48's KDA layer, float64 numpy, a token at a time, from the
    program's own leaves ([q | k | v], [f | g | b]); ``wrong`` names one way
    of getting it wrong."""
    lp = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    x = np.asarray(x, np.float64)
    b, t, _ = x.shape
    wide = H * DH
    y = rms(x, lp["ln1"])
    qkv = y @ lp["w_kda_in"]
    f_in, g_in, beta = np.split(y @ lp["w_kda_low"], [RANK, 2 * RANK], -1)
    padded = np.pad(qkv, ((0, 0), (3, 0), (0, 0)))
    qkv = silu(sum(padded[:, j:j + t] * lp["conv_w"][j] for j in range(4)))
    q, k, v = (part.reshape(b, t, H, DH)
               for part in np.split(qkv, [wide, 2 * wide], axis=-1))
    if wrong != "no l2 norm":
        q, k = (part / np.sqrt(np.sum(part * part, -1, keepdims=True) + 1e-6)
                for part in (q, k))
    if wrong != "q unscaled":
        q = q * DH ** -0.5
    beta = sigmoid(beta)
    bias = 0.0 if wrong == "dt_bias dropped" else lp["dt_bias"]
    rate = np.exp(lp["a_log"])[:, None]             # one a head
    if wrong == "A_log read a channel":             # channel c reads head c % H
        rate = np.exp(np.tile(lp["a_log"], DH)).reshape(H, DH)
    g = -rate * np.log1p(np.exp(f_in @ lp["w_kda_f"] + bias)).reshape(
        b, t, H, DH)
    if wrong == "the decay averaged to one number a head":
        g = np.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    o = np.zeros((b, t, H, DH))
    for h in range(H):
        s = np.zeros((b, DH, DH))
        for i in range(t):
            s = s * np.exp(g[:, i, h])[:, :, None]      # Diag(exp g) S
            told = np.einsum("bkv,bk->bv", s, k[:, i, h])
            if wrong != "beta left out of the correction":
                write = beta[:, i, h, None] * (v[:, i, h] - told)
            else:
                write = beta[:, i, h, None] * v[:, i, h] - told
            s = s + k[:, i, h, :, None] * write[:, None, :]
            o[:, i, h] = np.einsum("bkv,bk->bv", s, q[:, i, h])
    gate = (g_in @ lp["w_kda_g"]).reshape(b, t, H, DH)
    gate = silu(gate) if wrong == "a silu gate" else sigmoid(gate)
    if wrong == "gate before norm":
        o = rms(o * gate, lp["kda_norm"])
    elif wrong == "norm over the whole width":
        o = rms(o.reshape(b, t, wide), np.tile(lp["kda_norm"], H)).reshape(
            o.shape) * gate
    else:
        o = rms(o, lp["kda_norm"]) * gate
    return o.reshape(b, t, wide) @ lp["w_kda_out"]


@pytest.fixture(scope="module")
def halves():
    """(layer 1's leaves (KDA before experts), layer 3's (latent attention),
    a stream)."""
    params = transformer_init(jax.random.key(5), CFG)
    # norm scales away from one, so that where a norm stands shows
    lp = dict(params["layers"][1])
    lp["kda_norm"] = 1.0 + 0.3 * jax.random.normal(jax.random.key(6), (DH,))
    lp["ln1"] = 1.0 + 0.3 * jax.random.normal(jax.random.key(9), (D,))
    attn = dict(params["layers"][3])
    attn["kv_latent_norm"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.key(10), (CKV,))
    x = jax.random.normal(jax.random.key(7), (2, T, D))
    return lp, attn, x


@pytest.mark.parametrize("wrong", [None] + KDA_SLIPS)
def test_a_kda_layers_first_half_is_its_equations(halves, wrong):
    lp, _attn, x = halves
    got = tf._kda_mixer(CFG, lp, x, tp_axis=None, sp_axis=None)
    read = off_by(np.asarray(got, np.float64), plain_kda_half(lp, x, wrong))
    assert (read < 1e-4) == (wrong is None), read


def named_layer(lp: dict) -> dict:
    return ref.from_system({"embed": 0, "ln_f": 0, "lm_head": 0,
                            "layers": [lp]}, MODEL)["layers"][0]


def test_the_references_kda_layer_is_the_same_equations(halves):
    lp, _attn, x = halves
    got = ref.kda_segment(MODEL, named_layer(lp),
                          ref.kda_start(MODEL, 2, x.dtype), x)[1] - x
    assert off_by(np.asarray(got, np.float64), plain_kda_half(lp, x)) < 1e-4


def test_the_references_segments_carry_the_state_and_the_taps(halves):
    """A KDA layer a segment at a time is the layer at once."""
    lp, _attn, x = halves
    named = named_layer(lp)
    whole = ref.kda_segment(MODEL, named, ref.kda_start(MODEL, 2, x.dtype),
                            x)[1]
    carry, parts = ref.kda_start(MODEL, 2, x.dtype), []
    for i in range(0, T, 8):
        carry, out = ref.kda_segment(MODEL, named, carry, x[:, i:i + 8])
        parts.append(out)
    np.testing.assert_allclose(jnp.concatenate(parts, axis=1), whole,
                               atol=1e-5)


LATENT_SLIPS = ["RoPE applied", "scaled by nope^-0.5", "a k_pe a head",
                "the latent un-normed"]


def plain_latent_half(lp: dict, x, wrong=None):
    """ISSUE 48's latent attention layer, float64 numpy, from the program's
    own leaves: one query product, ONE shared `k_pe` a token, no rotation."""
    lp = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    x = np.asarray(x, np.float64)
    b, t, _ = x.shape
    nh, dv = 4, 8
    y = rms(x, lp["ln1"])
    q = (y @ lp["w_q"]).reshape(b, t, nh, NOPE + ROPE)
    down = y @ lp["w_dkv"]
    c, k_pe = down[..., :CKV], down[..., CKV:]
    if wrong != "the latent un-normed":
        c = rms(c, lp["kv_latent_norm"])
    kv = (c @ lp["w_ukv"]).reshape(b, t, nh, NOPE + dv)

    def rope(a):        # [b, t, width]: halves rotated
        half = a.shape[-1] // 2
        ang = np.arange(t)[:, None] / 10000.0 ** (np.arange(half) / half)
        a1, a2 = a[..., :half], a[..., half:]
        return np.concatenate([a1 * np.cos(ang) - a2 * np.sin(ang),
                               a1 * np.sin(ang) + a2 * np.cos(ang)], axis=-1)
    scale = NOPE ** -0.5 if wrong == "scaled by nope^-0.5" \
        else (NOPE + ROPE) ** -0.5
    o = np.zeros((b, t, nh, dv))
    for h in range(nh):
        q_pe, key_pe = q[:, :, h, NOPE:], k_pe
        if wrong == "a k_pe a head":    # head h reads a turn of the shared one
            key_pe = np.roll(k_pe, h, axis=-1)
        if wrong == "RoPE applied":
            q_pe, key_pe = rope(q_pe), rope(key_pe)
        s = (np.einsum("bqd,bkd->bqk", q[:, :, h, :NOPE], kv[:, :, h, :NOPE])
             + np.einsum("bqd,bkd->bqk", q_pe, key_pe)) * scale
        s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        o[:, :, h] = np.einsum("bqk,bkd->bqd", p / p.sum(-1, keepdims=True),
                               kv[:, :, h, NOPE:])
    return o.reshape(b, t, nh * dv) @ lp["w_proj"]


@pytest.mark.parametrize("wrong", [None] + LATENT_SLIPS)
def test_the_latent_layers_first_half_is_its_equations(halves, wrong):
    _kda, lp, x = halves
    got = tf._attn(CFG, lp, x, jnp.arange(T), h_local=4, tp_axis=None,
                   sp_axis=None)
    read = off_by(np.asarray(got, np.float64), plain_latent_half(lp, x, wrong))
    assert (read < 1e-4) == (wrong is None), read


def test_the_references_latent_layer_is_the_same_equations(halves):
    _kda, lp, x = halves
    named = named_layer(lp)
    y = ref.rms_norm(x, named["input_layernorm"], 1e-5)
    got = ref.attention(MODEL, named, y) @ named["o_proj"]
    assert off_by(np.asarray(got, np.float64), plain_latent_half(lp, x)) < 1e-4


@pytest.mark.parametrize("held", [4, 2])
def test_the_expert_layers_shares_add_up_to_the_uncut_layer(halves, held):
    """16 / held chips that hold `held` of 16 experts each under the model's
    router (sigmoid scores, the top 4 of all 16, renormalised, x 2.446): the
    parts their held experts add, with the shared expert (which every chip
    computes alike, ungated) counted once, are what the uncut reference
    gives for the whole layer (the model-configs guide's section 4 test)."""
    lp, _attn, x = halves
    keys = jax.random.split(jax.random.key(8), 3)
    whole = {name: jax.random.normal(key, (16,) + lp[name].shape[1:]) * 0.2
             for name, key in zip(("w_gate", "w_in", "w_out"), keys)}
    y = tf._norm(CFG, x, lp, "ln2")
    rows = y.reshape(-1, D)
    shared = ref.gated(rows, lp["w_shared_gate"], lp["w_shared_in"],
                       lp["w_shared_out"]).reshape(x.shape)
    shares = range(0, 16, held)
    total = -(len(shares) - 1) * shared
    for first in shares:
        share = dict(lp, **{k: v[first:first + held]
                            for k, v in whole.items()})
        out, sent = tf._expert_ffn(
            dataclasses.replace(CFG, experts_held=(first, held)), share, y)
        assert int(sent[2][0]) == int(sent[1][first:first + held].sum())
        total = total + out
    uncut = dict(MODEL, held_experts_first=0, num_experts=16)
    named = named_layer(dict(lp, **whole))
    with jax.default_matmul_precision("highest"):
        want = ref.ffn_half(uncut, True, named, x) - x
    assert off_by(total, want) < 1e-5
    assert off_by(total - shared, want) > 1e-2
    # the scale is in the routed part alone
    assert off_by((total - shared) / 2.446 + shared, want) > 1e-2


@pytest.mark.parametrize("slots, n_experts, held, tokens, rows", [
    (8192 * 8, 256, 32, 8192, 16384),       # this model: 2 x the tokens
    (8192 * 10, 512, 64, 8192, 20480),      # 64 of 512, the top 10
    (8192 * 8, 128, 8, 8192, 8192),         # 8 of 128: the tokens
    (4096 * 8, 256, 8, 4096, 4096)])        # 8 of 256: the tokens
def test_the_held_buffer_is_the_programs_rule(slots, n_experts, held, tokens,
                                              rows):
    """The model states no buffer of its own: 32 of 256 experts under the top
    8 of 8192 tokens get the program's 2 x the balanced rows, as the other
    shares get theirs. (A sigmoid router at random weights sends this share
    up to 2.6 x the balanced rows and further buffers then run: my chip
    runs, PR 48, PERF.md section 7.)"""
    from tpu_mpi.parallel import ep
    assert ep.held_row_buffer(slots, n_experts, held, tokens) == rows
    assert "held_rows_factor" not in {
        f.name for f in dataclasses.fields(TransformerConfig)}


# -- the model against the plain reference --------------------------------------

@pytest.fixture(scope="module")
def both():
    """The model of two periods and one batch, the program's step and the
    reference's loss, logits and gradient."""
    params = transformer_init(jax.random.key(0), CFG)
    tok = jax.random.randint(jax.random.key(1), (2, T), 0, V)
    lab = jnp.roll(tok, -1, axis=1)
    named = ref.from_system(params, MODEL)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: ref.loss_of(MODEL, p, tok, lab))(named)
        logits = ref.forward(MODEL, named, tok)
    mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1},
                         devices=jax.devices()[:1])
    step, _specs = transformer_train_step(CFG, mesh, lr=LR)
    after, got_loss = step(params, tok, lab)
    return dict(params=params, tok=tok, lab=lab, named=named, loss=loss,
                grads=grads, logits=logits, after=after, got_loss=got_loss)


def test_the_model_agrees_with_the_reference_in_loss_and_logits(both):
    assert abs(float(both["got_loss"]) - float(both["loss"])) < 1e-5
    got = transformer_forward(CFG, both["params"], both["tok"])
    assert off_by(got, both["logits"]) < 5e-5


def test_the_first_update_agrees_leaf_by_leaf(both):
    """after = before - lr x the reference's gradient, every leaf of every
    layer, the three leaves that are cut into the model's among them."""
    after = ref.from_system(both["after"], MODEL)
    flat = jax.tree_util.tree_leaves_with_path
    seen = set()
    for (path, b), (_p, a), (_q, g) in zip(flat(both["named"]), flat(after),
                                           flat(both["grads"])):
        want = b - LR * g
        moved = float(jnp.sum(jnp.square(want - b)))
        assert moved > 0.0, path
        assert float(jnp.sum(jnp.square(a - want))) / moved < 1e-6, path
        seen.add(path[-1].key)
    assert seen >= {"q_proj", "k_proj", "v_proj", "q_conv1d", "k_conv1d",
                    "v_conv1d", "A_log", "f_a_proj", "f_b_proj", "dt_bias",
                    "b_proj", "g_a_proj", "g_b_proj", "o_norm", "o_proj",
                    "kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_proj",
                    "gate", "shared_gate_proj", "input_layernorm",
                    "post_attention_layernorm", "norm", "embed_tokens",
                    "lm_head"}


def test_the_references_layerwise_gradient_is_its_whole_gradient(both):
    seen = 0
    for i, part in ref.make_grads_from(MODEL)(both["named"], both["tok"],
                                              both["lab"]):
        whole = both["grads"] if i is None else both["grads"]["layers"][i]
        for name, g in part.items():
            assert off_by(g, whole[name]) < 1e-4, (i, name)
            seen += 1
    assert seen == len(jax.tree.leaves(both["grads"]))
    loss, _none = ref.make_loss_from(MODEL)(both["named"], both["tok"],
                                            both["lab"])
    assert abs(loss - float(both["loss"])) < 1e-5


def test_the_references_routing_drops_nothing(both):
    chosen = ref.chosen_experts(MODEL, both["named"], both["tok"])
    assert len(chosen) == 7         # the dense layer routes nothing
    assert all(c.shape == (2 * T, 4) for c in chosen)


# -- traces, counters, and what is refused ---------------------------------------

@pytest.mark.parametrize("word, width, who", [
    (None, DH, "plain"), ("interpret", DH, "plain"),
    ("interpret", 128, "kernel")])
def test_eight_layers_are_three_traces_and_counted(word, width, who,
                                                   kernel_backend):
    """Six KDA layers (one before the dense FFN) and two latent layers: one
    trace a (mixer, FFN) kind, the scan counted once a trace of its kind by
    its form, by who computes it and by its decay: the plain path off a
    kernel backend and, with the tests' word for one, at heads of 8; the
    kernels for a decay a channel at heads of 128 in chunks of 64."""
    cfg = dataclasses.replace(CFG, remat_layers=(), gdn_key_dim=width,
                              gdn_value_dim=width,
                              gdn_chunk=64 if width == 128 else 8)
    perfvars.reset()
    tf._block_traced_once.cache_clear()
    params = transformer_init(jax.random.key(0), cfg)
    tokens = jnp.zeros((1, T), jnp.int32)
    with kernel_backend(word):
        jax.jit(lambda p: transformer_forward(cfg, p, tokens)).lower(params)
    assert tf._block_traced_once.cache_info().currsize == 3
    snap = perfvars.snapshot()
    assert snap["mixer_kinds"]["kda"] == 2 and \
        snap["mixer_kinds"]["attention"] == 1
    assert snap["delta_decays"] == {"head": 0, "channel": 2}
    assert snap["delta_lowerings"] == (
        {"chunked": 0, "padded": 2} if width == 128     # 32 tokens of 64
        else {"chunked": 2, "padded": 0})
    assert snap["delta_kernel_lowerings"] == {
        "kernel": 2 * (who == "kernel"), "plain": 2 * (who == "plain")}
    assert snap["attn_kinds"] == {"latent": "plain"}
    assert snap["rope_forms"] == {"dense": 0, "halves": 0}     # nothing turns
    tf._block_traced_once.cache_clear()
    perfvars.reset()


def test_a_kda_layers_scopes_are_in_its_program(halves):
    lp, _attn, x = halves
    text = jax.jit(jax.grad(lambda lp, x: jnp.sum(tf._kda_mixer(
        CFG, lp, x, tp_axis=None, sp_axis=None)))).lower(lp, x).as_text(
            debug_info=True)
    for scope in ("in_proj", "conv", "prep", "decay", "scan", "gate_norm",
                  "out_proj"):
        assert f"/{scope}/" in text, scope


def test_a_kda_layers_norms_are_the_kernels_where_selected(kernel_backend):
    """At heads of 128 lanes (two heads, a rank of 128, 128 tokens) the
    traced gradient holds the L2 kernel for q and k under `mixer/prep`
    (four times forward: the half is recomputed; twice backward) and the
    gated kernel, the gate's product inside it, under `mixer/gate_norm`
    (twice forward, not three times: no recomputation of its own; once
    backward), no reciprocal root of XLA's in either, and no float32 [1,
    t, heads, 128] array is turned in the lowered module; on the CPU the
    plain arithmetic and no kernel."""
    from test_head_norm_kernel import check_the_norm_scopes
    check_the_norm_scopes(dataclasses.replace(
        CFG, n_layers=1, mixer_kinds=("kda",), ffn_kinds=("dense",),
        remat_layers=(), max_seq=128, gdn_key_heads=2, gdn_key_dim=128,
        gdn_value_heads=2, gdn_value_dim=128, gdn_chunk=64, kda_rank=128,
        dtype=jnp.bfloat16), kernel_backend, {"head_l2_norm_fwd": 4, "head_gated_norm_fwd": 2})


def test_the_backward_pass_keeps_the_states_alone(halves):
    """Of a KDA layer's half the backward pass keeps, beside its inputs, the
    state before each chunk: no [t, heads x key width] array (the decay, the
    12288-wide row, q, k, v, the scan's output) is a residual."""
    lp, _attn, x = halves
    _out, back = jax.vjp(lambda lp, x: tf._kda_mixer(
        CFG, lp, x, tp_axis=None, sp_axis=None), lp, x)
    kept = [a.shape for a in jax.tree.leaves(back)
            if hasattr(a, "shape") and a.ndim >= 3]
    states = (T // 8, 2, H, DH, DH)
    assert states in kept
    assert all(shape in (states, x.shape) for shape in kept), kept


@pytest.mark.parametrize("fields, match", [
    (dict(kda_rank=0), "kda_rank"),
    (dict(gdn_chunk=12), "power of two"),
    (dict(gdn_key_heads=3), "multiple"),
    (dict(d_rope=0), "together"),
    (dict(q_latent=8, kv_latent=0, mixer_kinds=("kda",) * 8), "kv_latent"),
    (dict(mixer_kinds=("kda",) * 7 + ("gated",)), "mixer"),
])
def test_what_the_configuration_refuses(fields, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **fields)


@pytest.mark.parametrize("axes", [{"dp": 1, "tp": 2, "sp": 1},
                                  {"dp": 1, "tp": 1, "sp": 2}])
def test_the_step_refuses_kda_layers_under_tp_or_sp(axes):
    mesh = xla.make_mesh(axes, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="tp 1 and sp 1"):
        transformer_train_step(CFG, mesh)


def test_a_query_latent_and_rotation_are_data(halves):
    """`q_latent` 0 is one `w_q` and no `q_latent_norm`; with a query latent
    and `rope_full_layers` the same function is openPangu's layer: its
    leaves, and positions that matter."""
    _kda, lp, x = halves
    assert "w_q" in lp and not {"w_dq", "w_uq", "q_latent_norm"} & set(lp)
    cfg = dataclasses.replace(CFG, q_latent=8, rope_full_layers=True)
    other = transformer_init(jax.random.key(5), cfg)["layers"][3]
    assert {"w_dq", "w_uq", "q_latent_norm"} <= set(other) and \
        "w_q" not in other
    def at(cfg, lp, positions):
        return tf._attn(cfg, lp, x, positions, h_local=4, tp_axis=None,
                        sp_axis=None)
    np.testing.assert_allclose(at(CFG, lp, jnp.arange(T)),
                               at(CFG, lp, jnp.arange(T) + 7), atol=1e-6)
    assert off_by(at(cfg, other, jnp.arange(T) * 3),
                  at(cfg, other, jnp.arange(T))) > 1e-3


def test_the_kda_layers_leaves_and_the_count():
    """The leaves of a KDA layer's mixer, their shapes and types, and the
    count of a layer at the published widths (ISSUE 48: 39 514 272)."""
    lp = transformer_init(jax.random.key(0), CFG)["layers"][0]
    wide = H * DH
    shapes = {"w_kda_in": (D, 3 * wide), "w_kda_low": (D, 2 * RANK + H),
              "conv_w": (4, 3 * wide), "a_log": (H,), "dt_bias": (wide,),
              "w_kda_f": (RANK, wide), "w_kda_g": (RANK, wide),
              "kda_norm": (DH,), "w_kda_out": (wide, D)}
    for name, shape in shapes.items():
        assert lp[name].shape == shape, name
    assert lp["a_log"].dtype == lp["dt_bias"].dtype == jnp.float32
    assert float(jnp.exp(lp["a_log"]).min()) >= 1.0
    assert float(jnp.exp(lp["a_log"]).max()) <= 16.0
    dt = jax.nn.softplus(lp["dt_bias"])
    assert 1e-3 * 0.99 <= float(dt.min()) and float(dt.max()) <= 0.1 * 1.01
    published = dataclasses.replace(
        CFG, d_model=2304, gdn_key_heads=32, gdn_value_heads=32,
        gdn_key_dim=128, gdn_value_dim=128, kda_rank=128, n_layers=1,
        mixer_kinds=("kda",), ffn_kinds=("dense",), remat_layers=())
    tree = jax.eval_shape(lambda k: transformer_init(k, published),
                          jax.random.key(0))["layers"][0]
    mixer = sum(tree[name].size for name in shapes)
    assert mixer == 39_514_272
