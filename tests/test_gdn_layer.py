"""Linear attention with a gated delta rule, gated attention and a gated
shared expert through the program's normal path: the chunked scan of
`parallel.delta` against the recurrence one token at a time, values and every
gradient, at a multiple of the chunk and padded, under repeated keys, and
its float32 inside bfloat16 operands; a delta-rule layer's first half
against the layer's equations written out here, and against each way of
getting them wrong; the attention layer likewise; the expert layer's shares
adding up to the uncut layer with the gated shared expert counted once; the
model at two periods against the plain reference
(yardstick/reference/lm_gdn_train_step.py) on seeded random weights,
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
from yardstick.reference import lm_gdn_train_step as ref        # noqa: E402

V, T, LR = 96, 32, 0.05
HK, HV, DK, DV = 2, 4, 8, 8

CFG = TransformerConfig(
    vocab=V, d_model=32, n_heads=4, n_kv_heads=2, d_head=16, d_ff=16,
    n_layers=8, max_seq=T, dtype=jnp.float32, norm_eps=1e-6,
    tie_embeddings=False, qk_norm_heads=True, rope_theta=1e7,
    n_experts=16, experts_per_tok=4, router_renorm=True, n_shared_experts=1,
    experts_held=(4, 4), mixer_kinds=("gdn", "gdn", "gdn", "attention") * 2,
    remat_layers=("ffn", "", "", "ffn", "", "", "", ""),
    gdn_key_heads=HK, gdn_key_dim=DK, gdn_value_heads=HV, gdn_value_dim=DV,
    gdn_conv=4, gdn_chunk=8, attn_out_gate=True, rotary_dim=4,
    norm_unit_offset=True, shared_expert_gate=True)
MODEL = dict(hidden_size=32, num_hidden_layers=8, full_attention_interval=4,
             rms_norm_eps=1e-6, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, partial_rotary_factor=0.25, rope_theta=1e7,
             linear_num_key_heads=HK, linear_num_value_heads=HV,
             linear_key_head_dim=DK, linear_value_head_dim=DV,
             linear_conv_kernel_dim=4, num_experts_per_tok=4,
             norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
             router_num_experts=16, held_experts_first=4, num_experts=4,
             moe_intermediate_size=16, shared_expert_intermediate_size=16,
             vocab_size=V)


def off_by(got, want) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(got - want))
                          / jnp.sum(jnp.square(want))))


# -- the chunked scan against the recurrence ------------------------------------

def scan_inputs(t: int, dtype=jnp.float32, slow: bool = False):
    keys = jax.random.split(jax.random.key(3), 5)
    q, k = (tf._l2_normed(jax.random.normal(key, (2, t, HK, DK)))
            for key in keys[:2])
    v = jax.random.normal(keys[2], (2, t, HV, DV))
    g = -jax.random.uniform(keys[3], (2, t, HV)) * (0.02 if slow else 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (2, t, HV)))
    return tuple(a.astype(dtype) for a in (q * DK ** -0.5, k, v)) \
        + (g.astype(jnp.float32), beta.astype(jnp.float32))


@pytest.mark.parametrize("t, chunk, form", [
    (32, 8, "chunked"), (64, 64, "chunked"), (20, 8, "padded"),
    (5, 16, "padded")])
def test_the_chunked_scan_is_the_recurrence(t, chunk, form):
    """Values and the gradient of each of the five operands, float32; the
    form is counted where it is chosen."""
    args = scan_inputs(t)
    weigh = jax.random.normal(jax.random.key(4), (2, t, HV, DV))
    perfvars.reset()
    got, got_grads = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(delta.delta_scan(*a, chunk) * weigh),
        argnums=range(5)))(*args)
    counted = perfvars.snapshot()["delta_lowerings"]
    assert counted[form] == 1 and sum(counted.values()) == 1
    perfvars.reset()
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(delta.delta_recurrence(*a) * weigh),
        argnums=range(5)))(*args)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    np.testing.assert_allclose(
        jax.jit(lambda *a: delta.delta_scan(*a, chunk))(*args),
        jax.jit(delta.delta_recurrence)(*args), atol=2e-6)
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)


def test_the_backward_pass_runs_the_state_chain_once_each_way():
    """Two `scan`s in the gradient's program, the forward chain and the
    backward one: the kept states stand in for the forward chain's second
    run, and no [chunk x chunk] array is an input of the backward pass."""
    args = scan_inputs(64)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(delta.delta_scan(*a, 16)), argnums=range(5)))(
            *args))
    assert text.count("scan[") == 2, text.count("scan[")


def test_keys_that_repeat_cost_the_inverse_no_digits():
    """Every key the same, no decay, beta one: `A` is all ones under the
    diagonal, whose powers reach 1e17 at a chunk of 64 while its inverse has
    entries of one: the inverse by halves forms no power."""
    q, k, v, g, beta = scan_inputs(64)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, beta = jnp.zeros_like(g), jnp.ones_like(beta)
    np.testing.assert_allclose(delta.delta_scan(q, k, v, g, beta, 64),
                               delta.delta_recurrence(q, k, v, g, beta),
                               atol=5e-6)
    lower = jnp.tril(jnp.ones((64, 64), jnp.float32), -1)
    want = jnp.eye(64) - jnp.eye(64, k=-1)
    np.testing.assert_allclose(delta._unit_lower_inverse(lower), want,
                               atol=1e-6)


def test_the_scan_refuses_a_chunk_that_is_no_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        delta.delta_scan(*scan_inputs(24), 12)


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
    products' operands and the output are rounded, and the output is within
    half a percent (rms) of the float32 recurrence; with the state after
    each chunk, or a chunk's summed decays, rounded to bfloat16 it reads
    twice that and more."""
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


def test_the_inverse_is_float32_under_bfloat16(monkeypatch):
    """The triangular system is set up, inverted and differentiated in
    float32 whatever the operands' type (its products at `HIGHEST`), and
    only the finished inverse is rounded for the products that use it: with
    keys that nearly repeat, the rounded operands' products hide whether it
    was, so the types are read where it is called."""
    seen = []
    inverse = delta._unit_lower_inverse

    def spied(a):
        out = inverse(a)
        seen.append((a.dtype, out.dtype))
        return out
    monkeypatch.setattr(delta, "_unit_lower_inverse", spied)
    jax.clear_caches()
    args = rounding_inputs(64, 0.02, 3.0)
    jax.grad(lambda *a: jnp.sum(delta.delta_scan(*a, 32).astype(jnp.float32)),
             argnums=(0, 1, 2))(*args)
    jax.clear_caches()
    assert seen and all(pair == (jnp.float32, jnp.float32) for pair in seen)
    text = str(jax.make_jaxpr(inverse)(jnp.zeros((4, 4), jnp.float32)))
    assert text.count("Precision.HIGHEST, Precision.HIGHEST") >= 4
    assert "bf16" not in text


def _kernels_traced():
    """The Pallas kernels' own programs (forward, backward) as traced for
    bfloat16 operands inside the kernels' contract, with the program around
    them: ([the `pallas_call` equations], the whole text)."""
    t = 128
    keys = jax.random.split(jax.random.key(5), 5)
    q, k = (tf._l2_normed(jax.random.normal(key, (1, t, 1, 128)))
            for key in keys[:2])
    args = (q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
            jax.random.normal(keys[2], (1, t, 2, 128)).astype(jnp.bfloat16),
            -jax.random.uniform(keys[3], (1, t, 2)),
            jax.nn.sigmoid(jax.random.normal(keys[4], (1, t, 2))))
    closed = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(delta.delta_scan(*a, 64).astype(jnp.float32)),
        argnums=range(5)))(*args)
    calls = [eqn for eqn in _eqns(closed.jaxpr)
             if eqn.primitive.name == "pallas_call"]
    return calls, str(closed)


def _eqns(jaxpr):
    """Every equation of a program, those of its inner programs after the
    equation that holds them."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in eqn.params.values():
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield from _eqns(sub)


def test_the_kernels_state_and_decay_sums_are_float32_under_bfloat16(
        kernel_backend):
    """The guard above reads the plain path by breaking it; the kernel pair
    is read by type: under bfloat16 operands the sums of g are taken in
    float32 in front of the kernel, every exponential inside and outside it
    is of a float32, the state carried in VMEM, the state handed to the
    backward pass and the tile of scalars are float32, and both kernels run
    (`delta_kernel_lowerings` counts the trace)."""
    perfvars.reset()
    with kernel_backend("interpret"):
        calls, text = _kernels_traced()
    assert perfvars.snapshot()["delta_kernel_lowerings"] == {
        "kernel": 1, "plain": 0}
    perfvars.reset()
    assert sorted(eqn.params["name"] for eqn in calls) == [
        "delta_scan_bwd", "delta_scan_fwd"]
    assert "cumsum" in text and "bf16[1,2,64,1,2]" not in text
    for eqn in calls:
        inner = eqn.params["jaxpr"]
        scratch = [v.aval for v in inner.invars][-1]
        assert scratch.shape == (2, 128, 128)
        assert scratch.dtype == jnp.float32         # the state, its cotangent
        exps = [e for e in _eqns(inner) if e.primitive.name == "exp"]
        assert exps and all(e.invars[0].aval.dtype == jnp.float32
                            for e in exps)
    forward = next(eqn for eqn in calls
                   if eqn.params["name"] == "delta_scan_fwd")
    o, before = (v.aval for v in forward.outvars)
    assert o.dtype == jnp.bfloat16
    assert before.shape == (1, 2, 2, 128, 128) and before.dtype == jnp.float32
    assert forward.invars[3].aval.dtype == jnp.float32      # the scalars


def test_the_kernels_inverse_is_float32_under_bfloat16(kernel_backend):
    """Inside both kernels a product either takes two bfloat16 operands or
    two float32 operands at `HIGHEST`: the float32 ones are the inverse's
    two rounds of two (blocks of 16 tokens are solved by substitution, on
    the VPU, and multiply nothing) and, backward, its gradient's two more;
    no product rounds a float32 operand."""
    with kernel_backend("interpret"):
        calls, _text = _kernels_traced()
    perfvars.reset()
    exact = {}
    for eqn in calls:
        name = eqn.params["name"]
        exact[name] = 0
        for e in _eqns(eqn.params["jaxpr"]):
            if e.primitive.name != "dot_general":
                continue
            types = {v.aval.dtype for v in e.invars}
            assert len(types) == 1, (name, types)
            assert e.params["preferred_element_type"] == jnp.float32
            if types == {jnp.dtype(jnp.float32)}:
                assert e.params["precision"] == (
                    jax.lax.Precision.HIGHEST,) * 2, name
                exact[name] += 1
            else:
                assert types == {jnp.dtype(jnp.bfloat16)}, (name, types)
    assert exact == {"delta_scan_fwd": 4, "delta_scan_bwd": 6}


# -- a layer's halves against their equations ------------------------------------

def silu(x):
    return x / (1.0 + np.exp(-x))


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def rms(x, scale, eps=1e-6):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def plain_delta_half(lp: dict, x, wrong=None):
    """ISSUE 45's delta-rule layer, float64 numpy, a token at a time, from
    the program's own leaves ([q | k | v | z], [b | a]); ``wrong`` names one
    way of getting it wrong."""
    lp = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    x = np.asarray(x, np.float64)
    b, t, _ = x.shape
    kw, vw, r = HK * DK, HV * DV, HV // HK
    y = rms(x, 1.0 + lp["ln1"])
    qkv, z = np.split(y @ lp["w_gdn_in"], [2 * kw + vw], axis=-1)
    beta, a = np.split(y @ lp["w_gdn_ba"], 2, axis=-1)
    padded = np.pad(qkv, ((0, 0), (3, 0), (0, 0)))
    qkv = silu(sum(padded[:, j:j + t] * lp["conv_w"][j] for j in range(4)))
    q, k, v = np.split(qkv, [kw, 2 * kw], axis=-1)
    q, k = (part.reshape(b, t, HK, DK) for part in (q, k))
    if wrong != "no l2 norm":
        q, k = (part / np.sqrt(np.sum(part * part, -1, keepdims=True) + 1e-6)
                for part in (q, k))
    q = q * DK ** -0.5
    v, z = v.reshape(b, t, HV, DV), z.reshape(b, t, HV, DV)
    beta = sigmoid(beta)
    g = -np.exp(lp["a_log"]) * np.log1p(np.exp(a + lp["dt_bias"]))
    o = np.zeros((b, t, HV, DV))
    for h in range(HV):
        kh = h if wrong == "value head h reads key head h" and h < HK \
            else h // r
        s = np.zeros((b, DK, DV))
        for i in range(t):
            s = s * np.exp(g[:, i, h])[:, None, None]
            told = np.einsum("bkv,bk->bv", s, k[:, i, kh])
            write = v[:, i, h] - told
            if wrong != "beta left out of the correction":
                write = beta[:, i, h, None] * write
            else:
                write = beta[:, i, h, None] * v[:, i, h] - told
            s = s + k[:, i, kh, :, None] * write[:, None, :]
            o[:, i, h] = np.einsum("bkv,bk->bv", s, q[:, i, kh])
    if wrong == "gate before norm":
        o = rms(o * silu(z), lp["gdn_norm"])
    elif wrong == "norm over the whole width":
        o = rms(o.reshape(b, t, vw), np.tile(lp["gdn_norm"], HV)).reshape(
            o.shape) * silu(z)
    else:
        o = rms(o, lp["gdn_norm"]) * silu(z)
    return o.reshape(b, t, vw) @ lp["w_gdn_out"]


@pytest.fixture(scope="module")
def halves():
    """(layer 0's leaves (delta rule), layer 3's (attention), a stream)."""
    params = transformer_init(jax.random.key(5), CFG)
    # norm scales away from one and decays that differ, so that each shows
    lp = dict(params["layers"][0])
    lp["gdn_norm"] = 1.0 + 0.3 * jax.random.normal(jax.random.key(6), (DV,))
    x = jax.random.normal(jax.random.key(7), (2, T, 32))
    return lp, params["layers"][3], x


@pytest.mark.parametrize("wrong", [
    None, "gate before norm", "norm over the whole width", "no l2 norm",
    "beta left out of the correction", "value head h reads key head h"])
def test_a_delta_layers_first_half_is_its_equations(halves, wrong):
    lp, _attn, x = halves
    got = tf._gdn_mixer(CFG, lp, x, tp_axis=None, sp_axis=None)
    read = off_by(np.asarray(got, np.float64), plain_delta_half(lp, x, wrong))
    assert (read < 1e-4) == (wrong is None), read


def test_the_references_delta_layer_is_the_same_equations(halves):
    lp, _attn, x = halves
    named = ref.from_system({"embed": 0, "ln_f": 0, "lm_head": 0,
                             "layers": [lp]}, MODEL)["layers"][0]
    start = (jnp.zeros((2, HV, DK, DV)), jnp.zeros((2, 3, 2 * HK * DK + HV * DV)))
    got = ref.linear_segment(MODEL, named, start, x)[1] - x
    assert off_by(np.asarray(got, np.float64), plain_delta_half(lp, x)) < 1e-4


def plain_attn_half(lp: dict, x, wrong=None):
    """ISSUE 45's attention layer, float64 numpy, from the program's own
    leaves (`w_q`: [queries | gate])."""
    lp = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    x = np.asarray(x, np.float64)
    b, t, _ = x.shape
    nh, nkv, dh, turned = 4, 2, 16, 16 if wrong == "all of a head turns" else 4
    y = rms(x, 1.0 + lp["ln1"])
    q, gate = np.split(y @ lp["w_q"], 2, axis=-1)
    if wrong == "the gate from the wrong half":
        q, gate = gate, q
    offset = 0.0 if wrong == "a plain w scale" else 1.0
    q = rms(q.reshape(b, t, nh, dh), offset + lp["q_norm"])
    k = rms((y @ lp["w_k"]).reshape(b, t, nkv, dh), offset + lp["k_norm"])
    v = (y @ lp["w_v"]).reshape(b, t, nkv, dh)

    def rope(a):        # the first `turned` values, halves rotated
        half = turned // 2
        ang = np.arange(t)[:, None] / 1e7 ** (np.arange(half) / half)
        cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
        a1, a2 = a[..., :half], a[..., half:turned]
        return np.concatenate([a1 * cos - a2 * sin, a1 * sin + a2 * cos,
                               a[..., turned:]], axis=-1)
    q, k = rope(q), rope(k)
    o = np.zeros((b, t, nh, dh))
    for h in range(nh):
        s = np.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, h // 2]) * dh ** -0.5
        s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        o[:, :, h] = np.einsum("bqk,bkd->bqd", p / p.sum(-1, keepdims=True),
                               v[:, :, h // 2])
    o = o.reshape(b, t, nh * dh)
    return (o * (silu(gate) if wrong == "a silu gate" else sigmoid(gate))) \
        @ lp["w_proj"]


@pytest.mark.parametrize("wrong", [
    None, "all of a head turns", "a silu gate", "the gate from the wrong half",
    "a plain w scale"])
def test_the_attention_layers_first_half_is_its_equations(halves, wrong):
    _gdn, lp, x = halves
    got = tf._attn(CFG, lp, x, jnp.arange(T), h_local=4, tp_axis=None,
                   sp_axis=None)
    read = off_by(np.asarray(got, np.float64), plain_attn_half(lp, x, wrong))
    assert (read < 1e-4) == (wrong is None), read


def test_the_expert_layers_shares_add_up_to_the_uncut_layer(halves):
    """Four chips that hold 4 of 16 experts each: the parts their held
    experts add, with the gated shared expert (which every chip computes
    alike) counted once, are what the uncut reference gives for the whole
    layer (the model-configs guide's section 4 test)."""
    lp, _attn, x = halves
    keys = jax.random.split(jax.random.key(8), 3)
    whole = {name: jax.random.normal(key, (16,) + lp[name].shape[1:]) * 0.2
             for name, key in zip(("w_gate", "w_in", "w_out"), keys)}
    y = tf._norm(CFG, x, lp, "ln2")
    rows = y.reshape(-1, 32)
    shared = jax.nn.sigmoid(rows @ lp["w_shared_sigmoid"]) * ref.gated(
        rows, lp["w_shared_gate"], lp["w_shared_in"], lp["w_shared_out"])
    total = -3 * shared.reshape(x.shape)
    for first in (0, 4, 8, 12):
        share = dict(lp, **{k: v[first:first + 4] for k, v in whole.items()})
        out, sent = tf._expert_ffn(
            dataclasses.replace(CFG, experts_held=(first, 4)), share, y)
        assert int(sent[2][0]) == int(sent[1][first:first + 4].sum())
        total = total + out
    uncut = dict(MODEL, held_experts_first=0, num_experts=16)
    named = ref.from_system({"embed": 0, "ln_f": 0, "lm_head": 0,
                             "layers": [dict(lp, **whole)]}, MODEL)["layers"][0]
    with jax.default_matmul_precision("highest"):
        want = ref.expert_half(uncut, named, x) - x
    assert off_by(total, want) < 1e-5
    assert off_by(total - shared.reshape(x.shape), want) > 1e-2


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
    layer, the three re-laid projections among them."""
    after = ref.from_system(both["after"], MODEL)
    flat = jax.tree_util.tree_leaves_with_path
    for (path, b), (_p, a), (_q, g) in zip(flat(both["named"]), flat(after),
                                           flat(both["grads"])):
        want = b - LR * g
        moved = float(jnp.sum(jnp.square(want - b)))
        assert moved > 0.0, path
        assert float(jnp.sum(jnp.square(a - want))) / moved < 1e-6, path


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


def test_eight_layers_are_two_traces_and_counted():
    """Six delta-rule layers and two attention layers: one trace a (mixer,
    recomputation) kind, the scan counted once a trace of its kind."""
    cfg = dataclasses.replace(CFG, remat_layers=())
    params = transformer_init(jax.random.key(0), cfg)
    tok = jnp.zeros((1, T), jnp.int32)
    tf._block_traced_once.cache_clear()
    perfvars.reset()
    jax.jit(lambda p, t: transformer_forward(cfg, p, t)).lower(params, tok)
    assert tf._block_traced_once.cache_info().currsize == 2
    snap = perfvars.snapshot()
    assert snap["mixer_kinds"]["gdn"] == 1
    assert snap["mixer_kinds"]["attention"] == 1
    assert snap["delta_lowerings"] == {"chunked": 1, "padded": 0}
    tf._block_traced_once.cache_clear()
    perfvars.reset()


def test_a_delta_layers_conv_scope_holds_the_kernel_where_selected(
        kernel_backend):
    """Wide enough for the contract (q, k and v of 128, 128 and 256
    channels, 128 tokens), the traced gradient holds `conv_silu_fwd` and
    `conv_silu_bwd` under `mixer/conv` (its forward again in what the
    backward pass recomputes) and no pad or shifted-slice chain; on the CPU
    the chain and no kernel."""
    from test_conv_kernel import check_the_conv_scope
    check_the_conv_scope(dataclasses.replace(
        CFG, n_layers=1, mixer_kinds=("gdn",), remat_layers=(), max_seq=128,
        gdn_key_heads=1, gdn_key_dim=128, gdn_value_heads=2,
        gdn_value_dim=128, gdn_chunk=64), kernel_backend)


def test_a_delta_layers_norms_are_the_kernels_where_selected(kernel_backend):
    """At heads of 128 lanes (two key heads, four value heads, 128 tokens) the
    traced gradient holds the L2 kernel for q and k under `mixer/prep`
    (twice forward, twice backward) and the gated kernel under
    `mixer/gate_norm` (once each way), no reciprocal root of XLA's in
    either, and no float32 [1, t, heads, 128] array is turned in the
    lowered module; on the CPU the plain arithmetic and no kernel."""
    from test_head_norm_kernel import check_the_norm_scopes
    check_the_norm_scopes(dataclasses.replace(
        CFG, n_layers=1, mixer_kinds=("gdn",), remat_layers=(), max_seq=128,
        gdn_key_heads=2, gdn_key_dim=128, gdn_value_heads=4,
        gdn_value_dim=128, gdn_chunk=64, dtype=jnp.bfloat16), kernel_backend,
        {"head_l2_norm_fwd": 2, "head_gated_norm_fwd": 1})


# -- what is refused ------------------------------------------------------------

@pytest.mark.parametrize("fields, match", [
    (dict(gdn_key_heads=0), "delta-rule layers name"),
    (dict(gdn_value_heads=3), "multiple of the key heads"),
    (dict(gdn_value_dim=0), "delta-rule layers name"),
    (dict(gdn_chunk=24), "power of two"),
    (dict(rotary_dim=5), "an even share"),
    (dict(rotary_dim=32), "an even share"),
    (dict(qk_norm_heads=False), "turned after the norm"),
    (dict(n_kv_heads=0), "attn_out_gate widens"),
    (dict(norm_kind="layer"), "norm_unit_offset is RMSNorm's"),
    (dict(norm_out=True), "norm_unit_offset is RMSNorm's"),
    (dict(n_shared_experts=0), "shared_expert_gate gates"),
    (dict(mixer_kinds=("gdn",) * 7 + ("gdn2",)), "mixer"),
])
def test_what_the_configuration_refuses(fields, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **fields)


def test_the_gate_is_refused_with_latent_and_differential_attention():
    plain = dict(vocab=V, d_model=32, n_heads=4, d_ff=16, n_layers=2)
    with pytest.raises(ValueError, match="attn_out_gate"):
        TransformerConfig(**plain, n_kv_heads=2, diff_attn=True,
                          attn_bias=True, attn_out_gate=True)
    with pytest.raises(ValueError, match="attn_out_gate"):
        TransformerConfig(**plain, kv_latent=8, q_latent=8, d_rope=4, d_head=8,
                          attn_out_gate=True)


@pytest.mark.parametrize("axes", [{"dp": 1, "tp": 2, "sp": 1},
                                  {"dp": 1, "tp": 1, "sp": 2}],
                         ids=["tp2", "sp2"])
def test_the_step_refuses_delta_layers_under_tp_or_sp(axes):
    mesh = xla.make_mesh(axes, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="delta-rule layers"):
        transformer_train_step(CFG, mesh, lr=LR)
    with pytest.raises(NotImplementedError, match="delta-rule layer runs"):
        jax.shard_map(
            lambda x: tf._gdn_mixer(CFG, {}, x, tp_axis="tp", sp_axis="sp"),
            mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
            out_specs=jax.sharding.PartitionSpec())(jnp.zeros((1, T, 32)))


def test_the_new_fields_add_no_leaf_and_no_equation_at_their_defaults():
    """A model of the benchmark's older kind (window and full attention,
    held experts, a shared expert) has the leaves it had, and its norms'
    scales start at one."""
    cfg = TransformerConfig(
        vocab=V, d_model=32, n_heads=4, n_kv_heads=2, d_head=8, d_ff=16,
        n_layers=2, tie_embeddings=False, qk_norm_heads=True, n_experts=8,
        experts_per_tok=2, n_shared_experts=1, experts_held=(0, 4))
    params = transformer_init(jax.random.key(0), cfg)
    assert sorted(params["layers"][0]) == sorted([
        "ln1", "w_q", "w_k", "w_v", "w_proj", "ln2", "w_in", "w_out", "q_norm",
        "k_norm", "w_gate", "w_router", "w_shared_gate", "w_shared_in",
        "w_shared_out"])
    assert params["layers"][0]["w_q"].shape == (32, 32)
    for name in ("ln1", "ln2", "q_norm", "k_norm"):
        assert bool(jnp.all(params["layers"][0][name] == 1.0)), name
    assert bool(jnp.all(params["ln_f"] == 1.0))


def test_the_delta_layers_leaves_and_the_count():
    """A delta-rule layer has the mixer's seven leaves in attention's place
    (the recurrence's two in float32), an attention layer a `w_q` twice as
    wide; under `norm_unit_offset` every scale of a 1 + w norm starts near 0
    and `gdn_norm` at one."""
    params = transformer_init(jax.random.key(0), dataclasses.replace(
        CFG, dtype=jnp.bfloat16))
    gdn, attn = params["layers"][0], params["layers"][3]
    assert {k: v.shape for k, v in gdn.items() if k.startswith(
        ("w_gdn", "conv", "a_log", "dt_bias", "gdn_norm"))} == {
        "w_gdn_in": (32, 2 * HK * DK + 2 * HV * DV), "w_gdn_ba": (32, 2 * HV),
        "conv_w": (4, 2 * HK * DK + HV * DV), "a_log": (HV,),
        "dt_bias": (HV,), "gdn_norm": (DV,), "w_gdn_out": (HV * DV, 32)}
    assert gdn["a_log"].dtype == gdn["dt_bias"].dtype == jnp.float32
    assert "w_proj" not in gdn and "w_q" not in gdn
    assert attn["w_q"].shape == (32, 2 * 4 * 16)
    assert gdn["w_shared_sigmoid"].shape == (32, 1)
    assert bool(jnp.all(gdn["gdn_norm"] == 1.0))
    for leaf in (gdn["ln1"], gdn["ln2"], attn["q_norm"], params["ln_f"]):
        assert 0.0 < float(jnp.max(jnp.abs(leaf.astype(jnp.float32)))) < 0.2
    specs = tf.transformer_param_specs(CFG, "tp")
    assert jax.tree.structure(specs) == jax.tree.structure(
        jax.tree.map(lambda a: jax.sharding.PartitionSpec(), params))
