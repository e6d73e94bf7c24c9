"""State-space (Mamba-2) layers among attention layers: the chunked scan of
`parallel.ssm` against the recurrence one token at a time, values and
gradients, at several chunk sizes and at lengths that are no multiple of the
chunk; the causal convolution against a loop over tokens and taps; the model
(`m m A m`, the three multipliers and a score scale that is not
head_dim ** -0.5) through the program's normal path against the plain
reference (yardstick/reference/lm_ssm_train_step.py) on seeded random
weights, float32; each departure planted and caught; one trace a mixer kind;
a program without such a layer traces what it traced; what the configuration
and the step refuse; the two counters."""

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
from yardstick.reference import lm_ssm_train_step as ref        # noqa: E402


# -- the scan against the recurrence ------------------------------------------

def recurrence(x, dt, a, b, c, d):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t, one
    token at a time."""
    bsz, _t, h, p = x.shape

    def token(s, at):
        x_t, dt_t, b_t, c_t = at
        s = jnp.exp(dt_t * a)[:, :, None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return s, jnp.einsum("bhpn,bn->bhp", s, c_t) + d[:, None] * x_t
    _, ys = lax.scan(token, jnp.zeros((bsz, h, p, b.shape[-1]), x.dtype),
                     tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1)


SCAN_ARGS = ("x", "dt", "a", "b", "c", "d")


@pytest.fixture(scope="module")
def scanned():
    """Operands of a scan (2 sequences of 96 tokens, 4 heads of 8, state 16),
    a cotangent, and the recurrence's output and gradients."""
    keys = jax.random.split(jax.random.key(0), 5)
    bsz, t, h, p, n = 2, 96, 4, 8, 16
    args = (jax.random.normal(keys[0], (bsz, t, h, p)),
            jax.nn.softplus(jax.random.normal(keys[1], (bsz, t, h)) - 2.0),
            -jnp.arange(1, h + 1, dtype=jnp.float32),
            jax.random.normal(keys[2], (bsz, t, n)),
            jax.random.normal(keys[3], (bsz, t, n)),
            jnp.linspace(0.5, 1.5, h))
    w = jax.random.normal(keys[4], (bsz, t, h, p))
    with jax.default_matmul_precision("highest"):
        want = recurrence(*args)
        grads = jax.grad(lambda *a: jnp.sum(recurrence(*a) * w),
                         argnums=tuple(range(6)))(*args)
    return args, w, want, grads


@pytest.mark.parametrize("chunk, form", [
    (8, "chunked"), (32, "chunked"), (96, "chunked"), (256, "chunked"),
    (40, "padded"), (7, "padded")])
def test_the_chunked_scan_is_the_recurrence(scanned, chunk, form):
    """Values and all six gradients in float32; a chunk longer than the
    sequence is one chunk, a length that is no multiple of the chunk is
    filled up with tokens of dt = 0 and counted as `padded`."""
    args, w, want, grads = scanned
    perfvars.reset()
    with jax.default_matmul_precision("highest"):
        value = jax.jit(lambda *a: ssm.scan(*a, chunk))(*args)
        got = jax.jit(jax.grad(lambda *a: jnp.sum(ssm.scan(*a, chunk) * w),
                               argnums=tuple(range(6))))(*args)
    assert value.shape == want.shape and value.dtype == want.dtype
    assert float(jnp.abs(value - want).max()) < 1e-4
    for name, g, wg in zip(SCAN_ARGS, got, grads):
        scale = float(jnp.abs(wg).max())
        assert float(jnp.abs(g - wg).max()) < 1e-4 * scale, name
    counted = perfvars.snapshot()["scan_lowerings"]
    assert counted[form] == 2 and sum(counted.values()) == 2


def test_the_scans_backward_keeps_the_states_and_not_the_decay_matrix():
    """What the backward pass is handed: the inputs and the chunks' states;
    nothing of [.., heads, chunk, chunk], which is computed again."""
    bsz, t, h, p, n, chunk = 1, 64, 4, 8, 16, 16
    shapes = [(bsz, t, h, p), (bsz, t, h), (h,), (bsz, t, n), (bsz, t, n),
              (h,)]
    args = [jnp.ones(s, jnp.float32) for s in shapes]
    from jax._src.ad_checkpoint import saved_residuals
    kept = saved_residuals(lambda *a: ssm.scan(*a, chunk), *args)
    shapes_kept = [tuple(aval.shape) for aval, _why in kept]
    assert (bsz, t // chunk, h, p, n) in shapes_kept        # the states
    assert not [s for s in shapes_kept if s[-2:] == (chunk, chunk)]


def test_the_causal_convolution_is_the_loop_over_taps():
    """A token sees itself (the last tap) and the taps - 1 before it, zeros
    before the sequence's start, a bias a channel."""
    keys = jax.random.split(jax.random.key(1), 3)
    bsz, t, ch, taps = 2, 12, 6, 4
    x = np.asarray(jax.random.normal(keys[0], (bsz, t, ch)))
    w = np.asarray(jax.random.normal(keys[1], (taps, ch)))
    bias = np.asarray(jax.random.normal(keys[2], (ch,)))
    want = np.zeros_like(x)
    for i in range(t):
        for j in range(taps):
            at = i - (taps - 1) + j
            if at >= 0:
                want[:, i] += w[j] * x[:, at]
        want[:, i] += bias
    got = ssm.causal_conv(jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


# -- the model against the plain reference ------------------------------------

L, T, V = 4, 48, 128
# the published keys the reference reads, at a small size; the score scale
# 1/16 is not head_dim ** -0.5 = 8 ** -0.5
PUBLISHED = dict(
    hidden_size=64, layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
    num_hidden_layers=L, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=32,
    mamba_d_conv=4, mamba_n_groups=1, mamba_expand=2, mamba_chunk_size=16,
    num_attention_heads=8, num_key_value_heads=2, attention_multiplier=1 / 16,
    embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
    rms_norm_eps=1e-5, position_embedding_type="nope",
    tie_word_embeddings=True, shared_intermediate_size=96)
CFG = TransformerConfig(
    vocab=V, d_model=64, n_heads=8, n_layers=L, d_ff=96, max_seq=T,
    dtype=jnp.float32, norm_eps=1e-5, n_kv_heads=2, rope_full_layers=False,
    dense_gated=True, mixer_kinds=["ssm", "ssm", "attention", "ssm"],
    ssm_expand=2, ssm_heads=8, ssm_head_dim=16, ssm_state=32, ssm_conv=4,
    ssm_chunk=16, embed_multiplier=12.0, residual_multiplier=0.22,
    logits_divisor=8.0, attn_scale=1 / 16, remat_layers=["ffn", "", "", ""])
LR = 0.01


def _both(cfg, t):
    params = transformer_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, t), 0, V)
    labels = jnp.roll(tokens, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(lambda p: tf._xent(
            tf._forward(cfg, p, tokens)[0], labels)))(params)
        want = jax.jit(jax.value_and_grad(lambda p: ref.loss_of(
            PUBLISHED, p, tokens, labels)))(ref.from_system(params))
        logits = (jax.jit(lambda p: transformer_forward(cfg, p, tokens))(
            params), jax.jit(lambda p: ref.forward(PUBLISHED, p, tokens))(
                ref.from_system(params)))
    return dict(params=params, tokens=tokens, labels=labels, got=got,
                want=want, logits=logits)


@pytest.fixture(scope="module")
def both():
    """(params, tokens, labels) and, computed once: the program's logits,
    loss and gradient, and the reference's."""
    return _both(CFG, T)


def off_by(got, want) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(got - want))
                          / jnp.sum(jnp.square(want))))


def test_logits_match_the_reference(both):
    assert off_by(*both["logits"]) < 1e-4


def test_loss_matches_the_reference(both):
    assert abs(float(both["got"][0]) - float(both["want"][0])) < 1e-4


LEAVES = sorted({name for layer in ref.from_system(
    jax.eval_shape(lambda k: transformer_init(k, CFG),
                   jax.random.key(0)))["layers"] for name in layer}) + [
                       "embed_tokens", "norm"]


@pytest.mark.parametrize("name", LEAVES)
def test_gradient_leaf_matches_the_reference(both, name):
    got, want = ref.from_system(both["got"][1]), both["want"][1]
    pairs = [(got[name], want[name])] if name in got else [
        (g[name], w[name]) for g, w in zip(got["layers"], want["layers"])
        if name in g]
    assert pairs
    for g, w in pairs:
        scale = float(jnp.abs(w).max()) or 1.0
        assert float(jnp.abs(g - w).max()) / scale < 1e-4


def test_one_update_of_the_step_matches_the_reference(both):
    """The jitted step on a 1 x 1 x 1 mesh against params - lr x the
    reference's gradient, leaf by leaf, to 1e-6 of the update's energy."""
    mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1},
                         devices=jax.devices()[:1])
    step, _specs = transformer_train_step(CFG, mesh, lr=LR)
    with jax.default_matmul_precision("highest"):
        after, loss = step(both["params"], both["tokens"], both["labels"])
    assert abs(float(loss) - float(both["want"][0])) < 1e-4
    before = ref.from_system(both["params"])
    after = ref.from_system(after)
    want = jax.tree.map(lambda p, g: p - LR * g, before, both["want"][1])
    for b, a, w in zip(jax.tree.leaves(before), jax.tree.leaves(after),
                       jax.tree.leaves(want)):
        moved = float(jnp.sum(jnp.square(w - b)))
        missed = float(jnp.sum(jnp.square(a - w)))
        assert missed <= 1e-6 * moved + 1e-20


def test_a_layer_at_a_time_is_the_references_gradient(both):
    """`make_grads_from` and `make_loss_from` (what the chip run uses: a
    mamba layer a segment at a time, one layer's weights at a time) are
    `loss_of`'s gradient and loss, every leaf yielded once."""
    params = ref.from_system(both["params"])
    want, seen = both["want"][1], []
    for i, grads in ref.make_grads_from(PUBLISHED)(
            params, both["tokens"], both["labels"]):
        for name, g in grads.items():
            seen.append((i, name))
            w = want[name] if i is None else want["layers"][i][name]
            scale = float(jnp.abs(w).max()) or 1.0
            assert float(jnp.abs(g - w).max()) / scale < 1e-4, (i, name)
    assert len(seen) == len(set(seen)) == len(jax.tree.leaves(want))
    loss, logits = ref.make_loss_from(PUBLISHED)(
        params, both["tokens"], both["labels"], logits=True)
    assert abs(loss - float(both["want"][0])) < 1e-4
    assert off_by(logits, both["logits"][1]) < 1e-5


def test_the_reference_in_segments_is_the_reference_whole(both, monkeypatch):
    """A mamba layer 16 tokens at a time, the state and the convolution's
    last inputs carried over, is the layer over the sequence at once."""
    params = ref.from_system(both["params"])
    with jax.default_matmul_precision("highest"):
        whole = ref.forward(PUBLISHED, params, both["tokens"])
        monkeypatch.setattr(ref, "SEGMENT", 16)
        cut = ref.forward(PUBLISHED, params, both["tokens"])
    assert off_by(cut, whole) < 1e-5


def test_a_length_that_is_no_multiple_of_the_chunk_takes_the_padded_path():
    """40 tokens through chunks of 16: the model's scan fills the sequence
    up, and the logits are the reference's."""
    perfvars.reset()
    tf._block_traced_once.cache_clear()
    try:
        out = _both(dataclasses.replace(CFG, max_seq=40), 40)
    finally:
        tf._block_traced_once.cache_clear()
    assert off_by(*out["logits"]) < 1e-4
    assert abs(float(out["got"][0]) - float(out["want"][0])) < 1e-4
    counted = perfvars.snapshot()["scan_lowerings"]
    assert counted["padded"] and not counted["chunked"]


# -- each departure, planted in the program, misses the reference ------------

SCAN, CONV = ssm.scan, ssm.causal_conv


def _shifted_conv(x, w, bias):
    """A convolution whose last tap weighs the NEXT token."""
    return jnp.roll(CONV(x, w, bias), -1, axis=1)


def _scan_without_decay(x, dt, a, b, c, d, chunk=256):
    return SCAN(x, dt, jnp.zeros_like(a), b, c, d, chunk)


def _scan_without_skip(x, dt, a, b, c, d, chunk=256):
    return SCAN(x, dt, a, b, c, jnp.zeros_like(d), chunk)


DEPARTURES = {
    "scores scaled by head_dim ** -0.5": dict(attn_scale=0.0),
    "no embedding multiplier": dict(embed_multiplier=1.0),
    "no residual multiplier": dict(residual_multiplier=1.0),
    "logits not divided": dict(logits_divisor=1.0),
    "rotated queries and keys": dict(rope_full_layers=True),
}
PATCHES = {
    "the convolution sees the next token": ("causal_conv", _shifted_conv),
    "a state that never decays": ("scan", _scan_without_decay),
    "no skip term": ("scan", _scan_without_skip),
}


@pytest.mark.parametrize("what", sorted(DEPARTURES) + sorted(PATCHES) + [
    "dt without its softplus", "the norm before the gate"])
def test_a_planted_departure_misses_the_reference(both, what, monkeypatch):
    cfg = CFG
    if what in DEPARTURES:
        cfg = dataclasses.replace(CFG, **DEPARTURES[what])
    elif what in PATCHES:
        monkeypatch.setattr(ssm, *PATCHES[what])
    elif what == "dt without its softplus":
        monkeypatch.setattr(jax.nn, "softplus", jnp.abs)
    else:       # RMSNorm(y) x silu(z) for RMSNorm(y x silu(z))
        real = tf._rms_norm
        width = CFG.ssm_inner

        def norm_first(x, scale, eps=1e-6):
            if scale.shape != (width,):
                return real(x, scale, eps)
            return x * jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                + eps) ** -1 * scale
        monkeypatch.setattr(tf, "_rms_norm", norm_first)
        monkeypatch.setattr(
            jax.nn, "silu", lambda z: z if z.shape[-1] == width
            else z * jax.nn.sigmoid(z))
    tf._block_traced_once.cache_clear()
    try:
        with jax.default_matmul_precision("highest"):
            got = jax.jit(lambda p: transformer_forward(
                cfg, p, both["tokens"]))(both["params"])
    finally:
        tf._block_traced_once.cache_clear()
    assert off_by(got, both["logits"][1]) > 1e-3, what


# -- one trace a kind, the counters, and what is refused ----------------------

def test_ten_layers_of_two_mixers_are_two_traces_and_counted():
    """m m m m m A m m m m: the nine state-space layers share one trace of
    the block, the attention layer has its own; `mixer_kinds` counts a
    trace each, `scan_lowerings` the one scan, `attn_lowerings` the one
    attention."""
    cfg = dataclasses.replace(
        CFG, n_layers=10, mixer_kinds=["ssm"] * 5 + ["attention"]
        + ["ssm"] * 4, remat_layers=())
    perfvars.reset()
    tf._block_traced_once.cache_clear()
    params = transformer_init(jax.random.key(0), cfg)
    tokens = jnp.zeros((1, T), jnp.int32)
    text = jax.jit(lambda p: transformer_forward(cfg, p, tokens)).lower(
        params).compile().as_text()
    assert tf._block_traced_once.cache_info().currsize == 2
    snap = perfvars.snapshot()
    assert snap["mixer_kinds"] == {"attention": 1, "ssm": 1}
    assert snap["scan_lowerings"] == {"chunked": 1, "padded": 0}
    assert sum(snap["attn_lowerings"].values()) == 1
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("in_proj", "conv", "scan", "gate_norm", "out_proj"):
        assert [n for n in names if "layer_0" in n
                and f"/mixer/{scope}/" in n], scope
    at_5 = [n for n in names if "layer_5" in n]
    assert [n for n in at_5 if "/attn/" in n]
    assert not [n for n in at_5 if "/mixer/" in n]
    assert not [n for n in names if "layer_0" in n and "/attn/" in n]
    tf._block_traced_once.cache_clear()
    perfvars.reset()
    assert perfvars.snapshot()["mixer_kinds"] == {"attention": 0, "ssm": 0}
    assert perfvars.snapshot()["scan_lowerings"] == {"chunked": 0, "padded": 0}


def test_a_state_space_layers_conv_scope_holds_the_kernel_where_selected(
        kernel_backend):
    """Wide enough for the contract (512 + 2 x 128 channels from column 512
    of the in-projection's product, 128 tokens), the traced gradient holds
    `conv_silu_fwd` and `conv_silu_bwd` under `mixer/conv` and no pad or
    shifted-slice chain; on the CPU the chain and no kernel."""
    from test_conv_kernel import check_the_conv_scope
    check_the_conv_scope(TransformerConfig(
        vocab=64, d_model=256, n_heads=4, n_layers=1, d_ff=128, max_seq=128,
        dtype=jnp.float32, mixer_kinds=["ssm"], ssm_expand=2, ssm_heads=8,
        ssm_head_dim=64, ssm_state=128, ssm_conv=4, ssm_chunk=128,
        rope_full_layers=False, dense_gated=True), kernel_backend)


def test_attention_alone_at_multipliers_of_one_traces_what_it_traced():
    """A model without a state-space layer, its mixers named or not, its
    multipliers 1.0: the same jaxpr, equation for equation; the three
    multipliers and the score scale each add theirs when set."""
    base = TransformerConfig(vocab=V, d_model=64, n_heads=8, n_layers=2,
                             d_ff=96, max_seq=T, dtype=jnp.float32,
                             n_kv_heads=2)
    named = dataclasses.replace(
        base, mixer_kinds=["attention"] * 2, embed_multiplier=1.0,
        residual_multiplier=1.0, logits_divisor=1.0, attn_scale=0.0)
    params = transformer_init(jax.random.key(0), base)
    tokens = jnp.zeros((1, T), jnp.int32)

    def jaxpr(cfg):
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
    for field, value in (("embed_multiplier", 12.0), ("attn_scale", 1 / 16),
                         ("residual_multiplier", 0.22),
                         ("logits_divisor", 8.0)):
        assert jaxpr(dataclasses.replace(base, **{field: value})) != want


def test_a_program_without_a_state_space_layer_never_imports_the_scan():
    """Set-up of the other programs pays nothing for the new layer kind: a
    fresh process that traces the flagship's forward pass has not imported
    `tpu_mpi.parallel.ssm`; one that traces a state-space layer has."""
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
            "mixer_kinds=['ssm', 'attention'], ssm_heads=4, ssm_head_dim=16, "
            "ssm_state=8", "True")):
        out = subprocess.run(
            [sys.executable, "-c", code.format(root=ROOT, extra=extra)],
            env=env, capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().splitlines()[-1] == want, extra


@pytest.mark.parametrize("axes", [{"dp": 1, "tp": 2, "sp": 1},
                                  {"dp": 1, "tp": 1, "sp": 2},
                                  {"dp": 2, "tp": 2, "sp": 2}])
def test_the_step_refuses_state_space_layers_under_tp_or_sp(axes):
    n = axes["dp"] * axes["tp"] * axes["sp"]
    mesh = xla.make_mesh(axes, devices=jax.devices()[:n])
    with pytest.raises(NotImplementedError, match="tp 1 and sp 1"):
        transformer_train_step(CFG, mesh, lr=LR)


def test_the_step_takes_state_space_layers_under_dp():
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


@pytest.mark.parametrize("fields, match", [
    (dict(mixer_kinds=["ssm", "attention"]), "mixer_kinds"),
    (dict(mixer_kinds=["ssm", "ssm", "linear", "ssm"]), "mixer"),
    (dict(ssm_heads=7), "ssm_heads"),
    (dict(ssm_head_dim=8), "ssm_heads"),
    (dict(ssm_state=0), "ssm_state"),
    (dict(ssm_expand=4), "ssm_expand"),
])
def test_what_the_configuration_refuses(fields, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **fields)


def test_a_state_space_layers_leaves_and_the_others_defaults():
    """A state-space layer has the mixer's eight leaves in attention's
    place (the recurrence's three scalars a head in float32) and the
    model's FFN; a configuration without the new fields has no new leaf."""
    params = transformer_init(jax.random.key(0), dataclasses.replace(
        CFG, dtype=jnp.bfloat16))
    mamba, attention = params["layers"][0], params["layers"][2]
    assert sorted(mamba) == sorted([
        "ln1", "w_ssm_in", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
        "ssm_norm", "w_ssm_out", "ln2", "w_in", "w_gate", "w_out"])
    assert sorted(attention) == sorted([
        "ln1", "w_q", "w_k", "w_v", "w_proj", "ln2", "w_in", "w_gate",
        "w_out"])
    assert mamba["w_ssm_in"].shape == (64, 128 + 128 + 2 * 32 + 8)
    assert mamba["conv_w"].shape == (4, 128 + 2 * 32)
    for name in ("dt_bias", "a_log", "d_skip"):
        assert mamba[name].dtype == jnp.float32 and mamba[name].shape == (8,)
    np.testing.assert_allclose(np.exp(np.asarray(mamba["a_log"])),
                               np.arange(1, 9), rtol=1e-6)
    dt = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 1e-1 * 1.001).all()
    specs = tf.transformer_param_specs(CFG, "tp")
    assert jax.tree.structure(specs) == jax.tree.structure(
        jax.tree.map(lambda a: 0, params))
    layer = transformer_init(jax.random.key(0),
                             TransformerConfig())["layers"][0]
    assert sorted(layer) == ["ln1", "ln2", "w_in", "w_out", "w_proj", "w_qkv"]
