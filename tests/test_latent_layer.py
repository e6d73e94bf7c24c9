"""Latent attention in sandwich-normed blocks (two normed latents, heads
up-projected into an unrotated and a rotated part, ONE rotary key a token
shared by all heads, values of a width of their own, an RMSNorm on each
half's output), this rank holding a share of the heads and of the experts,
through the program's normal path against the plain reference
(yardstick/reference/lm_latent_train_step.py): a dense and four sparse
layers at a small size, seeded random weights, float32; each departure
planted and caught; the shares of all ranks add up to the uncut layer; one
trace a layer kind, and what the configuration refuses."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
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
from tpu_mpi.parallel import ep, ring                           # noqa: E402
from yardstick.reference import lm_latent_train_step as ref     # noqa: E402

L, T, V = 5, 32, 128
# the published keys the reference reads, at a small size: the widths all
# differ (16 + 8 wide scores beside 24-wide values), 4 of 8 heads and 4 of 16
# experts are here
PUBLISHED = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=40, kv_lora_rank=24,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24, rope_theta=25600000,
    rms_norm_eps=1e-5, sandwich_norm=True, num_hidden_layers=L,
    first_k_dense_replace=1, scoring_func="sigmoid", num_experts_per_tok=4,
    norm_topk_prob=True, routed_scaling_factor=2.5, router_num_experts=16,
    held_experts_first=4, n_routed_experts=4)
CFG = TransformerConfig(
    vocab=V, d_model=64, n_heads=8, n_layers=L, d_ff=32, max_seq=T,
    dtype=jnp.float32, norm_eps=1e-5, n_experts=16, experts_per_tok=4,
    tie_embeddings=False, d_head=16, rope_theta=25600000.0,
    ffn_kinds=["dense"] + ["sparse"] * 4, d_ff_dense=96, dense_gated=True,
    n_shared_experts=1, router_score="sigmoid", router_renorm=True,
    router_scale=2.5, experts_held=[4, 4],
    remat_layers=["ffn", "", "ffn", "", ""], kv_latent=24, q_latent=40,
    d_rope=8, d_value=24, heads_held=[4, 4], norm_out=True)
LR = 0.01


@pytest.fixture(scope="module")
def both():
    """(params, tokens, labels) and, computed once: the program's logits,
    loss and gradient, and the reference's."""
    params = transformer_init(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (2, T), 0, V)
    labels = jnp.roll(tokens, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(lambda p: tf._xent(
            tf._forward(CFG, p, tokens)[0], labels)))(params)
        want = jax.jit(jax.value_and_grad(lambda p: ref.loss_of(
            PUBLISHED, p, tokens, labels)))(ref.from_system(params))
        logits = (jax.jit(lambda p: transformer_forward(CFG, p, tokens))(
            params), jax.jit(lambda p: ref.forward(PUBLISHED, p, tokens)[0])(
                ref.from_system(params)))
    return dict(params=params, tokens=tokens, labels=labels, got=got,
                want=want, logits=logits)


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
                       "embed_tokens", "norm", "lm_head"]


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


def test_a_few_leaves_at_a_time_is_the_references_gradient(both, monkeypatch):
    """`make_grads_from` (what the chip run uses) is `loss_of`'s gradient,
    with the groups of leaves cut as small as the layer allows (every leaf
    of more than 2000 elements alone) and every leaf yielded once."""
    monkeypatch.setattr(ref, "GROUP_ELEMENTS", 2000)
    params = ref.from_system(both["params"])
    assert len(ref.leaf_groups(params["layers"][1])) > 4
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
        params, both["tokens"], both["labels"])
    assert abs(loss - float(both["want"][0])) < 1e-4
    assert off_by(logits, both["logits"][1]) < 1e-5


# -- each departure, planted in the program, misses the reference ------------

def _rotary_key_per_head(q, k, v, window=0, rope=()):
    """`local_attention` whose head j reads a rotary key of its own: the
    shared one scaled by the head (a key a head is what a projection of the
    model's width into heads x rope would give)."""
    q2, k2 = rope
    h = q.shape[1]
    gain = 1.0 + jnp.arange(h, dtype=k2.dtype).reshape(1, h, 1, 1) / h
    return ring.local_attention(q, k, v, window, (q2, k2 * gain))


def _scale_by_the_unrotated_width(q, k, v, window=0, rope=()):
    q2, k2 = rope
    fix = ((q.shape[3] + q2.shape[3]) / q.shape[3]) ** 0.5
    return ring.local_attention(q * fix, k, v, window, (q2 * fix, k2))


def _without(params, *names):
    return dict(params, layers=[{k: v for k, v in layer.items()
                                 if k not in names}
                                for layer in params["layers"]])


def _rope_everywhere(cfg, layer, y, positions, **kw):
    """`_latent_attn` that rotates the unrotated parts too."""
    real = tf.local_attention

    def rotated(q, k, v, window=0, rope=()):
        return real(tf._rope(q, positions, cfg.rope_theta),
                    tf._rope(k, positions, cfg.rope_theta), v, window, rope)
    tf.local_attention = rotated
    try:
        return LATENT(cfg, layer, y, positions, **kw)
    finally:
        tf.local_attention = real


LATENT = tf._latent_attn
DEPARTURES = {
    "no output norm": dict(norm_out=False),
    "softmax for sigmoid": dict(router_score="softmax"),
    "weights not renormalised": dict(router_renorm=False),
    "RoPE theta 1e4": dict(rope_theta=1e4),
}


@pytest.mark.parametrize("what", sorted(DEPARTURES) + [
    "RoPE on the unrotated part", "a rotary key a head",
    "scale 16^-0.5 for 24^-0.5", "no norm on the query latent",
    "no norm on the key/value latent", "a head share off by one"])
def test_a_planted_departure_misses_the_reference(both, what, monkeypatch):
    cfg, params = CFG, both["params"]
    # every scale is one at initialisation: a norm that is taken away must
    # show through the normalisation, so the scales stay and the norm goes
    if what in DEPARTURES:
        cfg = dataclasses.replace(CFG, **DEPARTURES[what])
        if what == "no output norm":
            params = _without(params, "ln1_out", "ln2_out")
    elif what == "RoPE on the unrotated part":
        monkeypatch.setattr(tf, "_latent_attn", _rope_everywhere)
    elif what == "a rotary key a head":
        monkeypatch.setattr(tf, "local_attention", _rotary_key_per_head)
    elif what == "scale 16^-0.5 for 24^-0.5":
        monkeypatch.setattr(tf, "local_attention",
                            _scale_by_the_unrotated_width)
    elif what.startswith("no norm on the"):
        skipped = "q_latent_norm" if "query" in what else "kv_latent_norm"
        real = tf._rms_norm
        monkeypatch.setattr(
            tf, "_rms_norm", lambda x, scale, eps=1e-6: x * scale
            if scale.shape == params["layers"][0][skipped].shape
            else real(x, scale, eps))
    else:       # heads [3, 7) of the same weights' columns: one head wrong
        def shifted(w, width, axis):
            w = jnp.moveaxis(w, axis, 0)
            w = jnp.roll(w.reshape((4, width) + w.shape[1:]), 1, axis=0)
            return jnp.moveaxis(w.reshape((4 * width,) + w.shape[2:]), 0, axis)
        params = dict(params, layers=[
            dict(layer, w_uq=shifted(layer["w_uq"], 24, 1))
            for layer in params["layers"]])
    tf._block_traced_once.cache_clear()
    try:
        with jax.default_matmul_precision("highest"):
            got = jax.jit(lambda p: transformer_forward(
                cfg, p, both["tokens"]))(params)
    finally:
        tf._block_traced_once.cache_clear()
    assert off_by(got, both["logits"][1]) > 1e-3, what


# -- the share ties to the model ----------------------------------------------

def _uncut_layer(share: int):
    """An uncut sparse layer at 8 heads and 32 experts with the published
    ratios (top 8), its weights, and the shares' configurations: `share`
    heads a rank, 4 experts a rank."""
    d, f, e, k, h = 32, 16, 32, 8, 8
    model = dict(PUBLISHED, hidden_size=d, num_attention_heads=h,
                 router_num_experts=e, num_experts_per_tok=k,
                 held_experts_first=0, n_routed_experts=e,
                 num_hidden_layers=1, first_k_dense_replace=0)
    cfg = dataclasses.replace(
        CFG, d_model=d, d_ff=f, n_experts=e, experts_per_tok=k, n_layers=1,
        ffn_kinds=["sparse"], remat_layers=[""], experts_held=(),
        heads_held=())
    whole = transformer_init(jax.random.key(3), cfg)["layers"][0]
    return model, cfg, whole


@pytest.mark.parametrize("share", [4, 2], ids=["two shares of the heads",
                                               "four shares of the heads"])
def test_the_shares_add_up_to_the_uncut_layer(share):
    """With the output norms set aside (RMSNorm is not linear: a partial sum
    is normed as it stands, which the configuration's file says), the
    partial `a` of every head share and the held experts' parts over all 8
    expert shares, the shared expert counted once, add up to the uncut
    reference's two halves."""
    model, cfg, whole = _uncut_layer(share)
    t, d = 48, cfg.d_model
    x = jax.random.normal(jax.random.key(4), (1, t, d), jnp.float32)
    rp = {ref.NAMES[n]: w for n, w in whole.items()}
    positions = jnp.arange(t)
    with jax.default_matmul_precision("highest"):
        # the uncut halves, by the reference
        h1 = ref.rms_norm(x, rp["input_layernorm"], 1e-5)
        uncut_a = ref.attention(model, rp, h1) @ rp["o_proj"]
        y = ref.rms_norm(x, rp["pre_mlp_layernorm"], 1e-5)[0]
        _s, _i, dense = ref.route(model, rp, y)
        shared = ref.gated(y, rp["shared_gate_proj"], rp["shared_up_proj"],
                           rp["shared_down_proj"])
        uncut_f = ref.held_experts_mix(model, rp, y, dense) + shared

        # the head shares' partial sums, by the program
        def columns(w, first, count, width):
            return w.reshape(w.shape[0], 8, width)[:, first:first + count] \
                .reshape(w.shape[0], count * width)
        total = jnp.zeros_like(uncut_a)
        for first in range(0, 8, share):
            mine = dict(
                whole, w_uq=columns(whole["w_uq"], first, share, 24),
                w_ukv=columns(whole["w_ukv"], first, share, 40),
                w_proj=whole["w_proj"].reshape(8, 24, d)[first:first + share]
                .reshape(share * 24, d))
            held = dataclasses.replace(cfg, heads_held=(first, share))
            total = total + jax.jit(lambda lp, x: tf._attn(
                held, lp, x, positions, h_local=share, tp_axis=None,
                sp_axis=None))(mine, x)
        assert off_by(total, uncut_a) < 1e-5

        # the expert shares' parts, the shared expert once
        routed, slots = jnp.zeros_like(uncut_f), 0
        for first in range(0, cfg.n_experts, 4):
            mine = {n: (w[first:first + 4] if n in ("w_gate", "w_in", "w_out")
                        else w) for n, w in whole.items()}
            held = dataclasses.replace(cfg, experts_held=(first, 4))
            out, (_p, sent, did) = jax.jit(
                lambda lp, y: tf._expert_ffn(held, lp, y))(mine, y[None])
            routed = routed + (out[0] - shared)
            assert int(did[0]) == int(sent[first:first + 4].sum())
            slots += int(did[0])
        assert slots == t * cfg.experts_per_tok
        assert off_by(routed + shared, uncut_f) < 1e-5


def test_an_overflowing_batch_is_computed_whole(both, monkeypatch, request):
    """A buffer an eighth of the expected rows: the further buffers run,
    every held slot is computed, and loss and gradient are still the
    reference's (the recomputed buffers' backward pass too)."""
    monkeypatch.setattr(ep, "HELD_ROWS_FACTOR", 0.125)
    tf._block_traced_once.cache_clear()     # a trace holds the buffer's rows
    request.addfinalizer(tf._block_traced_once.cache_clear)
    tokens = jnp.tile(both["tokens"], (8, 1))       # 512 tokens, 2048 slots
    assert ep.held_row_buffer(2048, 16, 4, 512) == 128
    labels = jnp.roll(tokens, -1, axis=1)
    slots, did = jax.jit(lambda p: tf.transformer_held_counts(
        CFG, p, tokens))(both["params"])
    held = slots[:, 4:8].sum(axis=1)
    assert (held > 128).all() and (did[:, 0] == held).all()
    assert (did[:, 1] == 2048).all() and did[:, 2].all()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(lambda p: tf._xent(
            tf._forward(CFG, p, tokens)[0], labels)))(both["params"])
        want = jax.jit(jax.value_and_grad(lambda p: ref.loss_of(
            PUBLISHED, p, tokens, labels)))(ref.from_system(both["params"]))
    assert abs(float(got[0]) - float(want[0])) < 1e-4
    for g, w in zip(jax.tree.leaves(ref.from_system(got[1])),
                    jax.tree.leaves(want[1])):
        scale = float(jnp.abs(w).max()) or 1.0
        assert float(jnp.abs(g - w).max()) / scale < 1e-4


@pytest.mark.parametrize("slots, n_experts, held, tokens, rows", [
    (8192 * 8, 128, 8, 8192, 8192),     # twice the balanced rows = the tokens
    (4096 * 8, 256, 8, 4096, 4096),     # a 32nd of the experts: the tokens
    (4096 * 8, 256, 64, 4096, 16384),   # a quarter of them: twice balanced
    (1024, 16, 4, 256, 512), (1024, 16, 4, 0, 512), (64, 4, 4, 64, 128),
])
def test_the_held_buffer_holds_what_one_hot_expert_can_be_sent(
        slots, n_experts, held, tokens, rows):
    assert ep.held_row_buffer(slots, n_experts, held, tokens) == rows


# -- one trace a kind, the counter, and what is refused -----------------------

def test_a_program_traces_each_layer_kind_once_and_counts_latent_calls():
    """Five layers of three kinds (dense + ffn, sparse, sparse + ffn): three
    traces of the block, five attention calls noted, all of them latent."""
    perfvars.reset()
    tf._block_traced_once.cache_clear()
    params = transformer_init(jax.random.key(0), CFG)
    tokens = jnp.zeros((1, T), jnp.int32)
    jax.jit(lambda p: transformer_forward(CFG, p, tokens)).lower(params)
    assert tf._block_traced_once.cache_info().currsize == 3
    snap = perfvars.snapshot()
    assert snap["attn_kinds"] == {"latent": "plain"}
    # a kind is traced once and called by its other layers
    assert snap["attn_lowerings"] == {"fused": 0, "plain": 3}
    tf._block_traced_once.cache_clear()


@pytest.mark.parametrize("fields, match", [
    (dict(d_rope=0), "together"),
    (dict(n_kv_heads=2), "no window"),
    (dict(heads_held=[6, 4]), "heads_held"),
    (dict(heads_held=[0, 0]), "heads_held"),
])
def test_what_the_configuration_refuses(fields, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **fields)
    with pytest.raises(ValueError, match="heads_held"):
        TransformerConfig(heads_held=[0, 2])    # no latent attention


def test_defaults_leave_the_other_programs_their_leaves():
    """A configuration without the new fields has no new leaf and holds all
    its heads."""
    cfg = TransformerConfig()
    layer = transformer_init(jax.random.key(0), cfg)["layers"][0]
    assert sorted(layer) == ["ln1", "ln2", "w_in", "w_out", "w_proj", "w_qkv"]
    assert cfg.n_heads_here == cfg.n_heads and cfg.value_dim == cfg.head_dim
