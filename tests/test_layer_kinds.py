"""A stack whose layers differ in kind (window or full grouped-query
attention, a dense gated FFN or routed experts beside a shared one, this
rank holding a share of the experts) through the program's normal path,
against the plain reference (yardstick/reference/lm_kinds_train_step.py):
every kind in one 6-layer list at a small size, seeded random weights,
float32; each departure planted and caught; the shares of all ranks add up
to the uncut layer; the fused kernel on the interpret machine against the
plain path for a window and for grouped heads; one trace a layer kind."""

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
from tpu_mpi.models import transformer as tf                    # noqa: E402
from tpu_mpi.models.transformer import (TransformerConfig,      # noqa: E402
                                        transformer_forward,
                                        transformer_held_counts,
                                        transformer_init,
                                        transformer_train_step)
from tpu_mpi.parallel import ep, ring                           # noqa: E402
from yardstick.reference import lm_kinds_train_step as ref      # noqa: E402

L, T, V = 6, 32, 128
ATTN = ["sliding_attention", "sliding_attention", "sliding_attention",
        "full_attention", "sliding_attention", "full_attention"]
MLP = ["dense", "sparse", "sparse", "sparse", "sparse", "dense"]
# the published keys the reference reads, at a small size: every kind of
# layer is in the list (window + dense, window + sparse, full + sparse,
# full + dense)
PUBLISHED = dict(
    hidden_size=64, num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    layer_types=ATTN, mlp_layer_types=MLP, sliding_window=8,
    num_hidden_layers=L, rope_parameters={"rope_theta": 1000000,
                                          "rope_type": "default"},
    rms_norm_eps=1e-5, scoring_func="sigmoid", num_experts_per_tok=4,
    norm_topk_prob=True, routed_scaling_factor=2.5, n_group=1, topk_group=1,
    router_num_experts=16, held_experts_first=4, num_experts=4)
CFG = TransformerConfig(
    vocab=V, d_model=64, n_heads=8, n_layers=L, d_ff=32, max_seq=T,
    dtype=jnp.float32, norm_eps=1e-5, n_experts=16, experts_per_tok=4,
    tie_embeddings=False, d_head=16, n_kv_heads=2, qk_norm_heads=True,
    rope_theta=1e6, attn_windows=[8, 8, 8, 0, 8, 0], rope_full_layers=False,
    ffn_kinds=MLP, d_ff_dense=96, dense_gated=True, n_shared_experts=1,
    router_score="sigmoid", router_renorm=True, router_scale=2.5,
    experts_held=[4, 4], remat_layers=["ffn", "", "ffn", "", "", ""])
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
    """The jitted, donated step on a 1 x 1 x 1 mesh against params - lr x
    the reference's gradient, leaf by leaf, to 1e-6 of the update's
    energy."""
    from tpu_mpi import xla
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
    """`make_grads_from` (what the chip run uses) is `loss_of`'s gradient."""
    params = ref.from_system(both["params"])
    want = both["want"][1]
    for i, grads in ref.make_grads_from(PUBLISHED)(
            params, both["tokens"], both["labels"]):
        for name, g in grads.items():
            w = want[name] if i is None else want["layers"][i][name]
            scale = float(jnp.abs(w).max()) or 1.0
            assert float(jnp.abs(g - w).max()) / scale < 1e-4, (i, name)
    loss, logits = ref.make_loss_from(PUBLISHED)(
        params, both["tokens"], both["labels"])
    assert abs(loss - float(both["want"][0])) < 1e-4
    assert off_by(logits, both["logits"][1]) < 1e-5


# -- each departure, planted in the program, misses the reference ------------

def _kv_head_modulo(q, k, v, window=0):
    """`local_attention` with query head j reading key/value head j % kv."""
    group = q.shape[1] // k.shape[1]
    return ring.local_attention(q, jnp.tile(k, (1, group, 1, 1)),
                                jnp.tile(v, (1, group, 1, 1)), window)


def _drop_a_slot(tokens, idx, weights, *args, **kw):
    """`moe_dropless_held` that loses the first held slot it is given."""
    first, held = args[2], args[3]
    here = jnp.logical_and(idx >= first, idx < first + held)
    flat = here.reshape(-1)
    lost = jnp.argmax(flat)                     # the first held slot
    keep = jnp.arange(flat.size) != lost
    return ep.moe_dropless_held(
        tokens, idx, weights * keep.reshape(weights.shape), *args, **kw)


DEPARTURES = {
    "no window": dict(attn_windows=[0] * L),
    "window off by one": dict(attn_windows=[9, 9, 9, 0, 9, 0]),
    "RoPE on the full layer": dict(rope_full_layers=True),
    "softmax for sigmoid": dict(router_score="softmax"),
    "weights not renormalised": dict(router_renorm=False),
    "scale 1": dict(router_scale=1.0),
    "no QK-norm": dict(qk_norm_heads=False),
    "RoPE theta 1e4": dict(rope_theta=1e4),
}


@pytest.mark.parametrize("what", sorted(DEPARTURES) + [
    "key/value head j % 8", "shared expert missing", "a dropped slot"])
def test_a_planted_departure_misses_the_reference(both, what, monkeypatch):
    cfg, params = CFG, both["params"]
    if what in DEPARTURES:
        cfg = dataclasses.replace(CFG, **DEPARTURES[what])
    elif what == "key/value head j % 8":
        monkeypatch.setattr(tf, "local_attention", _kv_head_modulo)
    elif what == "shared expert missing":
        params = dict(params, layers=[
            {k: (jnp.zeros_like(v) if k == "w_shared_out" else v)
             for k, v in layer.items()} for layer in params["layers"]])
    else:
        monkeypatch.setattr(tf, "moe_dropless_held", _drop_a_slot)
    if what == "no QK-norm":        # its scales are ones: take the norm away
        params = dict(params, layers=[
            {k: v for k, v in layer.items() if k not in ("q_norm", "k_norm")}
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

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One sparse layer at 128 experts, top 8: the routed parts of all 16
    shares of 8 experts plus the shared expert once equal the uncut layer
    of the reference (every expert held)."""
    d, f, e, k, t = 32, 16, 128, 8, 64
    model = dict(PUBLISHED, router_num_experts=e, num_experts_per_tok=k,
                 held_experts_first=0)
    cfg = dataclasses.replace(
        CFG, d_model=d, d_ff=f, n_experts=e, experts_per_tok=k, n_layers=1,
        attn_windows=[0], ffn_kinds=["sparse"], remat_layers=[""],
        experts_held=())
    keys = jax.random.split(jax.random.key(3), 8)
    y = jax.random.normal(keys[0], (1, t, d), jnp.float32)
    def normal(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
    lp = {"w_router": normal(keys[1], (d, e), d),
          "w_gate": normal(keys[2], (e, d, f), d),
          "w_in": normal(keys[3], (e, d, f), d),
          "w_out": normal(keys[4], (e, f, d), f),
          "w_shared_gate": normal(keys[5], (d, f), d),
          "w_shared_in": normal(keys[6], (d, f), d),
          "w_shared_out": normal(keys[7], (f, d), f)}
    with jax.default_matmul_precision("highest"):
        rp = {ref.NAMES[n]: w for n, w in lp.items()}
        _s, _i, dense = ref.route(model, rp, y[0])
        uncut = ref.held_experts_mix(model, rp, y[0], dense) + ref.gated(
            y[0], rp["shared_gate_proj"], rp["shared_up_proj"],
            rp["shared_down_proj"])
        shared = ref.gated(y[0], rp["shared_gate_proj"], rp["shared_up_proj"],
                           rp["shared_down_proj"])
        total, slots = jnp.zeros((t, d)), 0
        for first in range(0, e, 8):
            share = dataclasses.replace(cfg, experts_held=(first, 8))
            mine = {n: (w[first:first + 8] if n in ("w_gate", "w_in", "w_out")
                        else w) for n, w in lp.items()}
            out, (_p, sent, did) = jax.jit(
                lambda lp, y: tf._expert_ffn(share, lp, y))(mine, y)
            total = total + (out[0] - shared)       # its routed part alone
            assert int(did[0]) == int(sent[first:first + 8].sum())
            slots += int(did[0])
        assert slots == t * k
        assert off_by(total + shared, uncut) < 1e-5
        whole, _sent = jax.jit(lambda lp, y: tf._expert_ffn(cfg, lp, y))(lp, y)
        assert off_by(whole[0], uncut) < 1e-5


@pytest.mark.parametrize("factor", [0.25, 2.0])
def test_nothing_is_dropped_when_more_slots_arrive_than_the_buffer_holds(
        both, factor, monkeypatch, request):
    """A buffer a quarter of the expected rows (128 rows for about 256 held
    slots): the further buffers run, every held slot is computed and the
    logits are the reference's."""
    monkeypatch.setattr(ep, "HELD_ROWS_FACTOR", factor)
    tf._block_traced_once.cache_clear()     # a trace holds the buffer's rows
    request.addfinalizer(tf._block_traced_once.cache_clear)
    cfg = CFG
    tokens = jax.random.randint(jax.random.key(9), (8, T), 0, V)
    slots, did = jax.jit(lambda p: transformer_held_counts(
        cfg, p, tokens))(both["params"])
    slots, did = np.asarray(slots), np.asarray(did)
    held = slots[:, 4:8].sum(axis=1)
    assert (slots.sum(axis=1) == 8 * T * 4).all() and (held > 128).all()
    assert (did[:, 0] == held).all()
    assert did[:, 2].all() == (factor < 1) == did[:, 2].any()
    assert (did[:, 1] == (1024 if factor < 1 else 512)).all()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: transformer_forward(cfg, p, tokens))(
            both["params"])
        want = jax.jit(lambda p: ref.forward(PUBLISHED, p, tokens)[0])(
            ref.from_system(both["params"]))
    assert off_by(got, want) < 1e-4


# -- the fused kernel, a window and grouped heads -----------------------------

@pytest.mark.parametrize("window", [0, 128, 200], ids=["full", "w128", "w200"])
@pytest.mark.parametrize("block", [128, 256])
def test_the_kernel_equals_the_plain_path(kernel_backend, window, block):
    """`causal_attention` on the interpret machine, 64 query heads reading 8
    key/value heads as the model has them (at 8 and 2 here), forward and
    gradients, against `ring.plain_attention`. One jitted program
    each, waited for (.claude/skills/verify: the interpret machine)."""
    from tpu_mpi.xla import pallas_kernels as pk
    b, h, hk, t, dh = 1, 8, 2, 512, 128
    ks = jax.random.split(jax.random.key(5), 4)
    q = jax.random.normal(ks[0], (b, h, t, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, hk, t, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, hk, t, dh), jnp.float32)
    w = jax.random.normal(ks[3], (b, h, t, dh), jnp.float32)
    want = jax.block_until_ready(jax.jit(jax.value_and_grad(
        lambda q, k, v: (ring.plain_attention(q, k, v, window) * w).sum(),
        (0, 1, 2)))(q, k, v))
    got = jax.block_until_ready(jax.jit(jax.value_and_grad(
        lambda q, k, v: (pk.causal_attention(
            q, k, v, window=window, block_q=block, block_k=block,
            interpret=True) * w).sum(), (0, 1, 2)))(q, k, v))
    assert abs(float(got[0]) - float(want[0])) < 1e-3
    for g, x in zip(got[1], want[1]):
        assert float(jnp.abs(g - x).max()) < 1e-4
    # and `local_attention` selects it where the backend says so
    kernel_backend("interpret")
    assert ring.fused_attention_selected(q.shape, q.dtype)
    kernel_backend(None)
    assert not ring.fused_attention_selected(q.shape, q.dtype)


@pytest.mark.parametrize("t,bq,bk,window,want", [
    (8192, 512, 512, 0, (16, 16, 136)),     # the diagonal and below
    (8192, 512, 512, 128, (2, 2, 31)),      # a block and the one before it
    (8192, 128, 128, 128, (2, 2, 127)),
    (8192, 256, 256, 128, (2, 2, 63)),
    (1024, 512, 512, 0, (2, 2, 3)),         # the flagship's, as before
    (512, 128, 128, 200, (3, 3, 9)),
])
def test_the_walk_of_the_kernel_by_hand(t, bq, bk, window, want):
    from tpu_mpi.xla import pallas_kernels as pk
    assert pk.causal_attention_walk(t, bq, bk, window) == want


# -- one trace a layer kind ----------------------------------------------------

def test_a_program_traces_each_layer_kind_once():
    """Six layers of four kinds (one of them twice with another `remat`):
    `_attn_ffn_block` is traced once a kind, not once a layer, and the
    attention counter counts once a trace."""
    tf._block_traced_once.cache_clear()
    perfvars.reset()
    calls = []
    real = tf._attn_ffn_block

    def counted(cfg, *args, **kw):
        calls.append(kw["kind"])
        return real(cfg, *args, **kw)
    tf._attn_ffn_block = counted
    try:
        params = transformer_init(jax.random.key(0), CFG)
        tokens = jnp.zeros((1, T), jnp.int32)
        jax.jit(lambda p: transformer_forward(CFG, p, tokens)).lower(params)
    finally:
        tf._attn_ffn_block = real
        tf._block_traced_once.cache_clear()
    kinds = [CFG.layer_kind(i) for i in range(L)]
    assert sorted(calls) == sorted(set(kinds)) and len(set(kinds)) == 5
    built = perfvars.snapshot()["attn_lowerings"]
    assert built == {"fused": 0, "plain": len(set(kinds))}
    assert perfvars.snapshot()["attn_kinds"] == {
        "window": "plain", "full": "plain"}


def test_the_default_config_has_one_kind_and_its_fields_are_the_flagships():
    cfg = TransformerConfig(n_layers=3)
    assert {cfg.layer_kind(i) for i in range(3)} == {tf.LayerKind(0, False, "")}
    assert cfg.head_dim == cfg.d_model // cfg.n_heads
    assert cfg.n_experts_here == 0 and not cfg.experts_held
    params = transformer_init(jax.random.key(0), cfg)
    assert sorted(params["layers"][0]) == ["ln1", "ln2", "w_in", "w_out",
                                           "w_proj", "w_qkv"]
    with pytest.raises(ValueError):
        TransformerConfig(n_layers=3, attn_windows=[8, 8])
