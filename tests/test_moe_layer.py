"""The sparse-expert block of `models/transformer.py` + `parallel/ep.py`
against its plain reference (`yardstick/reference/lm_train_step.py`, the one
copy, which the benchmark's generator runs on the chip too), at the
configuration file's rehearse size: float32, seeded, on the CPU.

Tolerances. Both sides compute in float32 here (the reference at `highest`
precision, which on the CPU is what the system's default is too), so they
differ by the order of their sums alone: a few units in the last place of
float32 per matrix multiplication, two layers deep. Logits of size ~1 agree
to 2e-5, the loss (near ln 256 = 5.5) to 1e-5, a gradient leaf to 2e-4 of
its largest entry. Every wrong model below (one expert fewer per token,
renormalised weights, no QK-norm, a bfloat16 router softmax, dropped tokens)
misses the loss by over 1e-3: a hundred times the tolerance."""

import dataclasses
import functools
import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tpu_mpi import xla
from tpu_mpi.models import transformer as tf
from tpu_mpi.models.transformer import (TransformerConfig, transformer_forward,
                                        transformer_init,
                                        transformer_train_step)
from tpu_mpi.parallel.ep import moe_dropless
from yardstick.reference import lm_train_step as ref

LOGITS_ATOL, LOSS_ATOL, GRAD_RTOL, WRONG_BY = 2e-5, 1e-5, 2e-4, 1e-3
LR = 0.01
# yardstick/configs/olmoe-1b-7b-1c.json's `rehearse` block, both halves
CFG = TransformerConfig(
    vocab=256, d_model=64, n_heads=4, n_layers=2, d_ff=32, max_seq=32,
    dtype=jnp.float32, norm_eps=1e-5, qk_norm=True, n_experts=8,
    experts_per_tok=2, router_aux_coef=0.01, tie_embeddings=False,
    remat_attn=True)
MODEL = {"num_attention_heads": 4, "num_experts_per_tok": 2,
         "norm_topk_prob": False, "rms_norm_eps": 1e-5, "rope_theta": 10000,
         "router_aux_loss_coef": 0.01}
BATCH, SEQ = 4, 32


def seeded(seed: int = 0, cfg: TransformerConfig = CFG):
    """(params with every norm scale away from one, tokens, labels)."""
    key = jax.random.key(seed)
    params = transformer_init(jax.random.fold_in(key, 0), cfg)

    def scales(path, leaf):
        if leaf.ndim != 1:
            return leaf
        k = jax.random.fold_in(key, zlib.crc32(
            jax.tree_util.keystr(path).encode()) % 2**31)
        return (1.0 + 0.3 * jax.random.normal(k, leaf.shape)).astype(leaf.dtype)
    params = jax.tree_util.tree_map_with_path(scales, params)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (BATCH, SEQ), 0,
                                cfg.vocab, dtype=jnp.int32)
    return params, tokens, jnp.roll(tokens, -1, axis=1)


def one_chip_step(cfg: TransformerConfig = CFG, **mesh_axes):
    axes = {"dp": 1, "tp": 1, "sp": 1, **mesh_axes}
    n = int(np.prod(list(axes.values())))
    mesh = xla.make_mesh(axes, devices=jax.devices()[:n])
    return transformer_train_step(cfg, mesh, lr=LR)[0]


def system_loss_and_grads(cfg, params, tokens, labels):
    """The loss the step descends and its gradients, read off one SGD step:
    grad = (params - new params) / lr is what the step applied."""
    def loss_fn(p):
        logits, routed = tf._forward(cfg, p, tokens)
        return tf._xent(logits, labels) + cfg.router_aux_coef * \
            tf.load_balancing_loss(routed, tokens.size)
    return jax.value_and_grad(loss_fn)(params)


@pytest.fixture(scope="module")
def both():
    params, tokens, labels = seeded()
    loss, grads = system_loss_and_grads(CFG, params, tokens, labels)
    rparams = ref.from_system(params, CFG.n_heads)
    with jax.default_matmul_precision("highest"):
        rloss, rgrads = jax.value_and_grad(
            lambda p: ref.loss_of(MODEL, p, tokens, labels))(rparams)
        rlogits = ref.forward(MODEL, rparams, tokens)[0]
    return {"logits": transformer_forward(CFG, params, tokens),
            "rlogits": rlogits, "loss": float(loss), "rloss": float(rloss),
            "grads": ref.from_system(grads, CFG.n_heads), "rgrads": rgrads,
            "step_loss": float(one_chip_step()(params, tokens, labels)[1])}


def test_logits_match_the_reference(both):
    np.testing.assert_allclose(both["logits"], both["rlogits"],
                               atol=LOGITS_ATOL, rtol=0)


def test_loss_with_the_auxiliary_term_matches_the_reference(both):
    assert abs(both["loss"] - both["rloss"]) <= LOSS_ATOL
    # the jitted, shard_map'd step reports that same loss
    assert abs(both["step_loss"] - both["rloss"]) <= LOSS_ATOL
    # and the auxiliary term is in it: without it the loss is lower by about
    # coef x experts_per_tok
    params, tokens, labels = seeded()
    bare = system_loss_and_grads(dataclasses.replace(CFG, router_aux_coef=0.0),
                                 params, tokens, labels)[0]
    assert 0.5 * 0.01 * 2 < both["loss"] - float(bare) < 2 * 0.01 * 2


LEAVES = ["embed_tokens", "norm", "lm_head"] + [
    f"layers/{i}/{name}" for i in range(CFG.n_layers) for name in (
        "input_layernorm", "q_proj", "k_proj", "v_proj", "q_norm", "k_norm",
        "o_proj", "post_attention_layernorm", "gate", "gate_proj", "up_proj",
        "down_proj")]


def leaf(tree, path):
    for part in path.split("/"):
        tree = tree[int(part) if part.isdigit() else part]
    return np.asarray(tree)


@pytest.mark.parametrize("path", LEAVES)
def test_gradient_leaf_matches_the_reference(both, path):
    got, want = leaf(both["grads"], path), leaf(both["rgrads"], path)
    assert got.shape == want.shape and np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=GRAD_RTOL * np.abs(want).max())


@pytest.fixture(scope="module")
def stepped():
    """What one step of the jitted train step applied, (params - new
    params) / lr under the reference's names, and the reference's gradient
    taken one layer at a time from a copy of the parameters on the host, as
    the benchmark's generator takes it on the chip."""
    params, tokens, labels = seeded()
    new, _loss = one_chip_step()(params, tokens, labels)
    applied = jax.tree.map(lambda p, n: (p - n) / LR, params, new)
    rparams = jax.device_get(ref.from_system(params, CFG.n_heads))
    parts = {"layers": [None] * CFG.n_layers}
    for i, grads in ref.make_grads_from(MODEL)(rparams, tokens, labels):
        if i is None:
            parts.update(grads)
        else:
            parts["layers"][i] = grads
    return {"applied": ref.from_system(applied, CFG.n_heads), "parts": parts}


@pytest.mark.parametrize("path", LEAVES)
def test_the_steps_update_matches_the_reference(both, stepped, path):
    # the update reads the gradient through p - lr x g in float32: lr x g
    # under 1e-3 of a weight of size 0.1-1 keeps three to four digits of g
    got, want = leaf(stepped["applied"], path), leaf(both["rgrads"], path)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=5e-3 * float(jnp.abs(want).max()) + 1e-5)


@pytest.mark.parametrize("path", LEAVES)
def test_a_layer_at_a_time_is_the_references_gradient(both, stepped, path):
    got, want = leaf(stepped["parts"], path), leaf(both["rgrads"], path)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_the_reference_tree_has_no_other_leaf(both):
    paths = {jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(both["rgrads"])}
    assert len(paths) == len(LEAVES)


WRONG = {
    "one expert fewer per token": {"num_experts_per_tok": 1},
    "renormalised top-k weights": {"norm_topk_prob": True},
    "router softmax in bfloat16": {"router_softmax_dtype": "bfloat16"},
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_a_wrong_model_misses_the_tolerance(both, what):
    params, tokens, labels = seeded()
    model, rparams = {**MODEL, **WRONG[what]}, ref.from_system(params, CFG.n_heads)
    with jax.default_matmul_precision("highest"):
        loss = float(ref.loss_of(model, rparams, tokens, labels))
        logits = np.asarray(ref.forward(model, rparams, tokens)[0])
    # by the logits in every case (a bfloat16 softmax moves a mean loss
    # least, which is why the chip compares logits too), by the loss as well
    # where the routing itself is another
    assert np.abs(logits - both["logits"]).max() > 100 * LOGITS_ATOL
    if "bfloat16" not in what:
        assert abs(loss - both["loss"]) > WRONG_BY > 10 * LOSS_ATOL


def test_norm_topk_prob_false_is_what_runs(both):
    # the system has no such switch: it agrees with the reference at false
    # (test_loss_...) and the reference at true is elsewhere
    params, tokens, labels = seeded()
    with jax.default_matmul_precision("highest"):
        renorm = ref.forward({**MODEL, "norm_topk_prob": True},
                             ref.from_system(params, CFG.n_heads), tokens)[0]
    assert np.abs(np.asarray(renorm) - both["logits"]).max() > 100 * LOGITS_ATOL


def test_without_qk_norm_the_system_misses_the_reference(both):
    params, tokens, labels = seeded()
    bare = dataclasses.replace(CFG, qk_norm=False)
    loss = float(system_loss_and_grads(bare, params, tokens, labels)[0])
    assert abs(loss - both["rloss"]) > WRONG_BY


# -- nothing is dropped -------------------------------------------------------

def biased(params):
    """Weights under which expert 3 takes nearly every token: every
    embedding shares a large component along (1, .., 1), which the residual
    stream keeps, and expert 3's router column points along it."""
    out = jax.tree.map(lambda a: a, params)
    out["embed"] = out["embed"] + 1.0
    for lp in out["layers"]:
        lp["w_router"] = lp["w_router"].at[:, 3].add(0.2 / lp["ln2"])
    return out


def test_a_biased_router_drops_nothing():
    params, tokens, labels = seeded(1)
    params = biased(params)
    counts = np.asarray(tf.transformer_expert_counts(CFG, params, tokens))
    assert counts.shape == (CFG.n_layers, CFG.n_experts)
    assert (counts.sum(axis=1) == BATCH * SEQ * CFG.experts_per_tok).all()
    # the bias works: expert 3 holds far more than a fair share, more than
    # any capacity factor in use would admit
    fair = BATCH * SEQ * CFG.experts_per_tok / CFG.n_experts
    assert counts[:, 3].min() > 2.5 * fair
    with jax.default_matmul_precision("highest"):
        want = ref.forward(MODEL, ref.from_system(params, CFG.n_heads),
                           tokens)[0]
    np.testing.assert_allclose(transformer_forward(CFG, params, tokens), want,
                               atol=LOGITS_ATOL, rtol=0)


def test_dropping_over_capacity_would_miss_the_reference():
    # the layer's own function against the reference's expert mix, whole and
    # with every slot past a capacity of 2 x fair zeroed (what a
    # capacity-bound dispatch does)
    params, tokens, _ = seeded(1)
    lp = biased(params)["layers"][0]
    rp = ref.from_system(biased(params), CFG.n_heads)["layers"][0]
    h = (1.0 + 0.2 * jax.random.normal(jax.random.key(5),
                                       (BATCH, SEQ, CFG.d_model))) * lp["ln2"]
    got, (_probs, slots) = tf._expert_ffn(CFG, lp, h)
    rows = h.reshape(-1, CFG.d_model)
    with jax.default_matmul_precision("highest"):
        _p, idx, dense = ref.route(MODEL, rp, rows)
        whole = ref.experts_mix(rp, rows, dense)
        capacity = 2 * rows.shape[0] * CFG.experts_per_tok // CFG.n_experts
        rank = jnp.cumsum(dense > 0, axis=0)            # arrival order
        dropped = ref.experts_mix(rp, rows, jnp.where(rank <= capacity,
                                                      dense, 0.0))
    assert int(slots.sum()) == rows.shape[0] * CFG.experts_per_tok
    np.testing.assert_allclose(got.reshape(rows.shape), whole, atol=2e-5, rtol=0)
    assert np.abs(np.asarray(dropped - whole)).max() > 1e-2


# -- the same layer over an `ep` axis -----------------------------------------

def layer_over_ep(n: int, rows, idx, weights, w):
    """`moe_dropless` with tokens and experts sharded over `n` devices."""
    def experts_of(w_in, w_gate, w_out):
        def run(xs, sizes, scale):
            hid = jax.nn.silu(lax.ragged_dot(xs, w_gate, sizes)) * \
                lax.ragged_dot(xs, w_in, sizes)
            return lax.ragged_dot(hid * scale[:, None], w_out, sizes)
        return run
    if n == 1:
        return moe_dropless(rows, idx, weights, experts_of(*w), CFG.n_experts)
    mesh = xla.make_mesh({"ep": n}, devices=jax.devices()[:n])

    def local(rows, idx, weights, w_in, w_gate, w_out):
        out, sizes = moe_dropless(rows, idx, weights,
                                  experts_of(w_in, w_gate, w_out),
                                  CFG.n_experts, axis="ep")
        return out, lax.psum(sizes, "ep")
    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("ep"),) * 6,
        out_specs=(P("ep"), P())))(rows, idx, weights, *w)


@pytest.fixture(scope="module")
def routed_rows():
    key = jax.random.key(11)
    rows = jax.random.normal(jax.random.fold_in(key, 0), (64, CFG.d_model))
    probs = jax.nn.softmax(3.0 * jax.random.normal(
        jax.random.fold_in(key, 1), (64, CFG.n_experts)))
    weights, idx = lax.top_k(probs, 3)
    w = tuple(0.1 * jax.random.normal(jax.random.fold_in(key, 2 + i), s)
              for i, s in enumerate([(8, 64, 32), (8, 64, 32), (8, 32, 64)]))
    return rows, idx.astype(jnp.int32), weights, w


@pytest.mark.parametrize("n", [2, 4])
def test_an_ep_axis_equals_one_device(routed_rows, n):
    rows, idx, weights, w = routed_rows
    want, sizes = layer_over_ep(1, rows, idx, weights, w)
    got, total = layer_over_ep(n, rows, idx, weights, w)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    np.testing.assert_array_equal(total, sizes)
    assert int(total.sum()) == 64 * 3


@pytest.mark.parametrize("n", [1, 2])
def test_gradients_flow_through_the_dispatch(routed_rows, n):
    rows, idx, weights, w = routed_rows

    def total(rows, weights, w):
        return jnp.sum(layer_over_ep(n, rows, idx, weights, w)[0] ** 2)

    def dense(rows, weights, w):
        w_in, w_gate, w_out = w
        mix = jnp.sum(jax.nn.one_hot(idx, CFG.n_experts) * weights[..., None], 1)
        ys = jnp.einsum("tef,efd->ted", jax.nn.silu(
            jnp.einsum("td,edf->tef", rows, w_gate)) * jnp.einsum(
                "td,edf->tef", rows, w_in), w_out)
        return jnp.sum(jnp.einsum("te,ted->td", mix, ys) ** 2)
    got = jax.grad(total, argnums=(0, 1, 2))(rows, weights, w)
    want = jax.grad(dense, argnums=(0, 1, 2))(rows, weights, w)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, r, atol=2e-4 * np.abs(r).max(), rtol=0)


@pytest.mark.parametrize("axes", [{"tp": 2}, {"dp": 2}, {"sp": 2}],
                         ids=["tp2", "dp2", "sp2"])
def test_the_step_on_a_mesh_reports_one_chips_loss(axes):
    # the forward pass over a mesh: tokens sharded over dp or sp; over tp
    # QK-norm's sum of squares, in the same block with a dense FFN, because
    # a layer with experts refuses tp > 1. (The parameters after a tp step
    # are not compared: the flagship's own differ from one chip's there,
    # PERF.md section 7.)
    params, tokens, labels = seeded(2)
    if "tp" in axes:        # the same tokens on every rank: the same loss
        with pytest.raises(NotImplementedError, match="tp 1"):
            one_chip_step(**axes)(params, tokens, labels)
        dense = dataclasses.replace(CFG, n_experts=0, router_aux_coef=0.0)
        params = transformer_init(jax.random.key(2), dense)
        assert abs(float(one_chip_step(dense)(params, tokens, labels)[1]) -
                   float(one_chip_step(dense, **axes)(params, tokens, labels)[1])
                   ) <= LOSS_ATOL
        return
    loss1 = float(one_chip_step()(params, tokens, labels)[1])
    loss2 = float(one_chip_step(**axes)(params, tokens, labels)[1])
    # each data shard balances its own tokens' load, as under DDP
    bare = dataclasses.replace(CFG, router_aux_coef=0.0)
    assert abs(loss1 - loss2) <= 0.01 * CFG.experts_per_tok
    assert abs(float(one_chip_step(bare)(params, tokens, labels)[1]) -
               float(one_chip_step(bare, **axes)(params, tokens, labels)[1])
               ) <= LOSS_ATOL


# -- chained steps -------------------------------------------------------------

def test_three_chained_sgd_steps_match_the_reference():
    params, tokens, labels = seeded(3)
    batches = [(jnp.roll(tokens, i, axis=0), jnp.roll(labels, i, axis=0))
               for i in range(3)]
    want = ref.losses(MODEL, LR, ref.from_system(params, CFG.n_heads), batches)
    step, got = one_chip_step(), []
    for tok, lab in batches:
        params, loss = step(params, tok, lab)
        got.append(float(loss))
    assert max(abs(g - w) for g, w in zip(got, want)) <= 2 * LOSS_ATOL
    assert want[0] != want[1] != want[2]
    # one layer at a time from the system's own parameters: the same number
    assert abs(ref.make_loss_from(MODEL)(
        ref.from_system(params, CFG.n_heads), *batches[0])[0] -
               float(system_loss_and_grads(CFG, params, *batches[0])[0])) \
        <= LOSS_ATOL


def test_a_donated_step_gives_the_same_parameters():
    params, tokens, labels = seeded(4)
    mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1}, devices=jax.devices()[:1])
    want, loss = transformer_train_step(CFG, mesh, lr=LR)[0](
        params, tokens, labels)
    copy = jax.tree.map(jnp.copy, params)
    got, loss2 = transformer_train_step(CFG, mesh, lr=LR, donate=True)[0](
        copy, tokens, labels)
    assert float(loss) == float(loss2)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(a, b)


# -- the flagship is the program it was ---------------------------------------

def _flagship_init_before(key, cfg):
    """`transformer_init` as it stood before the expert layer (PR 24)."""
    def dense(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(cfg.dtype)
    keys = jax.random.split(key, 2 + 4 * cfg.n_layers)
    d, f = cfg.d_model, cfg.d_ff
    params = {"embed": dense(keys[0], (cfg.vocab, d), d ** -0.5),
              "ln_f": jnp.ones((d,), cfg.dtype), "layers": []}
    for i in range(cfg.n_layers):
        k = keys[2 + 4 * i: 6 + 4 * i]
        params["layers"].append({
            "ln1": jnp.ones((d,), cfg.dtype),
            "w_qkv": dense(k[0], (d, 3 * d), d ** -0.5),
            "w_proj": dense(k[1], (d, d), (2 * d * cfg.n_layers) ** -0.5),
            "ln2": jnp.ones((d,), cfg.dtype),
            "w_in": dense(k[2], (d, f), d ** -0.5),
            "w_out": dense(k[3], (f, d), (2 * f * cfg.n_layers) ** -0.5)})
    return params


def _flagship_forward_before(cfg, params, tokens):
    """`transformer_forward` (single device) as it stood before."""
    def rms_norm(x, scale):
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        return (x * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype) * scale

    def rope(x, positions):
        half = x.shape[-1] // 2
        freqs = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1).astype(x.dtype)
    b, t = tokens.shape
    dh, positions = cfg.head_dim, jnp.arange(t)
    x = params["embed"][tokens]
    for layer in params["layers"]:
        y = rms_norm(x, layer["ln1"])
        qkv = (y @ layer["w_qkv"]).reshape(b, t, cfg.n_heads, 3, dh) \
            .transpose(0, 2, 1, 3, 4)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        q, k = rope(q, positions), rope(k, positions)
        s = jnp.einsum("bhqd,bhkd->bhqk", q * dh ** -0.5, k)
        s = jnp.where(jnp.tril(jnp.ones((t, t), dtype=bool)), s, -1e30)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        x = x + o.transpose(0, 2, 1, 3).reshape(b, t, cfg.n_heads * dh) \
            @ layer["w_proj"]
        y = rms_norm(x, layer["ln2"])
        x = x + jax.nn.gelu(y @ layer["w_in"]) @ layer["w_out"]
    x = rms_norm(x, params["ln_f"])
    return (x @ params["embed"].T).astype(jnp.float32)


FLAGSHIP_JIT_ATOL = {"float32": 1e-5, "bfloat16": 0.0}


def _cut_then_halves(row, positions, theta, heads, parts):
    """`tf._rope_heads` for the packed [head][q|k|v] row as the flagship had
    it before PR 36: the cut into heads first, then the rotation as two
    half-width products, concatenated (`tf._rope_halves`)."""
    assert [turned for _w, turned in parts] == [True, True, False]
    b, t, _ = row.shape
    qkv = row.reshape(b, t, heads, 3, -1).transpose(0, 2, 1, 3, 4)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    return (tf._rope_halves(q, positions, theta),
            tf._rope_halves(k, positions, theta), v)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_default_config_is_the_flagship_bit_for_bit(dtype, monkeypatch):
    cfg = TransformerConfig(vocab=128, d_model=32, n_heads=4, n_layers=3,
                            d_ff=64, max_seq=16, dtype=jnp.dtype(dtype))
    key = jax.random.key(7)
    params, before = transformer_init(key, cfg), _flagship_init_before(key, cfg)
    assert jax.tree.structure(params) == jax.tree.structure(before)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(before)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    tokens = jax.random.randint(jax.random.key(8), (2, 16), 0, cfg.vocab)
    labels = jnp.roll(tokens, -1, axis=1)

    def programs():
        return (jax.make_jaxpr(jax.grad(lambda p: tf._xent(
                    tf._forward(cfg, p, tokens)[0], labels)))(params),
                jax.make_jaxpr(jax.grad(lambda p: tf._xent(
                    _flagship_forward_before(cfg, p, tokens), labels)))(before))
    # the layers share one jitted trace since PR 29 (`_block_traced_once`);
    # with that jit taken away the layer is what it was, op for op
    with jax.disable_jit():
        # The rotation is its own function since PR 36 (x cos2 + swap(x) sin2
        # on the row, before the cut, with the inverse rotation for a
        # backward): the forward's numbers are the frozen copy's, bit for
        # bit (x1 cos - x2 sin and x1 cos + x2 (-sin) round alike, and so do
        # the two orders of the second half's sum), the program is not.
        np.testing.assert_array_equal(
            transformer_forward(cfg, params, tokens),
            _flagship_forward_before(cfg, before, tokens))
        now, was = programs()
        assert str(now) != str(was)
        # With the old order and form back in that one place, everything
        # else is the same program, not only the same numbers: equation for
        # equation
        monkeypatch.setattr(tf, "_rope_heads", _cut_then_halves)
        now, was = programs()
        assert str(now) == str(was)

    def jitted():
        tf._block_traced_once.cache_clear()     # the layer as `tf` has it now
        return (jax.jit(lambda p: transformer_forward(cfg, p, tokens))(params),
                jax.jit(lambda p: _flagship_forward_before(cfg, p, tokens))(
                    before))
    # and jitted as a step jits it, the shared trace changes no number
    np.testing.assert_array_equal(*jitted())
    monkeypatch.undo()
    # Compiled, the new form is a rounding away from the old one: XLA's CPU
    # backend contracts a product and a sum into one fused multiply-add,
    # and of x2 cos + x1 sin it does not keep the product it kept of
    # x1 sin + x2 cos. Float32 round-off through three layers; bfloat16's
    # one rounding of the float32 result hides it at this size.
    now, was = jitted()
    np.testing.assert_allclose(now, was, rtol=0, atol=FLAGSHIP_JIT_ATOL[dtype])
    tf._block_traced_once.cache_clear()


# -- a row's way into expert order and back (PR 31) ----------------------------
#
# `moe_dropless` against the plain form it replaced, written out with no
# custom gradient: tokens[order // k], the experts' rows permuted back
# (out[inverse]) as [t, k, d], times the weights, summed over k. Float32
# agrees to 1e-6 of the largest entry (the same products, sums in another
# order); in bfloat16 the two differ by their roundings (the plain form
# rounds the [t, k, d] product and the gradient's rows before they are
# summed, `moe_dropless` accumulates in float32 and rounds once, but rounds
# weight x hidden before the last product), so each must lie within
# bfloat16's rounding of the float32 answer through a chain of three
# products: 2^-5 of the largest entry (the worst case below reads 2^-5.8).

WAY_E, WAY_D, WAY_F, WAY_T = 8, 32, 48, 40
WAY_TOL = {"float32": 1e-6, "bfloat16": 2.0 ** -5}
ROUTERS = ("spread", "leaves experts empty", "all to the same")


def way_experts(w_gate, w_in, w_out):
    def run(xs, sizes, scale):
        hid = jax.nn.silu(lax.ragged_dot(xs, w_gate, sizes)) * \
            lax.ragged_dot(xs, w_in, sizes)
        return lax.ragged_dot(hid * scale[:, None].astype(hid.dtype),
                              w_out, sizes)
    return run


def plain_way(tokens, idx, weights, w):
    t, k = idx.shape
    flat = idx.reshape(t * k)
    order = jnp.argsort(flat, stable=True)
    inverse = jnp.argsort(order)
    sizes = jnp.sum(flat[:, None] == jnp.arange(WAY_E)[None, :], axis=0,
                    dtype=jnp.int32)
    out = way_experts(*w)(tokens[order // k], sizes,
                          jnp.ones(t * k, tokens.dtype))
    back = out[inverse].reshape(t, k, -1)
    return jnp.sum(back * weights[..., None], axis=1)


def new_way(tokens, idx, weights, w, n: int = 1):
    if n == 1:
        return moe_dropless(tokens, idx, weights, way_experts(*w), WAY_E)[0]
    mesh = xla.make_mesh({"ep": n}, devices=jax.devices()[:n])

    def local(tokens, idx, weights, *w):
        return moe_dropless(tokens, idx, weights, way_experts(*w), WAY_E,
                            axis="ep")[0]
    return jax.shard_map(local, mesh=mesh, in_specs=(P("ep"),) * 6,
                         out_specs=P("ep"))(tokens, idx, weights, *w)


def way_inputs(dtype: str, k: int, router: str):
    key = jax.random.key(zlib.crc32(f"{dtype}/{k}/{router}".encode()))
    fold = lambda i: jax.random.fold_in(key, i)
    tokens = jax.random.normal(fold(0), (WAY_T, WAY_D))
    logits = jax.random.normal(fold(1), (WAY_T, WAY_E))
    if router == "leaves experts empty":        # experts 2, 5 and 7 get none
        logits = logits.at[:, jnp.array([2, 5, 7])].set(-1e9) \
            if k <= WAY_E - 3 else logits
    elif router == "all to the same":           # every token the same k
        logits = logits + 1e3 * (jnp.arange(WAY_E) < k)
    weights, idx = lax.top_k(jax.nn.softmax(logits), k)
    w = tuple(0.3 * jax.random.normal(fold(2 + i), s) for i, s in enumerate(
        [(WAY_E, WAY_D, WAY_F), (WAY_E, WAY_D, WAY_F), (WAY_E, WAY_F, WAY_D)]))
    cast = lambda a: a.astype(jnp.dtype(dtype))
    return cast(tokens), idx.astype(jnp.int32), cast(weights), \
        tuple(cast(m) for m in w)


@functools.lru_cache(maxsize=None)
def ways(dtype: str, k: int, router: str, n: int = 1) -> dict:
    """{what: (moe_dropless's, the plain form's, the plain form's in
    float32)} for the value and the three gradients of sum(value x c)."""
    tokens, idx, weights, w = way_inputs(dtype, k, router)
    c = jax.random.normal(jax.random.key(5), (WAY_T, WAY_D))

    def both(way, tokens, weights, w, **kw):
        def total(tokens, weights, w):
            y = way(tokens, idx, weights, w, **kw)
            return jnp.sum(y.astype(jnp.float32) * c), y
        grads, y = jax.jit(jax.grad(total, argnums=(0, 1, 2),
                                    has_aux=True))(tokens, weights, w)
        return dict(zip(("value", "tokens", "weights", "matrices"),
                        (y,) + grads))
    f32 = lambda a: jax.tree.map(lambda x: x.astype(jnp.float32), a)
    got = both(new_way, tokens, weights, w, n=n)
    want = both(plain_way, tokens, weights, w)
    exact = both(plain_way, f32(tokens), f32(weights), f32(w))
    return {what: (got[what], want[what], exact[what]) for what in got}


def assert_the_same_way(got, want, exact, dtype):
    for g, w, e in zip(*(jax.tree.leaves(a) for a in (got, want, exact))):
        g, w, e = (np.asarray(a, np.float32) for a in (g, w, e))
        assert g.shape == e.shape
        size = max(np.abs(e).max(), 1e-30)
        if dtype == "float32":
            np.testing.assert_allclose(g, w, atol=WAY_TOL[dtype] * size,
                                       rtol=0)
        else:       # both within the dtype's rounding of the exact answer
            assert np.abs(g - e).max() <= WAY_TOL[dtype] * size
            assert np.abs(w - e).max() <= WAY_TOL[dtype] * size


@pytest.mark.parametrize("what", ["value", "tokens", "weights", "matrices"])
@pytest.mark.parametrize("router", ROUTERS)
@pytest.mark.parametrize("k", [1, 2, 8], ids=["top1", "top2", "top8"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_rows_way_equals_the_plain_form(dtype, k, router, what):
    assert_the_same_way(*ways(dtype, k, router)[what], dtype)


@pytest.mark.parametrize("what", ["value", "tokens", "weights", "matrices"])
@pytest.mark.parametrize("n", [2, 4])
def test_a_rows_way_over_an_ep_axis_equals_the_plain_form(n, what):
    got, want, exact = ways("float32", 2, "spread", n)[what]
    assert_the_same_way(got, want, exact, "float32")


def test_the_routers_of_these_cases_do_what_they_say():
    for k in (1, 2, 8):
        counts = {r: np.bincount(np.asarray(way_inputs("float32", k, r)[1])
                                 .ravel(), minlength=WAY_E) for r in ROUTERS}
        assert (counts["spread"] > 0).all()
        assert (counts["all to the same"] > 0).sum() == k
        if k <= WAY_E - 3:
            assert (counts["leaves experts empty"][[2, 5, 7]] == 0).all()


def test_the_backward_pass_keeps_the_rows_and_no_other_slots_by_d_array():
    """What `jax.vjp` keeps of one layer: of arrays as large as the slots'
    rows ([t x k, d], or the same as [t, k, d]) only the experts' input
    rows, which their weights' gradient needs; the experts' output is not
    kept, nor its permutation, nor a broadcast of the weights. The plain
    form keeps the permuted output besides."""
    tokens, idx, weights, w = way_inputs("float32", 2, "spread")

    def kept(way):
        _y, back = jax.vjp(lambda tokens, weights, w:
                           way(tokens, idx, weights, w), tokens, weights, w)
        big = [a.shape for a in jax.tree.leaves(back)
               if hasattr(a, "shape") and a.size == WAY_T * 2 * WAY_D]
        return sorted(big)
    assert kept(new_way) == [(WAY_T * 2, WAY_D)]
    assert len(kept(plain_way)) >= 2
