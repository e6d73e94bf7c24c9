"""The vocabulary head and its cross-entropy over blocks of tokens
(`models.transformer.head_loss`): the loss and every gradient against
`_xent(transformer_forward(...))` differentiated by JAX, float32 and bf16, a
tied head in the embedding's [vocab, d] layout and an `lm_head` of its own,
at 1, 2 and 3 blocks, a token count the block does not divide, a logits
divisor, an embedding multiplier and a cotangent that is not 1 (a term
beside the loss); the rule that picks the block; one whole
`transformer_train_step` on a dp x sp and a tp 2 CPU mesh against the step
as it was written over the whole logits; what the lowered step holds; the
counter."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tpu_mpi import perfvars, xla                               # noqa: E402
from tpu_mpi.models import transformer as tf                    # noqa: E402
from tpu_mpi.models.transformer import (TransformerConfig,      # noqa: E402
                                        transformer_forward,
                                        transformer_init,
                                        transformer_param_specs,
                                        transformer_train_step)

VOCAB, D = 160, 32
BATCH, SEQ = 2, 150         # 300 tokens: blocks of 256 and 128 do not divide them
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 4e-2}


def model(tied: bool, dtype=jnp.float32, **fields) -> TransformerConfig:
    return TransformerConfig(vocab=VOCAB, d_model=D, n_heads=4, n_layers=2,
                             d_ff=64, tie_embeddings=tied, dtype=dtype,
                             **fields)


def seeded(cfg: TransformerConfig, batch: int = BATCH, seq: int = SEQ):
    """(params with `ln_f` away from one, tokens, labels)."""
    key = jax.random.key(3)
    params = transformer_init(jax.random.fold_in(key, 0), cfg)
    params["ln_f"] = (1.0 + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 1), (cfg.d_model,))).astype(cfg.dtype)
    tokens = jax.random.randint(jax.random.fold_in(key, 2), (batch, seq), 0,
                                cfg.vocab, dtype=jnp.int32)
    labels = jax.random.randint(jax.random.fold_in(key, 3), (batch, seq), 0,
                                cfg.vocab, dtype=jnp.int32)
    return params, tokens, labels


def blocks_of(monkeypatch, tokens: int, blocks: int) -> int:
    """Set the byte budget so that `_head_block` cuts ``tokens`` into
    ``blocks``; the block it then chooses."""
    monkeypatch.setattr(tf, "_HEAD_BLOCK_BYTES",
                        -(-tokens * VOCAB * 4 // blocks))
    block = tf._head_block(tokens, VOCAB)
    assert -(-tokens // block) == blocks
    return block


def whole(cfg, params, tokens, labels):
    return tf._xent(transformer_forward(cfg, params, tokens), labels)


def blocked(cfg, params, tokens, labels):
    x, _routed = tf._trunk(cfg, params, tokens)
    return tf.head_loss(cfg, params, x, labels)


def assert_trees_close(got, want, tol):
    """Every leaf within ``tol`` of the wanted leaf's largest entry."""
    flat, _ = jax.tree_util.tree_flatten_with_path(want)
    for (path, w), g in zip(flat, jax.tree_util.tree_leaves(got)):
        w, g = np.asarray(w, np.float32), np.asarray(g, np.float32)
        assert g.shape == w.shape
        assert np.max(np.abs(g - w)) <= tol * max(np.max(np.abs(w)), 1e-6), \
            jax.tree_util.keystr(path)


# -- the loss and its gradients against the whole logits ----------------------

@pytest.mark.parametrize("blocks", [1, 2, 3])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "lm_head"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_loss_and_every_gradient_are_the_whole_logits(
        monkeypatch, dtype, tied, blocks):
    """`ln_f`, the head (a tied `embed` gets the head's and the lookup's
    gradient in one [vocab, d] leaf) and, through the stream's gradient,
    every layer; a divisor and a multiplier that are not 1."""
    cfg = model(tied, dtype, logits_divisor=8.0, embed_multiplier=3.0)
    params, tokens, labels = seeded(cfg)
    block = blocks_of(monkeypatch, tokens.size, blocks)
    assert (tokens.size % block != 0) == (blocks > 1)
    want = jax.value_and_grad(lambda p: whole(cfg, p, tokens, labels))(params)
    got = jax.value_and_grad(lambda p: blocked(cfg, p, tokens, labels))(params)
    assert ("lm_head" in got[1]) != tied
    assert got[1]["embed"].shape == (VOCAB, D)
    assert got[0].dtype == jnp.float32
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6)
    assert_trees_close(got[1], want[1], TOL[dtype])


@pytest.mark.parametrize("blocks", [1, 2, 4])
def test_blocks_that_divide_the_tokens(monkeypatch, blocks):
    cfg = model(True)
    params, tokens, labels = seeded(cfg, 2, 256)
    assert tokens.size % blocks_of(monkeypatch, tokens.size, blocks) == 0
    want = jax.value_and_grad(lambda p: whole(cfg, p, tokens, labels))(params)
    got = jax.value_and_grad(lambda p: blocked(cfg, p, tokens, labels))(params)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6)
    assert_trees_close(got[1], want[1], 2e-5)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "lm_head"])
@pytest.mark.parametrize("scale", [1.0, 0.37, -2.5])
def test_a_cotangent_that_is_not_one_and_a_term_beside_the_loss(
        monkeypatch, tied, scale):
    """The loss times a constant plus another function of the same
    parameters (what the router's auxiliary loss is to it): the backward
    rule scales what the forward rule kept."""
    cfg = model(tied)
    params, tokens, labels = seeded(cfg)
    blocks_of(monkeypatch, tokens.size, 3)

    def beside(p):
        return 0.1 * jnp.sum(jnp.square(p["ln_f"])) \
            + jnp.sum(p["embed"][:4] ** 2)
    want = jax.value_and_grad(
        lambda p: scale * whole(cfg, p, tokens, labels) + beside(p))(params)
    got = jax.value_and_grad(
        lambda p: scale * blocked(cfg, p, tokens, labels) + beside(p))(params)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6)
    assert_trees_close(got[1], want[1], 2e-5)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "lm_head"])
@pytest.mark.parametrize("block", [50, 32, 24, 8])
def test_the_stream_and_the_head_directly(tied, block):
    """`_blocked_xent` alone against the plain expression, gradients of the
    stream and of the head, under a cotangent of 0.37."""
    key = jax.random.key(5)
    x = jax.random.normal(key, (50, D))
    w = 0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                (VOCAB, D) if tied else (D, VOCAB))
    labels = jax.random.randint(jax.random.fold_in(key, 2), (50,), 0, VOCAB)

    def plain(x, w):
        return 0.37 * tf._xent((x @ (w.T if tied else w)) / 4.0, labels)

    def ours(x, w):
        return 0.37 * tf._blocked_xent(x, w, labels, tied, 4.0, block)
    want = jax.value_and_grad(plain, argnums=(0, 1))(x, w)
    got = jax.value_and_grad(ours, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6)
    assert_trees_close(got[1], want[1], 2e-5)


def test_bf16_sums_the_heads_gradient_in_float32():
    """Eight blocks in bf16 against the float32 gradient of the same bf16
    operands: the blocks' parts are added in float32 and rounded once, so
    the head's gradient is within one bf16 rounding of it."""
    key = jax.random.key(6)
    x = jax.random.normal(key, (64, D)).astype(jnp.bfloat16)
    w = (0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                 (VOCAB, D))).astype(jnp.bfloat16)
    labels = jax.random.randint(jax.random.fold_in(key, 2), (64,), 0, VOCAB)
    _, (_, dw) = jax.value_and_grad(
        lambda x, w: tf._blocked_xent(x, w, labels, True, 1.0, 8),
        argnums=(0, 1))(x, w)
    _, (_, exact) = jax.value_and_grad(
        lambda x, w: tf._blocked_xent(x, w, labels, True, 1.0, 64),
        argnums=(0, 1))(x.astype(jnp.float32), w.astype(jnp.float32))
    assert dw.dtype == jnp.bfloat16 and dw.shape == (VOCAB, D)
    # bf16's logits' gradient is rounded to 8 bits before its two products
    assert_trees_close(dw, exact, 2.0 ** -7)


def test_the_primal_makes_no_gradient():
    """Not differentiated, a block costs one product: the logits'."""
    cfg = model(True)
    params, tokens, labels = seeded(cfg)
    x, _ = tf._trunk(cfg, params, tokens)
    x = x.reshape(-1, D)

    def products(fn, *args):
        return str(jax.make_jaxpr(fn)(*args)).count("dot_general")
    loss = lambda x, w: tf._blocked_xent(x, w, labels.reshape(-1), True,  # noqa: E731
                                         1.0, 128)
    assert products(loss, x, params["embed"]) == 3
    assert products(jax.grad(loss, argnums=(0, 1)), x, params["embed"]) == 9
    np.testing.assert_allclose(
        tf.head_loss(cfg, params, tf._trunk(cfg, params, tokens)[0], labels),
        whole(cfg, params, tokens, labels), rtol=2e-6)


# -- the rule -------------------------------------------------------------------

@pytest.mark.parametrize("tokens, vocab, blocks", [
    (8192, 100352, 7), (8192, 66688, 5), (8192, 50304, 4), (8192, 32768, 2),
    (8192, 19200, 2), (4096, 19200, 1), (8192, 200064, 13), (8191, 100352, 7),
    (300, 160, 1), (50, 1 << 24, 1)])
def test_a_blocks_logits_fit_the_budget(tokens, vocab, blocks):
    """The fewest equal blocks whose float32 logits each fit the budget, a
    block rounded up to tiles of 128 tokens: decided by the token count and
    the vocabulary alone. (The first six are the benchmark's train cells.)"""
    block = tf._head_block(tokens, vocab)
    assert -(-tokens // block) == blocks
    assert block == tokens or block % 128 == 0
    if tokens >= 128:       # under a tile of tokens there is one block
        assert (block - 127) * vocab * 4 <= tf._HEAD_BLOCK_BYTES
    if blocks > 1:          # and fewer blocks would not have fitted
        assert -(-tokens // (blocks - 1)) * vocab * 4 > tf._HEAD_BLOCK_BYTES


def test_no_field_of_the_configuration_names_the_block():
    assert not [f for f in TransformerConfig.__dataclass_fields__
                if "block" in f or "head_loss" in f]


# -- the whole step on a mesh ---------------------------------------------------

def step_over_whole_logits(cfg, mesh, lr):
    """`transformer_train_step` as it stood before the blocked loss: the
    same shard_map, reductions and update, the loss `_xent` of the whole
    logits differentiated by JAX."""
    specs = transformer_param_specs(cfg, "tp")

    def local_step(params, tokens, labels):
        def loss_fn(p):
            logits, _routed = tf._forward(cfg, p, tokens, tp_axis="tp",
                                          sp_axis="sp")
            return tf._xent(logits, labels)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree_util.tree_map(
            lambda g: lax.psum(g, ("dp", "sp")), grads)
        params = jax.tree_util.tree_map(
            lambda p, g: (p - lr * g).astype(p.dtype), params, grads)
        return params, lax.pmean(loss, ("dp", "sp"))
    return jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, P("dp", "sp"), P("dp", "sp")),
        out_specs=(specs, P())))


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "lm_head"])
@pytest.mark.parametrize("axes", [
    {"dp": 2, "tp": 1, "sp": 2}, {"dp": 2, "tp": 2, "sp": 2},
    {"dp": 1, "tp": 2, "sp": 1}, {"dp": 1, "tp": 1, "sp": 1}],
    ids=["dp2xsp2", "dp2xtp2xsp2", "tp2", "one"])
def test_a_whole_step_on_a_mesh_is_the_step_over_whole_logits(
        monkeypatch, axes, tied):
    """Each shard's loss is its own tokens' mean and the head is the same
    on every shard: the sum of the shards' gradients of it is made where it
    was (the transpose of the cast that says so), and `shard_map`'s check
    of what varies passes."""
    cfg = model(tied)
    params, tokens, _ = seeded(cfg, 4, 256)
    labels = jnp.roll(tokens, -1, axis=1)
    n = int(np.prod(list(axes.values())))
    mesh = xla.make_mesh(axes, devices=jax.devices()[:n])
    local = tokens.size // (axes["dp"] * axes["sp"])
    blocks_of(monkeypatch, local, 2)
    step, _specs = transformer_train_step(cfg, mesh, lr=0.05)
    got_params, got_loss = step(params, tokens, labels)
    want_params, want_loss = step_over_whole_logits(cfg, mesh, 0.05)(
        params, tokens, labels)
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-6)
    moved = jax.tree_util.tree_map(lambda a, b: a - b, got_params, params)
    wanted = jax.tree_util.tree_map(lambda a, b: a - b, want_params, params)
    assert_trees_close(moved, wanted, 1e-4)
    assert float(jnp.max(jnp.abs(wanted["embed"]))) > 1e-4


def test_a_step_with_experts_adds_the_routers_loss_beside_it(monkeypatch):
    """The auxiliary loss beside the cross-entropy: the step still descends
    both (the update is the gradient of their sum)."""
    cfg = model(False, n_experts=4, experts_per_tok=2, router_aux_coef=0.05,
                qk_norm=True)
    params, tokens, _ = seeded(cfg, 3, 128)
    labels = jnp.roll(tokens, -1, axis=1)
    mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1},
                         devices=jax.devices()[:1])
    blocks_of(monkeypatch, tokens.size, 3)
    lr = 1.0        # the gradient is read off the update: keep its digits
    step, _specs = transformer_train_step(cfg, mesh, lr=lr)
    got_params, got_loss = step(params, tokens, labels)

    def loss_fn(p):
        logits, routed = tf._forward(cfg, p, tokens)
        return tf._xent(logits, labels) + cfg.router_aux_coef \
            * tf.load_balancing_loss(routed, tokens.size)
    want_loss, grads = jax.value_and_grad(loss_fn)(params)
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-6)
    applied = jax.tree_util.tree_map(lambda a, b: (b - a) / lr,
                                     got_params, params)
    assert_trees_close(applied, grads, 1e-4)


# -- what the lowered step holds, and the counter ---------------------------------

def lowered_step(cfg, batch, seq):
    mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1},
                         devices=jax.devices()[:1])
    step, _specs = transformer_train_step(cfg, mesh, lr=0.01)
    params, tokens, labels = seeded(cfg, batch, seq)
    return step.lower(params, tokens, labels).as_text()


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "lm_head"])
def test_no_float32_array_of_tokens_by_vocab_is_in_the_step(monkeypatch, tied):
    """With more than one block no [tokens, vocab] float32 array (nor one in
    the model's dtype) is in the lowered step; with one block the logits
    are, which is what shows that the search would find them. The counter
    rises by one a traced step, with the block count beside it."""
    cfg = model(tied, jnp.bfloat16)
    batch, seq = 2, 256
    sizes = "(%dx%d|%dx%dx%d)" % (batch * seq, VOCAB, batch, seq, VOCAB)
    perfvars.reset()
    blocks_of(monkeypatch, batch * seq, 4)
    text = lowered_step(cfg, batch, seq)
    assert not re.findall(r"tensor<%sx(f32|bf16)>" % sizes, text)
    assert re.findall(r"tensor<%dx%dxf32>" % (batch * seq // 4, VOCAB), text)
    snap = perfvars.snapshot()
    assert snap["head_loss_lowerings"] == {"blocked": 1, "whole": 0}
    assert snap["head_loss_blocks"] == {"4": 1}
    blocks_of(monkeypatch, batch * seq, 1)
    text = lowered_step(cfg, batch, seq)
    assert re.findall(r"tensor<%sxf32>" % sizes, text)
    snap = perfvars.snapshot()
    assert snap["head_loss_lowerings"] == {"blocked": 2, "whole": 0}
    assert snap["head_loss_blocks"] == {"1": 1, "4": 1}
    perfvars.reset()
    assert perfvars.snapshot()["head_loss_lowerings"] == {
        "blocked": 0, "whole": 0}
    assert perfvars.snapshot()["head_loss_blocks"] == {}


def test_the_steps_head_ops_carry_the_scope(monkeypatch):
    """Forward rule, backward rule and the final norm stand under
    `head_loss`, the scope the benchmark's readers sum."""
    cfg = model(True, jnp.bfloat16)
    blocks_of(monkeypatch, 256, 2)
    mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1},
                         devices=jax.devices()[:1])
    step, _specs = transformer_train_step(cfg, mesh, lr=0.01)
    params, tokens, labels = seeded(cfg, 2, 128)
    text = step.lower(params, tokens, labels).as_text(debug_info=True)
    dots = [line for line in text.splitlines()    # the products over VOCAB
            if "dot_general" in line and re.search(r"[<x]%dx" % VOCAB, line)]
    assert len(dots) >= 6       # three products a block, two blocks
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))
    for line in dots:
        (ref,) = re.findall(r"loc\((#loc\d+)\)\s*$", line)
        seen, todo = "", [ref]
        while todo:     # a location names others: follow them to the names
            here = locs.get(todo.pop(), "")
            seen += here
            todo += re.findall(r"#loc\d+", here)
        assert "head_loss" in seen, line
