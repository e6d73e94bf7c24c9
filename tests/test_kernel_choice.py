"""The seam where a kernel or its plain path is chosen (`tpu_mpi/xla/
choice.py`) and the counters of it (`perfvars.FAMILIES`): what a snapshot
holds before anything is traced, by the names the yardstick's readers use;
each of the nine choices counted under its family as the kernel where the
tests' word selects it and as the plain path where nothing does; each
contract's own operand types; the rule's parts; and the trace key: a layer
and a sum of rows traced under one word are traced again under the other.
Nothing here runs a kernel: a choice is made while a program is traced, so
every case only traces (`jax.make_jaxpr`)."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tpu_mpi import perfvars                                    # noqa: E402
from tpu_mpi.models import transformer as tf                    # noqa: E402
from tpu_mpi.parallel import delta, ep, ring, ssm               # noqa: E402
from tpu_mpi.xla import choice                                  # noqa: E402

F32 = jnp.float32

# what `perfvars.snapshot()` holds of each family after a reset: the keys
# `yardstick/layer_metrics/*_share.py`, `yardstick/harness.py` and
# `chip_smoke.py` read, written out here so that an edit to the table that
# loses one fails
FRESH = {
    "attn_lowerings": {"fused": 0, "plain": 0},
    "attn_kinds": {},
    "gmm_lowerings": {"kernel": 0, "ragged_dot": 0},
    "row_sum_lowerings": {"product": 0, "scatter": 0},
    "rope_forms": {"dense": 0, "halves": 0},
    "mixer_kinds": {"attention": 0, "ssm": 0},
    "side_values": {"memory": 0, "kv": 0},
    "scan_lowerings": {"chunked": 0, "padded": 0},
    "scan_kernel_lowerings": {"kernel": 0, "plain": 0},
    "sel_scan_lowerings": {"chunked": 0, "padded": 0},
    "sel_scan_kernel_lowerings": {"kernel": 0, "plain": 0},
    "delta_lowerings": {"chunked": 0, "padded": 0},
    "delta_kernel_lowerings": {"kernel": 0, "plain": 0},
    "delta_decays": {"head": 0, "channel": 0},
    "conv_kernel_lowerings": {"kernel": 0, "plain": 0},
    "head_norm_lowerings": {"kernel": 0, "plain": 0},
    "head_loss_lowerings": {"blocked": 0, "whole": 0},
    "head_loss_blocks": {},
}


def test_the_table_is_the_families_the_readers_name():
    assert list(perfvars.FAMILIES) == list(FRESH)


@pytest.mark.parametrize("family", sorted(FRESH))
def test_a_family_is_there_at_zero_before_anything_is_traced(family):
    perfvars.note(family, "plain" if family != "head_loss_blocks" else 3)
    perfvars.reset()
    snap = perfvars.snapshot()
    assert snap[family] == FRESH[family]
    if FRESH[family]:       # counted as it is noted, a kind outside the
        kind = next(iter(FRESH[family]))    # table's from its first note
        perfvars.note(family, kind)
        perfvars.note(family, "another", 2)
        assert perfvars.snapshot()[family] == {
            **FRESH[family], kind: 1, "another": 2}
    perfvars.reset()
    assert perfvars.snapshot()[family] == FRESH[family]


def test_the_two_derived_families_are_shown_as_they_were():
    perfvars.reset()
    for kind, how in (("full", "fused"), ("window", "plain"),
                      ("diff", "fused"), ("diff", "plain"), ("full", "fused")):
        perfvars.note("attn_kinds", (kind, how))
    for blocks in (8, 2, 8):
        perfvars.note("head_loss_blocks", blocks)
    snap = perfvars.snapshot()
    assert snap["attn_kinds"] == {"diff": "mixed", "full": "fused",
                                  "window": "plain"}
    assert snap["head_loss_blocks"] == {"2": 1, "8": 2}
    assert list(snap["head_loss_blocks"]) == ["2", "8"]
    perfvars.reset()


def _attention():
    q = jnp.zeros((1, 2, 128, 64), F32)
    return lambda: ring.local_attention(q, q, q)


def _grouped():
    rows, weights = jnp.zeros((128, 128), F32), jnp.zeros((2, 128, 128), F32)
    sizes = jnp.array([64, 64], jnp.int32)
    return lambda: ep.grouped_products(sizes)(rows, weights)


def _row_sum():
    rows, place = jnp.zeros((128, 128), F32), jnp.zeros((128,), jnp.int32)
    return lambda: ep.sum_rows(rows, place, 128)


def _rope_heads():
    row = jnp.zeros((1, 128, 4 * 64), F32)
    return lambda: tf._rope_heads(row, jnp.arange(128), 1e4, 4,
                                  ((64, True),))


def _norm_rope():
    cfg = tf.TransformerConfig(vocab=64, d_model=256, n_heads=2, n_layers=1,
                               d_ff=64, max_seq=128, dtype=F32,
                               qk_norm_heads=True, d_head=128)
    x = jnp.zeros((1, 2, 128, 128), F32)
    return lambda: tf._norm_and_rope(cfg, x, jnp.ones((128,), F32),
                                     jnp.arange(128), None)


def _scan():
    x, dt = jnp.zeros((1, 128, 8, 64), F32), jnp.ones((1, 128, 8), F32)
    bc = jnp.zeros((1, 128, 128), F32)
    return lambda: ssm.scan(x, dt, -jnp.ones((8,), F32), bc, bc,
                            jnp.ones((8,), F32), 128)


def _sel_scan():
    x, dt = jnp.zeros((1, 128, 512), F32), jnp.ones((1, 128, 512), F32)
    bc = jnp.zeros((1, 128, 16), F32)
    return lambda: ssm.selective_scan(x, dt, -jnp.ones((512, 16), F32), bc,
                                      bc, jnp.ones((512,), F32))


def _delta_scan():
    qk, v = jnp.zeros((1, 64, 1, 128), F32), jnp.zeros((1, 64, 2, 128), F32)
    g = jnp.zeros((1, 64, 2), F32)
    return lambda: delta.delta_scan(qk, qk, v, g, g, 64)


def _conv():
    x, w = jnp.zeros((1, 128, 384), F32), jnp.zeros((4, 256), F32)
    return lambda: ssm.conv_silu(x, w, jnp.zeros((256,), F32), start=128,
                                 cuts=(128,))


def _head_norm():
    x = jnp.zeros((1, 128, 256), F32)
    return lambda: tf._l2_normed_rows(x, 2)


def _gated_head_norm():
    o, g_in = jnp.zeros((1, 128, 2, 128), F32), jnp.zeros((1, 128, 128), F32)
    cfg = tf.TransformerConfig(vocab=64, d_model=256, n_heads=2, n_layers=1,
                               d_ff=64, max_seq=128, dtype=F32)
    return lambda: tf._head_norm_gated(cfg, o, jnp.ones((128,), F32),
                                       "sigmoid", g_in,
                                       jnp.zeros((128, 256), F32))


# one small call of each choice, at a shape inside its kernel's contract
CALLS = {
    "attention": (choice.ATTENTION, _attention),
    "grouped product": (choice.GROUPED, _grouped),
    "row sum": (choice.ROW_SUM, _row_sum),
    "rope_heads": (choice.ROPE_HEADS, _rope_heads),
    "norm_rope": (choice.NORM_ROPE, _norm_rope),
    "scan": (choice.SCAN, _scan),
    "selective scan": (choice.SEL_SCAN, _sel_scan),
    "delta scan": (choice.DELTA_SCAN, _delta_scan),
    "convolution": (choice.CONV, _conv),
    "head norm": (choice.HEAD_NORM, _head_norm),
    "head norm, gated by a product": (choice.HEAD_NORM, _gated_head_norm),
}


@pytest.mark.parametrize("word", ["interpret", None])
@pytest.mark.parametrize("what", sorted(CALLS))
def test_a_choice_is_counted_under_its_family(what, word, kernel_backend):
    """Traced under "interpret" the call counts once as its family's kernel
    kind and holds the kernel (a `pallas_call`); traced under None it counts
    once as the plain kind and holds none. The two rotations count their
    form, `dense`, whoever computes it."""
    chosen, make = CALLS[what]
    call = make()
    perfvars.reset()
    with kernel_backend(word):
        traced = str(jax.make_jaxpr(call)())
    counted = perfvars.snapshot()[chosen.family]
    kind = chosen.kernel if word else (chosen.plain or "dense")
    assert counted == {**FRESH[chosen.family], kind: 1}
    assert ("pallas_call" in traced) is (word is not None)
    if chosen.by:       # and again by the attention's kind
        assert perfvars.snapshot()[chosen.by] == {"full": kind}
    perfvars.reset()


# (operands inside each contract, where the dtype stands among them)
CONTRACTS = {
    "attention": (choice.ATTENTION, (128, 64, 0, 0, None), 4),
    "grouped product": (choice.GROUPED, (128, 128, 128, None), 3),
    "row sum": (choice.ROW_SUM, (128, 128, None), 2),
    "rope_heads": (choice.ROPE_HEADS, (128, 4, ((64, True),), None), 3),
    "norm_rope": (choice.NORM_ROPE, (2, 128, 128, None), 3),
    "scan": (choice.SCAN, (8, 64, 128, 128, None), 4),
    "selective scan": (choice.SEL_SCAN, (512, 16, None), 2),
    "delta scan": (choice.DELTA_SCAN, (2, 1, 128, 128, 64, None), 5),
    "convolution": (choice.CONV, (128, 256, 4, None, 128, (128,)), 3),
    "head norm": (choice.HEAD_NORM, (128, 256, 128, None, 128), 3),
}


@pytest.mark.parametrize("what", sorted(CONTRACTS))
def test_a_contract_holds_its_kernel_to_its_own_operand_types(
        what, kernel_backend):
    """No caller names a set of types: float32 and bfloat16 fit, float16
    and float64 do not, and without a backend nothing does."""
    chosen, operands, at = CONTRACTS[what]

    def asked(dtype):
        return operands[:at] + (dtype,) + operands[at + 1:]
    for dtype in (F32, jnp.bfloat16, "bfloat16", jnp.dtype("float32")):
        assert choice.fit(chosen, *asked(dtype)) is None    # no backend
    kernel_backend("mosaic")
    for dtype in (F32, jnp.bfloat16, "bfloat16", jnp.dtype("float32")):
        assert choice.fit(chosen, *asked(dtype)) is not None
    for dtype in (jnp.float16, "float64", jnp.int32):
        assert choice.fit(chosen, *asked(dtype)) is None
    assert choice.fit(chosen, *asked(F32), also=False) is None


@pytest.mark.parametrize("operands, fits", [
    ((32, 16, 128, 128, 64, jnp.bfloat16), True),       # Qwen3-Next's
    ((32, 16, 128, 128, 64, jnp.bfloat16, 1), True),
    ((32, 16, 128, 128, 64, jnp.bfloat16, 128), False),     # a vector decay
    ((32, 32, 128, 128, 64, jnp.bfloat16, 1), False),       # 32 key heads
    ((32, 32, 128, 128, 64, jnp.bfloat16, 128), True),      # KDA's: both
    ((31, 31, 128, 128, 64, jnp.bfloat16, 128), False),     # no twos
    ((32, 32, 128, 128, 64, jnp.bfloat16, 64), False),      # half a key
], ids=["qwen3-next", "one-a-head", "a-channel", "32-key-heads", "kda",
        "odd-heads", "half-the-channels"])
def test_the_delta_kernels_contract_pairs_the_decay_with_the_heads(
        operands, fits, kernel_backend):
    """The contract's two rows: a token's decay as scalars with two value
    heads a key head (Qwen3-Next's shapes), or a decay a key channel with a
    key head a value head, the heads in twos (KDA's: PR 49). Under the
    `mosaic` word those two select a kernel pair, and a decay a channel over
    two value heads a key head, a decay a head over as many key heads, an
    odd number of heads or a decay narrower than the key is refused by rule,
    from the shapes."""
    kernel_backend("mosaic")
    assert (choice.fit(choice.DELTA_SCAN, *operands) is not None) is fits


def test_a_vector_decays_scan_is_counted_by_its_heads_under_the_kernels_word(
        kernel_backend):
    """A decay a channel traced under `mosaic`: over two value heads a key
    head no kernel is in the program and the call counts `plain`; KDA's
    shapes (a key head a value head) count `kernel`, and so do Qwen3-Next's
    beside them, whose decay counts `head`."""
    qk = jnp.zeros((1, 64, 2, 128), jnp.bfloat16)
    g = jnp.zeros((1, 64, 2, 128), F32)
    perfvars.reset()
    with kernel_backend("mosaic"):
        traced = str(jax.make_jaxpr(lambda: delta.delta_scan(
            qk[:, :, :1], qk[:, :, :1], qk, g, g[..., 0], 64))())
        assert "pallas_call" not in traced
        assert perfvars.snapshot()["delta_kernel_lowerings"] == {
            "kernel": 0, "plain": 1}
        assert perfvars.snapshot()["delta_decays"] == {"head": 0,
                                                       "channel": 1}
        traced = str(jax.make_jaxpr(
            lambda: delta.delta_scan(qk, qk, qk, g, g[..., 0], 64))())
        assert "pallas_call" in traced and "delta_channel_scan_fwd" in traced
        assert perfvars.snapshot()["delta_kernel_lowerings"] == {
            "kernel": 1, "plain": 1}
        assert perfvars.snapshot()["delta_decays"] == {"head": 0,
                                                       "channel": 2}
        traced = str(jax.make_jaxpr(lambda: delta.delta_scan(
            qk[:, :, :1], qk[:, :, :1], qk, g[..., 0], g[..., 0], 64))())
        assert "pallas_call" in traced
    assert perfvars.snapshot()["delta_kernel_lowerings"] == {"kernel": 2,
                                                             "plain": 1}
    assert perfvars.snapshot()["delta_decays"] == {"head": 1, "channel": 2}
    perfvars.reset()


def test_the_rule_counts_what_it_decides_and_hands_on_the_flag(
        kernel_backend):
    perfvars.reset()
    asked = (choice.GROUPED, 128, 128, 128, F32)
    assert choice.decide(*asked) is None                    # the CPU
    with kernel_backend("mosaic"):
        run = choice.decide(*asked, count=3)
        assert run == ((128, 2048), False) and not run.interpret
        assert choice.decide(*asked, also=False) is None
        assert choice.decide(choice.GROUPED, 100, 128, 128, F32) is None
    with kernel_backend("interpret"):
        assert choice.decide(*asked).interpret
        assert choice.interpret() and choice.trace_key() == ("interpret",)
    assert not choice.interpret() and choice.trace_key() == (None,)
    assert perfvars.snapshot()["gmm_lowerings"] == {"kernel": 4,
                                                    "ragged_dot": 3}
    perfvars.reset()


LAYERS = {      # a config's fields, and the family its layer's choice counts in
    "attention": (dict(), "attn_lowerings", "fused", "plain"),
    "scan": (dict(mixer_kinds=["ssm"], ssm_expand=2, ssm_heads=8,
                  ssm_head_dim=64, ssm_state=128, ssm_conv=4, ssm_chunk=128,
                  rope_full_layers=False, dense_gated=True),
             "scan_kernel_lowerings", "kernel", "plain"),
}


@pytest.mark.parametrize("what", sorted(LAYERS))
def test_a_layer_traced_under_one_word_is_traced_again_under_the_other(
        what, kernel_backend):
    """`_block_traced_once` keeps a kind's one trace, and the kernels are
    chosen inside it: keyed on `choice.trace_key`, the trace made on the
    CPU's word is not the one found under the tests', nor the reverse, and
    under the same word the trace is found (nothing is counted again)."""
    fields, family, kernel, plain = LAYERS[what]
    cfg = tf.TransformerConfig(vocab=64, d_model=256, n_heads=4, n_layers=1,
                               d_ff=128, max_seq=128, dtype=F32, **fields)
    params = tf.transformer_init(jax.random.key(0), cfg)
    tokens = jnp.zeros((1, 128), jnp.int32)

    def trace():
        jax.make_jaxpr(lambda p: tf._trunk(cfg, p, tokens)[0])(params)
        return perfvars.snapshot()[family]
    tf._block_traced_once.cache_clear()
    perfvars.reset()
    assert trace() == {kernel: 0, plain: 1}
    with kernel_backend("interpret"):
        assert trace() == {kernel: 1, plain: 1}
        assert trace() == {kernel: 1, plain: 1}     # found: the same word
    assert trace() == {kernel: 1, plain: 1}         # and the first one's
    tf._block_traced_once.cache_clear()
    perfvars.reset()


def test_a_sum_of_rows_is_built_again_under_another_word(kernel_backend):
    """`ep._summed` keeps one jitted sum a destination, and hands its kernel
    the flag the word gives: keyed on `choice.trace_key` too."""
    with kernel_backend("mosaic"):
        mosaic = ep._summed(128, jnp.dtype(F32), choice.trace_key())
        assert ep._summed(128, jnp.dtype(F32), choice.trace_key()) is mosaic
    with kernel_backend("interpret"):
        assert ep._summed(128, jnp.dtype(F32),
                          choice.trace_key()) is not mosaic
