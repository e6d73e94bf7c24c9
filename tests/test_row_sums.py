"""Rows summed into indexed places (`parallel.ep.sum_rows`) and the gather
that is its transpose (`rows_at`): the product on the MXU, run here on the
Pallas interpret machine, and the plain path, each against
``jnp.zeros(..).at[place].add(scale * rows)`` written out, with repeated
places, places nobody is sent to and rows masked out, in bfloat16 and
float32, into more places than rows (an embedding's gradient) and into as
many (the held expert layer); their gradients against `jax.grad` of that
form; and the two held models' steps at a test size, lowered with the
kernels selected: no scatter is left under `dispatch`, `combine` or
`embed`, and the counter says what was traced."""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from tpu_mpi import perfvars, xla                               # noqa: E402
from tpu_mpi.models import transformer as tf                    # noqa: E402
from tpu_mpi.models.transformer import (transformer_init,       # noqa: E402
                                        transformer_train_step)
from tpu_mpi.parallel import ep                                 # noqa: E402

F32 = jnp.float32
SHAPES = {"held": (256, 256, 128), "embedding": (128, 640, 256)}
CASES = ("repeated", "masked", "weighed")


def operands(shape: str, case: str, dtype):
    """(rows, place, scale, live, the result's dtype): half of the places
    are never named and the others several times each."""
    m, places, d = SHAPES[shape]
    keys = jax.random.split(jax.random.key(len(shape) + len(case)), 4)
    rows = jax.random.normal(keys[0], (m, d), F32).astype(dtype)
    place = (2 * jax.random.randint(keys[1], (m,), 0, places // 4)
             ).astype(jnp.int32)
    live = None if case == "repeated" else jax.random.bernoulli(
        keys[2], 0.6, (m,))
    scale = jax.random.uniform(keys[3], (m,), F32) \
        if case == "weighed" else None
    return rows, place, scale, live, (F32 if case == "weighed" else dtype)


def written_out(rows, place, places, scale, live, dtype):
    """The sum as XLA's scatter-add, float32 throughout, rounded once."""
    rows = rows.astype(F32)
    if scale is not None:
        rows = rows * scale[:, None]
    if live is not None:
        rows = jnp.where(live[:, None], rows, 0)
    return jnp.zeros((places, rows.shape[1]), F32).at[place].add(
        rows).astype(dtype)


def once(fn, *args):
    """One jitted program, waited for (the interpret machine's callbacks
    must not meet an eager computation of this thread: verify skill)."""
    return jax.tree.map(np.asarray, jax.block_until_ready(
        jax.jit(fn)(*args)))


@pytest.fixture(params=["plain", "interpret"])
def backend(request, kernel_backend):
    if request.param == "interpret":
        kernel_backend("interpret")
    return request.param


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_sum_is_the_scatter_add_written_out(backend, shape, case, dtype):
    dtype = jnp.dtype(dtype)
    rows, place, scale, live, out = operands(shape, case, dtype)
    places = SHAPES[shape][1]
    want = once(lambda *a: written_out(*a[:2], places, *a[2:], out),
                rows, place, scale, live).astype(np.float32)
    perfvars.reset()
    got = once(lambda r, p, s, lv: ep.sum_rows(
        r, p, places, scale=s, live=lv, dtype=out), rows, place, scale, live)
    assert got.dtype == out and got.shape == want.shape
    assert perfvars.snapshot()["row_sum_lowerings"] == {
        "product": int(backend == "interpret"),
        "scatter": int(backend == "plain")}
    # float32 products and a float32 sum, rounded once: the same sum in
    # another order
    np.testing.assert_allclose(
        got.astype(np.float32), want, rtol=0,
        atol=(2.0 ** -8 if out == jnp.bfloat16 else 1e-5) * np.abs(want).max())
    sent = np.zeros(places, bool)
    sent[np.asarray(place)[np.ones(len(place), bool) if live is None
                           else np.asarray(live)]] = True
    assert sent.sum() < places // 2 and not got[~sent].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_sums_gradient_is_jax_grads_of_the_scatter_add(backend, shape,
                                                           case, dtype):
    dtype = jnp.dtype(dtype)
    rows, place, scale, live, out = operands(shape, case, dtype)
    places = SHAPES[shape][1]
    pull = jax.random.normal(jax.random.key(9), (places, rows.shape[1]), F32)
    wrt = (0, 2) if scale is not None else (0,)

    def through(summed):
        return jax.grad(lambda r, p, s, lv: jnp.sum(
            summed(r, p, s, lv).astype(F32) * pull), wrt)
    want = once(through(lambda r, p, s, lv: written_out(
        r, p, places, s, lv, out)), rows, place, scale, live)
    got = once(through(lambda r, p, s, lv: ep.sum_rows(
        r, p, places, scale=s, live=lv, dtype=out, scope="combine")),
        rows, place, scale, live)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        w = w.astype(np.float32)
        np.testing.assert_allclose(
            g.astype(np.float32), w, rtol=0,
            atol=(2.0 ** -7 if g.dtype == jnp.bfloat16 else 1e-5)
            * np.abs(w).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_gathers_gradient_is_the_sum(backend, shape, dtype):
    """`rows_at` is `source[place]`, for places of any shape, and its
    gradient the rows summed back into their places: on the product's path
    in float32 and rounded once, where JAX's own transpose rounds at every
    addition (so each is held to the float32 answer)."""
    m, places, d = SHAPES[shape]
    rows, place, _scale, _live, _ = operands(shape, "repeated",
                                             jnp.dtype(dtype))
    source = jax.random.normal(jax.random.key(5), (places, d),
                               F32).astype(dtype)
    place = place.reshape(2, m // 2)
    want = once(lambda s: (s[place], written_out(
        rows, place.reshape(m), places, None, None, F32)), source)
    got = once(lambda s: jax.vjp(lambda s: ep.rows_at(
        s, place, scope="embed"), s)[1](rows.reshape(2, m // 2, d))[0], source)
    np.testing.assert_array_equal(
        once(lambda s: ep.rows_at(s, place, scope="embed"), source), want[0])
    assert got.dtype == source.dtype
    top = np.abs(want[1]).max()
    np.testing.assert_allclose(
        got.astype(np.float32), want[1], rtol=0,
        atol=(2.0 ** -6 if dtype == "bfloat16" else 1e-5) * top)


def test_a_place_out_of_range_is_indexings(backend):
    """A negative place wraps and one past the end adds nothing, on both
    paths, as `.at[].add` has it."""
    m, places, d = SHAPES["held"]
    rows, place, _scale, _live, _ = operands("held", "repeated", F32)
    place = place.at[:8].set(jnp.arange(-4, 4, dtype=jnp.int32) * 3 - 1) \
        .at[8:12].set(places + jnp.arange(4, dtype=jnp.int32) * 50)
    want = once(lambda r: written_out(r, place, places, None, None, F32), rows)
    got = once(lambda r: ep.sum_rows(r, place, places), rows)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * np.abs(want).max())


def test_what_selects_the_product(kernel_backend):
    """The backend and the shapes alone: blocks of 128 places, rows in row
    tiles, a width of 128s, float32 or bfloat16."""
    assert not ep.row_sum_selected((256, 128), 256, F32)    # no backend
    kernel_backend("mosaic")
    assert ep.row_sum_selected((4096, 7680), 19200, jnp.bfloat16)
    assert ep.row_sum_selected((8192, 6144), 8192, F32)
    assert ep.row_sum_selected((8192, 1024), 32768, jnp.bfloat16)
    assert not ep.row_sum_selected((4096, 7680), 19201, jnp.bfloat16)
    assert not ep.row_sum_selected((4100, 7680), 19200, jnp.bfloat16)
    assert not ep.row_sum_selected((4096, 7700), 19200, jnp.bfloat16)
    assert not ep.row_sum_selected((4096, 7680), 19200, jnp.float16)
    from tpu_mpi.xla import pallas_kernels as pk
    assert pk.grouped_row_sums_blocks(4096, 7680, 2) == (512, 1920)
    assert pk.grouped_row_sums_blocks(8192, 6144, 4) == (512, 2048)
    # the walk the embedding asks for: 150 blocks of places over 8 row
    # tiles, every block visited, the visits of a tile consecutive
    sizes = jnp.full((150,), 27, jnp.int32).at[7].set(0)
    offs, group, tile, _matrix, visits = (np.asarray(v) for v in
                                          pk.grouped_matmul_visits(
                                              sizes, 4096, 512))
    assert len(group) == 158 and offs[-2] == 149 * 27 and offs[-1] == 4096
    live = group[:visits[0]]
    assert sorted(set(live[live < 150])) == list(range(150))
    assert (np.diff(tile[:visits[0]]) >= 0).all()


# -- the two held models' steps at a test size ----------------------------------

def held_configs() -> dict:
    """tests/test_layer_kinds.py's and tests/test_latent_layer.py's models
    with the widths the product's contract asks for: 128 tokens of 128
    elements, a vocabulary of 128."""
    import test_latent_layer
    import test_layer_kinds
    return {name: dataclasses.replace(mod.CFG, d_model=128, max_seq=128)
            for name, mod in (("kinds", test_layer_kinds),
                              ("latent", test_latent_layer))}


def scatters_by_scope(jaxpr) -> list:
    """The name stacks of every scatter of a closed jaxpr, inner jaxprs
    included."""
    found = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name.startswith("scatter"):
                found.append(str(eqn.source_info.name_stack))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(getattr(sub, "jaxpr", sub))
    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("model", ["kinds", "latent"])
def test_a_held_step_with_the_kernels_selected_scatters_no_row(model,
                                                               kernel_backend):
    cfg = held_configs()[model]
    mesh = xla.make_mesh({"dp": 1, "tp": 1, "sp": 1},
                         devices=jax.devices()[:1])
    params = transformer_init(jax.random.key(0), cfg)
    tokens = jnp.zeros((1, 128), jnp.int32)
    ours = re.compile(r"\b(dispatch|combine|embed)\b")

    def traced():
        tf._block_traced_once.cache_clear()
        perfvars.reset()
        step, _specs = transformer_train_step(cfg, mesh, lr=0.01)
        stacks = scatters_by_scope(jax.make_jaxpr(step)(params, tokens,
                                                        tokens))
        tf._block_traced_once.cache_clear()
        return ([s for s in stacks if ours.search(s)],
                perfvars.snapshot()["row_sum_lowerings"])
    # the plain path: the embedding's gradient, and in each kind of sparse
    # layer the combine and the transpose of the dispatch's gather
    plain, counted = traced()
    kinds = len({cfg.layer_kind(i) for i in range(cfg.n_layers)
                 if cfg.layer_kind(i).sparse})
    assert {m.group(1) for s in plain for m in [ours.search(s)]} == {
        "dispatch", "combine", "embed"}
    assert counted["product"] == 0 and counted["scatter"] >= 1 + 2 * kinds
    kernel_backend("mosaic")
    left, counted = traced()
    assert left == []
    # one gather and one sum a buffer of a sparse layer kind's trace (the
    # first buffer and the further ones' scan), and the embedding's
    assert counted == {"product": 1 + 4 * kinds, "scatter": 0}


@pytest.mark.parametrize("buffers", ["one", "further"])
def test_a_held_model_on_the_product_is_the_plain_paths(buffers, monkeypatch,
                                                        kernel_backend):
    """The layer-kind model at the test size, float32, the product's path
    on the interpret machine against the plain path: loss and gradient leaf
    by leaf with every held slot in one buffer (the interpret machine's
    callbacks cannot be recomputed, so nothing is: no further buffer is
    built and no layer is marked), and the logits of a batch whose held
    slots overflow into further buffers."""
    cfg = dataclasses.replace(held_configs()["kinds"],
                              remat_layers=[""] * 6)
    params = transformer_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (1, 128), 0, cfg.vocab)
    labels = jnp.roll(tokens, -1, axis=1)
    monkeypatch.setattr(ep, "HELD_ROWS_FACTOR",
                        4.0 if buffers == "one" else 0.5)

    def run():
        tf._block_traced_once.cache_clear()
        if buffers == "one":
            out = once(jax.value_and_grad(lambda p: tf._xent(
                tf._forward(cfg, p, tokens)[0], labels)), params)
        else:
            out = once(lambda p: tf._forward(cfg, p, tokens), params)
        tf._block_traced_once.cache_clear()
        return out
    with jax.default_matmul_precision("highest"):
        want = run()
        kernel_backend("interpret")
        perfvars.reset()
        got = run()
    counted = perfvars.snapshot()["row_sum_lowerings"]
    assert counted["scatter"] == 0 and counted["product"] >= 3
    if buffers == "further":
        assert any(int(did[2]) == 1 for _p, _s, did in want[1])
    for (path, g), w in zip(jax.tree.leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert np.abs(g - w).max() <= 1e-5 * max(np.abs(w).max(), 1e-3), path
