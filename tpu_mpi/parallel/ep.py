"""Expert parallelism: capacity-bounded token routing over an 'ep' axis.

Reference primitive: Alltoallv! — variable-size token routing (SURVEY.md §2.5;
/root/reference/src/collective.jl:545-578). TPU realization: XLA needs static
shapes, so variable counts become a fixed per-expert *capacity* with masking
(the padded-all_to_all strategy SURVEY.md §2.3 prescribes for `*v` ops);
one ``lax.all_to_all`` ships token buffers to their experts and one ships
results back.

Four realizations live here:

- :func:`moe_dropless` — top-k routing in which every token-slot is computed
  (the layer of the public sparse-expert models, `models/transformer.py`):
  the slots are sorted by expert and the experts run over the row groups
  (:func:`grouped_products`: the grouped Pallas kernel where the backend and
  the shape allow it, `lax.ragged_dot` elsewhere). A row's way: every token
  is copied to its k slots' places in expert order (one gather from the
  ``[t, d]`` array), the experts multiply each row by its router weight
  where the row is narrowest, and the k rows of a token are read back and
  summed in float32, rounded once; the two maps are each other's
  transposes and each other's gradients, and the weights travel as the
  payload of the sort. Over an ``ep`` axis the row groups travel by
  ``lax.all_to_all`` in buffers sized for the worst case;
- :func:`moe_dropless_held` — the same layer on a rank that holds a few of
  the experts and is given no exchange to run (one chip's share of an
  expert-parallel layer): the part of the result its experts give, from a
  row buffer that follows the slots that arrive, nothing dropped; its rows
  are read by :func:`rows_at` and summed into their tokens' places by
  :func:`sum_rows`, which is also how the model's embedding is read and
  its gradient summed: a product with a 0/1 matrix on the MXU where the
  grouped kernels are selected, XLA's scatter-add elsewhere;
- :func:`moe_dispatch_combine` — top-1, rank == expert, tokens over a fixed
  capacity dropped (static shapes, capacity masking, ``lax.all_to_all``):
  the pipelined demo's (`transformer_pp_moe_*`);
- :func:`moe_host_dispatch_combine` — the host-path decode-step variant
  used by the inference engine (``tpu_mpi.infer``): true variable counts
  over :func:`tpu_mpi.Alltoallv` on an ``ep`` communicator, which routes
  every decode step through the algorithm-selection layer and the online
  bandit's decision point (``collective._maybe_explore``). Token routing
  is nonstationary traffic — exactly what the epsilon-greedy explorer was
  built for.
"""

from __future__ import annotations

import functools
import threading
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..xla import choice
from ..xla import pallas_kernels as pk

# Per-thread persistent count-exchange buffers, keyed by (cid, n). The
# count Alltoall has a FIXED signature (n int64 per rank, same comm)
# every decode step — reusing the same buffer objects is what lets the
# auto-arm signature table (PR 11 plan cache) promote it to an armed
# persistent collective instead of re-planning per step. Thread-local
# because in the thread tier every rank drives its own copy of this
# function concurrently over the shared comm object.
_count_bufs = threading.local()


def _count_exchange_bufs(cid: int, n: int):
    cache = getattr(_count_bufs, "m", None)
    if cache is None:
        cache = _count_bufs.m = {}
    key = (cid, n)
    if key not in cache:
        cache[key] = (np.zeros(n, np.int64), np.zeros(n, np.int64))
    return cache[key]


def grouped_matmul_selected(rows_shape: tuple, weights_shape: tuple,
                            dtype) -> bool:
    """Whether a product of :func:`grouped_products` runs the grouped
    kernel for ``[m, k]`` rows and ``[g, k, n]`` weights of ``dtype``:
    `xla.choice`'s rule over the kernel's contract,
    ``pallas_kernels.grouped_matmul_blocks``."""
    return choice.fit(choice.GROUPED, rows_shape[0], *weights_shape[1:],
                      dtype) is not None


def grouped_products(sizes: jnp.ndarray) -> Callable:
    """``product(rows, weights)`` over rows sorted by group, ``sizes[e]`` of
    them in group e, in order: row i of ``rows[m, k]`` times the matrix of
    ``weights[g, k, n]`` whose group it falls in; rows past the groups' sum
    come out zero (`lax.ragged_dot`'s meaning). On a TPU, at a shape inside
    its contract, the Pallas kernel ``xla.pallas_kernels.grouped_matmul``
    (forward, and in the backward pass the rows' and the weights' gradients,
    no weight transposed in HBM); everywhere else `lax.ragged_dot`.

    The kernel's walk over the groups depends on the sizes alone: it is
    computed at the first product that takes the kernel and kept here for
    the ones that follow, so an expert layer computes it once for its three
    products and their six gradients. Each product counts, where this
    choice is made (once for every trace of the layer that holds it), in
    ``perfvars.snapshot()["gmm_lowerings"]`` as ``kernel`` or
    ``ragged_dot``."""
    walks = {}

    def product(rows: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
        run = choice.decide(choice.GROUPED, rows.shape[0], *weights.shape[1:],
                            rows.dtype, also=rows.dtype == weights.dtype)
        if run is None:
            return lax.ragged_dot(rows, weights, sizes)
        walk = rows.shape[0], run.fit[0]    # what a walk depends on
        if walk not in walks:
            walks[walk] = pk.grouped_matmul_visits(sizes, *walk)
        return pk.grouped_matmul(
            rows, weights, sizes, visits=walks[walk], block_m=run.fit[0],
            block_c=run.fit[1], interpret=run.interpret)
    return product


@jax.custom_vjp
def _rows_of_tokens(tokens, order, inverse):
    """``rows[i] = tokens[order[i] // k]``: every token copied to the places
    of its k slots in expert order (``order[S]`` the slots sorted by expert,
    ``inverse[t, k]`` each slot's place there). One gather whose source is
    the ``[t, d]`` array; its gradient is :func:`_tokens_of_rows`, the
    transposed map, so neither direction scatters."""
    return tokens[order // inverse.shape[1]]


@jax.custom_vjp
def _tokens_of_rows(rows, order, inverse):
    """``tokens[t] = sum_j rows[inverse[t, j]]``: the k rows of a token's
    slots read back from expert order and summed, in float32, rounded once
    to the rows' dtype. The transpose of :func:`_rows_of_tokens`, which is
    its gradient."""
    return jnp.sum(rows[inverse].astype(jnp.float32),
                   axis=1).astype(rows.dtype)


def _rows_of_tokens_bwd(kept, g):
    with jax.named_scope("dispatch"):
        return _tokens_of_rows(g, *kept), None, None


def _tokens_of_rows_bwd(kept, g):
    with jax.named_scope("combine"):
        return _rows_of_tokens(g, *kept), None, None


_rows_of_tokens.defvjp(
    lambda tokens, order, inverse: (_rows_of_tokens(tokens, order, inverse),
                                    (order, inverse)), _rows_of_tokens_bwd)
_tokens_of_rows.defvjp(
    lambda rows, order, inverse: (_tokens_of_rows(rows, order, inverse),
                                  (order, inverse)), _tokens_of_rows_bwd)


@jax.custom_vjp
def _by_expert(weights, flat):
    """The slots sorted by expert, stably: ``(order[S], scale[S])`` with
    ``order`` the slots' numbers and ``scale[i] = weights.flat[order[i]]``
    each slot's weight at its row's place, both the payload of one
    key-value sort. The weights' gradient comes back by a sort on
    ``order``. An ``[S]`` vector is moved by a sort, never by a gather or
    a scatter: on the chip a sort of 65536 pairs takes 0.05 ms, a gather or
    scatter of as many scalars 0.3-0.5 (PERF.md, PR 31)."""
    slots = jnp.arange(flat.shape[0], dtype=jnp.int32)
    return lax.sort((flat, slots, weights.reshape(-1)), num_keys=1,
                    is_stable=True)[1:]


def _by_expert_bwd(kept, g):
    order, shape = kept
    with jax.named_scope("dispatch"):
        return lax.sort((order, g[1]), num_keys=1)[1].reshape(shape), None


def _by_expert_fwd(weights, flat):
    order, scale = _by_expert(weights, flat)
    return (order, scale), (order, weights.shape)


_by_expert.defvjp(_by_expert_fwd, _by_expert_bwd)


def moe_dropless(tokens: jnp.ndarray, expert_idx: jnp.ndarray,
                 weights: jnp.ndarray,
                 expert_fn: Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray],
                                     jnp.ndarray],
                 n_experts: int, *, axis: Optional[str] = None):
    """Top-k Mixture-of-Experts dispatch/combine that drops nothing.

    tokens: (t, d) local tokens; expert_idx: (t, k) each token's experts
    (global ids, distinct per token); weights: (t, k) what each expert's
    output is multiplied by. expert_fn(rows, group_sizes, scale): the
    experts held here applied to rows sorted by expert, group_sizes[e] rows
    for expert e (what :func:`grouped_products` takes, which is what an
    expert_fn multiplies with), **each row's result times scale[i]**, the
    weight of the row's slot: the experts multiply wherever a row is
    narrowest (the model: on the hidden activation, inside a pass that
    exists), so no ``[t x k, d]`` array is written for the weights' sake in
    either direction. Rows past the groups' sum are padding, and what
    expert_fn returns for them is not read.
    Returns ((t, d) sum over k of weights x expert(token), (n_experts,)
    int32 token-slots of these tokens per expert). The experts always
    process exactly t x k rows in all.

    How a row moves (scopes ``dispatch`` / ``experts`` / ``combine``, which
    the gradients keep): the slots are sorted by expert with their weights
    as the sort's payload (:func:`_by_expert`); every token is copied to its
    slots' places (:func:`_rows_of_tokens`: a gather whose source is the
    ``[t, d]`` array, small enough for XLA to keep on the chip); the
    experts' rows are read back through ``inverse`` and summed per token in
    float32, rounded once (:func:`_tokens_of_rows`). The two maps are
    transposes: the combine's gradient is the first (the token's cotangent
    copied to its slots: nothing is scaled, permuted or kept for it), the
    dispatch's gradient the second. The weights' gradient is whatever
    expert_fn's own differentiation gives for ``scale``, carried back to
    slot order by a sort.

    With ``axis``, inside shard_map: the tokens and the experts are both
    sharded over it, rank r holding experts [r x n_experts/n, (r+1) x
    n_experts/n). Every rank's row groups go to their experts' ranks and
    come back by ``lax.all_to_all``, in buffers of t x k rows per peer (what
    one peer receives when every slot picks its experts). No model calls it
    with an axis yet: `transformer_train_step` has no ``ep`` axis, and this
    path has run on virtual CPU devices only (tests/test_moe_layer.py).
    """
    t, k = expert_idx.shape
    with jax.named_scope("dispatch"):
        flat = expert_idx.reshape(t * k)        # slot s: token s // k
        order, scale = _by_expert(weights, flat)
        inverse = jnp.argsort(order).astype(jnp.int32).reshape(t, k)
        sizes = jnp.sum(flat[:, None] == jnp.arange(n_experts)[None, :],
                        axis=0, dtype=jnp.int32)
        rows = _rows_of_tokens(tokens, order, inverse)
    if axis is None:
        with jax.named_scope("experts"):
            out = expert_fn(rows, sizes, scale)
    else:
        out = _over_expert_ranks(rows, scale, sizes, expert_fn, axis)
    with jax.named_scope("combine"):
        return _tokens_of_rows(out, order, inverse), sizes


# -- rows summed into indexed places ------------------------------------------
#
# out[p] = sum over the rows i with place[i] == p of scale[i] x rows[i]: the
# held layer's combine, the transpose of its dispatch gather, the embedding's
# gradient. XLA's scatter-add runs it one to two orders under what the memory
# allows (2.7-8 G elements a second at the held cells' shapes: PERF.md
# section 6, PR 33), so where the grouped kernels are selected it is a
# product with a 0/1 matrix on the MXU: the rows are sorted by place (a sort
# of the places, one gather of the rows), a block of 128 places then owns a
# run of rows, and the sum of a block is onehot[its rows, 128]^T x its rows,
# accumulated in float32 (`pallas_kernels.grouped_row_sums`: the kernel of
# the experts' weights' gradient). The sum and the gather `source[place]` are
# each other's transposes and, there, each other's gradients, as
# `_rows_of_tokens` and `_tokens_of_rows` are.

def _row_sum_choice(ask: Callable, rows_shape: tuple, places: int, dtype):
    """`xla.choice`'s answer (``ask``: its `fit`, or its `decide`, which
    counts) for ``rows[m, d]`` of ``dtype`` summed into ``places`` places:
    the kernel's contract, ``pallas_kernels.grouped_row_sums_blocks`` (rows
    a multiple of a row tile, the width of 128), and the places in whole
    blocks of 128."""
    return ask(choice.ROW_SUM, *rows_shape, dtype,
               also=places % pk.LANE == 0)


def row_sum_selected(rows_shape: tuple, places: int, dtype) -> bool:
    """Whether ``rows[m, d]`` of ``dtype`` (float32 where they are weighed)
    are summed into ``places`` places by the product on the MXU and not by
    XLA's scatter-add."""
    return _row_sum_choice(choice.fit, rows_shape, places, dtype) is not None


def _by_place(rows, place, places: int, scale, live):
    """The product's operands: (the one-hot ``[m, 128]`` of each row's place
    within its block of 128 places, carrying the row's float32 ``scale``
    where there is one, in the order of the places; ``rows`` in that order,
    float32 where they are weighed; the rows in each block). A row that is
    not ``live`` comes last, under a place past all, and with it a row
    whose place lies outside [-places, places): indexing wraps a negative
    place and drops an update out of range. One key-value sort of ``[m]``
    vectors, one gather of the rows, a comparison grid."""
    key = place.astype(jnp.int32)
    key = jnp.where(key < 0, key + places, key)
    inside = jnp.logical_and(key >= 0, key < places)
    key = jnp.where(inside if live is None else jnp.logical_and(inside, live),
                    key, places)
    key, order, *scale = lax.sort(
        (key, jnp.arange(key.shape[0], dtype=jnp.int32))
        + (() if scale is None else (scale.astype(jnp.float32),)),
        num_keys=1, is_stable=True)
    blocks = jnp.arange(places // 128, dtype=jnp.int32)
    sizes = jnp.sum(key[:, None] // 128 == blocks[None, :], axis=0,
                    dtype=jnp.int32)
    hot = (key % 128)[:, None] == jnp.arange(128, dtype=jnp.int32)[None, :]
    if not scale:
        return hot.astype(rows.dtype), rows[order], sizes
    return (jnp.where(hot, scale[0][:, None], 0),
            rows[order].astype(jnp.float32), sizes)


@functools.lru_cache(maxsize=None)
def _summed(places: int, dtype, key: tuple):
    """The sum as the product (the form's own words are above): float32
    products where a scale weighs the rows, the rows' own dtype into the
    MXU where none does; float32 accumulation, one rounding to ``dtype``.
    Jitted once a destination: the sums of a program that share their
    shapes (a layer's forward pass and its recomputation, its first buffer
    and the further ones, two kinds of sparse layer: 13 calls of three
    shapes in the K-EXAONE step) share one trace, which is set-up time
    (PERF.md, Set-up). ``key``: `choice.trace_key`, what the trace read."""
    interpret = choice.interpret()

    @jax.jit
    def summed(rows, place, scale, live):
        return pk.grouped_row_sums(
            *_by_place(rows, place, places, scale, live), out_dtype=dtype,
            interpret=interpret).reshape(places, rows.shape[1])
    return summed


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _sum_rows(rows, place, scale, live, places: int, dtype, scope: str):
    return _summed(places, dtype, choice.trace_key())(rows, place, scale,
                                                       live)


def _sum_rows_bwd(places, dtype, scope, kept, g):
    rows, place, scale, live = kept
    with jax.named_scope(scope):
        back = _rows_at(g, place, places, scope).astype(jnp.float32)
        if live is not None:
            back = jnp.where(live[:, None], back, 0)
        if scale is None:
            return back.astype(rows.dtype), None, None, None
        d_scale = jnp.sum(back * rows.astype(jnp.float32), axis=1)
        return ((back * scale.astype(jnp.float32)[:, None]).astype(
            rows.dtype), None, d_scale.astype(scale.dtype), None)


_sum_rows.defvjp(lambda rows, place, scale, live, *static: (
    _sum_rows(rows, place, scale, live, *static),
    (rows, place, scale, live)), _sum_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rows_at(source, place, places: int, scope: str):
    return source[place]


def _rows_at_bwd(places, scope, place, g):
    with jax.named_scope(scope):
        return _sum_rows(g, place, None, None, places, g.dtype, scope), None


_rows_at.defvjp(lambda source, place, places, scope: (source[place], place),
                _rows_at_bwd)


def sum_rows(rows: jnp.ndarray, place: jnp.ndarray, places: int, *,
             scale: Optional[jnp.ndarray] = None,
             live: Optional[jnp.ndarray] = None, dtype=None,
             scope: str = "sum_rows") -> jnp.ndarray:
    """``out[p] = sum over the rows i with place[i] == p (and live[i]) of
    scale[i] x rows[i]``: ``rows[m, d]`` summed into ``[places, d]`` of
    ``dtype`` (the rows' where None), float32 products and a float32 sum,
    rounded once. Places nobody is sent to come out zero; a row that is not
    ``live`` adds nothing and its place is not read.

    Where :func:`row_sum_selected` says so it is the product on the MXU
    described above, with a gradient of its own (the rows' is the gather
    ``g[place] x scale``, the scale's the rows' dot with it; ``scope`` names
    their ops); everywhere else ``jnp.zeros(..).at[place].add(..)``, XLA's
    scatter-add, left to JAX's differentiation. Each call built into a
    traced program counts in ``perfvars.snapshot()["row_sum_lowerings"]`` as
    ``product`` or ``scatter``."""
    dtype = jnp.dtype(rows.dtype if dtype is None else dtype)
    weighed = jnp.float32 if scale is not None else rows.dtype
    if _row_sum_choice(choice.decide, rows.shape, places, weighed):
        rows, place, *rest = _vary_together(rows, place, scale, live)
        return _sum_rows(rows, place, *rest, places, dtype, scope)
    if scale is not None:
        rows = rows.astype(jnp.float32) * scale.astype(jnp.float32)[:, None]
    if live is not None:
        rows = jnp.where(live[:, None], rows, 0)
    return jnp.zeros((places, rows.shape[1]),
                     jnp.promote_types(rows.dtype, jnp.float32)
                     ).at[place].add(rows).astype(dtype)


def rows_at(source: jnp.ndarray, place: jnp.ndarray, *,
            scope: str = "rows_at") -> jnp.ndarray:
    """``source[place]``: the rows of ``source[places, d]`` at ``place`` (of
    any shape), the transpose of :func:`sum_rows`. Where the product form
    is selected for its gradient (these rows of the source's dtype summed
    back into its places; ``scope`` names the gradient's ops) the gradient
    is that sum; everywhere else the indexing is left to JAX, whose
    transpose is XLA's scatter-add. Counted as :func:`sum_rows` counts."""
    if _row_sum_choice(choice.decide, (place.size,) + source.shape[1:],
                       source.shape[0], source.dtype):
        source, flat = _vary_together(source, place.reshape(-1))
        return _rows_at(source, flat, source.shape[0], scope).reshape(
            place.shape + source.shape[1:])
    return source[place]


def _vary_together(*xs):
    """The arrays among ``xs`` (a None stays), each made to vary over every
    mesh axis any of them varies over: what a custom gradient's operands
    need under `shard_map`, where a replicated operand's gradient is then
    summed over those axes by the cast's own transpose."""
    some = pk._vary_together(*(x for x in xs if x is not None))
    return [None if x is None else some.pop(0) for x in xs]


def _zeros_varying_like(x):
    """Zeros of x's shape and dtype that vary over the mesh axes x varies
    over: what a `scan`'s carry and a `cond`'s other branch must be typed as
    under `shard_map`."""
    zeros = jnp.zeros(x.shape, x.dtype)
    axes = tuple(sorted(set(jax.typeof(x).vma) - set(jax.typeof(zeros).vma)))
    return lax.pcast(zeros, axes, to="varying") if axes else zeros


HELD_ROWS_FACTOR = 2.0      # the held experts' row buffer, x the rows a
#                             balanced router sends (more arrive: further
#                             buffers run; nothing is dropped)


def held_row_buffer(slots: int, n_experts: int, held: int,
                    tokens: int) -> int:
    """Rows of :func:`moe_dropless_held`'s buffer for ``slots`` token-slots
    of ``tokens`` tokens routed over ``n_experts`` of which ``held`` are
    here: :data:`HELD_ROWS_FACTOR` x the rows a balanced router sends, and
    no fewer than half that factor x the tokens (one expert can be sent a
    slot of every token, so with the factor at 2 a single hot expert alone
    never spills: a small share of many experts has few balanced rows and
    a skewed router sends it several times those, PERF.md PR 32); rounded
    up to a multiple of 128 (the contract of the grouped kernels: the
    experts' products, and the sums of the buffer's rows into their tokens'
    places and back, :func:`sum_rows`), no more than all slots."""
    want = HELD_ROWS_FACTOR * max(slots * held / n_experts, tokens / 2)
    rows = -(-int(want) // 128) * 128
    return max(128, min(rows, -(-slots // 128) * 128))


def moe_dropless_held(tokens: jnp.ndarray, expert_idx: jnp.ndarray,
                      weights: jnp.ndarray, expert_fn: Callable,
                      n_experts: int, first: int, held: int, *,
                      buffer_rows: int):
    """:func:`moe_dropless` on a rank that holds experts ``[first, first +
    held)`` of ``n_experts`` and runs no exchange: the sum over a token's
    chosen experts *that are held here* of weights x expert(token). Slots
    routed elsewhere add nothing (their experts' ranks add them). Same
    ``expert_fn(rows, group_sizes)``, over ``held`` groups.

    Static shapes without a capacity: the held experts' slots are sorted
    first, their weights the sort's payload (:func:`_by_expert`), and served
    from a buffer of ``buffer_rows`` rows (what :func:`held_row_buffer`
    gives): the buffer's tokens are read by :func:`rows_at` and the
    experts' rows, weighed, are summed into their tokens' places by
    :func:`sum_rows` (``buffer_rows`` rows in float32, not a gather over
    all t x k slots), which where the grouped kernels are selected is a
    product on the MXU in both directions and XLA's scatter-add elsewhere;
    no vector of the layer is moved by a scatter. Where more slots arrive
    than the buffer holds, the rest is served in further buffers of the
    same size, behind a ``lax.cond`` that the common case does not enter
    (recomputed in the backward pass, so they keep nothing): no slot of a
    held expert is dropped at any imbalance.

    Returns ((t, d) out, (n_experts,) int32 token-slots per expert of these
    tokens, over all experts, and (3,) int32 [rows the experts computed,
    rows gathered, 1 if the further buffers ran])."""
    t, k = expert_idx.shape
    slots = t * k
    rows_max = -(-slots // buffer_rows) * buffer_rows
    with jax.named_scope("dispatch"):
        flat = expert_idx.reshape(slots)
        local = flat - first
        here = jnp.logical_and(local >= 0, local < held)
        order, scale = _by_expert(weights, jnp.where(here, local, held))
        order = jnp.pad(order, (0, rows_max - slots))
        scale = jnp.pad(scale, (0, rows_max - slots))
        sizes = jnp.sum(flat[:, None] == jnp.arange(n_experts)[None, :],
                        axis=0, dtype=jnp.int32)
        ends = jnp.cumsum(sizes[first:first + held])
        arrived = ends[-1]

    def serve(n):
        """What rows [n x buffer_rows, (n + 1) x buffer_rows) of the held
        slots, in expert order, add to their tokens; and their count."""
        lo = n * buffer_rows
        with jax.named_scope("dispatch"):
            slot = lax.dynamic_slice(order, (lo,), (buffer_rows,))
            w = lax.dynamic_slice(scale, (lo,), (buffer_rows,))
            live = lo + jnp.arange(buffer_rows, dtype=jnp.int32) < arrived
            token = slot // k
            rows = rows_at(tokens, token, scope="dispatch")
            part = jnp.clip(ends - lo, 0, buffer_rows)
            part = part - jnp.concatenate([part[:1] * 0, part[:-1]])
        with jax.named_scope("experts"):
            out = expert_fn(rows, part.astype(jnp.int32))
        with jax.named_scope("combine"):
            return sum_rows(out, token, t, scale=w, live=live,
                            dtype=jnp.float32, scope="combine"), \
                part.sum().astype(jnp.int32)

    acc, computed = serve(0)
    further = rows_max // buffer_rows - 1
    if further:
        def rest():
            def body(carry, n):
                add, rows = jax.checkpoint(serve)(n)
                return (carry[0] + add, carry[1] + rows), None
            return lax.scan(body, nothing(),
                            jnp.arange(1, further + 1, dtype=jnp.int32))[0]

        def nothing():
            return _zeros_varying_like(acc), _zeros_varying_like(computed)
        spilled = arrived > buffer_rows
        more, more_rows = lax.cond(spilled, rest, nothing)
        acc, computed = acc + more, computed + more_rows
    else:
        spilled = jnp.bool_(False)
    did = jnp.stack([computed.astype(jnp.int32),
                     buffer_rows * (1 + further * spilled.astype(jnp.int32)),
                     spilled.astype(jnp.int32)])
    return acc.astype(tokens.dtype), sizes, did


def _over_expert_ranks(rows: jnp.ndarray, scale: jnp.ndarray,
                       sizes: jnp.ndarray, expert_fn: Callable,
                       axis: str) -> jnp.ndarray:
    """`expert_fn` over rows sorted by global expert, the experts sharded
    over ``axis``: ship each rank's row groups, and each row's weight with
    it, to their experts' ranks, run the local experts over what arrived,
    ship the results back."""
    n = lax.axis_size(axis)
    m, d = rows.shape                           # m = t x k slots
    local = sizes.shape[0] // n                 # experts per rank
    with jax.named_scope("dispatch"):
        to_rank = sizes.reshape(n, local)       # [destination, its expert]
        per_rank = to_rank.sum(axis=1)          # my rows for each rank
        last = jnp.cumsum(per_rank)
        first = last - per_rank
        j = jnp.arange(m, dtype=jnp.int32)
        # send[r, j] = the j-th of my rows for rank r's experts
        src = first[:, None] + j[None, :]
        live = j[None, :] < per_rank[:, None]
        src = jnp.clip(src, 0, m - 1)
        send = jnp.where(live[..., None], rows[src], 0)
        recv = lax.all_to_all(send, axis, 0, 0, tiled=True)     # [source, j]
        their = lax.all_to_all(jnp.where(live, scale[src], 0), axis, 0, 0,
                               tiled=True)
        counts = lax.all_to_all(to_rank, axis, 0, 0, tiled=True)
        # a source's rows arrive sorted by my expert; find each row's expert
        # (or `local` for padding) and sort all sources' rows together
        ends = jnp.cumsum(counts, axis=1)                       # (n, local)
        expert = jnp.sum(j[None, :, None] >= ends[:, None, :], axis=-1)
        by_expert = jnp.argsort(expert.reshape(n * m), stable=True)
        undo = jnp.argsort(by_expert)
        arrived = recv.reshape(n * m, d)[by_expert]
        weighing = their.reshape(n * m)[by_expert]
        groups = counts.sum(axis=0).astype(jnp.int32)
    with jax.named_scope("experts"):
        done = expert_fn(arrived, groups, weighing)
        done = jnp.where((jnp.arange(n * m) < groups.sum())[:, None], done, 0)
    with jax.named_scope("combine"):
        back = lax.all_to_all(done[undo].reshape(n, m, -1), axis, 0, 0,
                              tiled=True)
        rank = jnp.sum(j[:, None] >= last[None, :], axis=-1)    # row i's expert's
        return back[rank, j - first[rank]]


def moe_dispatch_combine(tokens: jnp.ndarray, expert_idx: jnp.ndarray,
                         expert_fn: Callable[[jnp.ndarray], jnp.ndarray], *,
                         capacity: int, axis: str = "ep") -> jnp.ndarray:
    """Top-1 Mixture-of-Experts dispatch/combine.

    tokens: (t, d) local tokens; expert_idx: (t,) target expert (== rank on
    ``axis``) per token; expert_fn: the local expert applied to (n*capacity, d).
    Tokens over capacity are dropped (returned as zeros), the standard
    static-shape MoE contract. Returns (t, d).
    """
    t, d = tokens.shape
    n = lax.axis_size(axis)

    # position of each token within its expert's capacity window
    onehot = jax.nn.one_hot(expert_idx, n, dtype=jnp.int32)       # (t, n)
    pos = jnp.cumsum(onehot, axis=0) * onehot                     # 1-based
    slot = (pos.sum(axis=1) - 1).astype(jnp.int32)                # (t,)
    keep = slot < capacity

    # scatter local tokens into per-expert send buffers (n, capacity, d)
    send = jnp.zeros((n, capacity, d), tokens.dtype)
    send = send.at[expert_idx, jnp.clip(slot, 0, capacity - 1)].add(
        jnp.where(keep[:, None], tokens, 0.0))

    recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=0, tiled=True)
    out = expert_fn(recv.reshape(n * capacity, d)).reshape(n, capacity, d)
    back = lax.all_to_all(out, axis, split_axis=0, concat_axis=0, tiled=True)

    # gather results back to token order
    gathered = back[expert_idx, jnp.clip(slot, 0, capacity - 1)]
    return jnp.where(keep[:, None], gathered, 0.0)


def moe_host_dispatch_combine(tokens: np.ndarray, expert_idx: np.ndarray,
                              expert_fn: Callable[[np.ndarray], np.ndarray],
                              comm, *, capacity: int) -> np.ndarray:
    """Top-1 MoE dispatch/combine on the host path: rank == expert over an
    ``ep`` communicator, shipped with :func:`tpu_mpi.Alltoallv` (true
    variable counts — the padded-capacity trick is only an XLA constraint).

    tokens: (t, d) float32 local tokens (t may be 0); expert_idx: (t,)
    target rank per token; expert_fn: this rank's expert, applied row-wise
    to whatever tokens arrive. Tokens beyond ``capacity`` per destination
    are dropped and come back as exact zeros (same contract as the jit
    path). Returns (t, d), bitwise-deterministic for a fixed routing.

    Every call makes exactly two Alltoallv rendezvous (dispatch, combine)
    plus one int64 Alltoall for the return counts — three decision-point
    visits per layer round for the online autotuner. The engine's
    vectorized decode path concatenates ALL co-batched requests' rows
    into one call, so the per-peer counts come from the whole batch and
    the round count per step is independent of batch width; batching is
    pure data movement here (the expert below stays row-wise), which is
    why a batched round is bitwise identical to the same rows sent one
    request at a time. The count exchange reuses per-thread persistent
    buffers so its fixed signature repeats verbatim and can auto-arm.
    """
    from .. import collective as _c
    tokens = np.ascontiguousarray(tokens)
    if tokens.ndim != 2:
        tokens = tokens.reshape(-1, tokens.shape[-1] if tokens.size else 1)
    t, d = tokens.shape
    n = comm.size()
    idx = np.asarray(expert_idx, dtype=np.int64).reshape(-1)

    # sender-side capacity bound: the first `capacity` tokens per
    # destination in original token order (stable — routing determines the
    # drop set, not arrival jitter)
    picked = [np.flatnonzero(idx == e)[:capacity] for e in range(n)]
    scounts = [int(p.size) for p in picked]
    order = (np.concatenate(picked) if picked else
             np.zeros(0, np.int64)).astype(np.int64)
    send = tokens[order] if t else tokens.reshape(0, d)

    sbuf, rbuf = _count_exchange_bufs(comm.cid, n)
    sbuf[:] = scounts
    rbuf[:] = 0
    _c.Alltoall(sbuf, rbuf, 1, comm)
    rcounts = [int(c) for c in rbuf]
    sc_el = [c * d for c in scounts]
    rc_el = [c * d for c in rcounts]

    flat_in = np.zeros(sum(rc_el), tokens.dtype)
    _c.Alltoallv(np.ascontiguousarray(send.reshape(-1)), flat_in,
                 sc_el, rc_el, comm)
    arrived = flat_in.reshape(-1, d)

    # apply the expert one row at a time: a token's result can never
    # depend on how many neighbors happened to share its exchange (BLAS
    # picks shape-dependent summation orders for larger operands), which
    # is what makes greedy decode scheduler-order independent.
    out = np.empty_like(arrived)
    for i in range(arrived.shape[0]):
        out[i] = expert_fn(arrived[i:i + 1])[0]

    flat_back = np.zeros(sum(sc_el), tokens.dtype)
    _c.Alltoallv(np.ascontiguousarray(out.reshape(-1)), flat_back,
                 rc_el, sc_el, comm)
    combined = np.zeros((t, d), tokens.dtype)   # dropped rows: exact zeros
    if order.size:
        combined[order] = flat_back.reshape(-1, d)
    return combined
