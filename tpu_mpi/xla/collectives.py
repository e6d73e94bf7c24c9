"""Compiled collectives: MPI operations as XLA ICI ops inside shard_map.

Reference: /root/reference/src/collective.jl enumerates the operation set;
SURVEY.md §2.3 gives the lowering table this module implements:

- Allreduce  → ``lax.psum`` / ``lax.pmax`` / ``lax.pmin`` (custom ops compile
  into an all_gather + unrolled reduction — any jittable binary fn works,
  src/operators.jl:56-88's @cfunction machinery has no TPU analog because
  none is needed)
- Allgather  → ``lax.all_gather``; Reduce_scatter → ``lax.psum_scatter``
- Alltoall   → ``lax.all_to_all``; Bcast → one-hot ``psum`` from the root
- Scan/Exscan → ``lax.associative_scan`` over the gathered rank axis
- Sendrecv/ring shifts → ``lax.ppermute``; Barrier → 1-element psum

Every function must be called inside ``shard_map``/``pjit`` tracing over a
mesh with the named axis. Rank = ``lax.axis_index(axis)``; there is no
communicator object in-graph — the mesh axis *is* the communicator
(SURVEY.md §2.2 Comm row).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Union

import numpy as np

from ..operators import LAND, LOR, LXOR, MAX, MIN, Op, PROD, SUM, as_op

Axis = Union[str, Sequence[str]]


def _lax():
    from jax import lax
    return lax


def rank(axis: str):
    """Rank along a mesh axis (Comm_rank analog, src/comm.jl:49-53)."""
    return _lax().axis_index(axis)


def size(axis: str) -> int:
    """Static size of a mesh axis (Comm_size analog, src/comm.jl:66-70)."""
    return _lax().axis_size(axis)


def barrier(axis: Axis):
    """Synchronization point (src/collective.jl:15-19): a 1-element psum —
    on TPU a collective is itself the barrier."""
    import jax.numpy as jnp
    return _lax().psum(jnp.zeros((), jnp.int32), axis)


def _replicate(x: Any, axis: str):
    """Assert replication to shard_map's static varying-axes system.

    Values equal on every rank (e.g. an all_gather followed by identical
    per-rank math) still count as 'varying' statically; a one-hot psum — a
    broadcast from rank 0 — makes the invariance checkable. Costs one
    payload-sized broadcast; only the non-native-op paths pay it."""
    import jax.numpy as jnp
    lax = _lax()
    idx = lax.axis_index(axis)
    return lax.psum(jnp.where(idx == 0, x, jnp.zeros_like(x)), axis)


def _gather_reduce(x: Any, op: Op, axis: str):
    """Generic rank-ordered reduction: all_gather + an unrolled chained
    fold. The unroll is static (axis size is known at trace time) and XLA
    fuses it; this is the custom-op path (SURVEY.md: 'custom ops are
    strictly easier on TPU')."""
    lax = _lax()
    g = lax.all_gather(x, axis)          # (n, ...)
    acc = _fold_gathered(g, op)
    return _replicate(acc, axis)


def _fold_gathered(g: Any, op: Op):
    """Rank-ordered left fold over the leading (per-rank) axis of a
    gathered array."""
    acc = g[0]
    for i in range(1, g.shape[0]):
        acc = op(acc, g[i])
    return acc


def _prod_native(x: Any, axis: Axis):
    """Approximate float PROD without the all_gather+unroll+replicate round
    trip: product magnitude via exp(psum(log|x|)) — log(0) = -inf makes
    zeros, infs, 0·inf→nan, and nan all come out right for free — and the
    sign via the parity of a negative count. Two payload-sized psums, O(1)
    in world size, and the psum outputs are statically invariant (no extra
    replicate broadcast).

    OPT-IN ONLY (``allreduce(..., approx_prod=True)``; ADVICE r2 medium):
    the log/exp round trip is approximate (~|log p|·eps relative error, so
    2.0^8 comes back as ~255.99997, not exactly 256.0), -0.0 factors lose
    their sign, and products that underflow flush to zero slightly earlier.
    MPI_PROD is exact multiplication (the host tier and the reference both
    are), so the default stays the exact gather-reduce path and callers who
    want the O(1) lowering say so explicitly."""
    import jax.numpy as jnp
    lax = _lax()
    mag = jnp.exp(lax.psum(jnp.log(jnp.abs(x)), axis))
    neg = lax.psum((x < 0).astype(jnp.int32), axis)
    sign = (1 - 2 * (neg % 2)).astype(x.dtype)
    return mag * sign


def allreduce(x: Any, op: Any = SUM, *, axis: Axis = "x",
              approx_prod: bool = False):
    """Allreduce (src/collective.jl:691-738) → psum/pmax/pmin (and native
    lowerings for the logical ops) or the gather-reduce path for
    bitwise/PROD/custom ops. ``approx_prod=True`` opts float PROD into the
    O(1)-in-world-size exp/log lowering (:func:`_prod_native`), trading
    exactness for bandwidth — the default matches the host tier's and the
    reference's exact MPI_PROD semantics (ADVICE r2 medium)."""
    import jax.numpy as jnp
    lax = _lax()
    op = as_op(op)
    if op is SUM:
        return lax.psum(x, axis)
    if op is MAX:
        return lax.pmax(x, axis)
    if op is MIN:
        return lax.pmin(x, axis)
    if (op is PROD and approx_prod
            and jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)):
        return _prod_native(x, axis)
    if op is LAND:
        return lax.pmin((jnp.asarray(x) != 0).astype(jnp.int32),
                        axis).astype(jnp.asarray(x).dtype)
    if op is LOR:
        return lax.pmax((jnp.asarray(x) != 0).astype(jnp.int32),
                        axis).astype(jnp.asarray(x).dtype)
    if op is LXOR:
        return (lax.psum((jnp.asarray(x) != 0).astype(jnp.int32), axis)
                % 2).astype(jnp.asarray(x).dtype)
    if isinstance(axis, (tuple, list)):
        acc = x
        for a in axis:
            acc = _gather_reduce(acc, op, a)
        return acc
    return _gather_reduce(x, op, axis)


def reduce(x: Any, op: Any = SUM, *, root: int = 0, axis: Axis = "x"):
    """Rooted reduce (src/collective.jl:605-666). SPMD programs compute the
    value everywhere (free on ICI — the all-reduce *is* the reduce tree);
    only root's shard is meaningful to the caller."""
    return allreduce(x, op, axis=axis)


def bcast(x: Any, *, root: int = 0, axis: str = "x"):
    """Broadcast root's shard to every rank (src/collective.jl:29-42):
    one-hot mask + psum, which XLA lowers to a broadcast from root."""
    import jax.numpy as jnp
    lax = _lax()
    idx = lax.axis_index(axis)
    contrib = jnp.where(idx == root, x, jnp.zeros_like(x))
    if jnp.issubdtype(jnp.asarray(x).dtype, jnp.bool_):
        return lax.psum(contrib.astype(jnp.int32), axis).astype(jnp.bool_)
    return lax.psum(contrib, axis)


def allgather(x: Any, *, axis: str = "x", tiled: bool = False):
    """Allgather (src/collective.jl:295-335) → lax.all_gather; ``tiled``
    concatenates along the leading dim instead of stacking."""
    return _lax().all_gather(x, axis, tiled=tiled)


def gather(x: Any, *, root: int = 0, axis: str = "x", tiled: bool = False):
    """Rooted gather (src/collective.jl:230-275); all ranks hold the result
    (rooted-ness is a host-API concept — in-graph it is an all_gather)."""
    return _lax().all_gather(x, axis, tiled=tiled)


def allgatherv(x: Any, counts: Sequence[int], *, axis: str = "x"):
    """Variable-count allgather (src/collective.jl:424-461): the static-shape
    regime requires max-padding (SURVEY.md §2.3 '*v' note) — each rank pads
    its shard to max(counts), gathers, and the caller slices by the static
    per-rank counts."""
    import jax.numpy as jnp
    lax = _lax()
    m = max(int(c) for c in counts)
    pad = [(0, m - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    g = lax.all_gather(jnp.pad(x, pad), axis)      # (n, m, ...)
    parts = [g[i, :int(c)] for i, c in enumerate(counts)]
    return _replicate(jnp.concatenate(parts, axis=0), axis)


def gatherv(x: Any, counts: Sequence[int], *, root: int = 0, axis: str = "x"):
    """Variable-count rooted gather (src/collective.jl:363-403). Rooted-ness
    is a host-API concept — in-graph every rank holds the concatenated
    result (the allgatherv path); ``root`` is accepted for API parity."""
    return allgatherv(x, counts, axis=axis)


def scatterv(x: Any, counts: Sequence[int], *, root: int = 0,
             axis: str = "x"):
    """Variable-count scatter (src/collective.jl:156-196) under the
    static-shape regime: ``x`` is the replicated flat send buffer; every
    rank gets a max(counts)-sized chunk whose first counts[rank] elements
    are its segment and the rest zeros (SURVEY.md §2.3: '*v' needs
    max-padding + per-rank slice sizes)."""
    import jax.numpy as jnp
    lax = _lax()
    counts = [int(c) for c in counts]
    n = size(axis)
    if len(counts) != n:
        raise ValueError(f"scatterv: {len(counts)} counts for {n} ranks")
    if sum(counts) > x.shape[0]:
        raise ValueError(f"scatterv: counts sum to {sum(counts)} but the "
                         f"send buffer holds {x.shape[0]}")
    m = max(counts)
    displs = np.concatenate([[0], np.cumsum(counts[:-1])]).astype(np.int32)
    idx = lax.axis_index(axis)
    start = jnp.asarray(displs)[idx]
    ln = jnp.asarray(np.asarray(counts, np.int32))[idx]
    xpad = jnp.pad(x, [(0, m)] + [(0, 0)] * (x.ndim - 1))
    chunk = lax.dynamic_slice_in_dim(xpad, start, m, axis=0)
    keep = jnp.arange(m) < ln
    return jnp.where(keep.reshape((m,) + (1,) * (x.ndim - 1)), chunk, 0)


def alltoallv(x: Any, counts: Sequence[Sequence[int]], *, axis: str = "x"):
    """Variable-count all-to-all (src/collective.jl:545-578), the EP
    token-routing primitive (SURVEY.md §2.5). ``counts[s][d]`` = elements
    rank s sends to rank d (a static table — XLA needs static shapes, so
    the counts are compile-time, exactly the capacity-bound EP regime).

    ``x`` is the flat local send buffer laid out in destination order
    (segment d at offset sum(counts[rank][:d])). Returns a flat buffer of
    static length max_r(total received by r); rank r's first
    sum_s(counts[s][r]) elements are its segments in source order, the
    rest zeros."""
    import jax.numpy as jnp
    lax = _lax()
    counts = [[int(c) for c in row] for row in counts]
    n = size(axis)
    if len(counts) != n or any(len(row) != n for row in counts):
        raise ValueError(f"alltoallv: counts must be {n}x{n} "
                         f"(got {len(counts)}x{min(map(len, counts))})")
    if any(sum(row) > x.shape[0] for row in counts):
        raise ValueError("alltoallv: a rank's send counts exceed the send "
                         f"buffer length {x.shape[0]}")
    idx = lax.axis_index(axis)
    m = max(max(row) for row in counts)             # block pad
    sdispls = np.zeros((n, n), np.int32)            # [s][d] send offset
    for s in range(n):
        sdispls[s, 1:] = np.cumsum(counts[s][:-1])
    rdispls = np.zeros((n, n), np.int32)            # [s][d] recv offset at d
    for d in range(n):
        acc = 0
        for s in range(n):
            rdispls[s, d] = acc
            acc += counts[s][d]
    # Both sides are ONE vectorized op, so the compiled graph is constant-
    # size in n (VERDICT r2 weak #7: the previous form unrolled n dynamic
    # slices + n scatter-adds per call and compiled O(n) HLO; measured
    # compile times in benchmarks/results/alltoallv-compile-cpusim.json).
    xpad = jnp.pad(x, [(0, m)] + [(0, 0)] * (x.ndim - 1))
    lens = jnp.asarray(np.asarray(counts, np.int32))   # [s][d]
    pos = jnp.arange(m)
    trail = (1,) * (x.ndim - 1)
    # send: gather all n destination blocks at once; invalid slots index
    # the zero pad zone and are masked besides
    srow = jnp.asarray(sdispls)[idx]                   # (n,) my send offsets
    svalid = pos[None, :] < lens[idx][:, None]         # (n, m)
    gidx = jnp.where(svalid, srow[:, None] + pos[None, :], x.shape[0])
    stacked = jnp.where(svalid.reshape((n, m) + trail), xpad[gidx], 0)
    recv = lax.all_to_all(stacked, axis, split_axis=0, concat_axis=0,
                          tiled=False)                 # (n, m, ...) by source
    total_r = [sum(counts[s][d] for s in range(n)) for d in range(n)]
    out_len = max(total_r)
    # recv: one flat scatter-add places every source segment at its
    # displacement; invalid slots aim out of range and are dropped
    rcol = jnp.asarray(rdispls)[:, idx]                # (n,) recv offsets
    rvalid = pos[None, :] < lens[:, idx][:, None]      # (n, m)
    ridx = jnp.where(rvalid, rcol[:, None] + pos[None, :], out_len)
    seg = jnp.where(rvalid.reshape((n, m) + trail), recv, 0)
    out = jnp.zeros((out_len,) + x.shape[1:], x.dtype)
    return out.at[ridx.reshape(-1)].add(
        seg.reshape((n * m,) + x.shape[1:]), mode="drop")


def scatter(x: Any, *, root: int = 0, axis: str = "x"):
    """Scatter root's array in equal chunks (src/collective.jl:90-129).

    In-graph the 'root array' is replicated input; each rank slices its own
    chunk — the bcast happened in the sharding, the slice is free."""
    lax = _lax()
    n = size(axis)
    idx = lax.axis_index(axis)
    chunk = x.shape[0] // n
    return lax.dynamic_slice_in_dim(x, idx * chunk, chunk, axis=0)


def reduce_scatter(x: Any, op: Any = SUM, *, axis: str = "x",
                   scatter_dimension: int = 0, tiled: bool = True):
    """Reduce_scatter → lax.psum_scatter (XLA-native; absent from the
    reference, SURVEY.md §2.3 note). Non-SUM ops take the gather-reduce +
    slice path."""
    lax = _lax()
    op = as_op(op)
    if op is SUM:
        return lax.psum_scatter(x, axis, scatter_dimension=scatter_dimension,
                                tiled=tiled)
    full = allreduce(x, op, axis=axis)
    n = size(axis)
    idx = lax.axis_index(axis)
    chunk = full.shape[scatter_dimension] // n
    return lax.dynamic_slice_in_dim(full, idx * chunk, chunk,
                                    axis=scatter_dimension)


def alltoall(x: Any, *, axis: str = "x", split_axis: int = 0,
             concat_axis: int = 0, tiled: bool = True):
    """Alltoall (src/collective.jl:489-532) → lax.all_to_all — the Ulysses
    head↔sequence reshard primitive (SURVEY.md §2.5)."""
    return _lax().all_to_all(x, axis, split_axis=split_axis,
                             concat_axis=concat_axis, tiled=tiled)


def _assoc_scan_take(x: Any, op: Op, axis: str, *, exclusive: bool):
    import jax.numpy as jnp
    lax = _lax()
    g = lax.all_gather(x, axis)                       # (n, ...)
    scanned = lax.associative_scan(op, g, axis=0)     # inclusive prefixes
    idx = lax.axis_index(axis)
    if not exclusive:
        return lax.dynamic_index_in_dim(scanned, idx, axis=0, keepdims=False)
    prev = lax.dynamic_index_in_dim(scanned, jnp.maximum(idx - 1, 0),
                                    axis=0, keepdims=False)
    # rank 0's exscan is undefined (src/collective.jl:834-855); return x
    # unchanged there so shapes/dtypes stay uniform.
    return jnp.where(idx == 0, x, prev)


def scan(x: Any, op: Any = SUM, *, axis: str = "x"):
    """Inclusive prefix reduction over ranks (src/collective.jl:760-808) via
    lax.associative_scan on the gathered rank axis."""
    return _assoc_scan_take(x, as_op(op), axis, exclusive=False)


def exscan(x: Any, op: Any = SUM, *, axis: str = "x"):
    """Exclusive prefix reduction (src/collective.jl:834-882)."""
    return _assoc_scan_take(x, as_op(op), axis, exclusive=True)


def ring_shift(x: Any, *, axis: str = "x", shift: int = 1):
    """Periodic ring step (the Cart_shift + Sendrecv! pattern,
    test/test_sendrecv.jl:100-115) → lax.ppermute. ``shift=+1`` sends to the
    next rank; data received comes from rank-shift."""
    n = size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return _lax().ppermute(x, axis, perm)


def sendrecv(x: Any, *, dest: Sequence[int], axis: str = "x"):
    """Static neighbor exchange (src/pointtopoint.jl:376-393 in-graph):
    ``dest[i]`` is where rank i's shard goes; pairs with PROC_NULL-style
    holes simply omit the edge (the hole receives zeros, matching ppermute
    semantics)."""
    perm = [(i, int(d)) for i, d in enumerate(dest) if d is not None and d >= 0]
    return _lax().ppermute(x, axis, perm)
