"""Hand-written Pallas TPU kernels for the compiled communication path.

Where ``tpu_mpi.xla.collectives`` lowers MPI operations to XLA's built-in
collectives (the right default — XLA's ring/tree algorithms are tuned per
generation), this module supplies the *custom-kernel* tier the reference
reaches by linking libmpi's hand-written algorithms (SURVEY.md §2.4): ring
collectives and neighbor transfers written directly against the ICI with
``pltpu.make_async_remote_copy`` (remote DMA) + semaphores, and a fused
ring-attention kernel as the long-context demo SURVEY.md §5 calls for. The
other kernels are local (no remote DMA) and differentiable, and are what a
train step on one chip runs: ``causal_attention``, its attention,
``grouped_matmul``, the products of its sparse-expert layers (rows sorted by
expert times each row's expert's matrix; forward, the rows' gradient and the
weights' gradient), ``grouped_row_sums``, rows summed into indexed places,
``rope_heads``, the rotary embedding of a projection's token-major row with
its cut into heads, and ``norm_rope``, the norm of q and k with the rotation
after it on heads that are cut. All are gridded over blocks in HBM, so none
is bounded by VMEM as the ring kernels are.

The ring kernels run under ``jax.shard_map`` over a 1-d mesh axis, the local
ones anywhere. On a TPU
backend they compile via Mosaic; on the CPU backend (or when the caller
passes ``interpret=True``) they execute under the Pallas TPU *interpret
machine* (``pltpu.InterpretParams``), which simulates per-device
VMEM/semaphores/RDMA — the CPU-sim substrate the test suite uses. No other
backend is supported and nothing selects the interpreter silently.

Layout contract: kernels operate on 2-d ``(rows, 128)`` tiles whose row
count is aligned to the dtype's native sublane tile (8 rows of 32-bit, 16
of 16-bit, 32 of 8-bit elements); the public wrappers flatten/pad arbitrary
operands in and slice them back out, so callers see plain MPI semantics.

The ring kernels hold their whole operand in VMEM, so their size is bounded
by VMEM, not HBM: a call whose working set exceeds :data:`VMEM_LIMIT_BYTES`
raises ``ValueError`` before tracing the kernel (use the XLA collectives in
``tpu_mpi.xla.collectives`` for HBM-sized operands).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional, Sequence

from .. import perfvars

LANE = 128      # TPU lane width: minor-most dim of every tile
SUBLANE = 8     # sublane multiple of a 32-bit tile (second-minor dim)

# Mosaic's scoped-VMEM default is 16 MiB; kernels whose whole-operand
# working set needs more ask for it explicitly, up to this cap (under the
# v5e's 128 MiB of physical VMEM, leaving room for Mosaic's own temporaries).
VMEM_DEFAULT_BYTES = 16 * 1024 * 1024
VMEM_LIMIT_BYTES = 96 * 1024 * 1024


def _sublane(dtype) -> int:
    """Rows of the native (sublane, 128) tile: sub-32-bit dtypes pack along
    the sublane axis, so bf16 tiles are (16, 128) and int8 tiles (32, 128)."""
    import numpy as np
    return SUBLANE * max(1, 4 // np.dtype(dtype).itemsize)


# Operand types the model's kernels take (part of every contract below and
# in the two scan files): the ones Mosaic compiles for the v5e. One listed
# here that fails to lower is an error, not a fallback.
KERNEL_DTYPES = frozenset({"float32", "bfloat16"})


def _typed(dtype) -> int:
    """What a contract makes of its ``dtype``: the bytes of an element where
    it is one of :data:`KERNEL_DTYPES` (anything numpy makes a dtype of), 0
    where it is another. None, or an int (the bytes alone), asks about the
    shapes alone and comes back as it is, None as 1: a kernel's own entry
    asks so (the interpret machine runs any type), and who sizes a workload."""
    import numpy as np
    if dtype is None or isinstance(dtype, int):
        return dtype or 1
    dtype = np.dtype(dtype)
    return dtype.itemsize if str(dtype) in KERNEL_DTYPES else 0


def _pl():
    from jax.experimental import pallas as pl
    return pl


def _pltpu():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu


def load() -> None:
    """Import Pallas and its TPU dialect now (0.8 s, most of it dialects the
    TPU never uses): for a caller that wants it off a later trace's path."""
    _pl()
    _pltpu()


def _interpret(interpret: Optional[bool]):
    """The TPU interpret machine when the caller asks for it or the backend
    is the CPU; Mosaic compilation otherwise. Never a silent choice on an
    accelerator: a kernel that cannot compile there raises."""
    import jax
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _pltpu().InterpretParams() if interpret else False


def _compiler_params(collective_id: Optional[int], vmem_bytes: int = 0,
                     what: str = "kernel",
                     semantics: Optional[tuple] = None):
    """Mosaic accepts a collective_id ONLY when the kernel actually uses the
    barrier semaphore — at n=1 the ring loops never trace a barrier, so the
    id must be omitted or compilation fails (interpret mode accepts both).
    ``vmem_bytes`` is the kernel's whole-operand VMEM working set: above the
    16 MiB scoped default it is requested explicitly, above
    :data:`VMEM_LIMIT_BYTES` the call is refused. ``semantics`` names each
    grid axis "parallel" or "arbitrary" (a reduction: it runs in order)."""
    kw = {}
    if semantics is not None:
        kw["dimension_semantics"] = semantics
    if collective_id is not None:
        kw["collective_id"] = collective_id
    if vmem_bytes > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"{what}: operands need {vmem_bytes / 2**20:.1f} MiB of VMEM "
            f"(whole-operand blocks + scratch); the limit is "
            f"{VMEM_LIMIT_BYTES // 2**20} MiB (VMEM_LIMIT_BYTES) — split "
            f"the operand or use the XLA collective in tpu_mpi.xla")
    # headroom for Mosaic's own temporaries (operand-sized vector values
    # spilled around the combine) on top of the declared buffers
    want = 2 * vmem_bytes
    if want > VMEM_DEFAULT_BYTES:
        kw["vmem_limit_bytes"] = min(want, VMEM_LIMIT_BYTES)
    return _pltpu().CompilerParams(**kw)


def _nbytes(shape, dtype) -> int:
    import numpy as np
    return int(np.prod(shape)) * np.dtype(dtype).itemsize


def _rows(idx, chunk: int):
    """Dynamic row-slice of chunk ``idx``; chunk counts are sublane-tile
    multiples, and Mosaic needs to be told so for a traced ``idx``."""
    pl = _pl()
    if isinstance(idx, int):
        return pl.ds(idx * chunk, chunk)
    return pl.ds(pl.multiple_of(idx * chunk, chunk), chunk)


# ---------------------------------------------------------------------------
# layout: arbitrary array <-> (rows, LANE) tile padded for n ring chunks
# ---------------------------------------------------------------------------

def _tile_rows(count: int, n: int, sublane: int = SUBLANE) -> int:
    """Rows of the (rows, LANE) tile holding `count` elements, padded so the
    row count splits into n equal sublane-tile-aligned ring chunks."""
    rows = -(-count // LANE)
    chunk = -(-rows // n)
    chunk = -(-chunk // sublane) * sublane
    return chunk * n


def _to_tile(x, n: int):
    import jax.numpy as jnp
    flat = x.reshape(-1)
    rows = _tile_rows(flat.size, n, _sublane(flat.dtype))
    pad = rows * LANE - flat.size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, LANE)


def _from_tile(tile, shape, size: int):
    return tile.reshape(-1)[:size].reshape(shape)


def _to_block_tile(x, n: int):
    """Per-rank-block layout: x (size divisible by n) viewed as n equal
    blocks, each padded independently to a sublane-tile-aligned (rows_b,
    LANE) tile, concatenated to (n*rows_b, LANE). Unlike _to_tile (end-padding),
    block boundaries land exactly on chunk boundaries — what Reduce_scatter
    and Alltoall semantics need (rank i's block = x[i*per:(i+1)*per])."""
    import jax.numpy as jnp
    flat = x.reshape(-1)
    if flat.size % n:
        raise ValueError(f"size {flat.size} not divisible by {n} ranks")
    per = flat.size // n
    rows = -(-per // LANE)
    sub = _sublane(flat.dtype)
    rows_b = -(-rows // sub) * sub
    blocks = flat.reshape(n, per)
    pad = rows_b * LANE - per
    if pad:
        blocks = jnp.concatenate(
            [blocks, jnp.zeros((n, pad), flat.dtype)], axis=1)
    return blocks.reshape(n * rows_b, LANE), per, rows_b


def _neighbor_barrier(my, n: int):
    """Barrier with both ring neighbors. Run before each ring step's DMA: a
    send into a neighbor's double-buffer slot is only safe once the neighbor
    has finished the step that consumed that slot (two-slot reuse would
    otherwise let a fast rank clobber data a slow neighbor hasn't forwarded —
    observed as reordered blocks under the interpret machine)."""
    pltpu = _pltpu()
    bar = pltpu.get_barrier_semaphore()
    for nb in ((my + 1) % n, (my - 1) % n):
        pltpu.semaphore_signal(bar, inc=1, device_id=nb,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(bar, 2)


# ---------------------------------------------------------------------------
# ring all-gather
# ---------------------------------------------------------------------------

def _ring_allgather_kernel(n: int, chunk: int, axis: str, local_ref, out_ref,
                           comm_ref, send_sem, recv_sem):
    import jax
    pl, pltpu = _pl(), _pltpu()
    my = jax.lax.axis_index(axis)
    out_ref[_rows(my, chunk), :] = local_ref[:]
    comm_ref[0] = local_ref[:]
    for step in range(n - 1):
        src_dev = (my - step - 1) % n
        s, r = step % 2, (step + 1) % 2
        _neighbor_barrier(my, n)
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm_ref.at[s],
            dst_ref=comm_ref.at[r],
            send_sem=send_sem.at[s],
            recv_sem=recv_sem.at[r],
            device_id=(my + 1) % n,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        out_ref[_rows(src_dev, chunk), :] = comm_ref[r]


def ring_allgather(x, *, axis: str = "x", interpret: Optional[bool] = None):
    """All-gather of each rank's block via a (n-1)-step RDMA ring; concatenated
    along a new leading per-rank axis. Call inside shard_map over `axis`
    (the Pallas realization of src/collective.jl:295-335)."""
    import jax
    pl, pltpu = _pl(), _pltpu()
    n = jax.lax.axis_size(axis)
    tile = _to_tile(x, 1)
    rows = tile.shape[0]
    kern = functools.partial(_ring_allgather_kernel, n, rows, axis)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((n * rows, LANE), tile.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, rows, LANE), tile.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=_interpret(interpret),
        compiler_params=_compiler_params(
            0 if n > 1 else None,
            _nbytes(((n + 3) * rows, LANE), tile.dtype), "ring_allgather"),
    )(tile)
    per = out.reshape(n, rows * LANE)[:, : x.size]
    return per.reshape((n,) + tuple(x.shape))


# ---------------------------------------------------------------------------
# ring all-reduce (reduce-scatter + all-gather, bandwidth-optimal)
# ---------------------------------------------------------------------------

def _combine_fn(op) -> Callable:
    """Normalize an operator the way the XLA-collective tier does
    (operators.as_op): accepts the predefined Ops, python functions, or the
    legacy string names. The combine runs on VMEM values inside the kernel,
    so any jittable binary fn works."""
    from ..operators import Op, as_op
    if isinstance(op, str):
        import jax.numpy as jnp
        table = {"sum": lambda a, b: a + b, "prod": lambda a, b: a * b,
                 "max": jnp.maximum, "min": jnp.minimum}
        if op not in table:
            raise ValueError(f"unsupported ring op {op!r}")
        return table[op]
    op = as_op(op)
    return op.fn


def _ring_allreduce_kernel(n: int, chunk: int, combine: Callable, axis: str,
                           local_ref, out_ref, comm_ref, send_sem, recv_sem):
    import jax
    pl, pltpu = _pl(), _pltpu()
    my = jax.lax.axis_index(axis)
    out_ref[:] = local_ref[:]

    def ring_step(step, src_slice_idx, accumulate):
        s, r = step % 2, (step + 1) % 2
        _neighbor_barrier(my, n)
        comm_ref[s] = out_ref[_rows(src_slice_idx, chunk), :]
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm_ref.at[s],
            dst_ref=comm_ref.at[r],
            send_sem=send_sem.at[s],
            recv_sem=recv_sem.at[r],
            device_id=(my + 1) % n,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        recv_idx = (src_slice_idx - 1) % n
        cur = out_ref[_rows(recv_idx, chunk), :]
        new = combine(cur, comm_ref[r]) if accumulate else comm_ref[r]
        out_ref[_rows(recv_idx, chunk), :] = new
        return recv_idx

    # reduce-scatter: after n-1 steps rank owns the fully reduced chunk
    # (my+1)%n …
    idx = my
    for step in range(n - 1):
        idx = ring_step(step, idx, True)
    # … then all-gather the reduced chunks (n-1 more steps).
    for step in range(n - 1):
        idx = ring_step(n - 1 + step, idx, False)


def ring_allreduce(x, op: Any = "sum", *, axis: str = "x",
                   interpret: Optional[bool] = None):
    """Bandwidth-optimal ring Allreduce (reduce-scatter + all-gather over
    remote DMA, 2·(n-1)/n·bytes on the wire — the libmpi ring algorithm
    the reference reaches through MPI_Allreduce, src/collective.jl:691-738,
    written natively against the ICI)."""
    import jax
    pl, pltpu = _pl(), _pltpu()
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    tile = _to_tile(x, n)
    rows = tile.shape[0]
    chunk = rows // n
    kern = functools.partial(_ring_allreduce_kernel, n, chunk,
                             _combine_fn(op), axis)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((rows, LANE), tile.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, LANE), tile.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=_interpret(interpret),
        compiler_params=_compiler_params(      # n>1 guaranteed (early return)
            1, _nbytes((2 * rows + 2 * chunk, LANE), tile.dtype),
            "ring_allreduce"),
    )(tile)
    return _from_tile(out, x.shape, x.size)


# ---------------------------------------------------------------------------
# ring reduce-scatter (the first half of the ring allreduce, standalone:
# the gradient-sharding primitive of ZeRO/FSDP-style data parallelism)
# ---------------------------------------------------------------------------

def _ring_reduce_scatter_kernel(n: int, chunk: int, combine: Callable,
                                axis: str, local_ref, out_ref, acc_ref,
                                comm_ref, send_sem, recv_sem):
    import jax
    pl, pltpu = _pl(), _pltpu()
    my = jax.lax.axis_index(axis)
    acc_ref[:] = local_ref[:]
    # start at (my-1) so after n-1 hops the fully-reduced chunk lands on
    # index `my` (MPI Reduce_scatter_block: rank i owns block i)
    idx = (my - 1) % n
    for step in range(n - 1):
        s, r = step % 2, (step + 1) % 2
        _neighbor_barrier(my, n)
        comm_ref[s] = acc_ref[_rows(idx, chunk), :]
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm_ref.at[s],
            dst_ref=comm_ref.at[r],
            send_sem=send_sem.at[s],
            recv_sem=recv_sem.at[r],
            device_id=(my + 1) % n,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        idx = (idx - 1) % n
        acc_ref[_rows(idx, chunk), :] = combine(
            acc_ref[_rows(idx, chunk), :], comm_ref[r])
    out_ref[:] = acc_ref[_rows(my, chunk), :]


def ring_reduce_scatter(x, op: Any = "sum", *, axis: str = "x",
                        interpret: Optional[bool] = None):
    """Reduce_scatter over an RDMA ring ((n-1)/n·bytes on the wire): every
    rank contributes the full x (size divisible by n) and receives block
    `rank` of the elementwise reduction — the XLA-tier psum_scatter
    (xla/collectives.py reduce_scatter) written natively against the ICI.
    Returns a flat (x.size/n,) array."""
    import jax
    pl, pltpu = _pl(), _pltpu()
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x.reshape(-1)
    tile, per, rows_b = _to_block_tile(x, n)
    kern = functools.partial(_ring_reduce_scatter_kernel, n, rows_b,
                             _combine_fn(op), axis)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((rows_b, LANE), tile.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((n * rows_b, LANE), tile.dtype),   # accumulator
            pltpu.VMEM((2, rows_b, LANE), tile.dtype),    # comm double buffer
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=_interpret(interpret),
        compiler_params=_compiler_params(      # n>1 guaranteed (early return)
            4, _nbytes(((2 * n + 3) * rows_b, LANE), tile.dtype),
            "ring_reduce_scatter"),
    )(tile)
    return out.reshape(-1)[:per]


# ---------------------------------------------------------------------------
# pairwise all-to-all (direct RDMA between every pair — one hop per block,
# versus a ring's k-hop forwarding; the Ulysses/EP reshard primitive)
# ---------------------------------------------------------------------------

def _alltoall_kernel(n: int, chunk: int, axis: str, local_ref, out_ref,
                     send_sem, recv_sem):
    import jax
    pl, pltpu = _pl(), _pltpu()
    my = jax.lax.axis_index(axis)
    out_ref[_rows(my, chunk), :] = local_ref[_rows(my, chunk), :]
    # one all-pairs barrier: every peer must have entered the kernel (its
    # out_ref allocated) before anyone's direct Put lands
    bar = pltpu.get_barrier_semaphore()
    for d in range(1, n):
        pltpu.semaphore_signal(bar, inc=1, device_id=(my + d) % n,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(bar, n - 1)
    # fire all n-1 puts concurrently; per-distance semaphore slots so no
    # reuse hazard and no per-step ordering
    rdmas = []
    for k in range(1, n):
        dst = (my + k) % n
        rdma = pltpu.make_async_remote_copy(
            src_ref=local_ref.at[_rows(dst, chunk), :],
            dst_ref=out_ref.at[_rows(my, chunk), :],
            send_sem=send_sem.at[k - 1],
            recv_sem=recv_sem.at[k - 1],
            device_id=dst,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdmas.append(rdma)
    for rdma in rdmas:
        rdma.wait()


def pairwise_alltoall(x, *, axis: str = "x", interpret: Optional[bool] = None):
    """All-to-all block exchange via direct pairwise RDMA: x (size divisible
    by n) is n destination blocks; the result's block s is what rank s sent
    here (src/collective.jl:489-532 semantics, one ICI hop per block).
    Returns a flat array of x.size with source-ordered blocks."""
    import jax
    pl, pltpu = _pl(), _pltpu()
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x.reshape(-1)
    tile, per, rows_b = _to_block_tile(x, n)
    kern = functools.partial(_alltoall_kernel, n, rows_b, axis)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((n * rows_b, LANE), tile.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((n - 1,)),
            pltpu.SemaphoreType.DMA((n - 1,)),
        ],
        interpret=_interpret(interpret),
        compiler_params=_compiler_params(      # n>1 guaranteed (early return)
            5, _nbytes((2 * n * rows_b, LANE), tile.dtype),
            "pairwise_alltoall"),
    )(tile)
    blocks = out.reshape(n, rows_b * LANE)[:, :per]
    return blocks.reshape(-1)


# ---------------------------------------------------------------------------
# collective permute (compiled Put: the in-graph RMA / halo / pipeline hop)
# ---------------------------------------------------------------------------

def _permute_kernel(perm_table, axis: str, local_ref, out_ref, comm_ref,
                    send_sem, recv_sem):
    import jax
    import jax.numpy as jnp
    pltpu = _pltpu()
    my = jax.lax.axis_index(axis)
    n = len(perm_table)

    def select(table):
        # static table -> scalar select chain (a captured constant array
        # would need to be a kernel input)
        v = jnp.int32(table[0])
        for r in range(1, n):
            v = jnp.where(my == r, jnp.int32(table[r]), v)
        return v

    dst = select(perm_table)
    if n > 1:
        # entry handshake: tell my SOURCE (inverse permutation) that this
        # rank's comm_ref is live, and wait for my DESTINATION's signal
        # before the Put — a fast sender must not land a DMA in a peer that
        # has not entered the kernel (same hazard as _alltoall's barrier)
        inv = [perm_table.index(r) for r in range(n)]
        src = select(inv)
        bar = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(bar, inc=1, device_id=src,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(bar, 1)
    rdma = pltpu.make_async_remote_copy(
        src_ref=local_ref,
        dst_ref=comm_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=dst,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )
    rdma.start()
    rdma.wait()
    out_ref[:] = comm_ref[:]


def collective_permute(x, perm: Sequence[int], *, axis: str = "x",
                       interpret: Optional[bool] = None):
    """Each rank r sends its block to rank ``perm[r]`` by remote DMA — the
    compiled Put (src/onesided.jl:168-184) and the hop under Cart_shift halo
    exchange / pipeline stages. ``perm`` must be a permutation (every rank
    sends and receives exactly once, like lax.ppermute with full pairs)."""
    import jax
    pl, pltpu = _pl(), _pltpu()
    n = jax.lax.axis_size(axis)
    perm = tuple(int(p) for p in perm)
    if sorted(perm) != list(range(n)):
        raise ValueError(f"perm {perm} is not a permutation of 0..{n - 1}")
    tile = _to_tile(x, 1)
    rows = tile.shape[0]
    kern = functools.partial(_permute_kernel, perm, axis)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((rows, LANE), tile.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((rows, LANE), tile.dtype),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=_interpret(interpret),
        compiler_params=_compiler_params(
            2 if n > 1 else None, _nbytes((3 * rows, LANE), tile.dtype),
            "collective_permute"),
    )(tile)
    return _from_tile(out, x.shape, x.size)


# ---------------------------------------------------------------------------
# fused ring attention (long-context demo: K/V rotate over the ICI while the
# MXU computes blockwise attention with online softmax)
# ---------------------------------------------------------------------------

def _ring_attention_kernel(n: int, scale: float, axis: str, causal: bool,
                           bq: int, q_ref, k_ref, v_ref, out_ref,
                           kv_comm, acc, m_ref, l_ref, send_sem, recv_sem):
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl(), _pltpu()
    my = jax.lax.axis_index(axis)
    t = q_ref.shape[0]
    # MXU precision follows the INPUT dtype: bf16 operands run the bf16
    # systolic path with float32 accumulation (standard TPU flash-attention
    # precision, ~4x the f32 MXU rate on v5e); float32 operands keep full
    # precision (HIGHEST — Mosaic's default would run them as bf16 passes).
    # The online-softmax state (m/l/acc) is always float32.
    cdt = q_ref.dtype
    prec = (jax.lax.Precision.HIGHEST if cdt == jnp.float32
            else jax.lax.Precision.DEFAULT)

    kv_comm[0, 0] = k_ref[:]
    kv_comm[0, 1] = v_ref[:]
    acc[:] = jnp.zeros_like(acc)
    m_ref[:] = jnp.full_like(m_ref, -1e30)
    l_ref[:] = jnp.zeros_like(l_ref)

    for step in range(n):
        s, r = step % 2, (step + 1) % 2
        if step < n - 1:
            _neighbor_barrier(my, n)
            rdma = pltpu.make_async_remote_copy(
                src_ref=kv_comm.at[s],
                dst_ref=kv_comm.at[r],
                send_sem=send_sem.at[s],
                recv_sem=recv_sem.at[r],
                device_id=(my + 1) % n,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            rdma.start()
        k = kv_comm[s, 0]
        v = kv_comm[s, 1]
        src = (my - step) % n
        # Q-blocked online softmax: scores live one (bq, t) panel at a
        # time, so VMEM holds O(bq*t) instead of O(t^2) and local blocks
        # of 2048-8192 fit (VERDICT r4 weak #2)
        for qlo in range(0, t, bq):
            bqe = min(bq, t - qlo)        # tail panel when bq doesn't divide t
            qs = slice(qlo, qlo + bqe)
            scores = jax.lax.dot_general(
                q_ref[qs, :], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=prec) * scale
            if causal:
                # the resident K/V block at this step originated on rank
                # (my - step); mask keys whose global index exceeds the
                # query's
                qg = (my * t + qlo
                      + jax.lax.broadcasted_iota(jnp.int32, (bqe, t), 0))
                kg = src * t + jax.lax.broadcasted_iota(jnp.int32, (bqe, t), 1)
                # -inf (not a big-finite) so a fully-masked panel yields
                # p = exp(-inf - m_prev) = 0 exactly (m init is finite)
                scores = jnp.where(qg >= kg, scores, -jnp.inf)
            m_prev = m_ref[qs, :]
            m_new = jnp.maximum(m_prev,
                                jnp.max(scores, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)
            l_ref[qs, :] = l_ref[qs, :] * corr + jnp.sum(p, axis=1,
                                                         keepdims=True)
            acc[qs, :] = acc[qs, :] * corr + jax.lax.dot_general(
                p.astype(cdt), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)
            m_ref[qs, :] = m_new
        if step < n - 1:
            rdma.wait()
    out_ref[:] = (acc[:] / l_ref[:]).astype(out_ref.dtype)


def ring_attention(q, k, v, *, axis: str = "x", causal: bool = False,
                   interpret: Optional[bool] = None):
    """Fused blockwise attention over a sequence sharded along `axis`: each
    rank holds a (T_local, d) block of Q/K/V; K/V blocks rotate around the
    RDMA ring while the MXU consumes the resident block (online-softmax
    accumulation), overlapping communication with compute. ``causal=True``
    masks by global position (query i attends keys ≤ i across the whole
    sharded sequence).

    The Pallas counterpart of tpu_mpi.parallel.ring.ring_attention
    (ppermute-based); the substrate demo SURVEY.md §5 requires. q/k/v:
    (T_local, d) with d ≤ 128-padded; vmap for batch/heads.

    Not what a train step runs: this kernel exists for the rotation of K/V
    over a ring of devices, holds its whole operands in VMEM and is forward
    only (no VJP). The local attention of a block with itself, gridded over
    HBM-sized operands and differentiable, is :func:`causal_attention`.

    Precision follows the input dtype: pass bfloat16 operands for the bf16
    MXU path (float32 softmax state and accumulation — standard TPU
    flash-attention numerics, ~4x f32 matmul throughput on v5e); float32
    operands compute fully in float32."""
    import jax
    import jax.numpy as jnp
    pl, pltpu = _pl(), _pltpu()
    n = jax.lax.axis_size(axis)
    t, d = q.shape
    sub = _sublane(q.dtype)
    if t % sub:
        raise ValueError(f"local seq len {t} must be a multiple of {sub} "
                         f"for {q.dtype} operands")
    pad = (-d) % LANE
    if pad:
        z = jnp.zeros((t, pad), q.dtype)
        q, k, v = (jnp.concatenate([a, z], axis=1) for a in (q, k, v))
    dp = q.shape[1]
    scale = 1.0 / math.sqrt(d)
    # Q-panel rows per online-softmax pass: bounds VMEM for the score
    # panel at bq*t floats so 2048-8192 local blocks compile (the panel,
    # not t^2, is the live working set)
    bq = t if t <= 1024 else 512
    kern = functools.partial(_ring_attention_kernel, n, scale, axis, causal,
                             bq)
    # q/k/v/out blocks + the K/V double buffer, the f32 accumulator, the
    # lane-padded (t, 1) softmax state, and one score panel with its exp
    vmem = (_nbytes(((4 + 4) * t, dp), q.dtype)
            + _nbytes((t, dp), jnp.float32)
            + _nbytes((2 * t, LANE), jnp.float32)
            + _nbytes((3 * bq, t), jnp.float32))
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((t, dp), q.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 3,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, 2, t, dp), q.dtype),          # kv double buffer
            pltpu.VMEM((t, dp), jnp.float32),            # acc
            pltpu.VMEM((t, 1), jnp.float32),             # running max
            pltpu.VMEM((t, 1), jnp.float32),             # running denom
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=_interpret(interpret),
        compiler_params=_compiler_params(3 if n > 1 else None, vmem,
                                         "ring_attention"),
    )(q, k, v)
    return out[:, :d] if pad else out


# ---------------------------------------------------------------------------
# fused causal attention of a block with itself (the train step's local
# attention: no [b, h, t, t] tensor reaches HBM, forward or backward)
# ---------------------------------------------------------------------------

_ATTN_BLOCKS = (512, 256, 128)      # widest first; all multiples of LANE
_MASKED = -1e30     # the plain path's value for a future key


def causal_attention_blocks(t: int, dh: int, rope: int = 0, dv: int = 0,
                            dtype=None) -> Optional[tuple]:
    """(query block, key block) of the kernel for sequence length ``t`` and
    head dimension ``dh``, or None where its contract does not hold:
    operands of a kernel's type (``dtype``: :func:`_typed`), ``t`` a multiple
    of a block, ``dh`` 64 or a multiple of 128 (a head is the MXU's contraction
    and the minor dimension of every operand block), and the backward
    pass's working set, which holds one head's whole dq, inside
    :data:`VMEM_LIMIT_BYTES`. A windowed call takes the same blocks (sweep:
    PERF.md). ``rope`` > 0: the scores' second term is that wide, and ``dv``
    > 0 the values are (0: ``dh``); each is held to ``dh``'s rule, so the
    contract knows the triple (128 + 64, 128) as it stands."""
    if not _typed(dtype):
        return None
    if any(w != 64 and w % LANE for w in (dh, rope or dh, dv or dh)):
        return None
    block = next((b for b in _ATTN_BLOCKS if t % b == 0), None)
    if block is None:
        return None
    if _attn_bwd_vmem(t, dh, block, block, 4, rope, dv) > VMEM_LIMIT_BYTES:
        return None
    return block, block


def _attn_fwd_vmem(dh: int, bq: int, bk: int, itemsize: int,
                   rope: int = 0, dv: int = 0) -> int:
    """The forward kernel's VMEM: q, k, v, o blocks (double-buffered by the
    grid pipeline), the log-sum-exp row, the float32 running max, sum and
    accumulator, and a block of scores with its exponentials; with a second
    term of the scores, its q and k blocks too."""
    dl, vl = max(dh, LANE), max(dv or dh, LANE)
    second = 2 * (bq + bk) * max(rope, LANE) * itemsize if rope else 0
    return (2 * ((bq + bk) * dl + (bq + bk) * vl) * itemsize + second
            + 2 * SUBLANE * bq * 4 + (3 * LANE + vl) * bq * 4
            + 3 * bq * bk * 4)


def _attn_bwd_vmem(t: int, dh: int, bq: int, bk: int, itemsize: int,
                   rope: int = 0, dv: int = 0) -> int:
    """The backward kernel's VMEM: q, do, k, v, dk, dv blocks and one head's
    whole dq (double-buffered), the float32 dq, dk, dv accumulators, the
    log-sum-exp and row-sum rows, and five blocks of scores (s, p, dp, ds
    and the transposed ds); with a second term of the scores, what q, k, dq
    and dk take again at its width."""
    dl, vl = max(dh, LANE), max(dv or dh, LANE)
    out = (2 * ((bq + 2 * bk + t) * dl + (bq + 2 * bk) * vl) * itemsize
           + ((t + bk) * dl + bk * vl) * 4 + 4 * SUBLANE * bq * 4
           + 5 * bq * bk * 4)
    if rope:
        rl = max(rope, LANE)
        out += 2 * (bq + 2 * bk + t) * rl * itemsize + (t + bk) * rl * 4
    return out


def _lanes(x, n: int):
    """A (rows, LANE) lane-replicated column as (rows, n)."""
    import jax.numpy as jnp
    if n % LANE == 0:
        return x if n == LANE else jnp.tile(x, (1, n // LANE))
    return x[:, :n]


def _attn_precision(dtype):
    """bf16 operands take the MXU's bf16 path with float32 accumulation;
    float32 operands keep full precision (Mosaic's default would run them
    as bf16 passes)."""
    import jax
    import numpy as np
    return (jax.lax.Precision.HIGHEST if np.dtype(dtype) == np.float32
            else jax.lax.Precision.DEFAULT)


def _causal_pair(qi, ki, bq: int, bk: int, window: int = 0):
    """(below, crosses) of the pair (query block qi, key block ki): wholly
    below the diagonal (every key seen by every query, no mask needed), or
    on it (masked by position). A pair that is neither lies wholly above
    the diagonal and runs nothing. Under a ``window`` a second rule: a pair
    whose every key lies before every query's window runs nothing either,
    and one is unmasked only if every key is inside every query's window."""
    import jax.numpy as jnp
    below = ki * bk + (bk - 1) <= qi * bq
    seen = ki * bk <= qi * bq + (bq - 1)
    if window:
        below = jnp.logical_and(
            below, ki * bk >= qi * bq + (bq - 1) - (window - 1))
        seen = jnp.logical_and(
            seen, ki * bk + (bk - 1) >= qi * bq - (window - 1))
    return below, jnp.logical_and(seen, jnp.logical_not(below))


def _visible(rows, cols, window: int):
    """The mask of a pair on the band: key <= query, and under a window
    query - key < window."""
    import jax.numpy as jnp
    if not window:
        return rows >= cols
    return jnp.logical_and(rows >= cols, rows - cols < window)


def _first_key_block(qi, bq: int, bk: int, window: int):
    """The first key block a query block's walk visits under a window: the
    block of its first query's first key."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    return jax.lax.div(jnp.maximum(qi * bq - (window - 1), 0), np.int32(bk))


def _first_query_block(kj, bq: int, bk: int, window: int):
    """The first query block a key block's walk visits in the backward
    pass under a window: the block of its first key. (Without a window a
    walk is over all blocks and the ones above the diagonal are skipped
    where they stand.)"""
    import jax
    import numpy as np
    return jax.lax.div(kj * bk, np.int32(bq))


def causal_attention_walk(t: int, bq: int, bk: int, window: int = 0) -> tuple:
    """(key blocks a query block's walk is long, query blocks a key block's
    walk is long, pairs of blocks that run) for one head: the two inner
    grid axes, forward and backward, and what a count of the kernel's
    products as executed multiplies by. Without a window a walk is over all
    blocks (the ones above the diagonal run nothing); under one it starts
    at the band and is as long as the band's widest crossing."""
    nq, nk = t // bq, t // bk

    def runs(qi, ki):
        seen = ki * bk <= qi * bq + (bq - 1)
        return seen and (not window
                         or ki * bk + (bk - 1) >= qi * bq - (window - 1))
    pairs = sum(runs(qi, ki) for qi in range(nq) for ki in range(nk))
    if not window:
        return nk, nq, pairs
    span_k = max(sum(runs(qi, ki) for ki in range(nk)) for qi in range(nq))
    span_q = max(sum(runs(qi, kj) for qi in range(nq)) for kj in range(nk))
    return span_k, span_q, pairs


def _attn_fwd_kernel(scale: float, window: int, second: bool, q_ref, k_ref,
                     v_ref, *refs):
    """``second``: the scores are the sum of two products, and the refs of
    the second one's queries and keys follow v's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pl = _pl()
    q2_ref, k2_ref = refs[:2] if second else (None, None)
    o_ref, lse_ref, m_ref, l_ref, acc_ref = refs[-5:]
    bq, dv = o_ref.shape[2:]
    bk = k_ref.shape[2]
    qi, step = pl.program_id(2), pl.program_id(3)
    ki = step + _first_key_block(qi, bq, bk, window) if window else step
    prec = _attn_precision(q_ref.dtype)

    @pl.when(step == 0)
    def _first_step():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def pair(on_diagonal: bool):
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        if second:
            s = s + jax.lax.dot_general(
                q2_ref[0, 0], k2_ref[0, 0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)
        s = s * scale
        if on_diagonal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(_visible(rows, cols, window), s,
                          np.float32(_MASKED))
        # key block 0 runs first and holds key 0, which every query sees:
        # from then on the running max is finite and a masked score's
        # exponential is exactly 0. (Under a window a row may see nothing
        # of its walk's first block: its sum then holds exp(0) a key until
        # the block with the query's own key multiplies it by exp(-1e30 -
        # max) = 0, which every row's walk reaches.)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, bk))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = acc_ref[...] * _lanes(alpha, dv) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)

    below, crosses = _causal_pair(qi, ki, bq, bk, window)
    pl.when(below)(lambda: pair(False))
    pl.when(crosses)(lambda: pair(True))

    @pl.when(step == pl.num_programs(3) - 1)
    def _last_step():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] * _lanes(1.0 / l, dv)).astype(o_ref.dtype)
        # the rows' log-sum-exp leaves as one row along the lanes, which is
        # how the backward pass reads it: [b, h, 1, t], nothing replicated
        lse_ref[0, 0] = jnp.transpose(m_ref[...] + jnp.log(l))[:1, :]


def _attn_bwd_kernel(scale: float, window: int, second: bool, q_ref, k_ref,
                     v_ref, do_ref, lse_ref, di_ref, *refs):
    """One (key block, query block) pair of the backward pass, transposed:
    scores are [keys, queries], so a query's log-sum-exp and row-sum are
    rows along the lanes and four of the five products need no transpose.
    dk and dv accumulate over the query blocks (the inner grid axis); dq of
    the whole head stays in VMEM over both axes. Under a window a key
    block's walk starts at its own first query block and may run past the
    last one: such a step runs nothing. ``second``: the scores are the sum
    of two products; the second one's q and k refs follow `di_ref`, its dq
    and dk the first one's outputs, and its accumulators theirs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pl = _pl()
    if second:
        (q2_ref, k2_ref, dq_ref, dk_ref, dv_ref, dq2_ref, dk2_ref,
         dq_acc, dk_acc, dv_acc, dq2_acc, dk2_acc) = refs
    else:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    kj, step = pl.program_id(2), pl.program_id(3)
    qi = step + _first_query_block(kj, bq, bk, window) if window else step
    prec = _attn_precision(q_ref.dtype)
    nt = (((1,), (1,)), ((), ()))
    nn = (((1,), (0,)), ((), ()))

    @pl.when(jnp.logical_and(kj == 0, step == 0))
    def _first_pair():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)
        if second:
            dq2_acc[...] = jnp.zeros(dq2_acc.shape, jnp.float32)

    @pl.when(step == 0)
    def _first_step():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)
        if second:
            dk2_acc[...] = jnp.zeros(dk2_acc.shape, jnp.float32)

    def pair(on_diagonal: bool):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        s = jax.lax.dot_general(k, q, nt, preferred_element_type=jnp.float32,
                                precision=prec)                 # (bk, bq)
        if second:
            q2, k2 = q2_ref[0, 0], k2_ref[0, 0]
            s = s + jax.lax.dot_general(
                k2, q2, nt, preferred_element_type=jnp.float32, precision=prec)
        s = s * scale
        if on_diagonal:
            keys = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
            s = jnp.where(_visible(rows, keys, window), s,
                          np.float32(_MASKED))
        p = jnp.exp(s - lse_ref[0, 0])
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, nn, preferred_element_type=jnp.float32,
            precision=prec)
        dp = jax.lax.dot_general(v, do, nt, preferred_element_type=jnp.float32,
                                 precision=prec)
        ds = (p * (dp - di_ref[0, 0]) * scale).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, nn, preferred_element_type=jnp.float32, precision=prec)
        at = pl.ds(pl.multiple_of(qi * bq, bq), bq)
        dq_acc[at, :] += jax.lax.dot_general(
            ds, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        if second:
            dk2_acc[...] += jax.lax.dot_general(
                ds, q2, nn, preferred_element_type=jnp.float32, precision=prec)
            dq2_acc[at, :] += jax.lax.dot_general(
                ds, k2, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)

    below, crosses = _causal_pair(qi, kj, bq, bk, window)
    if window:      # a walk's steps past the last query block
        inside = qi * bq < dq_acc.shape[0]
        below = jnp.logical_and(below, inside)
        crosses = jnp.logical_and(crosses, inside)
    pl.when(below)(lambda: pair(False))
    pl.when(crosses)(lambda: pair(True))

    last_q = step == pl.num_programs(3) - 1

    @pl.when(last_q)
    def _last_query_block():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)
        if second:
            dk2_ref[0, 0] = dk2_acc[...].astype(dk2_ref.dtype)

    @pl.when(jnp.logical_and(last_q, kj == pl.num_programs(2) - 1))
    def _last_pair():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)
        if second:
            dq2_ref[0, 0] = dq2_acc[...].astype(dq2_ref.dtype)


def _varying_like(x, shape, dtype):
    """An output's shape that varies over the mesh axes ``x`` varies over
    (what `shard_map` asks of a kernel's outputs under `check_vma`)."""
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(x).vma)


def _vary_together(*xs):
    """The operands, each made to vary over every mesh axis any of them
    varies over: a kernel's operands and results then have one type under
    `shard_map`, and a replicated operand's gradient is summed over those
    axes by the cast's own transpose, as it is for any XLA operation."""
    import jax
    axes = set().union(*(jax.typeof(x).vma for x in xs))
    out = []
    for x in xs:
        missing = tuple(sorted(axes - set(jax.typeof(x).vma)))
        out.append(jax.lax.pcast(x, missing, to="varying") if missing else x)
    return out


def _attn_forward(q, k, v, bq: int, bk: int, interpret: Optional[bool],
                  window: int = 0, second: tuple = ()):
    """(o, log-sum-exp [b, h, 1, t] float32) of causal attention; k and v
    may hold fewer heads than q (query head j reads head j // group).
    ``second`` = (q2, k2): the scores' second product, its keys' heads a
    divisor of the queries' of their own (one key for all heads: the index
    map reads head 0 for each, nothing is repeated in HBM)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pl, pltpu = _pl(), _pltpu()
    b, h, t, dh = q.shape
    dv = v.shape[3]
    rope = second[0].shape[3] if second else 0
    group = h // k.shape[1]
    span = causal_attention_walk(t, bq, bk, window)[0]

    # index arithmetic stays int32 under jax_enable_x64 too (Mosaic has no
    # 64-bit scalars): `lax.div` and an int32 zero, not `//` and a literal
    zero = np.int32(0)

    def kv_head(hi):
        return hi if group == 1 else jax.lax.div(hi, np.int32(group))

    def key_block(bi, hi, qi, step, head=kv_head):
        # a skipped pair asks for the block already there: no copy
        last = jax.lax.div(qi * bq + (bq - 1), np.int32(bk))
        ki = step + _first_key_block(qi, bq, bk, window) if window else step
        return bi, head(hi), jnp.minimum(ki, last), zero

    def q_spec(width):
        return pl.BlockSpec((1, 1, bq, width),
                            lambda bi, hi, qi, step: (bi, hi, qi, zero))
    in_specs = [q_spec(dh), pl.BlockSpec((1, 1, bk, dh), key_block),
                pl.BlockSpec((1, 1, bk, dv), key_block)]
    if second:
        group2 = np.int32(h // second[1].shape[1])
        in_specs += [q_spec(rope), pl.BlockSpec(
            (1, 1, bk, rope), functools.partial(
                key_block, head=lambda hi: jax.lax.div(hi, group2)))]
    perfvars.note_kernel_build("causal_attention_fwd")
    return pl.pallas_call(
        functools.partial(_attn_fwd_kernel, (dh + rope) ** -0.5, window,
                          bool(second)),
        grid=(b, h, t // bq, span),
        in_specs=in_specs,
        out_specs=[q_spec(dv), pl.BlockSpec(
            (1, 1, 1, bq), lambda bi, hi, qi, step: (bi, hi, zero, qi))],
        out_shape=[_varying_like(q, (b, h, t, dv), q.dtype),
                   _varying_like(q, (b, h, 1, t), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, LANE), jnp.float32),    # running max
                        pltpu.VMEM((bq, LANE), jnp.float32),    # running sum
                        pltpu.VMEM((bq, dv), jnp.float32)],
        interpret=_interpret(interpret),
        compiler_params=_compiler_params(
            None, _attn_fwd_vmem(dh, bq, bk, q.dtype.itemsize, rope, dv),
            "causal_attention",
            ("parallel", "parallel", "parallel", "arbitrary")),
        name="causal_attention_fwd",
    )(q, k, v, *second)


def _attn_backward(q, k, v, o, lse, do, bq: int, bk: int,
                   interpret: Optional[bool], window: int = 0,
                   second: tuple = ()):
    """(dq, dk, dv), and with ``second`` = (q2, k2) their (dq2, dk2) too.
    Where k and v hold fewer heads than q the kernel writes every query
    head's dk and dv (in float32) and the group's are summed here: one pass
    over [b, h, t, dh] beside five products over it; so it is for k2."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pl, pltpu = _pl(), _pltpu()
    b, h, t, dh = q.shape
    dv = v.shape[3]
    rope = second[0].shape[3] if second else 0
    hk = k.shape[1]
    group = h // hk
    span = causal_attention_walk(t, bq, bk, window)[1]
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)

    zero = np.int32(0)          # int32 index arithmetic, as in the forward

    def kv_head(hi):
        return hi if group == 1 else jax.lax.div(hi, np.int32(group))

    def query_block(kj, step):
        # a skipped pair asks for a block the walk has had or will have
        first = jax.lax.div(kj * bk, np.int32(bq))
        if not window:
            return jnp.maximum(step, first)
        return jnp.minimum(first + step, np.int32(t // bq - 1))

    def q_spec(width):
        return pl.BlockSpec(
            (1, 1, bq, width),
            lambda bi, hi, kj, step: (bi, hi, query_block(kj, step), zero))

    def kv_spec(width, head=kv_head):
        return pl.BlockSpec(
            (1, 1, bk, width),
            lambda bi, hi, kj, step: (bi, head(hi), kj, zero))

    def dkv_spec(width):
        return pl.BlockSpec((1, 1, bk, width),
                            lambda bi, hi, kj, step: (bi, hi, kj, zero))
    row_spec = pl.BlockSpec(
        (1, 1, 1, bq),
        lambda bi, hi, kj, step: (bi, hi, zero, query_block(kj, step)))

    def head_spec(width):
        return pl.BlockSpec((1, 1, t, width),
                            lambda bi, hi, kj, step: (bi, hi, zero, zero))

    def like(width, dtype):
        return _varying_like(q, (b, h, t, width), dtype)
    summed = q.dtype if group == 1 else jnp.float32
    in_specs = [q_spec(dh), kv_spec(dh), kv_spec(dv), q_spec(dv), row_spec,
                row_spec]
    out_specs = [head_spec(dh), dkv_spec(dh), dkv_spec(dv)]
    out_shape = [like(dh, q.dtype), like(dh, summed), like(dv, summed)]
    scratch = [pltpu.VMEM((t, dh), jnp.float32),
               pltpu.VMEM((bk, dh), jnp.float32),
               pltpu.VMEM((bk, dv), jnp.float32)]
    if second:
        h2 = second[1].shape[1]
        group2 = np.int32(h // h2)
        summed2 = q.dtype if h2 == h else jnp.float32
        in_specs += [q_spec(rope), kv_spec(
            rope, lambda hi: jax.lax.div(hi, group2))]
        out_specs += [head_spec(rope), dkv_spec(rope)]
        out_shape += [like(rope, q.dtype), like(rope, summed2)]
        scratch += [pltpu.VMEM((t, rope), jnp.float32),
                    pltpu.VMEM((bk, rope), jnp.float32)]
    perfvars.note_kernel_build("causal_attention_bwd")
    grads = pl.pallas_call(
        functools.partial(_attn_bwd_kernel, (dh + rope) ** -0.5, window,
                          bool(second)),
        grid=(b, h, t // bk, span),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=_interpret(interpret),
        compiler_params=_compiler_params(
            None, _attn_bwd_vmem(t, dh, bq, bk, 4 if group > 1
                                 else q.dtype.itemsize, rope, dv),
            "causal_attention",
            ("parallel", "parallel", "arbitrary", "arbitrary")),
        name="causal_attention_bwd",
    )(q, k, v, do, lse, di[:, :, None, :], *second)

    def group_sum(g, like_k):
        """The sum over a key head's query heads (written in float32)."""
        hk, width = like_k.shape[1], g.shape[3]
        if hk == h:
            return g
        return g.reshape(b, hk, h // hk, t, width).sum(axis=2).astype(
            like_k.dtype)
    dq, dk, dv_ = grads[:3]
    out = (dq, group_sum(dk, k), group_sum(dv_, v))
    if second:
        out += (grads[3], group_sum(grads[4], second[1]))
    return out


@functools.lru_cache(maxsize=None)
def _causal_attention_fn(bq: int, bk: int, interpret: Optional[bool],
                         window: int = 0, second: bool = False):
    """The differentiable kernel at one block size and window, jitted once:
    every layer of a step that calls it shares one lowering. ``second``: the
    function of five operands (q, k, v, q2, k2) whose scores are the sum of
    two products; only a program that calls it traces that body."""
    import jax

    def forward(q, k, v, *second):
        return _attn_forward(q, k, v, bq, bk, interpret, window, second)

    def fwd(*operands):
        o, lse = forward(*operands)
        return o, (operands, o, lse)

    def bwd(kept, do):
        (q, k, v, *second), o, lse = kept
        return _attn_backward(q, k, v, o, lse, do, bq, bk, interpret, window,
                              tuple(second))

    if second:
        @jax.custom_vjp
        def attend(q, k, v, q2, k2):
            return forward(q, k, v, q2, k2)[0]
    else:
        @jax.custom_vjp
        def attend(q, k, v):
            return forward(q, k, v)[0]
    attend.defvjp(fwd, bwd)
    return jax.jit(attend)


def causal_attention(q, k, v, *, window: int = 0, rope: Optional[tuple] = None,
                     block_q: Optional[int] = None,
                     block_k: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """softmax(q k^T / sqrt(dh), keys <= query) v of (batch, heads, t, dh)
    operands, blockwise: the grid walks (batch, head, query block, key block)
    with a float32 running max and sum (online softmax), bf16 operands enter
    the MXU with float32 accumulation (float32 operands at full precision),
    scores stay float32 through the softmax and the probabilities are
    rounded to the operand dtype only as a product's operand. Key blocks
    wholly above the diagonal run nothing, the ones on it mask by position.

    ``window`` > 0: a query at position p sees keys p - window + 1 .. p.
    Key blocks wholly before a query block's window run nothing either, in
    both directions, and are not grid steps: a block's walk starts at the
    band and is as long as the band's widest crossing
    (:func:`causal_attention_walk`). k and v may hold fewer heads than q, a
    divisor of its count: query head j reads key/value head j // (heads /
    key-value heads), by the blocks' index maps alone (nothing is repeated
    in HBM), and its dk and dv are the sums over the group's query heads.

    Differentiable: the forward pass keeps o and the rows' log-sum-exp
    ([b, h, 1, t] float32: a row along the lanes, as the backward reads it);
    the backward pass is one kernel over (key block, query block) pairs that
    recomputes a pair's probabilities from q, k and the log-sum-exp,
    accumulates dk and dv over the query blocks and one head's dq in VMEM.
    No [b, h, t, t] tensor is written to HBM in either direction.

    ``rope`` = (q2, k2), (batch, heads, t, d2) queries and (batch, a
    divisor of heads, t, d2) keys: the scores are (q k^T + q2 k2^T) /
    sqrt(dh + d2), a head's rotated part beside its unrotated one; with one
    k2 head every query head reads it, again by the index map, and its dk2
    is the sum over them. v may be of a width of its own, with or without a
    second term (differential attention: a pair's values side by side).

    Blocks default to :func:`causal_attention_blocks`; a shape outside the
    kernel's contract raises. :func:`ring_attention` above is the other
    attention kernel here: it rotates K/V over a ring of devices, holds whole
    operands in VMEM and has no backward pass; this one is local and is what
    a train step runs."""
    t, dh = q.shape[2:]
    rope = tuple(rope or ())
    d2 = rope[0].shape[3] if rope else 0
    if block_q is None or block_k is None:
        blocks = causal_attention_blocks(t, dh, d2, v.shape[3])
        if blocks is None:
            raise ValueError(
                f"causal_attention: (t, dh, second term, values) = ({t}, "
                f"{dh}, {d2}, {v.shape[3]}) is outside the "
                f"kernel's contract (t a multiple of {_ATTN_BLOCKS[-1]}, dh "
                f"64 or a multiple of {LANE}, one head's dq in VMEM)")
        block_q, block_k = block_q or blocks[0], block_k or blocks[1]
    if t % block_q or t % block_k:
        raise ValueError(f"causal_attention: blocks ({block_q}, {block_k}) "
                         f"do not divide t = {t}")
    if not (k.shape[:3] == v.shape[:3] and q.dtype == k.dtype == v.dtype
            and q.shape[:1] + q.shape[2:] == k.shape[:1] + k.shape[2:]
            and q.shape[1] % k.shape[1] == 0):
        raise ValueError("causal_attention: q, k, v differ in dtype or in "
                         "shape beyond a whole number of query heads a "
                         "key/value head")
    if rope and not (
            rope[0].shape[:3] == q.shape[:3] and rope[0].dtype == q.dtype
            == rope[1].dtype and rope[1].shape[3] == d2
            and rope[1].shape[:1] + rope[1].shape[2:3] == q.shape[:1] + (t,)
            and q.shape[1] % rope[1].shape[1] == 0):
        raise ValueError("causal_attention: the second term's queries are "
                         "not q's in all but width, or its keys not theirs "
                         "in all but a whole number of heads a key head")
    return _causal_attention_fn(block_q, block_k, interpret, int(window),
                                bool(rope))(q, k, v, *rope)


# ---------------------------------------------------------------------------
# grouped matrix multiplication (a sparse-expert layer's products: rows
# sorted by group, one matrix a group; forward and both backward products,
# no weight transposed in HBM)
# ---------------------------------------------------------------------------

_GROUPED_ROW_TILES = (512, 256, 128)    # widest first
_GROUPED_SLICE_ROWS = 128   # what a tile shared by groups is multiplied in
# column tiles, widest first: a dimension no wider than one is taken whole
_GROUPED_COL_TILES = (2048, 1024, 512, 256, 128)


def _grouped_vmem(tm: int, k: int, n: int, tc: int, itemsize: int) -> int:
    """The largest VMEM working set of the three kernels at row tile ``tm``
    and column tile ``tc``: operand and result blocks double-buffered by the
    grid pipeline, the float32 product (and, for the weights' gradient, its
    accumulator and the row block transposed)."""
    tk, tn = min(k, tc), min(n, tc)
    fwd = 2 * (tm * k + k * tn + tm * tn) * itemsize + tm * tn * 4
    dlhs = 2 * (tm * n + tk * n + tm * tk) * itemsize + tm * tk * 4
    drhs = (2 * (tm * tk + tm * tn + tk * tn) + tm * tk) * itemsize \
        + 2 * tk * tn * 4
    return max(fwd, dlhs, drhs)


def grouped_matmul_blocks(m: int, k: int, n: int, dtype) -> Optional[tuple]:
    """(row tile, column tile) of the grouped kernels for ``[m, k]`` rows and
    ``[g, k, n]`` matrices of ``dtype``, or None where their contract does
    not hold: a kernel's type (:func:`_typed`), ``m`` a multiple of a row
    tile,
    ``k`` and ``n`` multiples of 128 (each is a contraction in one of the
    three products and a block's minor dimension in another), and the blocks
    of all three inside :data:`VMEM_LIMIT_BYTES` with the headroom
    :func:`_compiler_params` asks for. The contraction is always taken
    whole, so a group's matrix stays in VMEM over the group's row tiles; the
    other dimension is cut to the column tile only where VMEM forces it."""
    itemsize = _typed(dtype)
    if not itemsize or k % LANE or n % LANE or min(m, k, n) <= 0:
        return None
    tm = next((t for t in _GROUPED_ROW_TILES if m % t == 0), None)
    if tm is None:
        return None
    for tc in _GROUPED_COL_TILES:
        if any(d > tc and d % tc for d in (k, n)):
            continue
        if 2 * _grouped_vmem(tm, k, n, tc, itemsize) <= VMEM_LIMIT_BYTES:
            return tm, tc
    return None


def grouped_matmul_visits(group_sizes, m: int, block_m: int):
    """The grouped kernels' walk over ``m`` rows sorted by group, computed
    in the traced program from ``group_sizes`` and handed to them by scalar
    prefetch: (offsets[g + 2], group[v], tile[v], matrix[v], visits[1]), all
    int32. It depends on the sizes, ``m`` and the row tile alone: a layer
    computes it once and gives it to every product over those rows
    (:func:`grouped_matmul`'s ``visits``), forward and backward.

    A visit is one (group, row tile) pair; a row tile that spans several
    groups is visited once by each, in order, so the visits of one group
    and the visits of one tile are both consecutive. The rows past the
    groups' sum are group ``g``, whose product is zero; an empty group is
    visited once (its weight gradient has to be written); the static length
    is m / block_m + g: every group but the first may start inside a tile),
    and the entries from ``visits[0]`` on repeat the last (group g, last
    tile): a kernel does nothing there and no block moves. ``matrix[v]`` is
    the group whose matrix a product needs at visit v: the visit's own where
    it has rows, else the last one before it that had (an empty group's
    matrix is never copied in).

    Written with few, plain operations (comparisons against a [visits,
    groups] grid, not `repeat` or a scan) and jitted on its own: tracing is
    what it costs, and a second program over the same shapes finds the
    trace there."""
    return _group_visits_fn(m, block_m)(group_sizes)


@functools.lru_cache(maxsize=None)
def _group_visits_fn(m: int, tm: int):
    import jax
    return jax.jit(lambda sizes: _visits_of(m, tm, sizes))


def _visits_of(m: int, tm: int, sizes):
    import jax
    import jax.numpy as jnp
    import numpy as np
    i32 = jnp.int32
    g = sizes.shape[0]
    tiles_m = m // tm
    length = tiles_m + g
    sizes = sizes.astype(i32)
    rest = jnp.maximum(m - jnp.sum(sizes, dtype=i32), 0)
    sizes = jnp.concatenate([sizes, rest[None]])            # g + 1
    ends = jnp.cumsum(sizes, dtype=i32)
    first = jnp.minimum(jax.lax.div(ends - sizes, i32(tm)), tiles_m - 1)
    last = jax.lax.div(jnp.maximum(ends - 1, 0), i32(tm))
    # an empty group is visited once, empty rows past the sum never
    alone = np.array([1] * g + [0], np.int32)
    spanned = jnp.where(sizes > 0, last - first + 1, alone)
    ended = jnp.cumsum(spanned, dtype=i32)      # a group's last visit + 1
    v = jnp.arange(length, dtype=i32)
    group = jnp.minimum(jnp.sum(v[:, None] >= ended[None, :], axis=1,
                                dtype=i32), g)
    within = v - (ended - spanned)[group]
    tile = jnp.minimum(first[group] + within, tiles_m - 1)
    ids = jnp.arange(g, dtype=i32)
    held = jnp.logical_and(sizes[None, :g] > 0,
                           ids[None, :] <= group[:, None])
    matrix = jnp.max(jnp.where(held, ids[None, :], 0), axis=1)
    offsets = jnp.concatenate([jnp.zeros(1, i32), ends])
    return offsets, group, tile, matrix, ended[g:]


def _visit(g: int, tm: int, v, offs_ref, group_ref, tile_ref, visits_ref):
    """What visit ``v`` is: (live: not padding; group, clamped to a real
    one; row0 of its tile; lo, hi: the group's rows; real: a group with
    rows, not the rest)."""
    import jax.numpy as jnp
    import numpy as np
    raw = group_ref[v]
    lo, hi = offs_ref[raw], offs_ref[raw + 1]
    live = v < visits_ref[0]
    real = jnp.logical_and(raw < g, hi > lo)
    return (live, jnp.minimum(raw, np.int32(g - 1)), tile_ref[v] * tm, lo,
            hi, real)


def _for_slices_of_group(tm: int, row0, lo, hi, body) -> None:
    """``body(where in the tile, its first row among all rows)`` for each
    slice of _GROUPED_SLICE_ROWS rows of the tile at ``row0`` that holds a
    row of [lo, hi), and for no other: a visit of a tile that its group
    shares multiplies only those, so a boundary between groups costs a
    slice of needless products and not a tile's. One loop with traced
    bounds, so the body is traced (and compiled) once."""
    import jax
    import jax.numpy as jnp
    pl = _pl()
    rows = min(_GROUPED_SLICE_ROWS, tm)
    first = jax.lax.div(jnp.maximum(lo - row0, 0), jnp.int32(rows))
    stop = jax.lax.div(jnp.minimum(hi - row0, tm) + (rows - 1),
                       jnp.int32(rows))

    def one(j, carry):
        at = pl.ds(pl.multiple_of(j * rows, rows), rows)
        body(at, row0 + j * rows)
        return carry
    jax.lax.fori_loop(first, stop, one, jnp.int32(0))


def _rows_of_group(shape, row0, lo, hi):
    """[rows, cols] bool: the rows of the tile at ``row0`` that lie in
    [lo, hi)."""
    import jax
    import jax.numpy as jnp
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return jnp.logical_and(rows >= lo, rows < hi)


def _gmm_kernel(g: int, transposed: bool, offs_ref, group_ref, tile_ref,
                _matrix_ref, visits_ref, lhs_ref, rhs_ref, out_ref):
    """One visit of rows x matrix: the tile's rows times the group's matrix
    (or, ``transposed``, its transpose: the contraction runs over the
    block's minor dimension and nothing is re-laid), stored over the
    group's rows of the tile only. A tile that is not one group's alone is
    zeroed at its first visit, so the rows past the groups' sum come out
    zero."""
    import jax
    import jax.numpy as jnp
    pl = _pl()
    v = pl.program_id(1)
    tm = lhs_ref.shape[0]
    live, _group, row0, lo, hi, real = _visit(
        g, tm, v, offs_ref, group_ref, tile_ref, visits_ref)
    first = jnp.logical_or(v == 0,
                           tile_ref[jnp.maximum(v - 1, 0)] != tile_ref[v])
    whole = jnp.logical_and(jnp.logical_and(lo <= row0, hi >= row0 + tm),
                            real)
    dims = (((1,), (1 if transposed else 0,)), ((), ()))

    def product(rows=slice(None)):      # a slice, or a pl.ds of a loop
        return jax.lax.dot_general(
            lhs_ref[rows, :], rhs_ref[...], dims,
            preferred_element_type=jnp.float32,
            precision=_attn_precision(lhs_ref.dtype)).astype(out_ref.dtype)

    @pl.when(jnp.logical_and(live, whole))
    def _inside_one_group():
        out_ref[...] = product()

    @pl.when(jnp.logical_and(live, jnp.logical_and(first,
                                                   jnp.logical_not(whole))))
    def _first_visit_of_a_shared_tile():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(jnp.logical_and(live, jnp.logical_and(real,
                                                   jnp.logical_not(whole))))
    def _shared_tile():
        def a_slice(at, r0):
            kept = out_ref[at, :]
            out_ref[at, :] = jnp.where(
                _rows_of_group(kept.shape, r0, lo, hi), product(at), kept)
        _for_slices_of_group(tm, row0, lo, hi, a_slice)


def _tgmm_kernel(g: int, offs_ref, group_ref, tile_ref, _matrix_ref,
                 visits_ref, lhs_ref, dout_ref, out_ref, acc_ref):
    """One visit of the weights' gradient: rows^T x d out of the tile's rows
    that lie in the visit's group, accumulated in float32 over the group's
    visits and written once, at its last (an empty group's: zero)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pl = _pl()
    v = pl.program_id(2)
    tm = lhs_ref.shape[0]
    live, group, row0, lo, hi, real = _visit(
        g, tm, v, offs_ref, group_ref, tile_ref, visits_ref)
    top = np.int32(g - 1)
    before = jnp.minimum(group_ref[jnp.maximum(v - 1, 0)], top)
    after = jnp.minimum(group_ref[jnp.minimum(v + 1,
                                              pl.num_programs(2) - 1)], top)
    whole = jnp.logical_and(lo <= row0, hi >= row0 + tm)
    prec = _attn_precision(lhs_ref.dtype)
    tn = (((0,), (0,)), ((), ()))

    @pl.when(jnp.logical_and(live, jnp.logical_or(v == 0, before != group)))
    def _first_visit_of_the_group():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(jnp.logical_and(live, jnp.logical_and(real, whole)))
    def _inside_one_group():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], dout_ref[...], tn,
            preferred_element_type=jnp.float32, precision=prec)

    @pl.when(jnp.logical_and(live, jnp.logical_and(real,
                                                   jnp.logical_not(whole))))
    def _shared_tile():
        def a_slice(at, r0):
            lhs, dout = lhs_ref[at, :], dout_ref[at, :]
            lhs = jnp.where(_rows_of_group(lhs.shape, r0, lo, hi), lhs,
                            jnp.zeros_like(lhs))
            dout = jnp.where(_rows_of_group(dout.shape, r0, lo, hi), dout,
                             jnp.zeros_like(dout))
            acc_ref[...] += jax.lax.dot_general(
                lhs, dout, tn, preferred_element_type=jnp.float32,
                precision=prec)
        _for_slices_of_group(tm, row0, lo, hi, a_slice)

    @pl.when(jnp.logical_and(live, jnp.logical_or(
        v == visits_ref[0] - 1, after != group)))
    def _last_visit_of_the_group():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _gmm_call(lhs, rhs, visits, tm: int, tc: int, transposed: bool,
              interpret: Optional[bool]):
    """rows x matrix of each row's group: ``lhs[m, k] x rhs[g, k, n]``, or
    ``transposed`` ``lhs[m, n] x rhs[g, k, n]^T`` (the rows' gradient), the
    contraction whole. Grid (column tile, visit): consecutive visits of one
    group ask for the same block of ``rhs`` and the pipeline skips the
    copy."""
    import numpy as np
    pl, pltpu = _pl(), _pltpu()
    m, c = lhs.shape
    g = rhs.shape[0]
    cols = rhs.shape[1] if transposed else rhs.shape[2]
    tn = min(cols, tc)
    zero = np.int32(0)

    def rows_at(ni, v, offs, group, tile, matrix, visits):
        return tile[v], zero

    def matrix_at(ni, v, offs, group, tile, matrix, visits):
        return (matrix[v], ni, zero) if transposed else (matrix[v], zero, ni)

    def out_at(ni, v, offs, group, tile, matrix, visits):
        return tile[v], ni

    name = "grouped_matmul_dlhs" if transposed else "grouped_matmul_fwd"
    k, n = (cols, c) if transposed else (c, cols)
    perfvars.note_kernel_build(name)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, g, transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(cols // tn, visits[1].shape[0]),
            in_specs=[pl.BlockSpec((tm, c), rows_at),
                      pl.BlockSpec((None, tn, c) if transposed
                                   else (None, c, tn), matrix_at)],
            out_specs=pl.BlockSpec((tm, tn), out_at)),
        out_shape=_varying_like(lhs, (m, cols), lhs.dtype),
        interpret=_interpret(interpret),
        compiler_params=_compiler_params(
            None, _grouped_vmem(tm, k, n, tc, lhs.dtype.itemsize),
            "grouped_matmul", ("parallel", "arbitrary")),
        name=name,
    )(*visits, lhs, rhs)


def _tgmm_call(lhs, dout, g: int, visits, tm: int, tc: int,
               interpret: Optional[bool], out_dtype=None,
               name: str = "grouped_matmul_drhs"):
    """The weights' gradient ``[g, k, n]``: for each group ``lhs[rows of
    g]^T x dout[rows of g]``. Grid (k tile, n tile, visit): the visits are
    the reduction, one float32 accumulator per block of a group. (Its other
    caller, :func:`grouped_row_sums`, names the result's dtype and the
    call.)"""
    import jax.numpy as jnp
    import numpy as np
    pl, pltpu = _pl(), _pltpu()
    m, k = lhs.shape
    n = dout.shape[1]
    tk, tn = min(k, tc), min(n, tc)
    top = np.int32(g - 1)
    perfvars.note_kernel_build(name)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(k // tk, n // tn, visits[1].shape[0]),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ki, ni, v, offs, group, tile,
                             matrix, visits: (tile[v], ki)),
                pl.BlockSpec((tm, tn), lambda ki, ni, v, offs, group, tile,
                             matrix, visits: (tile[v], ni))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda ki, ni, v, offs, group, tile, matrix,
                visits: (jnp.minimum(group[v], top), ki, ni)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=_varying_like(lhs, (g, k, n), out_dtype or lhs.dtype),
        interpret=_interpret(interpret),
        compiler_params=_compiler_params(
            None, _grouped_vmem(tm, k, n, tc, lhs.dtype.itemsize),
            "grouped_matmul", ("parallel", "parallel", "arbitrary")),
        name=name,
    )(*visits, lhs, dout)


@functools.lru_cache(maxsize=None)
def _grouped_matmul_fn(tm: int, tc: int, interpret: Optional[bool]):
    """The differentiable grouped product at one tiling, over rows whose
    walk is given. Its forward and its backward are each jitted once,
    outside the `custom_vjp`: every product of a program with the same
    shapes (and the primal and the forward rule of each) shares one trace
    of the kernel's body and one lowering, which is what a kernel costs at
    set-up (PERF.md, Set-up)."""
    import jax

    @jax.jit
    def forward(lhs, rhs, visits):
        return _gmm_call(lhs, rhs, visits, tm, tc, False, interpret)

    @jax.jit
    def backward(lhs, rhs, visits, dout):
        return (_gmm_call(dout, rhs, visits, tm, tc, True, interpret),
                _tgmm_call(lhs, dout, rhs.shape[0], visits, tm, tc,
                           interpret))

    @jax.custom_vjp
    def grouped(lhs, rhs, visits):
        return forward(lhs, rhs, visits)

    def fwd(lhs, rhs, visits):
        return forward(lhs, rhs, visits), (lhs, rhs, visits)

    def bwd(kept, dout):
        return backward(*kept, dout) + (None,)

    grouped.defvjp(fwd, bwd)
    return grouped


def grouped_matmul(lhs, rhs, group_sizes, *, visits=None,
                   block_m: Optional[int] = None,
                   block_c: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """``lax.ragged_dot(lhs, rhs, group_sizes)``: row i of ``lhs[m, k]``
    times the matrix of ``rhs[g, k, n]`` whose group it falls in, the first
    ``group_sizes[0]`` rows in group 0 and so on; rows past the groups' sum
    come out zero; an empty group is legal. bf16 operands enter the MXU with
    float32 accumulation (float32 operands at full precision) and the result
    is rounded once, to the operands' dtype.

    The walk over (group, row tile) pairs is computed in the traced program
    from ``group_sizes`` (:func:`grouped_matmul_visits`; ``visits`` is that
    walk where the caller has it already, for these sizes, these rows and
    this row tile) and prefetched as scalars;
    the grid is (column tile, visit) with the contraction whole, so a
    group's matrix is copied into VMEM once per call and column tile
    however many row tiles the group has, and a tile that several groups
    share is visited once by each: only its 128-row slices that hold a row
    of the visiting group are multiplied, the others' rows masked at the
    store.

    Differentiable: d lhs is the same kernel contracting over the matrices'
    last axis (no transposed copy of a weight in HBM), d rhs the transposed
    kernel, one float32 accumulator per group and block over the group's
    visits (an empty group's gradient zero).

    Tiles default to :func:`grouped_matmul_blocks`; a shape outside the
    kernels' contract raises."""
    m, k = lhs.shape
    g, k2, n = rhs.shape
    if k != k2 or lhs.dtype != rhs.dtype or group_sizes.shape != (g,):
        raise ValueError("grouped_matmul: lhs [m, k], rhs [g, k, n] of one "
                         "dtype and group_sizes [g] are needed, not "
                         f"{lhs.shape} {lhs.dtype}, {rhs.shape} {rhs.dtype},"
                         f" {group_sizes.shape}")
    if block_m is None or block_c is None:
        blocks = grouped_matmul_blocks(m, k, n, lhs.dtype.itemsize)
        if blocks is None:
            raise ValueError(
                f"grouped_matmul: (m, k, n) = ({m}, {k}, {n}) is outside "
                f"the kernels' contract (m a multiple of "
                f"{_GROUPED_ROW_TILES[-1]}, k and n multiples of {LANE}, "
                "the blocks in VMEM)")
        block_m, block_c = block_m or blocks[0], block_c or blocks[1]
    if m % block_m or any(d > block_c and d % block_c for d in (k, n)):
        raise ValueError(f"grouped_matmul: tiles ({block_m}, {block_c}) do "
                         f"not divide (m, k, n) = ({m}, {k}, {n})")
    if visits is None:
        visits = grouped_matmul_visits(group_sizes, m, block_m)
    lhs, rhs, *visits = _vary_together(lhs, rhs, *visits)
    return _grouped_matmul_fn(block_m, block_c, interpret)(lhs, rhs,
                                                           tuple(visits))


# ---------------------------------------------------------------------------
# rows summed into indexed places (a held expert layer's combine, the
# transpose of a row gather, an embedding's gradient): the weights' gradient
# kernel above with a one-hot as its left operand
# ---------------------------------------------------------------------------

# column tiles of the sum, widest first: the widest that divides the rows'
# width (7680 = 4 x 1920, 6144 = 3 x 2048); a width no wider than one whole
_ROW_SUM_COL_TILES = tuple(range(2048, 0, -LANE))


def grouped_row_sums_blocks(m: int, n: int, dtype) -> Optional[tuple]:
    """(row tile, column tile) of :func:`grouped_row_sums` for ``[m, 128]``
    times ``[m, n]`` operands of ``dtype``, or None where the kernel's
    contract does not hold (a kernel's type, as :func:`grouped_matmul_blocks`
    takes it; ``m`` a multiple of a row tile, ``n`` of 128, the blocks in
    VMEM as it counts them)."""
    tm = next((t for t in _GROUPED_ROW_TILES if m % t == 0), None)
    itemsize = _typed(dtype)
    if not itemsize or n % LANE or min(m, n) <= 0 or tm is None:
        return None
    tc = next((c for c in _ROW_SUM_COL_TILES
               if n % c == 0 and 2 * _grouped_vmem(tm, LANE, n, c, itemsize)
               <= VMEM_LIMIT_BYTES), None)
    return tc and (tm, tc)


@functools.lru_cache(maxsize=None)
def _grouped_row_sums_fn(tm: int, tc: int, interpret: Optional[bool],
                         out_dtype: str):
    """The sum at one tiling and result type, jitted once: every sum of a
    program with the same shapes shares one trace of the kernel's body and
    one lowering (what a kernel costs at set-up: PERF.md, Set-up)."""
    import jax

    @jax.jit
    def sums(lhs, rows, visits):
        return _tgmm_call(lhs, rows, visits[0].shape[0] - 2, visits, tm, tc,
                          interpret, out_dtype, "grouped_row_sums")
    return sums


def grouped_row_sums(lhs, rows, group_sizes, *, out_dtype=None,
                     interpret: Optional[bool] = None):
    """``[g, 128, n]``: for each group of rows, ``lhs[its rows]^T x
    rows[its rows]``, the first ``group_sizes[0]`` rows of ``lhs[m, 128]``
    and ``rows[m, n]`` in group 0 and so on, accumulated in float32 and
    rounded once to ``out_dtype`` (the operands' where None); rows past the
    groups' sum are not read, an empty group's result is zero. With ``lhs``
    the one-hot of each row's place within its group's 128 places (times a
    weight, where the rows are weighed) this sums rows sorted by place into
    their places: ``parallel.ep.sum_rows``. It is the kernel of
    :func:`grouped_matmul`'s weights' gradient, walked the same way
    (:func:`grouped_matmul_visits`); bf16 operands enter the MXU as they
    are, float32 ones at full precision. Tiles are
    :func:`grouped_row_sums_blocks`'s; a shape outside the contract raises."""
    import numpy as np
    m, k = lhs.shape
    n = rows.shape[1]
    blocks = grouped_row_sums_blocks(m, n, lhs.dtype.itemsize)
    if rows.shape[0] != m or lhs.dtype != rows.dtype or k != LANE \
            or blocks is None:
        raise ValueError("grouped_row_sums: lhs [m, 128] and rows [m, n] of "
                         "one dtype, m a multiple of "
                         f"{_GROUPED_ROW_TILES[-1]} and n of {LANE}, are "
                         f"needed, not {lhs.shape} {lhs.dtype}, {rows.shape} "
                         f"{rows.dtype}")
    visits = grouped_matmul_visits(group_sizes, m, blocks[0])
    lhs, rows, *visits = _vary_together(lhs, rows, *visits)
    return _grouped_row_sums_fn(
        *blocks, interpret,
        str(np.dtype(out_dtype or lhs.dtype)))(lhs, rows, tuple(visits))


# ---------------------------------------------------------------------------
# rotary embeddings on token-major rows, and the cut into heads (what stands
# between a projection's product and the attention kernel: one pass each way)
# ---------------------------------------------------------------------------

_ROPE_SLOT = LANE // 2          # parts are placed and moved in 64-lane slots
_ROPE_ROW_TILES = (512, 256, 128)       # tokens a block, widest first
_ROPE_BLOCK_LANES = 1536        # a block's share of a row, at most


def rope_heads_plan(parts: tuple) -> Optional[tuple]:
    """(lanes of a group, heads of a group, slots) for a row of heads that
    are each the ``parts`` side by side, ``parts`` = ((width, rotated), ..):
    a *group* is the fewest whole heads that fill whole 128-lane tiles, and
    ``slots[s]`` = (head in the group, part, 64-lane slot of the part) says
    whose values lanes [64 s, 64 s + 64) of a group are. None where the
    kernel's contract does not hold: every width 64 or a multiple of 128,
    the rotated parts of one width, 64 or 128, and a rotated 128 on a tile
    of its own (its two halves are one tile's lanes)."""
    widths = [w for w, _turned in parts]
    turned = {w for w, t in parts if t}
    if not widths or any(w != _ROPE_SLOT and w % LANE for w in widths) \
            or len(turned) != 1 or not turned <= {_ROPE_SLOT, LANE}:
        return None
    period = sum(widths)
    lanes = math.lcm(period, LANE)
    slots = []
    for g in range(lanes // period):
        for p, (w, t) in enumerate(parts):
            if t and w == LANE and (len(slots) * _ROPE_SLOT) % LANE:
                return None
            slots += [(g, p, j) for j in range(w // _ROPE_SLOT)]
    return lanes, lanes // period, tuple(slots)


def rope_heads_lanes(parts: tuple):
    """For every lane of a group of ``parts`` (:func:`rope_heads_plan`), its
    place within its rotary head, or -1 where the lane's part passes: what
    the caller builds :func:`rope_heads`'s tables from."""
    import numpy as np
    at = np.arange(_ROPE_SLOT)
    return np.concatenate([
        _ROPE_SLOT * j + at if parts[p][1] else np.full(_ROPE_SLOT, -1)
        for _g, p, j in rope_heads_plan(parts)[2]])


def rope_heads_blocks(t: int, heads: int, parts: tuple,
                      dtype=None) -> Optional[tuple]:
    """(tokens a block, groups a block) of :func:`rope_heads` for ``heads``
    heads of ``parts`` of ``dtype`` over ``t`` tokens, or None where its
    contract does not hold (a kernel's type: :func:`_typed`;
    :func:`rope_heads_plan`'s, ``t`` a multiple of a row tile, whole groups
    of heads)."""
    plan = rope_heads_plan(tuple(parts))
    tt = next((r for r in _ROPE_ROW_TILES if t % r == 0), None)
    if not _typed(dtype) or plan is None or tt is None \
            or heads % plan[1]:
        return None
    groups = heads // plan[1]
    m = max(m for m in range(1, groups + 1)
            if groups % m == 0 and (m == 1 or m * plan[0] <= _ROPE_BLOCK_LANES))
    return tt, m


def _rope_heads_vmem(tt: int, m: int, lanes: int, itemsize: int) -> int:
    """A grid step's blocks, double-buffered: the row's share, the parts'
    (a 64-wide part's block is stored 128 lanes wide), the two tables."""
    return 2 * (3 * tt * m * lanes * itemsize + 2 * tt * lanes * 4)


def _rope_turn(x, cos, sin, width: int, keep):
    """``x cos + swap(x) sin`` on one [rows, 128] float32 tile whose lanes
    are rotary heads of ``width`` (64: two of them; 128: one): ``swap``
    exchanges a head's halves, a lane rotation (and for two heads a tile a
    select between the two directions). ``keep`` = (first, second): which
    64-lane half of the tile is a rotated part's; the other passes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pltpu = _pltpu()
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    half = width // 2
    # int32 shifts under jax_enable_x64 too (Mosaic has no 64-bit scalars)
    swapped = pltpu.roll(x, np.int32(half), 1)
    if width < LANE:
        swapped = jnp.where((lane & np.int32(width - 1)) < np.int32(half),
                            pltpu.roll(x, np.int32(LANE - half), 1), swapped)
    y = x * cos + swapped * sin
    if all(keep):
        return y
    return jnp.where((lane < np.int32(_ROPE_SLOT)) == keep[0], y, x)


def _rope_tiles(parts: tuple, slots: tuple, tiles: list, cos_ref, sin_ref):
    """A group's float32 tiles, the rotated parts' lanes turned."""
    width = next(w for w, t in parts if t)
    out = []
    for c, x in enumerate(tiles):
        keep = tuple(parts[slots[2 * c + i][1]][1] for i in (0, 1))
        if any(keep):
            at = slice(c * LANE, (c + 1) * LANE)
            x = _rope_turn(x, cos_ref[:, at], sin_ref[:, at], width, keep)
        out.append(x)
    return out


def _rope_slot(tile, second: bool):
    """A tile's first or second 64 lanes, as a [rows, 64] value."""
    if second:
        import numpy as np
        tile = _pltpu().roll(tile, np.int32(_ROPE_SLOT), 1)
    return tile[:, :_ROPE_SLOT]


def _rope_heads_fwd_kernel(parts: tuple, plan: tuple, m: int, x_ref, cos_ref,
                           sin_ref, *out_refs):
    """One block: ``m`` groups of a row's heads over a tile of tokens. Each
    group's tiles are read, turned in float32 and written to their parts'
    places in the [batch, heads, tokens, width] results."""
    import jax.numpy as jnp
    lanes, per, slots = plan
    for gi in range(m):
        tiles = _rope_tiles(parts, slots, [
            x_ref[0, :, gi * lanes + c * LANE:gi * lanes + (c + 1) * LANE]
            .astype(jnp.float32) for c in range(lanes // LANE)],
            cos_ref, sin_ref)
        for s, (g, p, j) in enumerate(slots):
            ref, head = out_refs[p], gi * per + g
            if parts[p][0] == _ROPE_SLOT:
                ref[0, head] = _rope_slot(tiles[s // 2], s % 2).astype(ref.dtype)
            elif j % 2 == 0:        # a 128-lane piece: a tile, or two halves
                piece = tiles[s // 2] if s % 2 == 0 else jnp.concatenate(
                    [_rope_slot(tiles[s // 2], True),
                     _rope_slot(tiles[s // 2 + 1], False)], axis=1)
                ref[0, head, :, j // 2 * LANE:(j // 2 + 1) * LANE] = \
                    piece.astype(ref.dtype)


def _rope_heads_bwd_kernel(parts: tuple, plan: tuple, m: int, *refs):
    """The transpose: the parts' cotangents are read from their places,
    joined into the row's tiles and turned by the negative angle (the caller
    hands ``sin`` negated: a rotation's transpose is its inverse)."""
    import jax.numpy as jnp
    lanes, per, slots = plan
    *part_refs, cos_ref, sin_ref, x_ref = refs

    def slot(gi, s):
        g, p, j = slots[s]
        ref, head = part_refs[p], gi * per + g
        if parts[p][0] == _ROPE_SLOT:
            return ref[0, head].astype(jnp.float32)
        tile = ref[0, head, :, j // 2 * LANE:(j // 2 + 1) * LANE]
        return _rope_slot(tile.astype(jnp.float32), j % 2)

    for gi in range(m):
        tiles = []
        for c in range(lanes // LANE):
            g, p, j = slots[2 * c]
            if parts[p][0] != _ROPE_SLOT and j % 2 == 0:    # a tile as it is
                tiles.append(part_refs[p][
                    0, gi * per + g, :, j // 2 * LANE:(j // 2 + 1) * LANE]
                    .astype(jnp.float32))
            else:
                tiles.append(jnp.concatenate(
                    [slot(gi, 2 * c), slot(gi, 2 * c + 1)], axis=1))
        for c, tile in enumerate(_rope_tiles(parts, slots, tiles, cos_ref,
                                             sin_ref)):
            x_ref[0, :, gi * lanes + c * LANE:gi * lanes + (c + 1) * LANE] = \
                tile.astype(x_ref.dtype)


def _rope_heads_call(back: bool, operands, cos, sin, heads: int, parts: tuple,
                     tt: int, m: int, interpret: Optional[bool]):
    """The forward kernel over (row, tables) or the backward one over (the
    parts' cotangents, tables). The grid walks (token tile, batch, block of
    groups): the tables' block changes with the first alone, so it is copied
    in once a token tile."""
    import numpy as np
    pl = _pl()
    plan = rope_heads_plan(parts)
    lanes, per, _slots = plan
    like = operands[0]
    b, t = like.shape[0], like.shape[2 if back else 1]
    zero = np.int32(0)          # int32 index arithmetic, as in the attention
    row = pl.BlockSpec((1, tt, m * lanes), lambda ti, bi, gi: (bi, ti, gi))
    table = pl.BlockSpec((tt, lanes), lambda ti, bi, gi: (ti, zero))
    cut = [pl.BlockSpec((1, m * per, tt, w),
                        lambda ti, bi, gi: (bi, gi, ti, zero))
           for w, _turned in parts]
    whole = _varying_like(like, (b, t, heads * (lanes // per)), like.dtype)
    pieces = [_varying_like(like, (b, heads, t, w), like.dtype)
              for w, _turned in parts]
    name = "rope_heads_bwd" if back else "rope_heads_fwd"
    perfvars.note_kernel_build(name)
    return pl.pallas_call(
        functools.partial(_rope_heads_bwd_kernel if back
                          else _rope_heads_fwd_kernel, parts, plan, m),
        grid=(t // tt, b, heads // (m * per)),
        in_specs=(cut if back else [row]) + [table, table],
        out_specs=row if back else cut,
        out_shape=whole if back else pieces,
        interpret=_interpret(interpret),
        compiler_params=_compiler_params(
            None, _rope_heads_vmem(tt, m, lanes, like.dtype.itemsize),
            "rope_heads", ("parallel", "parallel", "parallel")),
        name=name,
    )(*operands, cos, sin)


@functools.lru_cache(maxsize=None)
def _rope_heads_fn(heads: int, parts: tuple, tt: int, m: int,
                   interpret: Optional[bool]):
    """The differentiable pair at one pattern and tiling, jitted once: the
    layers of a step share one trace and one lowering a direction."""
    import jax

    @jax.custom_vjp
    def turn_and_cut(row, cos, sin):
        return tuple(_rope_heads_call(False, (row,), cos, sin, heads, parts,
                                      tt, m, interpret))

    def fwd(row, cos, sin):
        return turn_and_cut(row, cos, sin), (cos, sin)

    def bwd(tables, cotangents):
        cos, sin = tables
        return _rope_heads_call(True, tuple(cotangents), cos, -sin, heads,
                                parts, tt, m, interpret), None, None

    turn_and_cut.defvjp(fwd, bwd)
    return jax.jit(turn_and_cut)


def rope_heads(row, cos, sin, heads: int, parts: Sequence[tuple], *,
               interpret: Optional[bool] = None) -> tuple:
    """A token-major ``row`` [batch, tokens, heads x sum of widths], each
    head the ``parts`` = ((width, rotated), ..) side by side, as one
    [batch, heads, tokens, width] array a part (the attention kernel's
    operands), the rotated parts turned on the way: ``x cos + swap(x) sin``
    in float32, rounded once, where ``swap`` exchanges the halves of a rotary
    head and ``cos`` / ``sin`` [tokens, lanes of a group] float32 hold each
    angle twice, ``sin`` with the first half's sign folded in (1 and 0 on a
    part that passes: its values come through bit for bit). One pass: the
    row is read where it is 128 lanes dense and every part written once;
    the transpose (``custom_vjp``) reads the parts' cotangents and writes
    the row's, turned by the negative angle. A pattern outside
    :func:`rope_heads_blocks`'s contract raises."""
    parts = tuple((int(w), bool(t)) for w, t in parts)
    b, t, width = row.shape
    blocks = rope_heads_blocks(t, heads, parts)
    if blocks is None or width != heads * sum(w for w, _ in parts):
        raise ValueError(
            f"rope_heads: a row {row.shape} of {heads} heads of {parts} is "
            f"outside the kernel's contract (widths 64 or multiples of "
            f"{LANE}, one rotary width of 64 or {LANE}, tokens a multiple of "
            f"{_ROPE_ROW_TILES[-1]}, whole groups of heads)")
    lanes = rope_heads_plan(parts)[0]
    if cos.shape != (t, lanes) or sin.shape != cos.shape:
        raise ValueError(f"rope_heads: tables {cos.shape}, {sin.shape} for "
                         f"{t} tokens and groups of {lanes} lanes")
    row, cos, sin = _vary_together(row, cos, sin)
    return _rope_heads_fn(heads, parts, *blocks, interpret)(row, cos, sin)


# ---------------------------------------------------------------------------
# the norm of q and k and the rotation after it, on heads that are cut (where
# a norm stands between the projection and the rotation: one pass each way)
# ---------------------------------------------------------------------------

def norm_rope_blocks(n: int, t: int, width: int, dtype,
                     together: int = 0) -> Optional[tuple]:
    """(tokens a block, heads a block) of :func:`norm_rope` for ``n`` heads
    of ``width`` and ``dtype`` over ``t`` tokens, or None where its contract
    does not hold (a kernel's type: :func:`_typed`; ``width`` 128: a head is
    a tile's lanes; ``t`` a multiple of a row tile). ``together`` > 0: that
    many heads (one token's: the norm sums over them) stand in one block."""
    tt = next((r for r in _ROPE_ROW_TILES if t % r == 0), None)
    itemsize = _typed(dtype)
    if not itemsize or width != LANE or tt is None or n <= 0 \
            or (together and n % together):
        return None
    if together:
        while tt > _ROPE_ROW_TILES[-1] and \
                together * tt * width * itemsize > 4 * 2 ** 20:
            tt //= 2
        return tt, together
    return tt, max(m for m in range(1, n + 1)
                   if n % m == 0 and m * width <= _ROPE_BLOCK_LANES)


def _norm_rope_kernel(eps: float, denom: float, whole: bool, back: bool,
                      *refs):
    """One block of heads [heads, tokens, 128]: RMS-normed as the model's
    norms are (x rsqrt(sum of squares / denom + eps) rounded to x's type,
    times the learned scale, rounded), over each head or (``whole``) over
    all the block's heads, one token's whole vector, then turned. ``back``:
    the transpose. The cotangent is turned by the negative angle (the caller
    hands ``sin`` negated) and carried through the scale and the norm, whose
    inverse rms is computed again from x: dx = r gu - r^3 x sum(gu x) /
    denom; the scale's gradient, summed over the block's tokens, is the
    second result."""
    import jax.numpy as jnp
    from jax.lax import rsqrt
    f32 = jnp.float32
    if back:
        d_ref, x_ref, scale_ref, cos_ref, sin_ref, out_ref, dscale_ref = refs
    else:
        x_ref, scale_ref, cos_ref, sin_ref, out_ref = refs
    heads, dtype = x_ref.shape[0], x_ref.dtype

    def squares(i):
        xf = x_ref[i].astype(f32)
        return jnp.sum(xf * xf, axis=1, keepdims=True)

    def inverse_rms(ss):
        return rsqrt(ss * f32(1.0 / denom) + f32(eps))
    shared = inverse_rms(sum(squares(i) for i in range(heads))) \
        if whole else None

    def normed(i):
        """(x, inverse rms, x normed and rounded, the scale's row)."""
        xf = x_ref[i].astype(f32)
        r = shared if whole else inverse_rms(squares(i))
        row = i if whole else 0
        return (xf, r, (xf * r).astype(dtype).astype(f32),
                scale_ref[row:row + 1, :].astype(f32))

    def turned(x):
        return _rope_turn(x, cos_ref[...], sin_ref[...], LANE, (True, True))
    if not back:
        for i in range(heads):
            _xf, _r, u, scale = normed(i)
            out_ref[i] = turned((u * scale).astype(dtype).astype(f32)
                                ).astype(dtype)
        return
    dots, dscale = [], []
    for i in range(heads):          # gu parked in the result's block
        xf, _r, u, scale = normed(i)
        gw = turned(d_ref[i].astype(f32)).astype(dtype).astype(f32)
        dscale.append(jnp.sum(gw * u, axis=0, keepdims=True))
        gu = (gw * scale).astype(dtype)
        out_ref[i] = gu
        dots.append(jnp.sum(gu.astype(f32) * xf, axis=1, keepdims=True))
    if whole:
        dots = [sum(dots)] * heads
        dscale_ref[0, 0] = jnp.concatenate(dscale, axis=0)
    else:
        dscale_ref[0, 0] = sum(dscale)
    for i in range(heads):
        xf, r, _u, _scale = normed(i)
        out_ref[i] = (r * out_ref[i].astype(f32) - (r * r * r) * f32(
            1.0 / denom) * dots[i] * xf).astype(dtype)


@functools.lru_cache(maxsize=None)
def _norm_rope_fn(tt: int, m: int, eps: float, denom: float, whole: bool,
                  interpret: Optional[bool]):
    """The differentiable norm and rotation at one tiling, jitted once: the
    layers of a step share one trace and one lowering a direction."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pl = _pl()
    zero = np.int32(0)

    def call(back, x, *rest):
        n, t, width = x.shape
        rows = rest[1 if back else 0].shape[0]
        heads = pl.BlockSpec((m, tt, width), lambda ti, ni: (ni, ti, zero))
        scale = pl.BlockSpec((rows, width), lambda ti, ni: (zero, zero))
        table = pl.BlockSpec((tt, width), lambda ti, ni: (ti, zero))
        out_specs, out_shape = heads, _varying_like(x, x.shape, x.dtype)
        if back:        # and every block's part of the scale's gradient
            out_specs = [heads, pl.BlockSpec(
                (1, 1, rows, width), lambda ti, ni: (ti, ni, zero, zero))]
            out_shape = [out_shape, _varying_like(
                x, (t // tt, n // m, rows, width), jnp.float32)]
        name = "norm_rope_bwd" if back else "norm_rope_fwd"
        perfvars.note_kernel_build(name)
        return pl.pallas_call(
            functools.partial(_norm_rope_kernel, eps, denom, whole, back),
            grid=(t // tt, n // m),
            in_specs=[heads] * (2 if back else 1) + [scale, table, table],
            out_specs=out_specs, out_shape=out_shape,
            interpret=_interpret(interpret),
            compiler_params=_compiler_params(
                None, _rope_heads_vmem(tt, m, width, x.dtype.itemsize),
                "norm_rope", ("parallel", "parallel")),
            name=name)(x, *rest)

    @jax.custom_vjp
    def norm_and_turn(x, scale, cos, sin):
        return call(False, x, scale, cos, sin)

    def bwd(kept, d):
        x, scale, cos, sin = kept
        dx, dscale = call(True, d, x, scale, cos, -sin)
        return (dx, dscale.sum(axis=(0, 1)).astype(scale.dtype), None, None)
    norm_and_turn.defvjp(
        lambda *operands: (norm_and_turn(*operands), operands), bwd)
    return jax.jit(norm_and_turn)


def norm_rope(x, scale, cos, sin, *, eps: float, denom: float,
              interpret: Optional[bool] = None):
    """The norm of q or k and the rotary embedding after it in one pass, for
    heads that are cut already: ``x`` [..., tokens, 128], every last axis
    one rotary head, is RMS-normed as the model's norms of q and k are (x
    rsqrt(sum of squares / ``denom`` + ``eps``), float32 inside and rounded
    to x's type, times ``scale``, rounded again) and turned (``x cos +
    swap(x) sin`` in float32, rounded once, with :func:`rope_heads`'s tables
    [tokens, 128] and its meaning of ``swap``). ``scale`` [128]: the norm
    is over each head. ``scale`` [heads, 128] with x [batch, heads, tokens,
    128]: over a token's whole vector, all its heads (they stand in one
    block). One pass back too (``custom_vjp``): the cotangent is turned by
    the negative angle and carried through the norm, whose inverse rms is
    computed again from x (the only residuals are the operands), and the
    scale's gradient is summed in float32."""
    import jax.numpy as jnp
    *lead, t, width = x.shape
    n = math.prod(lead)
    scale = jnp.atleast_2d(scale)
    together = scale.shape[0] if scale.shape[0] > 1 else 0
    if scale.shape[1] != width or (together and (
            len(lead) != 2 or lead[1] != together)):
        raise ValueError(f"norm_rope: a scale {scale.shape} for {x.shape}")
    blocks = norm_rope_blocks(n, t, width, x.dtype.itemsize, together)
    if blocks is None or cos.shape != (t, width) or sin.shape != cos.shape:
        raise ValueError(
            f"norm_rope: {x.shape} with tables {cos.shape}, {sin.shape} is "
            f"outside the kernel's contract (heads of {LANE}, tokens a "
            f"multiple of {_ROPE_ROW_TILES[-1]}, tables [tokens, {LANE}])")
    operands = _vary_together(x.reshape(n, t, width), scale, cos, sin)
    return _norm_rope_fn(*blocks, float(eps), float(denom), bool(together),
                         interpret)(*operands).reshape(x.shape)
