"""The state-space (Mamba-2) scan's chunked form as one Pallas kernel each
way: what ``parallel/ssm.py:_chunked`` computes, with a chunk's decay matrix
``L``, the masked ``(L o C B^T)`` and the running state in VMEM alone.

A grid step is one chunk of ``length`` tokens for one block of
:data:`SCAN_HEAD_BLOCK` heads; the chunks run in order (backward: in reverse)
with every head's state [state, width] float32 carried in VMEM scratch, so
nothing of the recurrence over the chunks is written out but the state
before each chunk, which the backward pass reads. x, y and their cotangents
cross HBM as ``[batch, t, heads x width]`` rows, two heads of 64 to a tile of
128 lanes; a product for one head of a pair takes the pair's tile with the
other head's lanes zeroed, which costs the MXU what a 64-wide operand costs
it and moves no lane. B and C (one group) are read once a chunk; ``C B^T``
and ``B^T`` are built at a chunk's first block of heads and kept for the
others. Above the diagonal nothing is computed: a chunk's matrices are
walked in tiles of 128 x 128, the ones on the diagonal masked before the
exponential.

What is a scalar a head and token reaches the kernel twice. The sums of
``dt x A`` come head-major, ``[batch, heads, t]`` float32: a row of them is
a decay tile's columns, and turned in the kernel (a column, broadcast over
the lanes by the XLU) its rows. What multiplies x or y lane by lane (dt, the
decay from the chunk's start, to its end, and dt times that) is spread over
a head's 64 lanes by the MXU, which has the time: XLA packs each float32
as three bfloat16 pieces (:func:`_packed`), and a 0/1 matrix sums a head's
three into every lane of the head, exactly. (Broadcast lane by lane on the
XLU those were three quarters of its work and the kernel's bound.)

Precisions are ``_chunked``'s: the sums of ``dt x A``, the exponentials, the
decays and the states float32; MXU operands the input's type (float32
operands at `Precision.HIGHEST`) with float32 accumulation; y rounded once,
after the skip term. The backward kernel computes ``L`` again from the sums
and takes the exponents' gradient from ``dy . y`` and ``x dt . d(x dt)`` (a
row's and a column's sum of ``dM o M`` are those), in float32.
"""

from __future__ import annotations

import functools
from typing import Optional

from .. import perfvars
from .pallas_kernels import (LANE, _attn_precision, _compiler_params,
                             _interpret, _pl, _pltpu, _typed, _vary_together,
                             _varying_like)

SCAN_HEAD_WIDTH = LANE // 2     # two heads to a tile of lanes
SCAN_HEAD_BLOCK = 8             # heads a grid step: a float32 tile's sublanes
_SCAN_LENGTHS = (128, 256)      # a chunk: one or two tiles of the diagonal
_SCAN_STATES = (128, 256)       # beyond these Mosaic's own VMEM is not counted


def ssm_scan_selected(heads: int, width: int, state: int, length: int,
                      dtype) -> bool:
    """Whether :func:`ssm_scan` takes chunks of ``length`` tokens for
    ``heads`` heads of ``width`` and ``dtype`` over a state of ``state``:
    the contract, decided from the shapes and the type."""
    return (bool(_typed(dtype)) and width == SCAN_HEAD_WIDTH
            and heads % SCAN_HEAD_BLOCK == 0 and state in _SCAN_STATES
            and length in _SCAN_LENGTHS)


def _scan_vmem(heads: int, state: int, length: int, itemsize: int) -> int:
    """The backward kernel's blocks (twice: pipelined) and scratch."""
    wide = SCAN_HEAD_BLOCK * SCAN_HEAD_WIDTH
    blocks = 3 * length * wide * itemsize + 4 * length * state * itemsize \
        + 3 * SCAN_HEAD_BLOCK * length * 4 + state * wide * 4 \
        + length * LANE * 2 + _spread().nbytes
    scratch = heads * SCAN_HEAD_WIDTH * state * 4 + 2 * length * length * 4 \
        + state * length * itemsize + 2 * length * state * 4
    return 2 * blocks + scratch


# What multiplies a head's lanes, by its place among a token's packed pieces
# (`_packed`): dt, exp(sums from the chunk's start), dt x exp(sums to the
# chunk's end), and that decay alone.
_DT, _GROWN, _DT_TO_END, _TO_END = range(4)
_PIECES = 3     # bfloat16 pieces a float32: 3 x 8 bits are its 24, exactly


def _packed(dt, seg, length: int):
    """[batch, t, blocks x 128] bfloat16: for each token and block of 8
    heads the four scalars above, each as three bfloat16 pieces that sum to
    the float32 exactly, at lane (quantity x 3 + piece) x 8 + head. A 0/1
    matrix on the MXU then spreads a head's scalar over its lanes (bfloat16
    products, float32 sums of at most three pieces: exact)."""
    import jax
    import jax.numpy as jnp
    bsz, t, heads = dt.shape
    chunks = seg.reshape(bsz, t // length, length, heads)
    to_end = jnp.exp(chunks[:, :, -1:] - chunks).reshape(dt.shape)
    rest = jnp.stack([dt, jnp.exp(seg), dt * to_end, to_end], axis=2)
    pieces = []
    for _ in range(_PIECES):    # `reduce_precision`: a conversion to
        #                         bfloat16 and back may be dropped (PR 25)
        piece = jax.lax.reduce_precision(rest, exponent_bits=8,
                                         mantissa_bits=7)
        pieces.append(piece.astype(jnp.bfloat16))
        rest = rest - piece
    blocks = heads // SCAN_HEAD_BLOCK
    packed = jnp.stack(pieces, axis=3).reshape(
        bsz, t, 4, _PIECES, blocks, SCAN_HEAD_BLOCK).transpose(
            0, 1, 4, 2, 3, 5).reshape(bsz, t, blocks, -1)
    return jnp.pad(packed, ((0, 0), (0, 0), (0, 0),
                            (0, LANE - packed.shape[-1]))).reshape(
                                bsz, t, blocks * LANE)


@functools.lru_cache(maxsize=None)
def _spread():
    """[4, 128, heads x 64] bfloat16 (numpy) 0/1 matrices for a block of
    heads: a token's packed pieces times ``spread[q]`` is quantity q, each
    head's scalar over its 64 lanes."""
    import numpy as np
    import jax.numpy as jnp
    hb, w = SCAN_HEAD_BLOCK, SCAN_HEAD_WIDTH
    spread = np.zeros((4, LANE, hb * w), np.float32)
    for q in range(4):
        for piece in range(_PIECES):
            for g in range(hb):
                row = (q * _PIECES + piece) * hb + g
                spread[q, row, g * w:(g + 1) * w] = 1
    return spread.astype(jnp.bfloat16)


class _Chunk:
    """What both kernels read of a grid step's operands: a token's packed
    scalars spread over a pair of heads' lanes, the sums as rows and as
    columns, and the products at the operands' precision."""

    def __init__(self, packed_ref, seg_ref, spread_ref, dtype):
        import jax
        import jax.numpy as jnp
        self.f32 = jnp.float32
        self.prec = _attn_precision(dtype)  # float32 operands: HIGHEST
        self.packed = packed_ref[0]                     # [tokens, 128]
        self.spread_ref = spread_ref
        self.seg_rows = seg_ref[0]                      # [heads, tokens]
        self.length = self.seg_rows.shape[1]
        self.seg_cols = self.seg_rows.T
        lane = jax.lax.broadcasted_iota(jnp.int32, (self.length, LANE), 1)
        self.first = lane < SCAN_HEAD_WIDTH             # a pair's first head
        tile = (LANE, LANE)
        self.seen = jax.lax.broadcasted_iota(jnp.int32, tile, 0) \
            >= jax.lax.broadcasted_iota(jnp.int32, tile, 1)

    def pair(self, q: int, j: int):
        """[tokens, 128] float32: quantity ``q`` of heads 2j and 2j + 1,
        each over its 64 lanes."""
        import jax
        return jax.lax.dot_general(
            self.packed, self.spread_ref[q, :, _tile(j)],
            (((1,), (0,)), ((), ())), preferred_element_type=self.f32)

    def of_head(self, tile, k: int):
        """A pair's tile with the other head's lanes zeroed."""
        import jax.numpy as jnp
        zero = jnp.zeros((), tile.dtype)
        first = self.first[:tile.shape[0]]
        return jnp.where(first, tile, zero) if k == 0 \
            else jnp.where(first, zero, tile)

    def decay(self, head: int, i: int, k: int):
        """Tile (i, k) of a head's ``L``: exp of the sums from token s to
        token t, masked before the exponential on the diagonal (k == i)."""
        import jax.numpy as jnp
        gap = self.seg_cols[_tile(i), head:head + 1] \
            - self.seg_rows[head:head + 1, _tile(k)]
        if i == k:
            gap = jnp.where(self.seen, gap, -jnp.inf)
        return jnp.exp(gap)

    def dot(self, a, b, dims=(((1,), (0,)), ((), ()))):
        import jax
        return jax.lax.dot_general(a, b, dims, precision=self.prec,
                                   preferred_element_type=self.f32)

    def dot_nt(self, a, b):         # a b^T
        return self.dot(a, b, (((1,), (1,)), ((), ())))

    def dot_tn(self, a, b):         # a^T b
        return self.dot(a, b, (((0,), (0,)), ((), ())))


def _tile(i: int) -> slice:
    return slice(i * LANE, (i + 1) * LANE)


def _scan_fwd_kernel(x_ref, packed_ref, seg_ref, b_ref, c_ref, d_ref,
                     spread_ref, y_ref, before_ref, state, scores, b_t):
    """One chunk of one block of heads, forward. Scratch: every block's
    ``state`` [blocks, state, heads x width] float32 after the chunk before,
    the chunk's ``scores`` = C B^T and ``b_t`` = B^T."""
    import jax.numpy as jnp
    pl = _pl()
    ci, hi = pl.program_id(1), pl.program_id(2)
    dtype = x_ref.dtype
    ch = _Chunk(packed_ref, seg_ref, spread_ref, dtype)
    b, c = b_ref[0], c_ref[0]

    @pl.when(hi == 0)
    def _first_block_of_heads():
        scores[...] = ch.dot_nt(c, b)
        b_t[...] = b.T

    @pl.when(ci == 0)
    def _first_chunk():
        state[hi] = jnp.zeros(state.shape[1:], jnp.float32)

    for j in range(SCAN_HEAD_BLOCK // 2):
        lanes = _tile(j)
        xf = x_ref[0, :, lanes].astype(ch.f32)
        grown = ch.pair(_GROWN, j)
        before = state[hi, :, lanes]
        before_ref[0, 0, :, lanes] = before
        y = ch.dot(c, before.astype(dtype)) * grown + d_ref[:, lanes] * xf
        y = [y[_tile(i)] for i in range(ch.length // LANE)]
        rows_in = (xf * ch.pair(_DT, j)).astype(dtype)
        for k in range(2):
            mine = ch.of_head(rows_in, k)
            for i in range(len(y)):
                for s in range(i + 1):
                    m = (scores[_tile(i), _tile(s)]
                         * ch.decay(2 * j + k, i, s)).astype(dtype)
                    y[i] = y[i] + ch.dot(m, mine[_tile(s)])
        for i, tile in enumerate(y):
            y_ref[0, _tile(i), lanes] = tile.astype(dtype)
        state[hi, :, lanes] = before * grown[ch.length - 1:] + ch.dot(
            b_t[...], (xf * ch.pair(_DT_TO_END, j)).astype(dtype))


def _scan_bwd_kernel(x_ref, packed_ref, seg_ref, b_ref, c_ref, d_ref,
                     spread_ref, before_ref, dy_ref, dx_ref,
                     ddt_ref, dseg_ref, db_ref, dc_ref, dd_ref, dstate,
                     scores, dscores, b_t, db_acc, dc_acc):
    """One chunk of one block of heads, backward; the chunks come last
    first. Scratch: every block's ``dstate``, the cotangent of the state
    after this chunk; ``scores`` and ``b_t`` as forward; ``dscores``, dB and
    dC summed over the chunk's heads in float32."""
    import jax
    import jax.numpy as jnp
    pl = _pl()
    step, hi = pl.program_id(1), pl.program_id(2)
    dtype, f32 = x_ref.dtype, jnp.float32
    ch = _Chunk(packed_ref, seg_ref, spread_ref, dtype)
    length, tiles = ch.length, ch.length // LANE
    b, c = b_ref[0], c_ref[0]

    @pl.when(hi == 0)
    def _first_block_of_heads():
        scores[...] = ch.dot_nt(c, b)
        dscores[...] = jnp.zeros(dscores.shape, f32)
        b_t[...] = b.T
        db_acc[...] = jnp.zeros(db_acc.shape, f32)
        dc_acc[...] = jnp.zeros(dc_acc.shape, f32)

    @pl.when(step == 0)
    def _last_chunk():
        dstate[hi] = jnp.zeros(dstate.shape[1:], f32)

    head_lane = jax.lax.broadcasted_iota(
        jnp.int32, (length, SCAN_HEAD_BLOCK), 1)
    d_dt = jnp.zeros((length, SCAN_HEAD_BLOCK), f32)    # through x dt alone
    d_seg = jnp.zeros((length, SCAN_HEAD_BLOCK), f32)
    d_last = jnp.zeros((1, SCAN_HEAD_BLOCK), f32)       # the whole sum's

    def by_head(tile, into, j):
        """A pair's tile summed over each head's lanes, into the two
        heads' columns of ``into`` [rows, heads]."""
        lane = head_lane[:tile.shape[0]]
        for k in range(2):
            total = jnp.sum(ch.of_head(tile, k), axis=1, keepdims=True)
            into = jnp.where(lane == 2 * j + k, total, into)
        return into

    for j in range(SCAN_HEAD_BLOCK // 2):
        lanes = _tile(j)
        xf = x_ref[0, :, lanes].astype(f32)
        dy = dy_ref[0, :, lanes]
        dyf = dy.astype(f32)
        dt, grown = ch.pair(_DT, j), ch.pair(_GROWN, j)
        whole = grown[length - 1:]
        rows_in = (xf * dt).astype(dtype)
        to_state = (xf * ch.pair(_DT_TO_END, j)).astype(dtype)
        before = before_ref[0, 0, :, lanes]
        before_r = before.astype(dtype)
        dafter = dstate[hi, :, lanes]
        dafter_r = dafter.astype(dtype)
        # what the chunk read of the state before it, and what it added
        y = ch.dot(c, before_r) * grown
        dread = (dyf * grown).astype(dtype)
        dc_acc[...] += ch.dot_nt(dread, before_r)
        db_acc[...] += ch.dot_nt(to_state, dafter_r)
        added = ch.dot(b_t[...], to_state)
        d_last = by_head(jnp.sum(
            dafter * before * whole + dafter_r.astype(f32) * added, axis=0,
            keepdims=True), d_last, j)
        dstate[hi, :, lanes] = dafter * whole + ch.dot_tn(c, dread)
        d_to_state = ch.dot(b, dafter_r)
        # inside the chunk, a head at a time, tile by tile
        y = [y[_tile(i)] for i in range(tiles)]
        d_in = [jnp.zeros((LANE, LANE), f32) for _ in range(tiles)]
        for k in range(2):
            mine, rows_k = ch.of_head(dy, k), ch.of_head(rows_in, k)
            for i in range(tiles):
                for s in range(i + 1):
                    decay = ch.decay(2 * j + k, i, s)
                    m = (scores[_tile(i), _tile(s)] * decay).astype(dtype)
                    y[i] = y[i] + ch.dot(m, rows_k[_tile(s)])
                    d_in[s] = d_in[s] + ch.dot_tn(m, mine[_tile(i)])
                    dscores[_tile(i), _tile(s)] += decay * ch.dot_nt(
                        mine[_tile(i)], rows_in[_tile(s)])
        y, d_in = jnp.concatenate(y, axis=0), jnp.concatenate(d_in, axis=0)
        d_rows = d_in + d_to_state * ch.pair(_TO_END, j)    # d(x dt)
        dx_ref[0, :, lanes] = (d_rows * dt + d_ref[:, lanes] * dyf).astype(
            dtype)
        dd_ref[0, 0, :, lanes] = jnp.sum(dyf * xf, axis=0, keepdims=True)
        d_dt = by_head(xf * d_rows, d_dt, j)
        # the exponents: a token's sum gains dy . y as a target and loses
        # (x dt) . d(x dt) as a source, with the operands the products saw:
        # term for term the two cancel over a chunk as dM o M's do
        d_seg = by_head(dyf * y - rows_in.astype(f32) * d_in
                        - to_state.astype(f32) * d_to_state, d_seg, j)
    row = jax.lax.broadcasted_iota(jnp.int32, d_seg.shape, 0)
    d_seg = jnp.where(row == length - 1, d_seg + d_last, d_seg)
    ddt_ref[0] = d_dt.T
    dseg_ref[0] = d_seg.T

    @pl.when(hi == pl.num_programs(2) - 1)
    def _last_block_of_heads():
        for i in range(tiles):
            for s in range(i + 1):
                ds = dscores[_tile(i), _tile(s)].astype(dtype)
                dc_acc[_tile(i), :] += ch.dot(ds, b[_tile(s)])
                db_acc[_tile(s), :] += ch.dot_tn(ds, c[_tile(i)])
        db_ref[0] = db_acc[...].astype(dtype)
        dc_ref[0] = dc_acc[...].astype(dtype)


@functools.lru_cache(maxsize=None)
def _ssm_scan_fn(length: int, interpret: Optional[bool]):
    """The differentiable scan at one chunk length, jitted once: the layers
    of a step share one trace and one lowering a direction."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pl, pltpu = _pl(), _pltpu()
    zero = np.int32(0)
    wide = SCAN_HEAD_BLOCK * SCAN_HEAD_WIDTH

    def call(back: bool, x, packed, seg, b, c, d, *rest):
        bsz, t, inner = x.shape
        heads, n, nc = seg.shape[1], b.shape[2], t // length
        last = np.int32(nc - 1)

        def chunk(ci):      # backward walks the chunks last first
            return last - ci if back else ci
        rows = pl.BlockSpec((1, length, wide),
                            lambda bi, ci, hi: (bi, chunk(ci), hi))
        pieces = pl.BlockSpec((1, length, LANE),
                              lambda bi, ci, hi: (bi, chunk(ci), hi))
        scalars = pl.BlockSpec((1, SCAN_HEAD_BLOCK, length),
                               lambda bi, ci, hi: (bi, hi, chunk(ci)))
        group = pl.BlockSpec((1, length, n),
                             lambda bi, ci, hi: (bi, chunk(ci), zero))
        skip = pl.BlockSpec((1, wide), lambda bi, ci, hi: (zero, hi))
        states = pl.BlockSpec((1, 1, n, wide),
                              lambda bi, ci, hi: (bi, chunk(ci), zero, hi))
        spread = jnp.asarray(_spread())
        ones = pl.BlockSpec(spread.shape,
                            lambda bi, ci, hi: (zero, zero, zero))
        kept = [pltpu.VMEM((heads // SCAN_HEAD_BLOCK, n, wide), jnp.float32),
                pltpu.VMEM((length, length), jnp.float32)]
        transposed = pltpu.VMEM((n, length), x.dtype)
        in_specs = [rows, pieces, scalars, group, group, skip, ones]
        if back:
            sums = pl.BlockSpec((1, 1, 1, wide),
                                lambda bi, ci, hi: (bi, chunk(ci), zero, hi))
            in_specs += [states, rows]
            out_specs = [rows, scalars, scalars, group, group, sums]
            out_shape = [_varying_like(x, x.shape, x.dtype),
                         _varying_like(x, seg.shape, jnp.float32),
                         _varying_like(x, seg.shape, jnp.float32),
                         _varying_like(x, b.shape, b.dtype),
                         _varying_like(x, c.shape, c.dtype),
                         _varying_like(x, (bsz, nc, 1, inner), jnp.float32)]
            scratch = kept + [pltpu.VMEM((length, length), jnp.float32),
                              transposed,
                              pltpu.VMEM((length, n), jnp.float32),
                              pltpu.VMEM((length, n), jnp.float32)]
        else:
            out_specs = [rows, states]
            out_shape = [_varying_like(x, x.shape, x.dtype),
                         _varying_like(x, (bsz, nc, n, inner), jnp.float32)]
            scratch = kept + [transposed]
        name = "ssm_scan_bwd" if back else "ssm_scan_fwd"
        perfvars.note_kernel_build(name)
        return pl.pallas_call(
            _scan_bwd_kernel if back else _scan_fwd_kernel,
            grid=(bsz, nc, heads // SCAN_HEAD_BLOCK),
            in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch, interpret=_interpret(interpret),
            compiler_params=_compiler_params(
                None, _scan_vmem(heads, n, length, x.dtype.itemsize),
                "ssm_scan", ("parallel", "arbitrary", "arbitrary")),
            name=name)(x, packed, seg, b, c, d,
                       _vary_together(x, spread)[1], *rest)

    def operands(x, dt, seg, b, c, d):
        """The kernels' operands: the scalars a head and token packed for
        the MXU, and the sums head-major beside them."""
        return x, _packed(dt, seg, length), seg.transpose(0, 2, 1), b, c, d

    @jax.custom_vjp
    def scan(x, dt, seg, b, c, d):
        return call(False, *operands(x, dt, seg, b, c, d))[0]

    def fwd(*given):
        y, before = call(False, *operands(*given))
        return y, given + (before,)

    def bwd(kept, dy):
        *given, before = kept
        dx, ddt, dseg, db, dc, dd = call(True, *operands(*given), before, dy)
        return (dx, ddt.transpose(0, 2, 1), dseg.transpose(0, 2, 1), db, dc,
                dd.sum(axis=(0, 1)))
    scan.defvjp(fwd, bwd)
    return jax.jit(scan)


def ssm_scan(x, dt, a, b, c, d, *, length: int,
             interpret: Optional[bool] = None):
    """y [batch, t, heads, width] of the recurrence S_t = exp(dt_t A)
    S_{t-1} + (dt_t x_t) B_t^T, y_t = S_t C_t + D x_t in its chunked form,
    skip term included and rounded once to x's type: x [batch, t, heads,
    64], dt [batch, t, heads] float32 (> 0), a [heads] float32 (< 0), b and
    c [batch, t, state], d [heads] float32, t a multiple of ``length``.
    The cumulative sums of ``dt x A`` inside each chunk are taken here, in
    front of the kernel, and their gradient (A's with it) is XLA's; the
    backward pass (``custom_vjp``) is one kernel that keeps the operands
    and the state before each chunk [batch, chunks, state, heads x 64]
    float32, and computes the decay matrices again."""
    import jax.numpy as jnp
    bsz, t, heads, width = x.shape
    if not ssm_scan_selected(heads, width, b.shape[-1], length, x.dtype) \
            or t % length:
        raise ValueError(
            f"ssm_scan: x {x.shape} {x.dtype} over a state of {b.shape[-1]} "
            f"in chunks of {length} is outside the kernel's contract (heads "
            f"of {SCAN_HEAD_WIDTH} in blocks of {SCAN_HEAD_BLOCK}, a state "
            f"of {_SCAN_STATES}, a chunk of {_SCAN_LENGTHS}, t a multiple "
            f"of it)")
    seg = jnp.cumsum((dt * a).reshape(bsz, t // length, length, heads),
                     axis=2).reshape(bsz, t, heads)
    operands = _vary_together(
        x.reshape(bsz, t, heads * width), dt, seg, b, c,
        jnp.repeat(d, width)[None])
    return _ssm_scan_fn(length, interpret)(*operands).reshape(x.shape)
