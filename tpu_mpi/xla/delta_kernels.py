"""The gated delta rule's chunked scan as one Pallas kernel each way: what
``parallel/delta.py:_chunked`` computes, with a chunk's [chunk x chunk]
arrays (the decays, ``A``, its inverse ``T``, the masked scores), ``W``,
``U0``, the decayed keys and the running state in VMEM alone.

A grid step is one chunk of :data:`DELTA_CHUNK` = 64 tokens for one key head
and its :data:`DELTA_PAIR` = 2 value heads; the chunks run in order
(backward: in reverse) with the pair's states [2, key width, value width]
float32 carried in VMEM scratch, so nothing of the recurrence over the
chunks is written out but the state before each chunk, which the backward
pass reads. The two value heads of a key head are STACKED: their tokens are
the 128 rows of every array a step forms (head 0's 64, then head 1's), and
what is [chunk x chunk] a head is one [128 x 128] array, block-diagonal by
head. A product of two such arrays is one full pass of the MXU where a
head's alone would fill a quarter of it, ``K K^T`` and ``Q K^T`` are
computed once for both, and every elementwise pass fills its lanes. q, k, v,
o and their cotangents cross HBM as the ``[batch, t, heads x width]`` rows
they are outside: value head h reads key head h // 2 through the index map,
nothing is repeated in HBM.

What is a scalar a head and token (the decay sums ``gamma`` inside a chunk,
``beta``, exp(gamma), beta exp(gamma), the decay to the chunk's end and the
chunk's whole decay) is prepared by XLA in front of the kernel
(:func:`_rows`: [batch, t, value heads] float32 arrays, a thousandth of the
operands) as one [8, 128] tile a step, a quantity a row with the pair's
tokens on the lanes: a row is a decay tile's columns, and the tile turned in
the kernel (XLU) gives the columns that scale a token's row. The backward
kernel returns that tile's cotangent, a quantity a row, and the gradient of
g and beta through the sums and exponentials is XLA's.

Precisions are ``_chunked``'s: the decay sums, their exponentials, ``A``,
the inverse and the state float32, the inverse's products (and its
gradient's) float32 operands at `HIGHEST`; every other product takes
operands of the input's type (float32 operands at `HIGHEST`) and accumulates
in float32, rounding where ``_chunked`` rounds (the finished inverse, the
state, ``W`` and ``U`` to the input's type before their products) and
nowhere else; o is rounded once. The inverse (`_Chunk._inverse`) forms no
power of ``A``, so keys that repeat cost no digits: blocks of
:data:`DELTA_SOLVE` = 16 tokens by forward substitution in float32 on the
VPU, then ``_unit_lower_inverse``'s halves, 16 to 32 to 64, two products a
round over the rows a round changes. (On the chip at the cell's shape, a
layer forward and backward: all six rounds by halves 15.4 ms, blocks of 8,
16 and 32 solved first 12.1, 11.6 and 12.6, the substitution's lane
broadcasts against the rounds' products; with no inverse at all 6.2: my chip
runs, PR 46.) The backward kernel computes a chunk's arrays again from the
operands and the kept state, and sums the pair's dq and dk before it writes
them.
"""

from __future__ import annotations

import functools
from typing import Optional

from .. import perfvars
from .pallas_kernels import (LANE, SUBLANE, _attn_precision, _compiler_params,
                             _interpret, _pl, _pltpu, _typed, _vary_together,
                             _varying_like)

DELTA_WIDTH = LANE      # a head's key width and its value width
DELTA_CHUNK = 64        # tokens a chunk: a pair of heads' are a tile's 128
DELTA_PAIR = 2          # value heads a key head, stacked in one step
DELTA_KEYS = 2          # key heads a grid step, where they come in twos
DELTA_SOLVE = 16        # tokens a block that forward substitution inverts

# the rows of a step's tile of scalars (`_rows`)
_GAMMA, _BETA, _GROWN, _BETA_GROWN, _TO_END, _WHOLE = range(6)


def delta_scan_selected(value_heads: int, key_heads: int, key_width: int,
                        value_width: int, chunk: int, dtype,
                        decay_width: int = 1) -> bool:
    """Whether :func:`delta_scan` takes ``value_heads`` heads of
    ``value_width`` over ``key_heads`` heads of ``key_width`` in chunks of
    ``chunk`` tokens of ``dtype``, decayed by ``decay_width`` numbers a head
    and token (1, or one a key channel): the contract, decided from the
    shapes and the type. The kernels take a token's decay as scalars in a
    tile of rows (`_rows`): a decay a channel is refused."""
    return (bool(_typed(dtype)) and value_heads == DELTA_PAIR * key_heads
            and key_width == DELTA_WIDTH and value_width == DELTA_WIDTH
            and chunk == DELTA_CHUNK and decay_width == 1)


def _rows(g, beta):
    """[batch, key heads, chunks x 8, 128] float32: for each chunk and key
    head one tile whose row r is quantity r (above) of the pair's tokens,
    head 0's on lanes 0-63 and head 1's on 64-127; rows 5 and 6 hold the
    chunk's whole decay, head 0's and head 1's, on every lane; row 7 is
    zeros. g and beta [batch, t, value heads] float32, t a multiple of the
    chunk."""
    import jax.numpy as jnp
    bsz, t, hv = g.shape
    nc, hk = t // DELTA_CHUNK, hv // DELTA_PAIR
    g, beta = (a.reshape(bsz, nc, DELTA_CHUNK, hk, DELTA_PAIR)
               for a in (g, beta))
    gamma = jnp.cumsum(g, axis=2)
    last = gamma[:, :, -1:]
    grown = jnp.exp(gamma)
    by_token = jnp.stack([gamma, beta, grown, beta * grown,
                          jnp.exp(last - gamma)], axis=0)
    # [5, b, c, l, hk, 2] -> [b, hk, c, 5, 2 x l]
    by_token = by_token.transpose(1, 4, 2, 0, 5, 3).reshape(
        bsz, hk, nc, _WHOLE, LANE)
    whole = jnp.exp(last[:, :, 0]).transpose(0, 2, 1, 3)    # [b, hk, c, 2]
    rows = jnp.concatenate(
        [by_token,
         jnp.broadcast_to(whole[..., None], whole.shape + (LANE,)),
         jnp.zeros((bsz, hk, nc, SUBLANE - _WHOLE - DELTA_PAIR, LANE),
                   jnp.float32)], axis=3)
    return rows.reshape(bsz, hk, nc * SUBLANE, LANE)


class _Chunk:
    """What both kernels compute of a grid step's operands: the pair's
    stacked rows, the scalars as rows and as columns, the [128 x 128] arrays
    of the chunk, ``W`` and ``U0``, and the products at the operands'
    precision."""

    def __init__(self, q, k, v, rows):
        """q and k [64, dk], v [64, 2 x dv] (the pair's, side by side) and
        the tile of scalars [8, 128]."""
        import jax
        import jax.numpy as jnp
        self.f32 = f32 = jnp.float32
        self.dtype = dtype = v.dtype
        self.prec = _attn_precision(dtype)      # float32 operands: HIGHEST
        half = DELTA_CHUNK
        self.k = jnp.concatenate([k, k], axis=0)            # [128, dk]
        self.q = jnp.concatenate([q, q], axis=0)
        self.v = _stacked(v)                                # [128, dv]
        self.cols = cols = rows.T                           # [128, 8]
        tile = (LANE, LANE)
        i = jax.lax.broadcasted_iota(jnp.int32, tile, 0)
        j = jax.lax.broadcasted_iota(jnp.int32, tile, 1)
        self.apart = apart = i ^ j      # < 2^n: in one block of 2^n tokens
        self.seen = jnp.logical_and(apart < half, j <= i)
        self.strict = jnp.logical_and(apart < half, j < i)
        gap = cols[:, _GAMMA:_GAMMA + 1] - rows[_GAMMA:_GAMMA + 1, :]
        self.decay = jnp.exp(jnp.where(self.seen, gap, -jnp.inf))
        self.kk = self.dot_nt(self.k, self.k)               # both heads'
        self.qk = self.dot_nt(self.q, self.k)
        self.beta = cols[:, _BETA:_BETA + 1]
        self.inverse = self._inverse(jnp.where(
            self.strict, self.beta * self.decay * self.kk, 0.0))   # float32
        self.inverse_r = self.inverse.astype(dtype)
        kf, vf = self.k.astype(f32), self.v.astype(f32)
        self.kb = (kf * cols[:, _BETA_GROWN:_BETA_GROWN + 1]).astype(dtype)
        self.vb = (vf * self.beta).astype(dtype)
        self.w = self.dot(self.inverse_r, self.kb).astype(dtype)
        self.u0 = self.dot(self.inverse_r, self.vb)
        self.kd = (kf * cols[:, _TO_END:_TO_END + 1]).astype(dtype)
        self.qg = (self.q.astype(f32)
                   * cols[:, _GROWN:_GROWN + 1]).astype(dtype)
        self.scores = self.qk * self.decay                  # float32
        self.whole = [rows[_WHOLE + h:_WHOLE + h + 1, :]
                      for h in range(DELTA_PAIR)]   # [1, 128], one value

    def _inverse(self, a):
        """``(I + a)^-1`` of the strictly lower-triangular, block-diagonal
        ``a``. Blocks of :data:`DELTA_SOLVE` tokens by forward substitution,
        row k of a block's inverse taken from every row under it in turn
        (float32 on the VPU, :data:`DELTA_SOLVE` - 1 steps for all the
        blocks of a tile of rows at once); then by halves, as
        `parallel.delta._unit_lower_inverse` has it: T_2s = T_s - T_s a_s
        T_s with a_s what ``a`` holds inside a block of 2 s tokens and
        outside its two blocks of s. a_s, and so the round's whole
        correction, has rows in the second block of each pair alone: the
        two products take those as their left operands and leave the other
        half out. No power of ``a`` is formed either way."""
        import jax
        import jax.numpy as jnp
        n, size = a.shape[0], DELTA_SOLVE
        row = jax.lax.broadcasted_iota(jnp.int32, (size, n), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (size, n), 1)
        blocks = []
        for at in range(0, n, size):
            below, solved = a[at:at + size], (row + at == lane).astype(a.dtype)
            for k in range(size - 1):
                solved = solved - below[:, at + k:at + k + 1] * solved[k:k + 1]
            blocks.append(solved)
        t = jnp.concatenate(blocks, axis=0)
        while size < DELTA_CHUNK:
            between = jnp.logical_and(self.apart >= size,
                                      self.apart < 2 * size)
            second = range(size, n, 2 * size)   # where a pair's second starts

            def lower(x):       # [n / 2, n]: the second blocks' rows
                return jnp.concatenate([x[at:at + size] for at in second],
                                       axis=0)

            def woven(first, low):      # `low` under each first block's rows
                return jnp.concatenate(
                    [part for k, at in enumerate(second)
                     for part in (first[at - size:at],
                                  low[k * size:(k + 1) * size])], axis=0)
            coupled = woven(jnp.zeros_like(t), self.dot_exact(
                lower(jnp.where(between, a, 0.0)), t))
            t = woven(t, lower(t) - self.dot_exact(lower(t), coupled))
            size *= 2
        return t

    def dot(self, a, b, dims=(((1,), (0,)), ((), ()))):
        import jax
        return jax.lax.dot_general(a, b, dims, precision=self.prec,
                                   preferred_element_type=self.f32)

    def dot_nt(self, a, b):         # a b^T
        return self.dot(a, b, (((1,), (1,)), ((), ())))

    def dot_tn(self, a, b):         # a^T b
        return self.dot(a, b, (((0,), (0,)), ((), ())))

    def dot_exact(self, a, b, dims=(((1,), (0,)), ((), ()))):
        """Of two float32 arrays, as exact as float32 is."""
        import jax
        return jax.lax.dot_general(a, b, dims,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=self.f32)

    def written(self, before_r):
        """``U`` [128, dv] float32, the values the chunk writes, from the
        pair's states before it (of the input's type)."""
        import jax.numpy as jnp
        return self.u0 - jnp.concatenate(
            [self.dot(self.w[_head(h)], before_r[h])
             for h in range(DELTA_PAIR)], axis=0)


def _head(h: int) -> slice:
    """Head h's rows of a stacked array."""
    return slice(h * DELTA_CHUNK, (h + 1) * DELTA_CHUNK)


def _stacked(wide):
    """[128, width] from a pair's [64, 2 x width], head 1 under head 0."""
    import jax.numpy as jnp
    return jnp.concatenate([wide[:, :DELTA_WIDTH], wide[:, DELTA_WIDTH:]],
                           axis=0)


def _of(ref, at: int, width: int):
    """Key head ``at``'s [64, width] of a step's block of rows."""
    return ref[0, :, at * width:(at + 1) * width]


def _delta_fwd_kernel(q_ref, k_ref, v_ref, rows_ref, o_ref, before_ref,
                      state):
    """One chunk of a step's key heads (each with its pair of value heads),
    forward. Scratch: the ``state`` [value heads, dk, dv] float32 after the
    chunk before."""
    import jax.numpy as jnp
    pl = _pl()
    wide = DELTA_PAIR * DELTA_WIDTH

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros(state.shape, jnp.float32)

    for at in range(rows_ref.shape[1]):     # independent of one another
        ch = _Chunk(_of(q_ref, at, DELTA_WIDTH), _of(k_ref, at, DELTA_WIDTH),
                    _of(v_ref, at, wide), rows_ref[0, at])
        dtype = ch.dtype
        heads = [DELTA_PAIR * at + h for h in range(DELTA_PAIR)]
        before = [state[h] for h in heads]
        before_r = [s.astype(dtype) for s in before]
        u = ch.written(before_r).astype(dtype)
        o = ch.dot(ch.scores.astype(dtype), u)
        for h, head in enumerate(heads):
            rows = _head(h)
            before_ref[0, 0, head] = before[h]
            o_ref[0, :, head * DELTA_WIDTH:(head + 1) * DELTA_WIDTH] = (
                o[rows] + ch.dot(ch.qg[rows], before_r[h])).astype(dtype)
            state[head] = ch.whole[h] * before[h] \
                + ch.dot_tn(ch.kd[rows], u[rows])


def _delta_bwd_kernel(q_ref, k_ref, v_ref, rows_ref, before_ref, do_ref,
                      dq_ref, dk_ref, dv_ref, drows_ref, dstate):
    """One chunk of a step's key heads, backward; the chunks come last
    first. Scratch: ``dstate`` [value heads, dk, dv] float32, the cotangent
    of the states after this chunk."""
    import jax.numpy as jnp
    pl = _pl()
    wide = DELTA_PAIR * DELTA_WIDTH

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[...] = jnp.zeros(dstate.shape, jnp.float32)

    for at in range(rows_ref.shape[1]):     # independent of one another
        ch = _Chunk(_of(q_ref, at, DELTA_WIDTH), _of(k_ref, at, DELTA_WIDTH),
                    _of(v_ref, at, wide), rows_ref[0, at])
        heads = [DELTA_PAIR * at + h for h in range(DELTA_PAIR)]
        d_q, d_k, d_v, d_rows, d_before = _pair_backward(
            ch, _stacked(_of(do_ref, at, wide)),
            [before_ref[0, 0, h] for h in heads], [dstate[h] for h in heads])
        lanes = slice(at * DELTA_WIDTH, (at + 1) * DELTA_WIDTH)
        dq_ref[0, :, lanes] = d_q.astype(ch.dtype)
        dk_ref[0, :, lanes] = d_k.astype(ch.dtype)
        for h, head in enumerate(heads):
            dv_ref[0, :, head * DELTA_WIDTH:(head + 1) * DELTA_WIDTH] = \
                d_v[_head(h)].astype(ch.dtype)
            dstate[head] = d_before[h]
        drows_ref[0, at] = d_rows


def _pair_backward(ch: _Chunk, do, before, d_after):
    """A key head's chunk backward: (dq and dk [64, dk] float32, the pair's
    summed; dv [128, dv] float32, stacked; the tile of scalars' cotangent
    [8, 128]; the cotangents of the pair's states before the chunk) from o's
    cotangent ``do`` [128, dv] (stacked), the pair's states ``before`` the
    chunk and the cotangents ``d_after`` of the states after it."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    dtype, cols = ch.dtype, ch.cols
    before_r = [s.astype(dtype) for s in before]
    u = ch.written(before_r).astype(dtype)
    scores_r = ch.scores.astype(dtype)
    # o = qg S + scores u and S' = whole S + kd^T u, a head at a time where
    # the state is an operand
    d_scores = jnp.where(ch.seen, ch.dot_nt(do, u), 0.0)
    d_u = ch.dot_tn(scores_r, do)
    d_qg, d_kd, d_w, d_u_state, d_whole = [], [], [], [], []
    d_before = []
    for h in range(DELTA_PAIR):
        rows = _head(h)
        d_after_r = d_after[h].astype(dtype)
        d_qg.append(ch.dot_nt(do[rows], before_r[h]))
        d_kd.append(ch.dot_nt(u[rows], d_after_r))
        d_u_state.append(ch.dot(ch.kd[rows], d_after_r))
        d_whole.append(jnp.sum(before[h] * d_after[h], axis=0,
                               keepdims=True))
        d_before.append(ch.whole[h] * d_after[h]
                        + ch.dot_tn(ch.qg[rows], do[rows]))
    d_u = d_u + jnp.concatenate(d_u_state, axis=0)      # = dU0, float32
    d_u_r = d_u.astype(dtype)
    for h in range(DELTA_PAIR):                         # U = U0 - W S
        rows = _head(h)
        d_w.append(-ch.dot_nt(d_u_r[rows], before_r[h]))
        d_before[h] = d_before[h] - ch.dot_tn(ch.w[rows], d_u_r[rows])
    d_qg, d_kd, d_w = (jnp.concatenate(part, axis=0)
                       for part in (d_qg, d_kd, d_w))
    d_w_r = d_w.astype(dtype)
    # U0 = T (beta v), W = T (beta exp(gamma) k); T = (I + A)^-1
    d_inverse = ch.dot_nt(d_u_r, ch.vb) + ch.dot_nt(d_w_r, ch.kb)
    d_vb = ch.dot_tn(ch.inverse_r, d_u_r)
    d_kb = ch.dot_tn(ch.inverse_r, d_w_r)
    d_system = -ch.dot_exact(
        ch.inverse, ch.dot_exact(d_inverse, ch.inverse,
                                 (((1,), (1,)), ((), ()))),
        (((0,), (0,)), ((), ())))
    by_pair = jnp.where(ch.strict, d_system * ch.decay, 0.0)    # x kk: dbeta's
    d_kk = (by_pair * ch.beta).astype(dtype)
    d_qk = (d_scores * ch.decay).astype(dtype)
    # the exponents: a token's sum gains what it decays to and loses what
    # decays from it
    through = by_pair * ch.kk * ch.beta + d_scores * ch.scores
    kf, qf, vf = (a.astype(f32) for a in (ch.k, ch.q, ch.v))
    d_k = (ch.dot(d_kk, ch.k) + ch.dot_tn(d_kk, ch.k) + ch.dot_tn(d_qk, ch.q)
           + d_kb * cols[:, _BETA_GROWN:_BETA_GROWN + 1]
           + d_kd * cols[:, _TO_END:_TO_END + 1])
    d_q = ch.dot(d_qk, ch.k) + d_qg * cols[:, _GROWN:_GROWN + 1]
    # the scalars' cotangents, a quantity a column, turned into the tile
    lane = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    d_cols = jnp.zeros(cols.shape, f32)
    for at, total in (
            (_GAMMA, through),
            (_BETA, by_pair * ch.kk + d_vb * vf),
            (_GROWN, d_qg * qf), (_BETA_GROWN, d_kb * kf),
            (_TO_END, d_kd * kf)):
        d_cols = jnp.where(lane == at,
                           jnp.sum(total, axis=1, keepdims=True), d_cols)
    d_rows = d_cols.T
    row = jax.lax.broadcasted_iota(jnp.int32, d_rows.shape, 0)
    d_rows = jnp.where(row == _GAMMA,
                       d_rows - jnp.sum(through, axis=0, keepdims=True),
                       d_rows)
    for h in range(DELTA_PAIR):     # the whole decay's: its lanes are summed
        d_rows = jnp.where(row == _WHOLE + h, d_whole[h], d_rows)
    return (d_q[_head(0)] + d_q[_head(1)], d_k[_head(0)] + d_k[_head(1)],
            d_vb * ch.beta, d_rows, d_before)


def _delta_vmem(itemsize: int, back: bool, keys: int) -> int:
    """A kernel's blocks (twice: pipelined), scratch, and the [128 x 128]
    float32 arrays a step holds at once, for ``keys`` key heads a step."""
    rows, state = DELTA_CHUNK * DELTA_WIDTH, DELTA_WIDTH * DELTA_WIDTH * 4
    blocks = (2 + 2 * DELTA_PAIR) * rows * itemsize + SUBLANE * LANE * 4 \
        + DELTA_PAIR * state
    if back:
        blocks += (2 + 2 * DELTA_PAIR) * rows * itemsize + SUBLANE * LANE * 4
    return keys * (2 * blocks + DELTA_PAIR * state
                   + (48 if back else 24) * state)


@functools.lru_cache(maxsize=None)
def _delta_scan_fn(interpret: Optional[bool]):
    """The differentiable scan, jitted once: the layers of a step share one
    trace and one lowering a direction."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pl, pltpu = _pl(), _pltpu()
    zero = np.int32(0)
    f32 = jnp.float32
    wide = DELTA_PAIR * DELTA_WIDTH

    def call(back: bool, q, k, v, rows, *rest):
        bsz, t, _ = q.shape
        hk, nc = rows.shape[1], t // DELTA_CHUNK
        # two key heads a step where they pair up: the two are independent,
        # so one's products fill the other's waits, and a step's own cost
        # is paid half as often
        keys = DELTA_KEYS if hk % DELTA_KEYS == 0 else 1
        last = np.int32(nc - 1)

        def chunk(ci):      # backward walks the chunks last first
            return last - ci if back else ci
        narrow = pl.BlockSpec((1, DELTA_CHUNK, keys * DELTA_WIDTH),
                              lambda bi, hi, ci: (bi, chunk(ci), hi))
        values = pl.BlockSpec((1, DELTA_CHUNK, keys * wide),
                              lambda bi, hi, ci: (bi, chunk(ci), hi))
        scalars = pl.BlockSpec((1, keys, SUBLANE, LANE),
                               lambda bi, hi, ci: (bi, hi, chunk(ci), zero))
        states = pl.BlockSpec(
            (1, 1, keys * DELTA_PAIR, DELTA_WIDTH, DELTA_WIDTH),
            lambda bi, hi, ci: (bi, chunk(ci), hi, zero, zero))
        kept_shape = (bsz, nc, DELTA_PAIR * hk, DELTA_WIDTH, DELTA_WIDTH)
        in_specs = [narrow, narrow, values, scalars]
        if back:
            in_specs += [states, values]
            out_specs = [narrow, narrow, values, scalars]
            out_shape = [_varying_like(q, q.shape, q.dtype),
                         _varying_like(q, k.shape, k.dtype),
                         _varying_like(q, v.shape, v.dtype),
                         _varying_like(q, rows.shape, f32)]
        else:
            out_specs = [values, states]
            out_shape = [_varying_like(q, v.shape, v.dtype),
                         _varying_like(q, kept_shape, f32)]
        name = "delta_scan_bwd" if back else "delta_scan_fwd"
        perfvars.note_kernel_build(name)
        return pl.pallas_call(
            _delta_bwd_kernel if back else _delta_fwd_kernel,
            grid=(bsz, hk // keys, nc),
            in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM(
                (keys * DELTA_PAIR, DELTA_WIDTH, DELTA_WIDTH), f32)],
            interpret=_interpret(interpret),
            compiler_params=_compiler_params(
                None, _delta_vmem(q.dtype.itemsize, back, keys), "delta_scan",
                ("parallel", "parallel", "arbitrary")),
            name=name)(q, k, v, rows, *rest)

    @jax.custom_vjp
    def scan(q, k, v, rows):
        return call(False, q, k, v, rows)[0]

    def fwd(q, k, v, rows):
        o, before = call(False, q, k, v, rows)
        return o, (q, k, v, rows, before)

    def bwd(kept, do):
        return tuple(call(True, *kept, do))
    scan.defvjp(fwd, bwd)
    return jax.jit(scan)


def delta_scan(q, k, v, g, beta, *, interpret: Optional[bool] = None):
    """o [batch, t, value heads, 128], of v's type, of the recurrence S_t =
    exp(g_t) S_{t-1}, S_t += k_t (beta_t (v_t - S_t^T k_t))^T, o_t = S_t^T
    q_t in its chunked form at a chunk of 64: q and k [batch, t, key heads,
    128], v [batch, t, 2 x key heads, 128], g (<= 0) and beta [batch, t,
    value heads] float32, t a multiple of 64. The decay sums inside each
    chunk and their exponentials are taken here, in front of the kernel, and
    their gradient is XLA's; the backward pass (``custom_vjp``) is one
    kernel that keeps the operands and the state before each chunk [batch,
    chunks, value heads, 128, 128] float32 and computes every [chunk x
    chunk] array again."""
    import jax.numpy as jnp
    bsz, t, hk, dk = k.shape
    hv, dv = v.shape[2:]
    if not delta_scan_selected(hv, hk, dk, dv, DELTA_CHUNK, v.dtype) \
            or t % DELTA_CHUNK or q.dtype != v.dtype or k.dtype != v.dtype:
        raise ValueError(
            f"delta_scan: q {q.shape} {q.dtype}, k {k.shape} {k.dtype}, v "
            f"{v.shape} {v.dtype} is outside the kernel's contract (float32 "
            f"or bfloat16, {DELTA_PAIR} value heads a key head, heads of "
            f"{DELTA_WIDTH}, t a multiple of the chunk, {DELTA_CHUNK})")
    f32 = jnp.float32
    operands = _vary_together(
        q.reshape(bsz, t, hk * dk), k.reshape(bsz, t, hk * dk),
        v.reshape(bsz, t, hv * dv), _rows(g.astype(f32), beta.astype(f32)))
    return _delta_scan_fn(interpret)(*operands).reshape(v.shape)
