"""The gated delta rule's chunked scan as one Pallas kernel each way: what
``parallel/delta.py:_chunked`` computes, with a chunk's [chunk x chunk]
arrays (the decays, ``A``, its inverse ``T``, the masked scores), ``W``,
``U0``, the decayed keys and the running state in VMEM alone.

The first pair of kernels (`_delta_scan_fn`, `_Chunk`: ``delta_scan_fwd`` /
``_bwd``) takes a decay that is one number a value head and token, with
:data:`DELTA_PAIR` = 2 value heads a key head: the contract's first row. A
grid step is one chunk of :data:`DELTA_CHUNK` = 64 tokens for one key head
and its two value heads; the chunks run in order
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

A second pair of kernels (`_channel_scan_fn`, `_ChannelChunk`:
``delta_channel_scan_fwd`` / ``_bwd``) takes a decay that is a number a
value head, token and KEY CHANNEL, with a key head a value head: the
contract's second row (:func:`delta_scan_selected`). A grid step is again
one chunk of two heads stacked to 128 rows, but the two have their own q
and k, so nothing is paired through the index map and every operand,
the decay [batch, t, heads x 128] float32 among them, crosses HBM as the
rows it is outside; beta alone comes as a tile (`_beta_tiles`). The decay's
sums inside the chunk are taken in the kernel (`_summed`: six steps of
shifted adds a head's 64 rows) and so is their gradient, the kernel
returning the decay's cotangent as rows. The decayed forms sum_d k_id k_jd
exp(gamma_id - gamma_jd) and the same of q_i are `parallel.delta.
_decayed_products`' rounds by halves (`_ChannelChunk._rounds`): in a round
every token is either a row (the second half of its block of 2 s tokens,
scaled to that half's first token) or a column (the first half, scaled from
it), so ONE [128, 128] exponential exp(-|gamma - gamma of the reference|),
<= 1 everywhere, scales both operands, they are rounded to the input's type
where `_crossed` rounds them, and one [128 x 128] product a form and round
is kept where the round's places are (twelve products a step forward; three
more a round backward, the two transposed ones as one product over a
contraction of 256). exp(gamma), beta exp(gamma) and the decay to the
chunk's end are [128 x 128] float32 arrays; the chunk's whole decay is a
column a head that scales its state's rows. Everything behind the decayed
forms is `_Chunk`'s own code (`_invert`, `_solved`, `outputs`, `read`,
`stepped`, `_through_states`). The forward kernel's two outputs, o and the
state before each chunk, are named :data:`KEPT`, which a recomputed function
around the scan may keep (`models.transformer._kda_mixer`: the kernel then
runs once a step).
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
KEPT = "delta_scan_kept"    # what a recomputed function around the scan may keep
#                             of it (`checkpoint_name`; `parallel/delta.py`)

# the rows of a step's tile of scalars (`_rows`)
_GAMMA, _BETA, _GROWN, _BETA_GROWN, _TO_END, _WHOLE = range(6)


def delta_scan_selected(value_heads: int, key_heads: int, key_width: int,
                        value_width: int, chunk: int, dtype,
                        decay_width: int = 1) -> bool:
    """Whether :func:`delta_scan` takes ``value_heads`` heads of
    ``value_width`` over ``key_heads`` heads of ``key_width`` in chunks of
    ``chunk`` tokens of ``dtype``, decayed by ``decay_width`` numbers a head
    and token: the contract, decided from the shapes and the type. Its two
    rows, either with heads of :data:`DELTA_WIDTH`, a chunk of
    :data:`DELTA_CHUNK` and float32 or bfloat16: a decay a head (width 1)
    with :data:`DELTA_PAIR` value heads a key head (`_delta_scan_fn`: a
    token's decay as scalars in a tile of rows, `_rows`), and a decay a key
    channel (the key width) with a key head a value head, in twos
    (`_channel_scan_fn`). Every other pairing of the two is refused."""
    if not (_typed(dtype) and key_width == DELTA_WIDTH
            and value_width == DELTA_WIDTH and chunk == DELTA_CHUNK):
        return False
    if decay_width == 1:
        return value_heads == DELTA_PAIR * key_heads
    return decay_width == key_width and value_heads == key_heads \
        and value_heads % DELTA_PAIR == 0


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
    carry_axis = 0      # the whole decay is one number: every row is summed

    def __init__(self, q, k, v, rows):
        """q and k [64, dk], v [64, 2 x dv] (the pair's, side by side) and
        the tile of scalars [8, 128]."""
        import jax.numpy as jnp
        f32, dtype = self._typed_as(v)
        self.k = jnp.concatenate([k, k], axis=0)            # [128, dk]
        self.q = jnp.concatenate([q, q], axis=0)
        self.v = _stacked(v)                                # [128, dv]
        self.cols = cols = rows.T                           # [128, 8]
        self._masks()
        gap = cols[:, _GAMMA:_GAMMA + 1] - rows[_GAMMA:_GAMMA + 1, :]
        self.decay = jnp.exp(jnp.where(self.seen, gap, -jnp.inf))
        self.kk = self.dot_nt(self.k, self.k)               # both heads'
        self.qk = self.dot_nt(self.q, self.k)
        self.beta = cols[:, _BETA:_BETA + 1]
        self._invert(jnp.where(
            self.strict, self.beta * self.decay * self.kk, 0.0))
        kf, vf = self.k.astype(f32), self.v.astype(f32)
        self.kb = (kf * cols[:, _BETA_GROWN:_BETA_GROWN + 1]).astype(dtype)
        self.vb = (vf * self.beta).astype(dtype)
        self._solved()
        self.kd = (kf * cols[:, _TO_END:_TO_END + 1]).astype(dtype)
        self.qg = (self.q.astype(f32)
                   * cols[:, _GROWN:_GROWN + 1]).astype(dtype)
        self.scores = self.qk * self.decay                  # float32
        self.whole = [rows[_WHOLE + h:_WHOLE + h + 1, :]
                      for h in range(DELTA_PAIR)]   # [1, 128], one value

    def _typed_as(self, v):
        """(float32, the input's type), and the products' precision."""
        import jax.numpy as jnp
        self.f32, self.dtype = jnp.float32, v.dtype
        self.prec = _attn_precision(v.dtype)    # float32 operands: HIGHEST
        return self.f32, self.dtype

    def _masks(self):
        """Which places of a [128 x 128] array of the pair's tokens are a
        head's own: ``seen`` (j <= i) and ``strict`` (j < i)."""
        import jax
        import jax.numpy as jnp
        tile = (LANE, LANE)
        self.i = i = jax.lax.broadcasted_iota(jnp.int32, tile, 0)
        self.j = j = jax.lax.broadcasted_iota(jnp.int32, tile, 1)
        self.apart = apart = i ^ j      # < 2^n: in one block of 2^n tokens
        self.seen = jnp.logical_and(apart < DELTA_CHUNK, j <= i)
        self.strict = jnp.logical_and(apart < DELTA_CHUNK, j < i)

    def _invert(self, told):
        """``T`` of the system ``told``, float32 and of the input's type."""
        self.inverse = self._inverse(told)
        self.inverse_r = self.inverse.astype(self.dtype)

    def _solved(self):
        """``W`` (of the input's type) and ``U0`` (float32) from ``T``,
        beta exp(gamma) K and beta V."""
        self.w = self.dot(self.inverse_r, self.kb).astype(self.dtype)
        self.u0 = self.dot(self.inverse_r, self.vb)

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

    def outputs(self, before_r):
        """(``U`` of the input's type; what the chunk's own tokens give its
        outputs, [128, dv] float32) from the pair's states before it."""
        u = self.written(before_r).astype(self.dtype)
        return u, self.dot(self.scores.astype(self.dtype), u)

    def read(self, h: int, o, before_r):
        """Head h's outputs [64, dv], of the input's type: its rows of
        ``o`` and what the state before the chunk gives."""
        rows = _head(h)
        return (o[rows] + self.dot(self.qg[rows], before_r)).astype(
            self.dtype)

    def stepped(self, h: int, before, u):
        """Head h's state after the chunk, float32."""
        rows = _head(h)
        return self.whole[h] * before + self.dot_tn(self.kd[rows], u[rows])

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
        _pair_forward(ch, DELTA_PAIR * at, o_ref, before_ref, state)


def _pair_forward(ch: _Chunk, first: int, o_ref, before_ref, state):
    """A chunk of the pair of value heads ``first`` and ``first`` + 1 of a
    step's, forward, from what ``ch`` made of its operands: the states
    before it are written out for the backward pass, o is read from them
    and from the chunk's own tokens, and the states step."""
    heads = [first + h for h in range(DELTA_PAIR)]
    before = [state[h] for h in heads]
    before_r = [s.astype(ch.dtype) for s in before]
    u, o = ch.outputs(before_r)
    for h, head in enumerate(heads):
        before_ref[0, 0, head] = before[h]
        o_ref[0, :, head * DELTA_WIDTH:(head + 1) * DELTA_WIDTH] = \
            ch.read(h, o, before_r[h])
        state[head] = ch.stepped(h, before[h], u)


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


def _through_states(ch: _Chunk, do, before, d_after):
    """A pair's chunk backward, as far as both decays go the same way: from
    o's cotangent ``do`` [128, dv] (stacked), the pair's states ``before``
    the chunk and the cotangents ``d_after`` of the states after it, the
    cotangents of (the masked scores [128 x 128]; q decayed from the
    chunk's start, k decayed to its end and beta exp(gamma) k, [128, dk]
    each; beta v [128, dv]; the triangular system [128 x 128]; the chunk's
    whole decay, a head's summed over axis ``ch.carry_axis`` of its state;
    the pair's states before the chunk), float32."""
    import jax.numpy as jnp
    dtype = ch.dtype
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
        d_whole.append(jnp.sum(before[h] * d_after[h], axis=ch.carry_axis,
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
    return d_scores, d_qg, d_kd, d_kb, d_vb, d_system, d_whole, d_before


def _pair_backward(ch: _Chunk, do, before, d_after):
    """A key head's chunk backward: (dq and dk [64, dk] float32, the pair's
    summed; dv [128, dv] float32, stacked; the tile of scalars' cotangent
    [8, 128]; the cotangents of the pair's states before the chunk) from o's
    cotangent ``do`` [128, dv] (stacked), the pair's states ``before`` the
    chunk and the cotangents ``d_after`` of the states after it."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    cols = ch.cols
    d_scores, d_qg, d_kd, d_kb, d_vb, d_system, d_whole, d_before = \
        _through_states(ch, do, before, d_after)
    by_pair = jnp.where(ch.strict, d_system * ch.decay, 0.0)    # x kk: dbeta's
    d_kk = (by_pair * ch.beta).astype(ch.dtype)
    d_qk = (d_scores * ch.decay).astype(ch.dtype)
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


def _shifted(x, by: int):
    """``x`` [n, 128] with row i holding ``x``'s row i - ``by``, round the
    ends (``by`` < 0: the rows below come up). Whole tiles of eight rows are
    taken as they lie; inside a tile the sublanes rotate."""
    import jax.numpy as jnp
    import numpy as np
    by %= x.shape[0]
    if by % SUBLANE == 0:
        return jnp.concatenate([x[x.shape[0] - by:], x[:x.shape[0] - by]],
                               axis=0)
    return _pltpu().roll(x, np.int32(by), 0)


class _ChannelChunk(_Chunk):
    """A grid step of the scan whose decay is a number a key CHANNEL: two
    heads with their own q and k, stacked to 128 rows as the values are.
    What a token's scalars were in `_Chunk` (exp(gamma), beta exp(gamma),
    the decay to the chunk's end) are [128 x 128] float32 arrays here, one
    number a token and channel, made in the step from the decay itself; the
    chunk's whole decay is a column a head and scales its state's rows. The
    inverse, ``W``, ``U0``, the written values, the outputs and the state's
    step are `_Chunk`'s."""
    carry_axis = 1      # a channel's whole decay: its row of the state

    def __init__(self, q, k, v, g, tile):
        """q, k and v [64, 2 x 128] (the pair's, side by side), of one
        type; the decay ``g`` [64, 2 x 128] float32; ``tile`` [8, 128]
        float32 whose row 0 is beta, the pair's tokens on the lanes."""
        import jax.numpy as jnp
        f32, dtype = self._typed_as(v)
        self.k, self.q, self.v = (_stacked(a) for a in (k, q, v))
        self._masks()
        self.kf, self.qf = self.k.astype(f32), self.q.astype(f32)
        self.beta = tile.T[:, :1]                           # [128, 1]
        self.gamma = gamma = _summed(_stacked(g), self.i)   # float32
        self.rounds = list(self._rounds())
        self.kk, self.scores = self._decayed()
        self._invert(jnp.where(self.strict, self.beta * self.kk, 0.0))
        self.grown = jnp.exp(gamma)
        last = [gamma[(h + 1) * DELTA_CHUNK - 1:(h + 1) * DELTA_CHUNK]
                for h in range(DELTA_PAIR)]                 # [1, 128] a head
        self.to_end = jnp.exp(jnp.concatenate(
            [last[h] - gamma[_head(h)] for h in range(DELTA_PAIR)], axis=0))
        self.kb = (self.kf * (self.beta * self.grown)).astype(dtype)
        self.vb = (self.v.astype(f32) * self.beta).astype(dtype)
        self._solved()
        self.kd = (self.kf * self.to_end).astype(dtype)
        self.qg = (self.qf * self.grown).astype(dtype)
        whole = jnp.exp(jnp.concatenate(
            last + [jnp.zeros((SUBLANE - DELTA_PAIR, LANE), f32)], axis=0)).T
        self.whole = [whole[:, h:h + 1] for h in range(DELTA_PAIR)]  # [128, 1]

    def _reference(self, size: int):
        """[128, 128]: for every token, the decay sums of the first token
        of the SECOND half of its block of 2 ``size`` tokens."""
        import jax.numpy as jnp
        gamma, n = self.gamma, self.gamma.shape[0]
        if 2 * size >= SUBLANE:     # a block is whole tiles: a row, spread
            return jnp.concatenate(
                [jnp.broadcast_to(gamma[at + size:at + size + 1],
                                  (2 * size, LANE))
                 for at in range(0, n, 2 * size)], axis=0)
        at = self.i & (2 * size - 1)        # a block lies inside a tile
        ref = gamma
        for by in range(-size, size):
            if by:      # for the token `by` rows under the reference
                ref = jnp.where(at == size + by, _shifted(gamma, by), ref)
        return ref

    def _to_reference(self, size: int, x):
        """`_reference`'s transpose: [128, 128] that holds, at the first
        token of each second half of ``size``, the sum of ``x`` over the
        block's 2 ``size`` tokens, and zeros elsewhere."""
        import jax.numpy as jnp
        n = x.shape[0]
        if 2 * size >= SUBLANE:
            at = self.i[:2 * size] == size
            return jnp.concatenate(
                [jnp.where(at, jnp.sum(x[b:b + 2 * size], axis=0,
                                       keepdims=True), 0.0)
                 for b in range(0, n, 2 * size)], axis=0)
        by = 1
        while by < 2 * size:        # every token gets its block's sum
            x = x + jnp.where((self.i & by) != 0, _shifted(x, by),
                              _shifted(x, -by))
            by *= 2
        return jnp.where((self.i & (2 * size - 1)) == size, x, 0.0)

    def _rounds(self):
        """`parallel.delta._decayed_products`' rounds by halves, each (the
        size of a half; the places [128 x 128] a round fills: the rows of each
        block's second half against the columns of its first; which rows
        those are; the scaling exp(-|gamma - the reference's|) [128, 128]
        float32, <= 1 for rows and columns alike; k and q so scaled, of the
        input's type). The rows of a second half are scaled to its first
        token, the columns of the first half from it: no exponential of a
        positive number is formed, and a token is a row or a column of a
        round, never both."""
        import jax.numpy as jnp
        size = 1
        while size < DELTA_CHUNK:
            rows = (self.i & size) != 0
            gap = self.gamma - self._reference(size)
            scale = jnp.exp(jnp.where(rows, gap, -gap))
            between = jnp.logical_and(
                jnp.logical_and(self.apart >= size, self.apart < 2 * size),
                self.j < self.i)
            yield (size, between, rows, scale,
                   (self.kf * scale).astype(self.dtype),
                   (self.qf * scale).astype(self.dtype))
            size *= 2

    def _decayed(self):
        """(sum_d k_id k_jd exp(gamma_id - gamma_jd) for j < i, the same of
        q_i for j <= i), [128 x 128] float32 and zero elsewhere: one product
        a form and round, each kept where its round's places are."""
        import jax.numpy as jnp
        kk = jnp.zeros((LANE, LANE), self.f32)
        qk = jnp.where(self.i == self.j, jnp.sum(
            self.qf * self.kf, axis=1, keepdims=True), 0.0)
        for _size, between, _rows, _scale, ke, qe in self.rounds:
            kk = jnp.where(between, self.dot_nt(ke, ke), kk)
            qk = jnp.where(between, self.dot_nt(qe, ke), qk)
        return kk, qk


def _summed(g, row, back: bool = False):
    """The sums of ``g`` [128, 128] float32 over a head's tokens up to each
    (``back``: from each on), a head's :data:`DELTA_CHUNK` rows by
    themselves: six steps, each adding the sums ``by`` rows away. ``row`` is
    the rows' index."""
    import jax.numpy as jnp
    at = row & (DELTA_CHUNK - 1)
    by = 1
    while by < DELTA_CHUNK:
        inside = at + by < DELTA_CHUNK if back else at >= by
        g = g + jnp.where(inside, _shifted(g, -by if back else by), 0.0)
        by *= 2
    return g


def _side_by_side(stacked):
    """`_stacked`'s inverse: [64, 2 x width] from a pair's [128, width]."""
    import jax.numpy as jnp
    return jnp.concatenate([stacked[_head(h)] for h in range(DELTA_PAIR)],
                           axis=1)


def _channel_fwd_kernel(q_ref, k_ref, v_ref, g_ref, tile_ref, o_ref,
                        before_ref, state):
    """One chunk of a step's pairs of heads, forward. Scratch: the
    ``state`` [heads, dk, dv] float32 after the chunk before."""
    import jax.numpy as jnp
    pl = _pl()

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros(state.shape, jnp.float32)

    wide = DELTA_PAIR * DELTA_WIDTH
    for at in range(tile_ref.shape[1]):     # independent of one another
        ch = _ChannelChunk(*(_of(ref, at, wide) for ref in (
            q_ref, k_ref, v_ref, g_ref)), tile_ref[0, at])
        _pair_forward(ch, DELTA_PAIR * at, o_ref, before_ref, state)


def _channel_bwd_kernel(q_ref, k_ref, v_ref, g_ref, tile_ref, before_ref,
                        do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dtile_ref,
                        dstate):
    """One chunk of a step's pairs of heads, backward; the chunks come last
    first. Scratch: ``dstate`` [heads, dk, dv] float32, the cotangent of
    the states after this chunk."""
    import jax.numpy as jnp
    pl = _pl()

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[...] = jnp.zeros(dstate.shape, jnp.float32)

    wide = DELTA_PAIR * DELTA_WIDTH
    for at in range(tile_ref.shape[1]):     # independent of one another
        ch = _ChannelChunk(*(_of(ref, at, wide) for ref in (
            q_ref, k_ref, v_ref, g_ref)), tile_ref[0, at])
        heads = [DELTA_PAIR * at + h for h in range(DELTA_PAIR)]
        d_q, d_k, d_v, d_g, d_tile, d_before = _channel_backward(
            ch, _stacked(_of(do_ref, at, wide)),
            [before_ref[0, 0, h] for h in heads], [dstate[h] for h in heads])
        for h, head in enumerate(heads):
            dstate[head] = d_before[h]
        lanes = slice(at * wide, (at + 1) * wide)
        dq_ref[0, :, lanes] = _side_by_side(d_q).astype(ch.dtype)
        dk_ref[0, :, lanes] = _side_by_side(d_k).astype(ch.dtype)
        dv_ref[0, :, lanes] = _side_by_side(d_v).astype(ch.dtype)
        dg_ref[0, :, lanes] = _side_by_side(d_g)
        dtile_ref[0, at] = d_tile


def _channel_backward(ch: _ChannelChunk, do, before, d_after):
    """A pair's chunk backward with a decay a channel: (dq, dk, dv and the
    decay's cotangent, [128, 128] float32, stacked; beta's tile's cotangent
    [8, 128]; the cotangents of the pair's states before the chunk) from o's
    cotangent ``do`` [128, dv] (stacked), the pair's states ``before`` the
    chunk and the cotangents ``d_after`` of the states after it."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    dtype, kf, qf, beta = ch.dtype, ch.kf, ch.qf, ch.beta
    d_scores, d_qg, d_kd, d_kb, d_vb, d_system, d_whole, d_before = \
        _through_states(ch, do, before, d_after)
    by_pair = jnp.where(ch.strict, d_system, 0.0)
    d_kk = by_pair * beta
    # the three arrays a token and channel: exp(gamma) in beta exp(gamma) k
    # and in exp(gamma) q, and the decay to the chunk's end
    d_to_end = d_kd * kf * ch.to_end
    d_gamma = (d_kb * kf * beta + d_qg * qf) * ch.grown - d_to_end
    d_k = d_kb * (beta * ch.grown) + d_kd * ch.to_end
    d_q = d_qg * ch.grown
    own = jnp.sum(jnp.where(ch.i == ch.j, d_scores, 0.0), axis=1,
                  keepdims=True)            # a token's q . k with itself
    d_q, d_k = d_q + own * kf, d_k + own * qf
    # k scaled is the rows' operand of its own form and the columns' of
    # both, and a token is one or the other: the cotangent of k's form and
    # its transpose land apart and are one symmetric array, turned once a
    # step as the scores' is; a round takes its places of the three
    d_kk = d_kk + d_kk.T
    d_scores_t = d_scores.T
    for size, between, rows, scale, ke, qe in ch.rounds:
        here = jnp.logical_and(ch.apart >= size, ch.apart < 2 * size)
        y = jnp.where(between, d_scores, 0.0).astype(dtype)
        d_ke = ch.dot(
            jnp.concatenate([
                jnp.where(here, d_kk, 0.0),
                jnp.where(jnp.logical_and(here, ch.i < ch.j), d_scores_t,
                          0.0)], axis=1).astype(dtype),
            jnp.concatenate([ke, qe], axis=0))
        d_qe = ch.dot(y, ke)
        d_k, d_q = d_k + d_ke * scale, d_q + d_qe * scale
        d_gap = (d_ke * kf + d_qe * qf) * scale
        d_gap = jnp.where(rows, d_gap, -d_gap)
        d_gamma = d_gamma + d_gap - ch._to_reference(size, d_gap)
    # the tile's cotangent: beta's, and a head's whole decay's, which with
    # every token's decay to the end is its last sums'
    lane = jax.lax.broadcasted_iota(jnp.int32, (LANE, SUBLANE), 1)
    d_cols = jnp.where(lane == 0, jnp.sum(
        by_pair * ch.kk + d_vb * ch.v.astype(f32) + d_kb * kf * ch.grown,
        axis=1, keepdims=True), 0.0)
    for h in range(DELTA_PAIR):
        d_cols = jnp.where(lane == 1 + h, d_whole[h] * ch.whole[h], d_cols)
    d_rows = d_cols.T                                       # [8, 128]
    for h in range(DELTA_PAIR):
        d_last = d_rows[1 + h:2 + h] + jnp.sum(d_to_end[_head(h)], axis=0,
                                               keepdims=True)
        d_gamma = jnp.where(ch.i == (h + 1) * DELTA_CHUNK - 1,
                            d_gamma + d_last, d_gamma)
    row = jax.lax.broadcasted_iota(jnp.int32, d_rows.shape, 0)
    return (d_q, d_k, d_vb * beta, _summed(d_gamma, ch.i, back=True),
            jnp.where(row == 0, d_rows, 0.0), d_before)


def _channel_vmem(itemsize: int, back: bool) -> int:
    """`_delta_vmem` of the kernels with a decay a channel: a step's blocks
    (twice: pipelined), scratch, and the [128 x 128] float32 arrays it
    holds at once."""
    rows, state = DELTA_CHUNK * DELTA_PAIR * DELTA_WIDTH, \
        DELTA_WIDTH * DELTA_WIDTH * 4
    blocks = 4 * rows * itemsize + rows * 4 + SUBLANE * LANE * 4 \
        + DELTA_PAIR * state
    if back:
        blocks += 4 * rows * itemsize + rows * 4 + SUBLANE * LANE * 4
    return 2 * blocks + DELTA_PAIR * state + (64 if back else 32) * state


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


def _scan_call(name: str, kernel, grid: tuple, in_specs, out_specs, out_shape,
               heads: int, vmem: int, interpret: Optional[bool]):
    """The ``pallas_call`` of one of the four kernels, noted under its
    ``name``: a grid of (batch, steps of heads, chunks in order) with the
    step's ``heads`` states [128, 128] float32 as scratch."""
    pl, pltpu = _pl(), _pltpu()
    import jax.numpy as jnp
    perfvars.note_kernel_build(name)
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, DELTA_WIDTH, DELTA_WIDTH),
                                   jnp.float32)],
        interpret=_interpret(interpret),
        compiler_params=_compiler_params(
            None, vmem, name, ("parallel", "parallel", "arbitrary")),
        name=name)


@functools.lru_cache(maxsize=None)
def _delta_scan_fn(interpret: Optional[bool]):
    """The differentiable scan, jitted once: the layers of a step share one
    trace and one lowering a direction."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pl = _pl()
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
        return _scan_call(
            "delta_scan_bwd" if back else "delta_scan_fwd",
            _delta_bwd_kernel if back else _delta_fwd_kernel,
            (bsz, hk // keys, nc), in_specs, out_specs, out_shape,
            keys * DELTA_PAIR, _delta_vmem(q.dtype.itemsize, back, keys),
            interpret)(q, k, v, rows, *rest)

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


def _beta_tiles(beta):
    """[batch, pairs of heads, chunks x 8, 128] float32: for each chunk and
    pair one tile whose row 0 is beta of the pair's tokens, head 0's on
    lanes 0-63 and head 1's on 64-127, over seven rows of zeros. beta
    [batch, t, heads] float32, t a multiple of the chunk."""
    import jax.numpy as jnp
    bsz, t, hv = beta.shape
    nc, pairs = t // DELTA_CHUNK, hv // DELTA_PAIR
    rows = beta.reshape(bsz, nc, DELTA_CHUNK, pairs, DELTA_PAIR).transpose(
        0, 3, 1, 4, 2).reshape(bsz, pairs, nc, 1, LANE)
    return jnp.pad(rows, ((0, 0),) * 3 + ((0, SUBLANE - 1), (0, 0))).reshape(
        bsz, pairs, nc * SUBLANE, LANE)


@functools.lru_cache(maxsize=None)
def _channel_scan_fn(interpret: Optional[bool]):
    """The differentiable scan with a decay a key channel, jitted once."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.ad_checkpoint import checkpoint_name
    pl = _pl()
    zero = np.int32(0)
    f32 = jnp.float32

    def call(back: bool, q, k, v, g, tiles, *rest):
        bsz, t, _ = q.shape
        pairs, nc = tiles.shape[1], t // DELTA_CHUNK
        # two pairs a step where they come in twos, as `_delta_scan_fn`
        # takes two key heads: one's products fill the other's waits
        keys = DELTA_KEYS if pairs % DELTA_KEYS == 0 else 1
        last = np.int32(nc - 1)

        def chunk(ci):      # backward walks the chunks last first
            return last - ci if back else ci
        rows = pl.BlockSpec((1, DELTA_CHUNK, keys * DELTA_PAIR * DELTA_WIDTH),
                            lambda bi, pi, ci: (bi, chunk(ci), pi))
        tile = pl.BlockSpec((1, keys, SUBLANE, LANE),
                            lambda bi, pi, ci: (bi, pi, chunk(ci), zero))
        states = pl.BlockSpec(
            (1, 1, keys * DELTA_PAIR, DELTA_WIDTH, DELTA_WIDTH),
            lambda bi, pi, ci: (bi, chunk(ci), pi, zero, zero))
        kept_shape = (bsz, nc, DELTA_PAIR * pairs, DELTA_WIDTH, DELTA_WIDTH)
        in_specs = [rows, rows, rows, rows, tile]
        if back:
            in_specs += [states, rows]
            out_specs = [rows, rows, rows, rows, tile]
            out_shape = [_varying_like(q, a.shape, a.dtype)
                         for a in (q, k, v, g, tiles)]
        else:
            out_specs = [rows, states]
            out_shape = [_varying_like(q, v.shape, v.dtype),
                         _varying_like(q, kept_shape, f32)]
        return _scan_call(
            "delta_channel_scan_bwd" if back else "delta_channel_scan_fwd",
            _channel_bwd_kernel if back else _channel_fwd_kernel,
            (bsz, pairs // keys, nc), in_specs, out_specs, out_shape,
            keys * DELTA_PAIR, keys * _channel_vmem(q.dtype.itemsize, back),
            interpret)(q, k, v, g, tiles, *rest)

    @jax.custom_vjp
    def scan(q, k, v, g, tiles):
        return call(False, q, k, v, g, tiles)[0]

    def fwd(q, k, v, g, tiles):
        o, before = call(False, q, k, v, g, tiles)
        # What a recomputed function around the scan keeps of it
        # (`save_only_these_names(KEPT)`: `_kda_mixer`'s half): the
        # states AND o, so that this kernel runs once a step. With the
        # states alone it runs again for o, which the half's backward pass
        # reads, and the states bought nothing. (The Kimi step compiled for
        # the v5e holds 8.41 GB with neither, 9.70 with the states, 10.21
        # with both: PERF.md section 6, PR 49.)
        return checkpoint_name(o, KEPT), (
            q, k, v, g, tiles, checkpoint_name(before, KEPT))

    def bwd(kept, do):
        return tuple(call(True, *kept, do))
    scan.defvjp(fwd, bwd)
    return jax.jit(scan)


def delta_scan(q, k, v, g, beta, *, interpret: Optional[bool] = None):
    """o [batch, t, value heads, 128], of v's type, of the recurrence S_t =
    Diag(exp(g_t)) S_{t-1}, S_t += k_t (beta_t (v_t - S_t^T k_t))^T, o_t =
    S_t^T q_t in its chunked form at a chunk of 64: q and k [batch, t, key
    heads, 128], v [batch, t, value heads, 128], beta [batch, t, value
    heads] float32, t a multiple of 64, and g (<= 0) float32 either as beta
    is, with two value heads a key head, or [batch, t, value heads, 128], a
    number a key channel, with a key head a value head
    (:func:`delta_scan_selected`). With a decay a head, its sums inside each
    chunk and their exponentials are taken here, in front of the kernel, and
    their gradient is XLA's; with a decay a channel the kernels take g as
    it is and return its cotangent. The backward pass (``custom_vjp``) is
    one kernel that keeps the operands and the state before each chunk
    [batch, chunks, value heads, 128, 128] float32 (named :data:`KEPT`,
    with o, where the decay is a channel's) and computes every [chunk x chunk]
    array again."""
    import jax.numpy as jnp
    bsz, t, hk, dk = k.shape
    hv, dv = v.shape[2:]
    by_channel = g.ndim == 4
    if not delta_scan_selected(hv, hk, dk, dv, DELTA_CHUNK, v.dtype,
                               g.shape[3] if by_channel else 1) \
            or t % DELTA_CHUNK or q.dtype != v.dtype or k.dtype != v.dtype:
        raise ValueError(
            f"delta_scan: q {q.shape} {q.dtype}, k {k.shape} {k.dtype}, v "
            f"{v.shape} {v.dtype}, g {g.shape} is outside the kernel's "
            f"contract (float32 or bfloat16, heads of {DELTA_WIDTH}, t a "
            f"multiple of the chunk, {DELTA_CHUNK}; {DELTA_PAIR} value heads "
            f"a key head and a decay a head, or a key head a value head, in "
            f"twos, and a decay a key channel)")
    f32 = jnp.float32
    q, k, v = (q.reshape(bsz, t, hk * dk), k.reshape(bsz, t, hk * dk),
               v.reshape(bsz, t, hv * dv))
    if by_channel:
        out = _channel_scan_fn(interpret)(*_vary_together(
            q, k, v, g.astype(f32).reshape(bsz, t, hv * dk),
            _beta_tiles(beta.astype(f32))))
    else:
        out = _delta_scan_fn(interpret)(*_vary_together(
            q, k, v, _rows(g.astype(f32), beta.astype(f32))))
    return out.reshape(bsz, t, hv, dv)
