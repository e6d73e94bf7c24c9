"""The per-channel selective (Mamba-1) scan as one Pallas kernel each way:
what ``parallel/ssm.py:_selective_chunks`` computes with its skip term,

    S_t[n, c] = exp(dt_t[c] A[c, n]) S_{t-1}[n, c] + dt_t[c] B_t[n] x_t[c]
    y_t[c] = sum_n C_t[n] S_t[n, c] + D[c] x_t[c]

with the [state, channels] float32 state, and in the backward pass a block's
token states, their decays and the state's cotangent, in VMEM alone. The
channels are independent of one another: a grid step holds a tile of
:data:`SEL_TILE` channels on the lanes (the state index on the sublanes, as
the plain form has it) and a block of tokens. The blocks run in order
(backward: in reverse), a block's tiles one after the other, with every
tile's state carried in VMEM scratch, as ``ssm_kernels.py`` carries its own.

**The block of tokens is the kernel's own, decided from the length alone**:
the sequence is filled up to a multiple of 128 with tokens of ``dt`` = 0
(they decay nothing and add nothing) and walked :data:`_SEL_BLOCKS` = 256 or
128 tokens a grid step, the largest that divides it. The result does not
depend on the caller's ``chunk``, so the kernel does not take it: a block
is several of the model's chunks, and the state is kept at the block's
grain. x, dt, y and their cotangents cross HBM as the ``[batch, t,
channels]`` rows they are outside. B and C come transposed, ``[batch, state,
t]``: at a block's first tile the XLU spreads each token's column over the
128 lanes of a vreg, once for all the block's tiles, and a token then loads
its B_t and C_t as it loads a row of dt. Inside a block the tokens go eight
at a time (a float32 tile's sublanes): a row of dt is a sublane spread over
the state's, and the eight sums over the state index are folded into one
tile (:func:`_rows`).

Precisions are the plain form's: ``dt x A``, its exponential (one a token,
channel and state index, no approximation), the state and the sum over the
state index float32; x, B and C read at their own type and widened; y
rounded once, after the skip term. The backward kernel reads the state
BEFORE each block (what the forward leaves: ``[batch, blocks, state,
channels]`` float32), runs the block forward into VMEM (``[tokens, state,
tile]`` float32 states and decays) and walks its tokens in reverse carrying
the state's cotangent; every gradient is float32 until it is written at its
operand's type. dB and dC, sums over ALL channels, leave the kernel as one
partial a channel tile, lane-dense (``[batch, tiles, t x state / 128,
128]``: eight tokens' lane sums are one 128 x 128 tile turned on the XLU,
idle otherwise, and summed over the sublanes), dA and dD as one partial a
block of tokens, for XLA to add.
"""

from __future__ import annotations

import functools
from typing import Optional

from .. import perfvars
from .pallas_kernels import (LANE, SUBLANE, _compiler_params, _interpret,
                             _pl, _pltpu, _typed, _vary_together,
                             _varying_like)

SEL_TILE = 512          # channels a grid step: four vregs of lanes
_SEL_BLOCKS = (256, 128)    # tokens a grid step, the largest that divides
SEL_STATE = 16          # a Mamba-1 layer's: eight tokens' dB are one 128 x
#                         128 tile for the XLU to turn
_UNROLL = 8             # tokens of the inner loops laid out in one body


def sel_scan_selected(channels: int, state: int, dtype) -> bool:
    """Whether :func:`sel_scan` takes ``channels`` channels of ``dtype`` over
    a state of ``state``: the contract, decided from the shapes and the
    type."""
    return (bool(_typed(dtype)) and channels % SEL_TILE == 0
            and state == SEL_STATE)


def _block(t: int) -> int:
    """Tokens a grid step for a sequence of ``t`` (a multiple of 128)."""
    return next(n for n in _SEL_BLOCKS if t % n == 0)


def _groups():
    return range(SEL_TILE // LANE)


def _lanes(j: int) -> slice:
    return slice(j * LANE, (j + 1) * LANE)


def _by_group(tokens: int, body, carry, back: bool = False):
    """``body(t0, carry)`` for t0 = 0, 8, .. in order (``back``: last
    first): a group of :data:`_UNROLL` = 8 tokens is a float32 tile's
    sublanes, so a group's rows of dt are one aligned load and its rows of
    y one aligned store, and the group is laid out in one body (Mosaic
    takes a `fori_loop` whole or not unrolled at all)."""
    import jax
    import jax.numpy as jnp
    pl = _pl()
    groups = tokens // _UNROLL

    def group(g, carry):
        g = groups - 1 - g if back else g
        return body(pl.multiple_of(g * _UNROLL, _UNROLL), carry)
    # (int32 bounds: under jax_enable_x64 a Python bound makes the index 64
    # bits wide, and Mosaic has no 64-bit scalars)
    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(groups), group, carry)


def _rows(sums):
    """[8, 128] float32 whose row r is ``sums[r]`` [state, 128] summed over
    the state index. The eight are folded pairwise, halving the sublanes a
    sum still lies over at each of three steps (two rotations and a select
    a pair): half the work of eight sums each rotated down on its own."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pltpu = _pltpu()
    row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANE, LANE), 0)
    parts = [sum(v[i:i + SUBLANE] for i in range(0, v.shape[0], SUBLANE))
             for v in sums]
    while len(parts) > 1:
        half = len(parts) // 2      # = the sublanes' distance at this step
        low = (row & half) == 0
        up, down = np.int32(SUBLANE - half), np.int32(half)   # (int32 under
        #                                       jax_enable_x64 too)
        parts = [jnp.where(low, a + pltpu.roll(a, up, 0),
                           b + pltpu.roll(b, down, 0))
                 for a, b in zip(parts[:half], parts[half:])]
    return parts[0]


def _spread(v_ref, out_ref):
    """out [tokens x state, 128] float32 from ``v_ref[0]`` [state, tokens]:
    rows t x state .. hold token t's vector in every lane. Eight tokens at
    a time, the 128 tokens around them are rotated until theirs are lanes 0
    to 7, and a lane is spread over the others on the XLU. (Once a block of
    tokens, for all its tiles of channels: spread for every tile anew, the
    XLU's pops held the token loops up by a third.)"""
    import jax.numpy as jnp
    import numpy as np
    pl, pltpu = _pl(), _pltpu()
    n, tokens = v_ref.shape[1:]

    def group(t0, _):
        first = pl.multiple_of(t0 & np.int32(-LANE), LANE)
        near = v_ref[0, :, pl.ds(first, LANE)].astype(jnp.float32)
        near = pltpu.roll(near, LANE - (t0 & np.int32(LANE - 1)), 1)
        for r in range(_UNROLL):
            out_ref[pl.ds(pl.multiple_of((t0 + r) * n, n), n), :] = \
                jnp.broadcast_to(near[:, r:r + 1], (n, LANE))
    _by_group(tokens, group, None)


def _sel_fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref,
                    y_ref, before_ref, state, u, ybuf, bb, cc):
    """One block of tokens of one tile of channels, forward. Scratch: every
    tile's ``state`` [tiles, state, tile] float32 after the block before;
    the block's ``u`` = dt x and y before its skip term (``ybuf``), float32;
    B and C spread over the lanes (``bb``, ``cc``) at the block's first
    tile and kept for the others."""
    import jax.numpy as jnp
    pl = _pl()
    f32 = jnp.float32
    n, tokens = b_ref.shape[1], b_ref.shape[2]
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _first_tile():
        _spread(b_ref, bb)
        _spread(c_ref, cc)

    @pl.when(pl.program_id(1) == 0)
    def _first_block():
        state[ci] = jnp.zeros(state.shape[1:], f32)

    before_ref[0, 0] = state[ci]
    xf = x_ref[0].astype(f32)
    u[...] = xf * dt_ref[0]

    def group(t0, s):
        at = pl.ds(t0, _UNROLL)
        dt = [dt_ref[0, at, _lanes(j)] for j in _groups()]
        dtx = [u[at, _lanes(j)] for j in _groups()]
        s, read = list(s), [[] for _ in _groups()]
        for r in range(_UNROLL):
            rows = pl.ds(pl.multiple_of((t0 + r) * n, n), n)
            b_t, c_t = bb[rows, :], cc[rows, :]
            for j in _groups():
                decay = jnp.exp(dt[j][r:r + 1] * a_ref[:, _lanes(j)])
                s[j] = decay * s[j] + dtx[j][r:r + 1] * b_t
                read[j].append(s[j] * c_t)
        for j in _groups():
            ybuf[at, _lanes(j)] = _rows(read[j])
        return tuple(s)
    s = _by_group(tokens, group,
                  tuple(state[ci, :, _lanes(j)] for j in _groups()))
    for j in _groups():
        state[ci, :, _lanes(j)] = s[j]
    y_ref[0] = (ybuf[...] + d_ref[...] * xf).astype(y_ref.dtype)


def _lane_sums(staged_ref):
    """[8, 128] float32 from a group's ``staged`` [128, 128] (row r x 16 +
    n: token r's partial sums for state index n, a lane each): turned on
    the XLU, idle otherwise, and its sixteen tiles added, so that lane
    r x 16 + n holds 8 partial sums of that row's lanes on the sublanes;
    :func:`_rows` finishes eight groups at a time."""
    turned = staged_ref[...].T
    return sum(turned[i:i + SUBLANE] for i in range(0, LANE, SUBLANE))


def _sel_bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, before_ref,
                    dy_ref, dx_ref, ddt_ref, da_ref, db_ref, dc_ref, dd_ref,
                    dstate, states, decays, u, dyf, q, db_part, dc_part,
                    db_staged, dc_staged, bb, cc):
    """One block of tokens of one tile of channels, backward; the blocks
    come last first. Scratch: every tile's ``dstate``, the cotangent of the
    state after this block; the block's token ``states`` [tokens + 1,
    state, tile] (row 0 the state before the block) and ``decays``; ``u``,
    ``bb``, ``cc`` as forward; dy widened (``dyf``); ``q`` = sum_n dS B a
    token and channel; dB's and dC's partial sums, a group's staged for
    the XLU."""
    import jax.numpy as jnp
    pl = _pl()
    f32 = jnp.float32
    n, tokens = b_ref.shape[1], b_ref.shape[2]
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _first_tile():
        _spread(b_ref, bb)
        _spread(c_ref, cc)

    @pl.when(pl.program_id(1) == 0)
    def _last_block():
        dstate[ci] = jnp.zeros(dstate.shape[1:], f32)

    xf = x_ref[0].astype(f32)
    u[...] = xf * dt_ref[0]
    dyf[...] = dy_ref[0].astype(f32)

    # the block forward again, every token's state kept
    states[0] = before_ref[0, 0]

    def group(t0, s):
        at = pl.ds(t0, _UNROLL)
        dt = [dt_ref[0, at, _lanes(j)] for j in _groups()]
        dtx = [u[at, _lanes(j)] for j in _groups()]
        s = list(s)
        for r in range(_UNROLL):
            b_t = bb[pl.ds(pl.multiple_of((t0 + r) * n, n), n), :]
            for j in _groups():
                decay = jnp.exp(dt[j][r:r + 1] * a_ref[:, _lanes(j)])
                s[j] = decay * s[j] + dtx[j][r:r + 1] * b_t
                states[t0 + r + 1, :, _lanes(j)] = s[j]
                decays[t0 + r, :, _lanes(j)] = decay
        return tuple(s)
    _by_group(tokens, group,       # (from scratch: a carry's type is its own)
              tuple(states[0, :, _lanes(j)] for j in _groups()))

    # and its tokens in reverse, the state's cotangent carried
    def back(t0, carry):
        at = pl.ds(t0, _UNROLL)
        dt = [dt_ref[0, at, _lanes(j)] for j in _groups()]
        dtx = [u[at, _lanes(j)] for j in _groups()]
        dy = [dyf[at, _lanes(j)] for j in _groups()]
        ds, da = (list(v) for v in carry)
        to_u = [[None] * _UNROLL for _ in _groups()]
        to_dt = [[None] * _UNROLL for _ in _groups()]
        for r in reversed(range(_UNROLL)):
            t = t0 + r
            rows = pl.ds(pl.multiple_of(t * n, n), n)
            b_t, c_t = bb[rows, :], cc[rows, :]
            db_t = jnp.zeros((n, LANE), f32)
            dc_t = jnp.zeros((n, LANE), f32)
            for j in _groups():
                lanes = _lanes(j)
                dt_t, dy_t = dt[j][r:r + 1], dy[j][r:r + 1]
                a_j = a_ref[:, lanes]
                ds_j = ds[j] + c_t * dy_t
                dc_t = dc_t + states[t + 1, :, lanes] * dy_t
                db_t = db_t + ds_j * dtx[j][r:r + 1]
                to_u[j][r] = ds_j * b_t
                ds[j] = ds_j * decays[t, :, lanes]      # the state before's
                de = ds[j] * states[t, :, lanes]        # d(dt x A)
                da[j] = da[j] + de * dt_t
                to_dt[j][r] = de * a_j
            db_staged[r * n:(r + 1) * n, :] = db_t
            dc_staged[r * n:(r + 1) * n, :] = dc_t
        for j in _groups():
            q[at, _lanes(j)] = _rows(to_u[j])
            ddt_ref[0, at, _lanes(j)] = _rows(to_dt[j])
        db_part[at, :] = _lane_sums(db_staged)
        dc_part[at, :] = _lane_sums(dc_staged)
        return tuple(ds), tuple(da)
    ds, da = _by_group(
        tokens, back, (tuple(dstate[ci, :, _lanes(j)] for j in _groups()),
                       tuple(jnp.zeros((n, LANE), f32) for j in _groups())),
        back=True)
    for j in _groups():
        dstate[ci, :, _lanes(j)] = ds[j]
        da_ref[0, 0, :, _lanes(j)] = da[j]
    dx_ref[0] = (q[...] * dt_ref[0] + d_ref[...] * dyf[...]).astype(
        dx_ref.dtype)
    ddt_ref[0] = ddt_ref[0] + q[...] * xf
    dd_ref[0, 0] = jnp.sum(dyf[...] * xf, axis=0, keepdims=True)
    for part, out_ref in ((db_part, db_ref), (dc_part, dc_ref)):
        for i in range(out_ref.shape[2] // SUBLANE):
            rows = slice(i * SUBLANE, (i + 1) * SUBLANE)
            out_ref[0, 0, rows, :] = _rows([
                part[(i * SUBLANE + g) * SUBLANE:
                     (i * SUBLANE + g + 1) * SUBLANE, :]
                for g in range(SUBLANE)])


def _sel_vmem(tokens: int, tiles: int, state: int, itemsize: int,
              back: bool) -> int:
    """A kernel's blocks (twice: pipelined) and scratch."""
    rows, wide = tokens * SEL_TILE, state * SEL_TILE * 4
    spread = tokens * state * LANE * 4
    blocks = rows * (2 * itemsize + 4) + 2 * state * tokens * itemsize + wide
    scratch = tiles * wide + 2 * rows * 4 + 2 * spread
    if back:
        blocks += rows * (itemsize + 4) + wide + 2 * tokens * state * 4
        scratch += (2 * tokens + 1) * wide + rows * 4 \
            + 2 * (tokens + LANE) * LANE * 4
    return 2 * blocks + scratch


@functools.lru_cache(maxsize=None)
def _sel_scan_fn(interpret: Optional[bool]):
    """The differentiable scan, jitted once: the layers of a step share one
    trace and one lowering a direction."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pl, pltpu = _pl(), _pltpu()
    zero = np.int32(0)
    f32 = jnp.float32

    def call(back: bool, x, dt, a_t, b_t, c_t, d, *rest):
        bsz, t, ch = x.shape
        n = a_t.shape[0]
        tokens = _block(t)
        nb, tiles = t // tokens, ch // SEL_TILE
        last = np.int32(nb - 1)

        def block(k):       # backward walks the blocks last first
            return last - k if back else k
        rows = pl.BlockSpec((1, tokens, SEL_TILE),
                            lambda bi, k, ci: (bi, block(k), ci))
        by_tile = pl.BlockSpec((n, SEL_TILE), lambda bi, k, ci: (zero, ci))
        group = pl.BlockSpec((1, n, tokens),
                             lambda bi, k, ci: (bi, zero, block(k)))
        skip = pl.BlockSpec((1, SEL_TILE), lambda bi, k, ci: (zero, ci))
        states = pl.BlockSpec((1, 1, n, SEL_TILE),
                              lambda bi, k, ci: (bi, block(k), zero, ci))
        wide = pltpu.VMEM((tiles, n, SEL_TILE), f32)
        block_f32 = pltpu.VMEM((tokens, SEL_TILE), f32)
        spread = pltpu.VMEM((tokens * n, LANE), f32)
        in_specs = [rows, rows, by_tile, group, group, skip]
        if back:
            sums = pl.BlockSpec((1, 1, 1, SEL_TILE),
                                lambda bi, k, ci: (bi, block(k), zero, ci))
            part = pl.BlockSpec((1, 1, tokens * n // LANE, LANE),
                                lambda bi, k, ci: (bi, ci, block(k), zero))
            in_specs += [states, rows]
            out_specs = [rows, rows, states, part, part, sums]
            parts = (bsz, tiles, t * n // LANE, LANE)
            out_shape = [_varying_like(x, x.shape, x.dtype),
                         _varying_like(x, dt.shape, f32),
                         _varying_like(x, (bsz, nb, n, ch), f32),
                         _varying_like(x, parts, f32),
                         _varying_like(x, parts, f32),
                         _varying_like(x, (bsz, nb, 1, ch), f32)]
            scratch = [wide, pltpu.VMEM((tokens + 1, n, SEL_TILE), f32),
                       pltpu.VMEM((tokens, n, SEL_TILE), f32),
                       block_f32, block_f32, block_f32,
                       pltpu.VMEM((tokens, LANE), f32),
                       pltpu.VMEM((tokens, LANE), f32),
                       pltpu.VMEM((LANE, LANE), f32),
                       pltpu.VMEM((LANE, LANE), f32), spread, spread]
        else:
            out_specs = [rows, states]
            out_shape = [_varying_like(x, x.shape, x.dtype),
                         _varying_like(x, (bsz, nb, n, ch), f32)]
            scratch = [wide, block_f32, block_f32, spread, spread]
        name = "sel_scan_bwd" if back else "sel_scan_fwd"
        perfvars.note_kernel_build(name)
        return pl.pallas_call(
            _sel_bwd_kernel if back else _sel_fwd_kernel,
            grid=(bsz, nb, tiles),
            in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch, interpret=_interpret(interpret),
            compiler_params=_compiler_params(
                None, _sel_vmem(tokens, tiles, n, x.dtype.itemsize, back),
                "sel_scan", ("parallel", "arbitrary", "arbitrary")),
            name=name)(x, dt, a_t, b_t, c_t, d, *rest)

    def operands(x, dt, a, b, c, d):
        """The kernels' operands: A, B and C with the state index in front
        (on the sublanes), D as a row."""
        return (x, dt, a.T, b.transpose(0, 2, 1), c.transpose(0, 2, 1),
                d[None])

    @jax.custom_vjp
    def scan(x, dt, a, b, c, d):
        return call(False, *operands(x, dt, a, b, c, d))[0]

    def fwd(*given):
        y, before = call(False, *operands(*given))
        return y, given + (before,)

    def bwd(kept, dy):
        *given, before = kept
        x, _dt, _a, b, c, _d = given
        dx, ddt, da, db, dc, dd = call(True, *operands(*given), before, dy)
        return (dx, ddt, da.sum(axis=(0, 1)).T,
                db.sum(axis=1).reshape(b.shape).astype(b.dtype),
                dc.sum(axis=1).reshape(c.shape).astype(c.dtype),
                dd.sum(axis=(0, 1, 2)))
    scan.defvjp(fwd, bwd)
    return jax.jit(scan)


def sel_scan(x, dt, a, b, c, d, *, interpret: Optional[bool] = None):
    """y [batch, t, channels] of the recurrence above, skip term included
    and rounded once to x's type: x [batch, t, channels], dt [batch, t,
    channels] float32 (> 0), a [channels, state] float32 (< 0), b and c
    [batch, t, state], d [channels] float32; any t (filled up to a multiple
    of 128 here with tokens of ``dt`` = 0 and cut again). The backward
    pass (``custom_vjp``) is one kernel that keeps the operands and the
    state before each block of tokens, [batch, blocks, state, channels]
    float32, and computes a block's token states again in VMEM."""
    import jax.numpy as jnp
    bsz, t, ch = x.shape
    if not sel_scan_selected(ch, a.shape[-1], x.dtype):
        raise ValueError(
            f"sel_scan: x {x.shape} {x.dtype} over a state of "
            f"{a.shape[-1]} is outside the kernel's contract (float32 or "
            f"bfloat16, channels in tiles of {SEL_TILE}, a state of "
            f"{SEL_STATE})")
    pad = -t % LANE

    def filled(v):
        return jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v
    f32 = jnp.float32       # what dt, A and D are; a no-op then
    operands = _vary_together(filled(x), filled(dt.astype(f32)),
                              a.astype(f32), filled(b), filled(c),
                              d.astype(f32))
    return _sel_scan_fn(interpret)(*operands)[:, :t]
