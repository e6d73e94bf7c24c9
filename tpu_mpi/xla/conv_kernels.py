"""The recurrent mixers' short causal convolution with its silu as one Pallas
kernel each way: what ``jax.nn.silu(parallel/ssm.py:causal_conv(x, w,
bias))`` computes,

    out_t[c] = silu(bias[c] + sum_j w[j, c] x_{t - (k - 1 - j)}[c])

(tap k - 1 weighs the token itself, zeros before the sequence's start), at
four bytes an element forward (the row read, the row written) and six
backward (the row and the cotangent read, one written). The channels are
independent of one another, so a call takes ``width`` of them where they
stand in a wider row (``start``, a static column offset: a mixer's
convolved channels are a part of its in-projection's product), and what the
mixer cuts the result into (``cuts``) are calls of their own over the
parts' columns, each writing an array of its own: no copy stands in front
of a kernel or behind it.

A grid step is a block of tokens (:data:`_CONV_BLOCKS`, the largest that
divides the sequence) of a tile of channels (:data:`_CONV_TILES` x 128
lanes, the widest that divides the part and its offset); the batch and the
tiles are parallel, a tile's blocks run in order (backward: in reverse).
The arithmetic is the VPU's and the kernels are bound by it, not by HBM (a
sublane rotation is a VALU operation on the v5e, a float32 division
fourteen), so a tap's shifted rows cost none: a lane tile's rows are
widened to float32 ONCE into VMEM scratch (``[lane tiles, 8 + tokens,
128]``, a slab a lane tile so that rows follow one another in memory) and
every tap loads them from its own unaligned row, one `vld` each, through a
view whose first row is aligned (Mosaic lowers no dynamic unaligned index).
Inside a step the tokens go :data:`_ROWS` at a time, a lane tile after the
other, a group's values in registers: the taps' products summed in float32,
silu of the float32 sum, ONE rounding at the store. Where the result is
stored narrower than float32 the sigmoid's reciprocal is the EUP's with one
Newton step (what a division is, without its care for zeros, infinities
and subnormal numbers); a float32 result gets the division. Forward the
eight rows in front of a block are the last of the block before, left in
the scratch (zeros at a sequence's start).

The backward kernel keeps **x, w and bias alone**: it computes the
pre-activation again (the rows in front of a block are not in VMEM when the
blocks come last first: a second window on x reads the sixteen rows before
the block), takes the cotangent through silu in float32 into a second
scratch, whose rows s BELOW a token (the first of the block after kept
behind the block's) give dx, the taps' sum, and, against the row as it
stands, the taps' gradient: dw[k - 1 - s] = sum_t dpre_{t + s} x_t. dw and
dbias are summed in float32 over the walk, eight partial sums a channel in
registers and VMEM, and written once a tile and batch element (``[batch, 8,
width]`` float32: rows 0 .. k - 1 the taps, row k the bias; XLA adds the
batch). No float32 ``[tokens x channels]`` array crosses HBM.
"""

from __future__ import annotations

import functools
from typing import Optional

from .. import perfvars
from .pallas_kernels import (LANE, SUBLANE, _compiler_params, _interpret,
                             _pl, _pltpu, _typed, _vary_together,
                             _varying_like)

CONV_TAPS = (2, 3, 4)               # what a Mamba or delta-rule layer has
_CONV_BLOCKS = (1024, 512, 256, 128)    # tokens a grid step
_CONV_TILES = (4, 2, 1)             # lane tiles of channels a grid step
_ROWS = 64                          # tokens of the inner loop's body
_HALO = 2 * SUBLANE                 # rows of the backward's second window
#                                     on x: a bfloat16 tile's
_LOG2_E = 1.4426950408889634


def conv_silu_blocks(t: int, width: int, taps: int, dtype, start: int = 0,
                     cuts: tuple = ()) -> Optional[tuple]:
    """Where :func:`conv_silu` takes a sequence of ``t`` tokens, ``width``
    channels from column ``start`` of their row and ``taps`` taps of
    ``dtype``, cut into parts at ``cuts``: a (first column, width, tokens
    and channels of a grid step) a part, else None. The contract, decided
    from the shapes and the type."""
    bounds = (0, *cuts, width)
    if not _typed(dtype) or taps not in CONV_TAPS \
            or any(at % LANE for at in (start, *bounds)):
        return None
    tokens = next((n for n in _CONV_BLOCKS if t % n == 0), None)
    return tokens and tuple(
        (start + lo, hi - lo, tokens, _tile(start + lo, hi - lo))
        for lo, hi in zip(bounds, bounds[1:]))


def _tile(start: int, width: int) -> int:
    """The widest tile of channels that divides a part and its offset."""
    return next(m * LANE for m in _CONV_TILES
                if width % (m * LANE) == 0 and start % (m * LANE) == 0)


def _groups(tokens: int, body, carry=None, back: bool = False):
    """``body(r0, carry)`` for r0 = 0, _ROWS, .. in order (``back``: last
    first)."""
    import jax
    import jax.numpy as jnp
    pl = _pl()
    n = tokens // _ROWS

    def group(g, carry):
        g = n - 1 - g if back else g
        return body(pl.multiple_of(g * _ROWS, _ROWS), carry)
    # (int32 bounds: under jax_enable_x64 a Python bound makes the index 64
    # bits wide, and Mosaic has no 64-bit scalars)
    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(n), group, carry)


def _taps_of(taps_ref, k: int, lanes):
    """A lane tile's taps and bias, each laid over a group's rows."""
    import jax.numpy as jnp
    return [jnp.broadcast_to(taps_ref[j:j + 1, lanes], (_ROWS, LANE))
            for j in range(k + 1)]


def _pre(xf, lane: int, r0, w, k: int):
    """The pre-activation of the group at ``r0``: bias + sum over the taps
    of the rows s above, float32. ``xf[lane]``: a lane tile of the block's
    rows in float32 behind the eight before them."""
    near = xf.at[lane, _pl().ds(r0, SUBLANE + _ROWS), :]
    out = w[k]
    for s in range(k):
        out = out + w[k - 1 - s] * near[SUBLANE - s:SUBLANE - s + _ROWS, :]
    return out


def _sigmoid(pre, exact: bool):
    """1 / (1 + exp(-pre)) in float32. ``exact``: a float32 division, for
    a result stored as float32. Else the EUP's reciprocal and one Newton
    step (what a division is, without its care for zeros, infinities and
    subnormal numbers: a third of a group's arithmetic), the exponential
    held under float32's largest, where the result is 0 to 35 digits."""
    import jax.numpy as jnp
    if exact:
        return 1.0 / (1.0 + jnp.exp(-pre))
    d = 1.0 + jnp.exp2(jnp.minimum(pre * -_LOG2_E, 115.0))
    r = _pl().reciprocal(d, approx=True)
    return r * (2.0 - d * r)


def _conv_fwd_kernel(k: int, x_ref, taps_ref, out_ref, xf):
    """One block of tokens of one tile of channels, forward. Scratch: ``xf``
    [lane tiles, 8 + tokens, 128] float32, the block's rows behind the last
    eight of the block before."""
    import jax.numpy as jnp
    pl = _pl()
    f32 = jnp.float32
    tokens, tile = x_ref.shape[1:]
    exact = out_ref.dtype == f32

    @pl.when(pl.program_id(2) == 0)
    def _first_block():
        xf[:, :SUBLANE] = jnp.zeros((tile // LANE, SUBLANE, LANE), f32)

    for lane in range(tile // LANE):
        lanes = slice(lane * LANE, (lane + 1) * LANE)
        w = _taps_of(taps_ref, k, lanes)

        def group(r0, _, lane=lane, lanes=lanes, w=w):
            at = pl.ds(r0, _ROWS)
            xf[lane, pl.ds(r0 + SUBLANE, _ROWS), :] = x_ref[
                0, at, lanes].astype(f32)
            pre = _pre(xf, lane, r0, w, k)
            out_ref[0, at, lanes] = (pre * _sigmoid(pre, exact)).astype(
                out_ref.dtype)
        _groups(tokens, group)
    xf[:, :SUBLANE] = xf[:, tokens:]


def _conv_bwd_kernel(k: int, x_ref, halo_ref, dy_ref, taps_ref, dx_ref,
                     dtaps_ref, xf, dpf, sums):
    """One block of tokens of one tile of channels, backward; the blocks
    come last first. ``halo_ref``: the sixteen rows of x in front of the
    block. Scratch: ``xf`` as forward; ``dpf`` [lane tiles, tokens + 8, 128]
    float32, the pre-activation's cotangent in the block's rows and in the
    first eight of the block after; ``sums`` [(k + 1) x 8, tile] float32, dw's
    and dbias' partial sums, eight a channel."""
    import jax.numpy as jnp
    pl = _pl()
    f32 = jnp.float32
    tokens, tile = x_ref.shape[1:]
    exact = dx_ref.dtype == f32
    step, steps = pl.program_id(2), pl.num_programs(2)

    @pl.when(step == 0)
    def _last_block():
        dpf[:, :SUBLANE] = jnp.zeros((tile // LANE, SUBLANE, LANE), f32)
        sums[...] = jnp.zeros(sums.shape, f32)

    dpf[:, tokens:] = dpf[:, :SUBLANE]
    # zeros in front of the sequence's first block, which comes last
    inside = (step < steps - 1).astype(f32)
    for lane in range(tile // LANE):
        lanes = slice(lane * LANE, (lane + 1) * LANE)
        w = _taps_of(taps_ref, k, lanes)
        xf[lane, :SUBLANE, :] = halo_ref[0, _HALO - SUBLANE:, lanes].astype(
            f32) * inside

        def widen(r0, _, lane=lane, lanes=lanes):
            xf[lane, pl.ds(r0 + SUBLANE, _ROWS), :] = x_ref[
                0, pl.ds(r0, _ROWS), lanes].astype(f32)
        _groups(tokens, widen)

        def group(r0, acc, lane=lane, lanes=lanes, w=w):
            at = pl.ds(r0, _ROWS)
            pre = _pre(xf, lane, r0, w, k)
            sig = _sigmoid(pre, exact)
            dpre = dy_ref[0, at, lanes].astype(f32) * (
                sig * (1.0 + pre * (1.0 - sig)))
            dpf[lane, at, :] = dpre
            cur = xf[lane, pl.ds(r0 + SUBLANE, _ROWS), :]
            acc = list(acc)
            dx, near = None, dpf.at[lane, pl.ds(r0, _ROWS + SUBLANE), :]
            for s in range(k):
                moved = near[s:s + _ROWS, :] if s else dpre
                part = w[k - 1 - s] * moved
                dx = part if dx is None else dx + part
                acc[k - 1 - s] = acc[k - 1 - s] + _by_sublane(moved * cur)
            acc[k] = acc[k] + _by_sublane(dpre)
            dx_ref[0, at, lanes] = dx.astype(dx_ref.dtype)
            return tuple(acc)
        acc = _groups(tokens, group, tuple(
            sums[j * SUBLANE:(j + 1) * SUBLANE, lanes]
            for j in range(k + 1)), back=True)
        for j in range(k + 1):
            sums[j * SUBLANE:(j + 1) * SUBLANE, lanes] = acc[j]

    @pl.when(step == steps - 1)
    def _first_block():
        dtaps_ref[0] = jnp.concatenate(
            [jnp.sum(sums[j * SUBLANE:(j + 1) * SUBLANE, :], axis=0,
                     keepdims=True) for j in range(k + 1)]
            + [jnp.zeros((SUBLANE - k - 1, tile), f32)], axis=0)


def _by_sublane(v):
    """[8, 128]: ``v``'s rows summed eight apart."""
    return sum(v[i:i + SUBLANE] for i in range(0, v.shape[0], SUBLANE))


def _conv_vmem(tokens: int, tile: int, itemsize: int) -> int:
    """The backward kernel's blocks (twice: pipelined) and scratch."""
    blocks = (3 * tokens + _HALO) * tile * itemsize + 2 * SUBLANE * tile * 4
    return 2 * blocks + (2 * (tokens + SUBLANE)
                         + (1 + max(CONV_TAPS)) * SUBLANE) * tile * 4


@functools.lru_cache(maxsize=None)
def _conv_silu_fn(k: int, start: int, width: int, tokens: int, tile: int,
                  interpret: Optional[bool]):
    """The differentiable convolution of ``width`` channels from column
    ``start`` with ``k`` taps, ``tokens`` x ``tile`` a grid step, jitted
    once: the layers of a step share one trace and one lowering a
    direction."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pl, pltpu = _pl(), _pltpu()
    zero = np.int32(0)
    f32 = jnp.float32

    def call(back: bool, x, taps, *dy):
        bsz, t, _ = x.shape
        nb, first = t // tokens, np.int32(start // tile)
        last = np.int32(nb - 1)

        def block(i):       # backward walks the blocks last first
            return last - i if back else i
        wide = pl.BlockSpec((1, tokens, tile),
                            lambda bi, ci, i: (bi, block(i), first + ci))
        rows = pl.BlockSpec((1, tokens, tile),
                            lambda bi, ci, i: (bi, block(i), ci))
        by_tile = pl.BlockSpec((SUBLANE, tile), lambda bi, ci, i: (zero, ci))
        narrow = _varying_like(x, (bsz, t, width), x.dtype)
        staged = pltpu.VMEM((tile // LANE, tokens + SUBLANE, LANE), f32)
        if back:
            per = np.int32(tokens // _HALO)
            halo = pl.BlockSpec(
                (1, _HALO, tile), lambda bi, ci, i: (
                    bi, jnp.maximum(block(i) * per - 1, 0), first + ci))
            in_specs = [wide, halo, rows, by_tile]
            operands = (x, x, *dy, taps)
            out_specs = [rows, pl.BlockSpec((1, SUBLANE, tile),
                                            lambda bi, ci, i: (bi, zero, ci))]
            out_shape = [narrow, _varying_like(x, (bsz, SUBLANE, width), f32)]
            scratch = [staged, staged,
                       pltpu.VMEM(((k + 1) * SUBLANE, tile), f32)]
        else:
            in_specs, operands = [wide, by_tile], (x, taps)
            out_specs, out_shape = rows, narrow
            scratch = [staged]
        name = "conv_silu_bwd" if back else "conv_silu_fwd"
        perfvars.note_kernel_build(name)
        return pl.pallas_call(
            functools.partial(_conv_bwd_kernel if back else _conv_fwd_kernel,
                              k),
            grid=(bsz, width // tile, nb),
            in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch, interpret=_interpret(interpret),
            compiler_params=_compiler_params(
                None, _conv_vmem(tokens, tile, x.dtype.itemsize),
                "conv_silu", ("parallel", "parallel", "arbitrary")),
            name=name)(*operands)

    def as_tile(w, bias):
        """[8, width] float32: the taps, the bias, zeros."""
        return jnp.concatenate(
            [w.astype(f32), bias.astype(f32)[None],
             jnp.zeros((SUBLANE - k - 1, width), f32)], axis=0)

    @jax.custom_vjp
    def conv(x, w, bias):
        return call(False, x, as_tile(w, bias))

    def fwd(x, w, bias):
        return call(False, x, as_tile(w, bias)), (x, w, bias)

    def bwd(kept, dy):
        x, w, bias = kept
        dx, dtaps = call(True, x, as_tile(w, bias), dy)
        dtaps = dtaps.sum(axis=0)
        if dx.shape != x.shape:     # the other columns' cotangent: zeros
            dx = jnp.pad(dx, ((0, 0), (0, 0),
                              (start, x.shape[-1] - start - width)))
        return dx, dtaps[:k].astype(w.dtype), dtaps[k].astype(bias.dtype)
    conv.defvjp(fwd, bwd)
    return jax.jit(conv)


def conv_silu(x, w, bias=None, *, start: int = 0, cuts: tuple = (),
              interpret: Optional[bool] = None):
    """silu(causal_conv(x[..., start:start + width], w, bias)) [batch, t,
    width], rounded once to x's type: x [batch, t, columns], w [taps,
    width], bias [width] (None: no bias). ``cuts``: the result as a list of
    its parts, cut at these channels as `jnp.split` cuts: each part is a
    call of its own over its columns. The backward pass (``custom_vjp``) is
    one kernel a part that keeps x, w and bias alone and computes the
    pre-activation again; dx is zeros outside the columns."""
    import jax.numpy as jnp
    k, width = w.shape
    parts = conv_silu_blocks(x.shape[1], width, k, x.dtype, start, cuts)
    if parts is None or start + width > x.shape[-1]:
        raise ValueError(
            f"conv_silu: {width} channels from column {start} of x "
            f"{x.shape} {x.dtype} with {k} taps, cut at {cuts}, are outside "
            f"the kernel's contract (float32 or bfloat16, channels in tiles "
            f"of {LANE} lanes, {CONV_TAPS} taps, tokens in blocks of "
            f"{_CONV_BLOCKS[-1]})")
    if bias is None:
        bias = jnp.zeros((width,), jnp.float32)
    x, w, bias = _vary_together(x, w, bias)
    outs = [_conv_silu_fn(k, at, n, tokens, tile, interpret)(
        x, w[:, at - start:at - start + n], bias[at - start:at - start + n])
        for at, n, tokens, tile in parts]
    return outs if cuts else outs[0]
