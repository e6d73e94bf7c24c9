"""A delta-rule mixer's per-head norms as one Pallas kernel each way over the
rows the convolution writes and the scan reads: for x [batch, t, heads x 128],

    out[.., h, :] = x[.., h, :] * rsqrt(sum_or_mean(x[.., h, :]^2) + eps)
                    * scale * gate

with the statistics, the products and the gate in float32 and ONE rounding at
the store. Two uses of the one body (`models/transformer.py`):

- :func:`l2_norm`: the sum, ``scale`` a constant, no gate: q's and k's L2
  norm behind the convolution (`_l2_normed`), q's ``key width ** -0.5`` in
  the same pass.
- :func:`gated_rms_norm`: the mean, ``scale`` a 128-wide leaf, ``gate`` an
  activation of a pre-activation that either enters as rows like x (silu(z),
  a `gdn` layer) or is a product taken here, ``g_in [batch, t, rank] @ w
  [rank, heads x 128]`` (sigmoid, a `kda` layer: operands of the input's
  type, float32 accumulation; the MXU is idle otherwise): the norm of the
  scan's output in front of the out-projection (`_head_norm_gated`).

A head IS one 128-lane tile of the row, so a head's sum is a lane reduction
of a tile the kernel holds anyway: nothing is laid out again, where XLA's
form over ``[batch, t, heads, 128]`` re-lays every operand (under the (8, 128)
tiling that reshape is no bitcast: a tile holds 8 tokens of one head as rows,
8 heads of one token as four dimensions) and its cotangent, in float32.

A grid step is a block of tokens (:data:`_NORM_BLOCKS`, the largest that
divides the sequence and keeps a block under :data:`_BLOCK_BYTES`) of WHOLE
rows; inside, a loop over the heads (a dynamic lane offset in 128s) takes the
tokens :data:`_ROWS` at a time, a group's values in registers. The kernels
are bound by HBM: four bytes an element forward (a row read, a row written;
six with z), six to ten backward.

The backward kernel keeps the kernel's **inputs alone** (x, the scale, the
gate's operands), computes the statistics and the gate again, and returns
every cotangent: dx as rows; dz as rows or, of the product, d g_in a block
(the heads' sum, one product a head over the block's tokens) and d w summed
over the token blocks in its float32 output block, which stays in VMEM while
a sequence's blocks run; d scale likewise, eight partial sums a lane (XLA
adds them and the batch). No float32 ``[tokens x channels]`` array crosses
HBM in either direction.
"""

from __future__ import annotations

import functools
from typing import Optional

from .. import perfvars
from .conv_kernels import _by_sublane, _sigmoid
from .pallas_kernels import (LANE, SUBLANE, _attn_precision, _compiler_params,
                             _interpret, _pl, _pltpu, _typed, _vary_together,
                             _varying_like)

HEAD_WIDTH = LANE                   # a head is one lane tile of the row
GATES = ("silu", "sigmoid")
_NORM_BLOCKS = (512, 256, 128)      # tokens a grid step
_BLOCK_BYTES = 2 * 1024 * 1024      # of one operand's block, at most
_ROWS = 64                          # tokens of the inner loop's body


def head_norm_blocks(t: int, width: int, head: int, dtype,
                     rank: int = 0) -> Optional[int]:
    """The tokens of a grid step where the kernels take rows of ``t`` tokens
    and ``width`` channels of ``dtype`` in heads of ``head``, the gate's
    pre-activation a product over ``rank`` (0: none, or rows), else None. The
    contract, decided from the shapes and the type."""
    size = _typed(dtype)
    if not size or head != HEAD_WIDTH or width % head or rank % LANE:
        return None
    return next((n for n in _NORM_BLOCKS
                 if t % n == 0 and n * width * size <= _BLOCK_BYTES), None)


def _heads(width: int, body, carry=None):
    """``body(lanes, carry)`` for the lanes of each head of the row in turn."""
    import jax
    import jax.numpy as jnp
    pl = _pl()

    def head(h, carry):
        return body(pl.ds(pl.multiple_of(h * LANE, LANE), LANE), carry)
    # (int32 bounds: under jax_enable_x64 a Python bound makes the index 64
    # bits wide, and Mosaic has no 64-bit scalars)
    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(width // LANE), head,
                             carry)


def _normed(x, mean: bool, eps: float):
    """(x / sqrt(sum or mean of x^2 + eps), the reciprocal root [rows, 1]) of
    a head's rows, float32."""
    import jax
    import jax.numpy as jnp
    total = jnp.sum(x * x, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(total * (1.0 / LANE if mean else 1.0) + eps)
    return x * r, r


def _product(g, w, dims=(((1,), (0,)), ((), ()))):
    """g w in float32: operands of the input's type, float32 ones as exact
    as float32 is."""
    import jax
    import jax.numpy as jnp
    return jax.lax.dot_general(g, w, dims, precision=_attn_precision(g.dtype),
                               preferred_element_type=jnp.float32)


def _pre(gate_refs, at, lanes):
    """The gate's pre-activation of a group of a head's rows, float32: the
    rows as they are, or the product's."""
    import jax.numpy as jnp
    if len(gate_refs) == 1:
        return gate_refs[0][0, at, lanes].astype(jnp.float32)
    g_ref, w_ref = gate_refs
    return _product(g_ref[0, at, :], w_ref[:, lanes])


def _norm_fwd_kernel(mean: bool, eps: float, act: Optional[str], x_ref,
                     scale_ref, *refs):
    """One block of tokens, forward. ``refs``: the gate's operands (z, or
    g_in and w), then the result."""
    import jax.numpy as jnp
    pl = _pl()
    f32 = jnp.float32
    *gate_refs, out_ref = refs
    tokens, width = x_ref.shape[1:]
    exact = out_ref.dtype == f32
    scale = jnp.broadcast_to(scale_ref[...], (_ROWS, LANE))

    def head(lanes, _):
        for r0 in range(0, tokens, _ROWS):
            at = pl.ds(r0, _ROWS)
            y = _normed(x_ref[0, at, lanes].astype(f32), mean, eps)[0] * scale
            if act:
                pre = _pre(gate_refs, at, lanes)
                sig = _sigmoid(pre, exact)
                y = y * (pre * sig if act == "silu" else sig)
            out_ref[0, at, lanes] = y.astype(out_ref.dtype)
    _heads(width, head)


def _norm_bwd_kernel(mean: bool, eps: float, act: Optional[str],
                     product: bool, x_ref, scale_ref, *refs):
    """One block of tokens, backward. ``refs``: the gate's operands, the
    result's cotangent; dx, the gate's operands' cotangents (dz, or d g_in
    and d w [1, rank, width] float32, summed over a sequence's blocks) and d
    scale [1, 8, 128] float32 (eight partial sums a lane, summed likewise);
    of a product, scratch: the pre-activation's cotangent of a head's rows
    [tokens, 128] of the input's type, d g_in [tokens, rank] float32 and
    g_in turned, [rank, tokens]."""
    import jax.numpy as jnp
    pl = _pl()
    f32 = jnp.float32
    tokens, width = x_ref.shape[1:]
    n_gate = 2 if product else 1 if act else 0
    gate_refs, dy_ref, dx_ref = refs[:n_gate], refs[n_gate], refs[n_gate + 1]
    rest = refs[n_gate + 2:]
    if product:
        dg_ref, dw_ref, dscale_ref, dp, dg, turned = rest
    elif act:
        dz_ref, dscale_ref = rest
    exact = dx_ref.dtype == f32
    scale = jnp.broadcast_to(scale_ref[...], (_ROWS, LANE))
    share = 1.0 / LANE if mean else 1.0

    if act:
        @pl.when(pl.program_id(1) == 0)
        def _first_block():
            dscale_ref[...] = jnp.zeros(dscale_ref.shape, f32)
            if product:
                dw_ref[...] = jnp.zeros(dw_ref.shape, f32)
    if product:     # g_in turned once a block: d w's products contract
        #             the tokens
        dg[...] = jnp.zeros(dg.shape, f32)
        turned[...] = gate_refs[0][0].astype(f32).T.astype(turned.dtype)

    def head(lanes, dscale):
        for r0 in range(0, tokens, _ROWS):
            at = pl.ds(r0, _ROWS)
            xr, r = _normed(x_ref[0, at, lanes].astype(f32), mean, eps)
            dy = dy_ref[0, at, lanes].astype(f32)
            by = dy * xr                                    # d (scale gate)
            through = scale
            if act:
                pre = _pre(gate_refs, at, lanes)
                sig = _sigmoid(pre, exact)
                gate = pre * sig if act == "silu" else sig
                slope = sig * (1.0 + pre * (1.0 - sig)) if act == "silu" \
                    else sig * (1.0 - sig)
                dpre = by * scale * slope
                if product:
                    dp[at, :] = dpre.astype(dp.dtype)
                else:
                    dz_ref[0, at, lanes] = dpre.astype(dz_ref.dtype)
                dscale = dscale + _by_sublane(by * gate)
                through = scale * gate
            # y = xr through: dx = r (dy through - xr mean_or_sum(dy through xr))
            along = jnp.sum(by * through, axis=-1, keepdims=True) * share
            dx_ref[0, at, lanes] = (r * (dy * through - xr * along)).astype(
                dx_ref.dtype)
        if product:     # the head's share of d g_in, its columns of d w
            dg[...] += _product(dp[...], gate_refs[1][:, lanes],
                                (((1,), (1,)), ((), ())))
            dw_ref[0, :, lanes] += _product(turned[...], dp[...])
        return dscale
    dscale = _heads(width, head, jnp.zeros((SUBLANE, LANE), f32))
    if act:
        dscale_ref[0] += dscale
    if product:
        dg_ref[0] = dg[...].astype(dg_ref.dtype)


def _norm_vmem(tokens: int, width: int, itemsize: int, rows: int,
               rank: int) -> int:
    """A kernel's blocks (twice: pipelined) and scratch: ``rows`` arrays of
    the rows' shape; of a product over ``rank`` its operands and, backward,
    their cotangents."""
    blocks = rows * tokens * width * itemsize + (
        tokens * rank * itemsize * 2 + rank * width * (itemsize + 4))
    return 2 * blocks + tokens * (LANE * itemsize + rank * (itemsize + 4))


@functools.lru_cache(maxsize=None)
def _head_norm_fn(mean: bool, eps: float, act: Optional[str], tokens: int,
                  interpret: Optional[bool]):
    """The differentiable norm of rows in blocks of ``tokens``. Its forward
    and its backward are each jitted once, outside the ``custom_vjp`` (as
    `pallas_kernels._grouped_matmul_fn`'s are): the layers of a step, and
    the primal and the forward rule of each, share one trace of a kernel's
    body and one lowering a direction. (Jitted around the ``custom_vjp``
    instead, the primal and the rule trace the forward once each.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pl, pltpu = _pl(), _pltpu()
    zero = np.int32(0)
    f32 = jnp.float32
    what = "head_gated_norm" if act else "head_l2_norm"

    def call(back: bool, x, scale, *rest):
        bsz, t, width = x.shape
        product = len(rest) - back == 2     # the gate's two operands
        rank = rest[0].shape[-1] if product else 0
        rows = pl.BlockSpec((1, tokens, width), lambda bi, i: (bi, i, zero))
        lane = pl.BlockSpec((1, LANE), lambda bi, i: (zero, zero))
        low = pl.BlockSpec((1, tokens, rank), lambda bi, i: (bi, i, zero))
        leaf = pl.BlockSpec((rank, width), lambda bi, i: (zero, zero))
        gate_specs = [low, leaf] if product else [rows] if act else []
        like_x = _varying_like(x, x.shape, x.dtype)
        in_specs = [rows, lane, *gate_specs] + [rows] * back
        scratch = []
        if not back:
            out_specs, out_shape = rows, like_x
        else:
            out_specs, out_shape = [rows], [like_x]
            if product:
                out_specs += [low, pl.BlockSpec(
                    (1, rank, width), lambda bi, i: (bi, zero, zero))]
                out_shape += [_varying_like(x, rest[0].shape, rest[0].dtype),
                              _varying_like(x, (bsz, rank, width), f32)]
                scratch = [pltpu.VMEM((tokens, LANE), x.dtype),
                           pltpu.VMEM((tokens, rank), f32),
                           pltpu.VMEM((rank, tokens), x.dtype)]
            elif act:
                out_specs.append(rows)
                out_shape.append(_varying_like(x, x.shape, rest[0].dtype))
            if act:
                out_specs.append(pl.BlockSpec((1, SUBLANE, LANE),
                                              lambda bi, i: (bi, zero, zero)))
                out_shape.append(_varying_like(x, (bsz, SUBLANE, LANE), f32))
        name = what + ("_bwd" if back else "_fwd")
        perfvars.note_kernel_build(name)
        gate_rows = bool(act and not product)
        n_rows = 3 + 2 * gate_rows if back else 2 + gate_rows
        return pl.pallas_call(
            functools.partial(_norm_bwd_kernel, mean, eps, act, product)
            if back else functools.partial(_norm_fwd_kernel, mean, eps, act),
            grid=(bsz, t // tokens),
            in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch, interpret=_interpret(interpret),
            compiler_params=_compiler_params(
                None, _norm_vmem(tokens, width, x.dtype.itemsize, n_rows,
                                 rank), what,
                ("parallel", "arbitrary" if back and act else "parallel")),
            name=name)(x, scale, *rest)

    forward = jax.jit(functools.partial(call, False))
    backward = jax.jit(functools.partial(call, True))

    @jax.custom_vjp
    def norm(x, scale, *gate_from):
        return forward(x, scale, *gate_from)

    def fwd(x, scale, *gate_from):
        return forward(x, scale, *gate_from), (x, scale, *gate_from)

    def bwd(kept, dy):
        if not act:
            return backward(*kept, dy)[0], jnp.zeros_like(kept[1])
        dx, *d_gate, dscale = backward(*kept, dy)
        if len(d_gate) == 2:    # the product's leaf: the batch's sum
            d_gate[1] = d_gate[1].sum(axis=0).astype(kept[3].dtype)
        return (dx, dscale.sum(axis=(0, 1))[None], *d_gate)
    norm.defvjp(fwd, bwd)
    return norm


def _norm(x, scale, gate_from: tuple, mean: bool, eps: float,
          act: Optional[str], interpret: Optional[bool]):
    """The kernel pair's call for rows x, a scale [128] and the gate's
    operands, the contract checked."""
    import jax.numpy as jnp
    product = len(gate_from) == 2
    rank = gate_from[0].shape[-1] if product else 0
    tokens = x.ndim == 3 and head_norm_blocks(
        x.shape[1], x.shape[2], HEAD_WIDTH, x.dtype, rank)
    if product:
        g, w = gate_from
        gated = g.shape[:2] == x.shape[:2] and w.shape == (rank, x.shape[2]) \
            and g.dtype == w.dtype == x.dtype
    else:
        gated = all(z.shape == x.shape and z.dtype == x.dtype
                    for z in gate_from)
    if not (tokens and gated and scale.shape == (HEAD_WIDTH,)):
        raise ValueError(
            f"head norm: x {x.shape} {x.dtype}, a scale {scale.shape} and "
            f"the gate's {[(g.shape, str(g.dtype)) for g in gate_from]} are "
            f"outside the kernel's contract (rows [batch, t, heads x "
            f"{HEAD_WIDTH}] of float32 or bfloat16, tokens in blocks of "
            f"{_NORM_BLOCKS[-1]}, a scale a lane, the gate from rows like x "
            f"or from [batch, t, rank] x [rank, heads x {HEAD_WIDTH}] of "
            f"x's type, the rank in {LANE}s)")
    fn = _head_norm_fn(mean, float(eps), act, tokens, interpret)
    return fn(*_vary_together(x, scale.astype(jnp.float32)[None],
                              *gate_from))


def l2_norm(x, *, scale: float = 1.0, eps: float = 1e-6,
            interpret: Optional[bool] = None):
    """x [batch, t, heads x 128] with each head's 128 values divided by
    sqrt(their squares' sum + eps) and scaled by the constant ``scale``,
    float32 inside, rounded once to x's type. The backward pass
    (``custom_vjp``) is one kernel that keeps x alone."""
    import jax.numpy as jnp
    return _norm(x, jnp.full((HEAD_WIDTH,), scale, jnp.float32), (), False,
                 eps, None, interpret)


def gated_rms_norm(x, scale, *gate_from, act: str, eps: float,
                   interpret: Optional[bool] = None):
    """RMSNorm over each head's 128 values of x [batch, t, heads x 128]
    (``scale`` [128], ``eps``) times ``act`` ("silu" or "sigmoid") of the
    gate's pre-activation, norm first and gate after, float32 inside,
    rounded once to x's type. ``gate_from``: the pre-activation as rows like
    x (z), or the two operands of the product that gives it (g_in [batch, t,
    rank], w [rank, heads x 128], of x's type: the product is taken in the
    kernel, float32 accumulation). The backward pass (``custom_vjp``) is one
    kernel that keeps x, the scale and ``gate_from`` alone and returns all
    their cotangents."""
    if act not in GATES or len(gate_from) not in (1, 2):
        raise ValueError(f"head norm: a gate is {GATES} of rows or of a "
                         f"product's two operands, not {act!r} of "
                         f"{len(gate_from)}")
    return _norm(x, scale, gate_from, True, eps, act, interpret)
