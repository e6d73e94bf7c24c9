"""State-space (Mamba-2) sequence mixing: the selective scan in its chunked
form, and the short causal convolution in front of it.

The recurrence, a head, with a state ``S`` of [head width, state width]:

    S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T        y_t = S_t C_t + D x_t

``A`` < 0 is one scalar a head, ``dt`` > 0 one a head and token, ``B`` and
``C`` one vector a token that all heads share (one group). :func:`scan` is
the one "state-space scan of a block" the model calls, as
``ring.local_attention`` is its attention: it computes the recurrence a
chunk of ``chunk`` tokens at a time. Inside a chunk the outputs are the
masked product ``(L o C B^T)(dt x)`` with ``L_ts = exp(sum_{s<r<=t} dt_r A)``
(s <= t, 0 above the diagonal); each chunk leaves a state, the states run
through the short recurrence over the chunks, and each chunk reads the state
before it. All decay arithmetic (the cumulative sums, the exponentials, the
states) is float32; the products take operands of the input's type and
accumulate in float32. For the backward pass the inputs and the chunks'
states are kept and ``L`` (heads x chunks x chunk x chunk float32) is
computed again: `jax.checkpoint` with a policy that saves the states alone.

The form is chosen from the shapes, never by trying, and counted where it is
chosen (``perfvars.snapshot()["scan_lowerings"]``): ``chunked`` where the
sequence is a multiple of the chunk (a sequence shorter than a chunk is one
chunk), ``padded`` where it is not: the sequence is filled up to the next
multiple with tokens of ``dt`` = 0, which decay nothing and add nothing, so
the result is exact, and their outputs are cut off.

Who computes that form is chosen the same way, after the padding, and
counted beside it (``["scan_kernel_lowerings"]``, ``kernel`` or ``plain``,
one count a traced scan). ``kernel``: the Pallas pair of
``xla/ssm_kernels.py``, forward and one backward (``jax.custom_vjp``), where
a kernel backend is there (`xla.choice.backend`: a TPU; the tests' word
selects the interpret machine) and the shapes fit its tiles: float32 or
bfloat16, heads 64 wide in a multiple of 8, a state of 128 or 256, a chunk
of 128 or 256 tokens (granite's cell: 64 heads of 64, state 128, chunk
256). There ``L``, ``L o C B^T`` and the running state live in VMEM alone,
the chunks are walked in order with the state carried (backward: in reverse
with its cotangent), x and y cross HBM as [batch, t, heads x width] rows,
and the backward pass keeps x, dt, the sums, B, C and the state BEFORE each
chunk ([batch, chunks, state, heads x width] float32: as many bytes as the
states above) and computes ``L`` again inside the kernel. ``plain``:
:func:`_chunked` below, as it stands, everywhere else (the CPU, odd
widths); it is the fallback and the yardstick that
`tests/test_ssm_kernel.py` holds the kernels to.

:func:`selective_scan` is the older (Mamba-1) recurrence beside it: one state
value a CHANNEL and state index, each with a decay of its own,

    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
    y_t[c] = sum_n C_t[n] S_t[c, n] + D[c] x_t[c]

``A`` [channels, state] < 0, ``dt`` > 0 one a channel and token. A decay that
differs a channel AND a state index does not factor into a chunk's matrix
products, so it is the recurrence as it stands, on the VPU and the EUP, the
[state, channels] float32 state carried token by token. ``kernel`` (a kernel
backend; float32 or bfloat16, channels in 512s, a state of 16): the Pallas
pair of ``xla/sel_scan_kernels.py``, which keeps the state BEFORE each block
of 256 or 128 tokens and holds a block's token states in VMEM alone. ``plain``
(everywhere else; the tests' yardstick): :func:`_selective_chunks`, `lax.scan`
over chunks and tokens, `jax.checkpoint` a chunk. Counted as ``chunked`` or
``padded`` (``sel_scan_lowerings``) and by who (``sel_scan_kernel_lowerings``).

:func:`conv_silu` is the convolution in front of either scan (and of the
delta rule's, `parallel/delta.py`) with the silu behind it, as one call: k
taps over a token and the k - 1 before it, a channel at a time, and who
computes it is chosen and counted as the scans' are
(``["conv_kernel_lowerings"]``, ``kernel`` or ``plain``, one count a traced
call). ``kernel``: the Pallas pair of ``xla/conv_kernels.py`` where a kernel
backend is there and the shapes fit: float32 or bfloat16, 2 to 4 taps, the
channels, their first column in the row they are read from and the places
the result is cut at in 128s (a tile's lanes), the tokens a multiple of 128
(a block is the largest of 1024, 512, 256 and 128 that divides them). It
reads its channels where they stand in a wider row (a mixer's in-projection
gives the gate and the convolved channels as one product) and writes each
part the caller cuts as an array of its own, so no copy stands beside it;
the taps' sum and the silu are float32 with ONE rounding at the store (the
plain path rounds the sum, then the silu), and the backward pass keeps x, w
and bias alone: the row and the cotangent read, one row written, dw and
dbias summed in VMEM. ``plain``: `jax.nn.silu` of :func:`causal_conv`, as it
stands, everywhere else (the CPU, odd widths, a sequence no block divides);
what `tests/test_conv_kernel.py` holds the kernels to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .. import perfvars
from ..xla import choice, conv_kernels, sel_scan_kernels, ssm_kernels

STATES = "ssm_chunk_states"     # what the backward pass keeps of `_chunked`


def causal_conv(x: jnp.ndarray, w: jnp.ndarray, bias: jnp.ndarray):
    """Depthwise causal convolution over the sequence: x [b, t, c], w [k, c]
    (tap k - 1 weighs the token itself, tap 0 the one k - 1 before it), bias
    [c]; a token sees itself and the k - 1 before it, zeros before the
    sequence's start. k shifted products summed in float32, rounded once."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = bias.astype(jnp.float32)
    for j in range(k):
        out = out + padded[:, j:j + t].astype(jnp.float32) * w[j].astype(
            jnp.float32)
    return out.astype(x.dtype)


def conv_silu(x: jnp.ndarray, w: jnp.ndarray, bias=None, *, start: int = 0,
              cuts: tuple = ()):
    """silu(causal_conv(x[..., start:start + channels], w, bias)): x [b, t,
    columns], w [k, channels], bias [channels] (None: no bias). ``cuts``:
    the result as the list of its parts, cut at these channels as
    `jnp.split` cuts. Each call built into a traced program counts in
    ``perfvars.snapshot()["conv_kernel_lowerings"]`` as ``kernel``
    (`conv_kernels.conv_silu`) or ``plain``."""
    (k, width), cuts = w.shape, tuple(cuts)
    run = choice.decide(choice.CONV, x.shape[1], width, k, x.dtype, start,
                        cuts)
    if run:
        return conv_kernels.conv_silu(x, w, bias, start=start, cuts=cuts,
                                      interpret=run.interpret)
    out = jax.nn.silu(causal_conv(
        x[..., start:start + width], w,
        jnp.zeros((), jnp.float32) if bias is None else bias))
    return jnp.split(out, cuts, axis=-1) if cuts else out


def scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
         c: jnp.ndarray, d: jnp.ndarray, chunk: int = 256) -> jnp.ndarray:
    """y [batch, t, heads, width] of the recurrence above from x [batch, t,
    heads, width], dt [batch, t, heads] (> 0), a [heads] (< 0), b and c
    [batch, t, state] and the skip's d [heads]; dt, a and d float32. Each
    call built into a traced program counts in
    ``perfvars.snapshot()["scan_lowerings"]`` as ``chunked`` or ``padded``,
    and in ``["scan_kernel_lowerings"]`` as ``kernel`` or ``plain``."""
    t = x.shape[1]
    length = min(chunk, t)
    pad = -t % length
    perfvars.note("scan_lowerings", "padded" if pad else "chunked")

    def filled(v):      # up to the next multiple, with tokens of zeros
        widths = ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)
        return jnp.pad(v, widths) if pad else v
    run = choice.decide(choice.SCAN, *x.shape[2:], b.shape[-1], length,
                        x.dtype)
    if run:
        return ssm_kernels.ssm_scan(
            filled(x), filled(dt), a, filled(b), filled(c), d, length=length,
            interpret=run.interpret)[:, :t]
    y = _chunked(filled(x), filled(dt), a, filled(b), filled(c),
                 length)[:, :t]
    return (y + x.astype(jnp.float32) * d[:, None]).astype(x.dtype)


def scan_kernel_selected(shape: tuple, dtype, state: int, length: int) -> bool:
    """Whether :func:`scan` runs the Pallas kernel pair for x of ``shape``
    [batch, t, heads, width] in chunks of ``length`` over a state of
    ``state``: `xla.choice`'s rule over the kernel's contract,
    `ssm_kernels.ssm_scan_selected`."""
    return choice.fit(choice.SCAN, *shape[2:], state, length,
                      dtype) is not None


@functools.partial(
    jax.checkpoint, static_argnums=(5,),
    policy=jax.checkpoint_policies.save_only_these_names(STATES))
def _chunked(x, dt, a, b, c, length: int):
    """The recurrence without its skip term, float32 [batch, t, heads,
    width], t a multiple of ``length``."""
    bsz, t, h, p = x.shape
    n, nc = b.shape[-1], t // length
    f32, dtype = jnp.float32, x.dtype
    dt = dt.reshape(bsz, nc, length, h)
    b, c = (v.reshape(bsz, nc, length, n) for v in (b, c))
    # seg[.., l] = sum over the chunk's tokens r <= l of dt_r A, a head
    seg = jnp.cumsum(dt * a, axis=2).transpose(0, 1, 3, 2)  # [b, c, h, l]
    xdt = x.reshape(bsz, nc, length, h, p).astype(f32) * dt[..., None]

    # inside a chunk: (L o C B^T) (dt x), L masked before its exponential
    scores = jnp.einsum("bcln,bcsn->bcls", c, b, preferred_element_type=f32)
    seen = jnp.tril(jnp.ones((length, length), dtype=bool))
    decay = jnp.exp(jnp.where(seen, seg[..., :, None] - seg[..., None, :],
                              -jnp.inf))                    # [b, c, h, l, s]
    y = jnp.einsum("bchls,bcshp->bclhp",
                   (scores[:, :, None] * decay).astype(dtype),
                   xdt.astype(dtype), preferred_element_type=f32)

    # the state a chunk adds: its tokens' dt x B^T, each decayed to its end
    to_end = jnp.exp(seg[..., -1:] - seg).transpose(0, 1, 3, 2)
    states = checkpoint_name(jnp.einsum(
        "bcshp,bcsn->bchpn", (xdt * to_end[..., None]).astype(dtype), b,
        preferred_element_type=f32), STATES)
    # the recurrence over the chunks, written out: the state before chunk i
    # is the sum over chunks j < i of state j decayed through chunks j+1..i-1
    whole = seg[..., -1]                                    # [b, c, h]
    upto = jnp.cumsum(whole, axis=1)
    between = (upto - whole)[:, :, None] - upto[:, None]    # [b, i, j, h]
    earlier = jnp.tril(jnp.ones((nc, nc), dtype=bool), -1)[..., None]
    carry = jnp.exp(jnp.where(earlier, between, -jnp.inf))
    before = jnp.einsum("bijh,bjhpn->bihpn", carry, states,
                        precision=jax.lax.Precision.HIGHEST)
    # what a chunk reads of the state before it
    y_before = jnp.einsum("bcln,bchpn->bclhp", c, before.astype(dtype),
                          preferred_element_type=f32)
    y = y + y_before * jnp.exp(seg).transpose(0, 1, 3, 2)[..., None]
    return y.reshape(bsz, t, h, p)


SEL_UNROLL = 16     # tokens of a chunk's inner loop laid out in one body: with
#                     chunks of 64 the fastest of those tried on the v5e
#                     (PERF.md section 6, PR 41)


def selective_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                   b: jnp.ndarray, c: jnp.ndarray, d: jnp.ndarray,
                   chunk: int = 64) -> jnp.ndarray:
    """y [batch, t, channels] of the per-channel recurrence above from x and
    dt [batch, t, channels] (dt > 0, float32), a [channels, state] (< 0), b
    and c [batch, t, state] and the skip's d [channels]; dt, a and d
    float32. Decays and state are float32, y is rounded to x's type once.
    Each call built into a traced program counts in
    ``perfvars.snapshot()["sel_scan_lowerings"]`` as ``chunked``, or as
    ``padded`` where t is no multiple of the chunk: it is filled up with
    tokens of ``dt`` = 0, which decay nothing and add nothing; and in
    ``["sel_scan_kernel_lowerings"]`` as ``kernel`` or ``plain``. The result
    does not depend on the chunk. ``kernel``: `sel_scan_kernels.sel_scan`,
    which fills the sequence up to its own blocks of tokens the same way
    and whose backward pass keeps the operands and the state before each
    block, [batch, blocks, state, channels] float32; ``plain``: a
    `lax.scan` over chunks that keeps the state before each chunk
    ([chunks, batch, state, channels]) and computes a chunk's token states
    again (`jax.checkpoint` around a chunk)."""
    t = x.shape[1]
    length = min(chunk, t)
    pad = -t % length
    perfvars.note("sel_scan_lowerings", "padded" if pad else "chunked")

    run = choice.decide(choice.SEL_SCAN, x.shape[2], a.shape[-1], x.dtype)
    if run:
        return sel_scan_kernels.sel_scan(x, dt, a, b, c, d,
                                         interpret=run.interpret)

    def filled(v):
        return jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v
    y = _selective_chunks(filled(x), filled(dt), a, filled(b), filled(c),
                          length)[:, :t]
    return (y + x.astype(jnp.float32) * d).astype(x.dtype)


def sel_scan_kernel_selected(shape: tuple, dtype, state: int) -> bool:
    """Whether :func:`selective_scan` runs the Pallas kernel pair for x of
    ``shape`` [batch, t, channels] over a state of ``state``: `xla.choice`'s
    rule over the kernel's contract, `sel_scan_kernels.sel_scan_selected`."""
    return choice.fit(choice.SEL_SCAN, shape[2], state, dtype) is not None


def _selective_chunks(x, dt, a, b, c, length: int):
    """The recurrence without its skip term, float32 [batch, t, channels], t
    a multiple of ``length``."""
    bsz, t, ch = x.shape
    f32 = jnp.float32
    a_t = a.astype(f32).T                               # [state, channels]

    def by_chunk(v):    # [batch, t, w] -> [chunks, length, batch, w]
        return jnp.moveaxis(v.reshape(bsz, t // length, length, -1), 0, 2)

    def token(s, at):
        x_t, dt_t, b_t, c_t = at        # [batch, channels] x 2, [batch, state] x 2
        dt_t = dt_t.astype(f32)[:, None, :]
        s = jnp.exp(dt_t * a_t) * s + (dt_t * x_t.astype(f32)[:, None, :]) \
            * b_t.astype(f32)[:, :, None]
        return s, jnp.sum(s * c_t.astype(f32)[:, :, None], axis=1)

    @jax.checkpoint     # keeps the state before the chunk, and its inputs
    def one_chunk(s, inputs):
        return lax.scan(token, s, inputs, unroll=min(SEL_UNROLL, length))
    start = jnp.zeros((bsz, a_t.shape[0], ch), f32)
    varies = tuple(sorted(set().union(*(jax.typeof(v).vma
                                        for v in (x, dt, a, b, c)))))
    if varies:      # under `shard_map` the carry varies as the operands do
        start = lax.pcast(start, varies, to="varying")
    _, y = lax.scan(one_chunk, start,
                    tuple(by_chunk(v) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 2, 0).reshape(bsz, t, ch)
