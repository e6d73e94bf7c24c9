"""Pipeline parallelism: microbatch rotation between stage neighbors.

Reference primitives: Send/Recv!/Isend/Irecv! between stage neighbors
(SURVEY.md §2.5; /root/reference/src/pointtopoint.jl:179-346). TPU
realization: stages live on ranks of a 'pp' mesh axis; activations advance
one stage per tick with ``lax.ppermute`` in a GPipe schedule — the
fill/steady/drain loop is a static unroll XLA pipelines on ICI, and the whole
thing is differentiable (grads ride the reverse permutation).
"""

from __future__ import annotations

from typing import Any, Callable

import jax.numpy as jnp
from jax import lax


def pipeline_forward(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                     params: Any, microbatches: jnp.ndarray, *,
                     axis: str = "pp") -> jnp.ndarray:
    """Run microbatches through a chain of stages.

    stage_fn(params, x): this rank's stage (params are the stage's own —
    already sharded over ``axis``). microbatches: (m, ...) — each rank feeds
    the same schedule; only rank 0's input matters, only the *last* stage's
    output is meaningful (others return zeros), mirroring how rooted MPI
    pipelines behave. Returns (m, ...) outputs on every rank (valid on the
    last stage).
    """
    n = lax.axis_size(axis)
    my = lax.axis_index(axis)
    m = microbatches.shape[0]
    fwd = [(i, (i + 1) % n) for i in range(n)]

    carry = jnp.zeros_like(microbatches[0])
    outs = []
    ticks = m + n - 1
    for tick in range(ticks):
        # rank 0 injects a fresh microbatch while any remain
        inject = microbatches[min(tick, m - 1)]
        x = jnp.where(my == 0, jnp.where(tick < m, inject, jnp.zeros_like(inject)),
                      carry)
        y = stage_fn(params, x)
        # the last stage emits microbatch (tick - (n-1)) at this tick
        outs.append(y)
        carry = lax.ppermute(y, axis, fwd)
    # collect the last stage's emissions for ticks n-1 .. n-1+m-1
    result = jnp.stack(outs[n - 1:n - 1 + m])
    return result
