"""Halo exchange: boundary-slice trading on an N-d process grid.

Reference primitives: Cartesian comms + Sendrecv! with subarray datatypes
(SURVEY.md §2.5; /root/reference/test/test_sendrecv.jl:100-133,
src/datatypes.jl:171-190). TPU realization: two ``lax.ppermute`` calls per
grid dimension (one per direction) moving the boundary slices — the subarray
datatype becomes a plain lax.slice, and XLA overlaps the neighbor DMAs.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp
from jax import lax


def halo_exchange(x: jnp.ndarray, *, axes: Sequence[str], halo: int = 1,
                  periodic: bool = True) -> jnp.ndarray:
    """Pad each spatial dim of the local block with neighbors' boundaries.

    x: local block, one array dim per mesh axis in ``axes`` (leading dims may
    be batch). Returns x padded by ``halo`` on both sides of each exchanged
    dim. Non-periodic edges receive zeros (the PROC_NULL analog —
    src/topology.jl:155-164)."""
    offset = x.ndim - len(axes)
    for d, axis in enumerate(axes):
        dim = offset + d
        n = lax.axis_size(axis)
        fwd = [(i, (i + 1) % n) for i in range(n)] if periodic else \
            [(i, i + 1) for i in range(n - 1)]
        bwd = [(i, (i - 1) % n) for i in range(n)] if periodic else \
            [(i, i - 1) for i in range(1, n)]
        lo = lax.slice_in_dim(x, 0, halo, axis=dim)               # my low edge
        hi = lax.slice_in_dim(x, x.shape[dim] - halo, x.shape[dim], axis=dim)
        from_prev = lax.ppermute(hi, axis, fwd)   # prev rank's high edge
        from_next = lax.ppermute(lo, axis, bwd)   # next rank's low edge
        x = jnp.concatenate([from_prev, x, from_next], axis=dim)
    return x
