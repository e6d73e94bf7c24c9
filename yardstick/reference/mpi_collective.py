"""Plain reference of the collectives the `mpi_collective` generator drives:
the same operation on the same per-rank operands, written with `jax.numpy`
alone and none of tpu_mpi. `expected(op, reduce, operands)` returns what
every rank must hold afterwards, one array per rank.

Operands are 0/1 integers, so a sum is exact in float32 in any order; it is
folded in rank order all the same, as MPI's deterministic reduction is."""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

REDUCERS = {"sum": jnp.add, "max": jnp.maximum, "min": jnp.minimum}


def fold(reduce: str, operands: Sequence) -> "jnp.ndarray":
    acc = operands[0]
    for x in operands[1:]:
        acc = REDUCERS[reduce](acc, x)
    return acc


def expected(op: str, reduce: str, operands: Sequence) -> list:
    """Per-rank results of `op` over `operands` (rank r's is operands[r]).
    `count` is the length of one rank's operand; alltoall and reduce_scatter
    cut it into `nranks` equal blocks."""
    n = len(operands)
    count = operands[0].shape[0]
    if op == "allreduce":
        return [fold(reduce, operands)] * n
    if op == "allgather":
        return [jnp.concatenate(list(operands))] * n
    if op == "bcast":                                   # root is rank 0
        return [operands[0]] * n
    if count % n:
        raise ValueError(f"{op}: count {count} is not a multiple of {n} ranks")
    c = count // n
    if op == "alltoall":        # block s of rank r's result = sender s's block r
        return [jnp.concatenate([x[r * c:(r + 1) * c] for x in operands])
                for r in range(n)]
    if op == "reduce_scatter":  # rank r keeps block r of the reduction
        total = fold(reduce, operands)
        return [total[r * c:(r + 1) * c] for r in range(n)]
    raise ValueError(f"no reference for op {op!r}")


def chained_allreduce(first, others_sum, k: int):
    """Rank 0's result after k chained allreduce(sum) ops, in which rank 0
    feeds every result back as its next operand: first + k * (sum of the
    other ranks' operands)."""
    return first + jnp.asarray(k, first.dtype) * others_sum
