"""Pallas RDMA kernels under the TPU interpret machine on the CPU-sim mesh.

The reference validates its native-algorithm tier (libmpi rings) simply by
using it through the API; here the hand-written ICI kernels are checked
against numpy semantics the same way the XLA-collective tier is
(test_xla_collectives.py)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tpu_mpi import xla
from tpu_mpi.xla import pallas_kernels as pk


def _mesh(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    return xla.make_mesh({"x": n})


def _run(mesh, fn, *args, in_specs=None, out_specs=None):
    n = mesh.devices.size
    in_specs = in_specs or tuple(P("x") for _ in args)
    f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs or P("x"),
                              check_vma=False))
    return f(*args)


@pytest.mark.parametrize("n", [4, 8])
def test_ring_allgather(n):
    mesh = _mesh(n)
    x = jnp.arange(n * 6 * 5, dtype=jnp.float32).reshape(n * 6, 5)
    out = _run(mesh, lambda v: pk.ring_allgather(v, axis="x"), x)
    # each rank gathers all blocks in rank order -> full x, replicated
    got = np.asarray(out).reshape(n, n * 6, 5)
    for r in range(n):
        np.testing.assert_array_equal(got[r], np.asarray(x))


@pytest.mark.parametrize("op,npop", [("sum", np.add), ("max", np.maximum),
                                     ("min", np.minimum)])
def test_ring_allreduce(op, npop):
    n = 4
    mesh = _mesh(n)
    rng = np.random.RandomState(0)
    x = rng.randn(n, 40).astype(np.float32)
    out = _run(mesh, lambda v: pk.ring_allreduce(v, op, axis="x"),
               jnp.asarray(x.reshape(-1)))
    expect = x[0]
    for r in range(1, n):
        expect = npop(expect, x[r])
    got = np.asarray(out).reshape(n, 40)
    for r in range(n):
        np.testing.assert_allclose(got[r], expect, rtol=1e-6)


def test_ring_allreduce_large_uneven():
    # element count not divisible by n*8*128: exercises the padding path
    n = 4
    mesh = _mesh(n)
    rng = np.random.RandomState(1)
    x = rng.randn(n, 1000).astype(np.float32)
    out = _run(mesh, lambda v: pk.ring_allreduce(v, "sum", axis="x"),
               jnp.asarray(x.reshape(-1)))
    got = np.asarray(out).reshape(n, 1000)
    for r in range(n):
        np.testing.assert_allclose(got[r], x.sum(0), rtol=1e-5)


def test_collective_permute_ring_shift():
    n = 4
    mesh = _mesh(n)
    x = jnp.arange(n * 24, dtype=jnp.float32)
    perm = [(r + 1) % n for r in range(n)]
    out = _run(mesh, lambda v: pk.collective_permute(v, perm, axis="x"), x)
    got = np.asarray(out).reshape(n, 24)
    base = np.asarray(x).reshape(n, 24)
    for r in range(n):
        np.testing.assert_array_equal(got[r], base[(r - 1) % n])


def test_collective_permute_rejects_non_permutation():
    n = 4
    mesh = _mesh(n)
    x = jnp.arange(n * 8, dtype=jnp.float32)
    with pytest.raises(ValueError):
        _run(mesh, lambda v: pk.collective_permute(v, [0, 0, 1, 2], axis="x"), x)


def test_ring_attention_matches_full_attention():
    n = 4
    t_local, d = 8, 16
    mesh = _mesh(n)
    rng = np.random.RandomState(2)
    q = rng.randn(n * t_local, d).astype(np.float32)
    k = rng.randn(n * t_local, d).astype(np.float32)
    v = rng.randn(n * t_local, d).astype(np.float32)

    out = _run(mesh, lambda a, b, c: pk.ring_attention(a, b, c, axis="x"),
               jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))

    s = (q @ k.T) / np.sqrt(d)
    p = np.exp(s - s.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    expect = p @ v
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-4, atol=2e-5)


def test_ring_reduce_scatter():
    n = 4
    mesh = _mesh(n)
    rng = np.random.RandomState(3)
    x = rng.randn(n, n * 50).astype(np.float32)   # each rank contributes n*50
    out = _run(mesh, lambda v: pk.ring_reduce_scatter(v, "sum", axis="x"),
               jnp.asarray(x.reshape(-1)))
    got = np.asarray(out).reshape(n, 50)
    total = x.sum(0).reshape(n, 50)               # block r belongs to rank r
    for r in range(n):
        np.testing.assert_allclose(got[r], total[r], rtol=1e-5)


def test_pairwise_alltoall():
    n = 4
    mesh = _mesh(n)
    per = 30
    # rank s's block for dest d = 100*s + 10*d + arange(per)
    x = np.zeros((n, n * per), np.float32)
    for s in range(n):
        for d in range(n):
            x[s, d * per:(d + 1) * per] = 100 * s + 10 * d + np.arange(per)
    out = _run(mesh, lambda v: pk.pairwise_alltoall(v, axis="x"),
               jnp.asarray(x.reshape(-1)))
    got = np.asarray(out).reshape(n, n * per)
    for r in range(n):
        for s in range(n):
            np.testing.assert_array_equal(
                got[r, s * per:(s + 1) * per],
                100 * s + 10 * r + np.arange(per, dtype=np.float32))


def test_ring_attention_causal():
    n = 4
    t_local, d = 8, 16
    mesh = _mesh(n)
    rng = np.random.RandomState(4)
    t = n * t_local
    q = rng.randn(t, d).astype(np.float32)
    k = rng.randn(t, d).astype(np.float32)
    v = rng.randn(t, d).astype(np.float32)

    out = _run(mesh,
               lambda a, b, c: pk.ring_attention(a, b, c, axis="x", causal=True),
               jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))

    s = (q @ k.T) / np.sqrt(d)
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    p = np.exp(s - s.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    expect = p @ v
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-4, atol=2e-5)
