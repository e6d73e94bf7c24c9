"""ingraph_psum_algbw (GB/s): the ceiling the host path across chips could
reach: the same payload through one jitted `shard_map` of `xla.allreduce`
over the cell's chips (XLA's own all-reduce over ICI), one rank's payload
bytes over the median of 20 synced calls. Measured by `prepare`, in the
traced run only, before the operands exist and before the profiler starts.
Bus bandwidth is 2(n-1)/n times this."""

import time

from yardstick import stats

CALLS = 20


def prepare(run):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    import tpu_mpi as MPI
    from tpu_mpi import xla

    n = len(run.devices)
    count = int(run.traffic["counts"][0])
    dtype = jnp.dtype(run.traffic["dtype"])
    mesh = xla.make_mesh({"x": n}, devices=run.devices)
    shard = NamedSharding(mesh, P("x"))
    x = jax.jit(lambda: jnp.ones((n * count,), dtype), out_shardings=shard)()
    f = jax.jit(jax.shard_map(lambda v: xla.allreduce(v, MPI.SUM, axis="x"),
                              mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    y = f(x).block_until_ready()
    if float(y[0]) != float(n) or float(y[-1]) != float(n):
        raise RuntimeError(f"in-graph allreduce of ones over {n} chips gave "
                           f"{float(y[0])}, {float(y[-1])}")
    times = []
    for _ in range(CALLS):
        t0 = time.perf_counter()
        f(x).block_until_ready()
        times.append(time.perf_counter() - t0)
    run.prepared["ingraph_psum_algbw"] = stats.coll_algbw_gbps(
        count * dtype.itemsize, stats.median(times))


def read(run):
    return run.prepared.get("ingraph_psum_algbw")
