"""Two-controller in-graph collective (VERDICT r5 #8): the deployment story
says multi-host = per-host processes + ``jax.distributed.initialize``; this
proves an XLA collective actually SPANS two controller processes. Two OS
processes x 4 fake CPU devices each run one in-graph psum through
``tpu_mpi.xla`` across all 8 global devices (jax CPU multi-controller
collectives via gloo)."""

import os
import socket
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = """
    import os, sys
    rank, port = int(sys.argv[1]), sys.argv[2]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                               num_processes=2, process_id=rank)
    assert jax.local_device_count() == 4, jax.local_device_count()
    assert jax.device_count() == 8, jax.device_count()

    sys.path.insert(0, "@REPO@")
    import numpy as np
    import tpu_mpi
    from tpu_mpi import xla as mx
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mx.world_mesh("world")

    def _step(x):
        return mx.allreduce(x, axis="world")

    step = jax.jit(jax.shard_map(_step, mesh=mesh, in_specs=P("world"),
                                 out_specs=P("world")))
    x = jax.device_put(np.arange(8, dtype=np.float32),
                       NamedSharding(mesh, P("world")))
    out = step(x)
    for s in out.addressable_shards:       # every local shard = sum(0..7)
        assert np.allclose(np.asarray(s.data), 28.0), np.asarray(s.data)
    print(f"TWO-CONTROLLER-PSUM-OK-{rank}", flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_psum_spans_two_controller_processes(tmp_path):
    script = tmp_path / "two_controller_worker.py"
    script.write_text(textwrap.dedent(_WORKER.replace("@REPO@", REPO)))
    port = _free_port()
    env = dict(os.environ)
    env.pop("TPU_MPI_PROC_RANK", None)
    procs = [subprocess.Popen([sys.executable, str(script), str(r), str(port)],
                              stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                              text=True, env=env, cwd=REPO)
             for r in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (r, out)
        assert f"TWO-CONTROLLER-PSUM-OK-{r}" in out, (r, out)
