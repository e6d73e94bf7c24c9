"""The runnable examples stay runnable (reference ships
docs/examples/01-hello.jl … 04-sendrecv.jl exercised by its doc build;
here each runs under `tpurun --sim N` as its header documents)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


@pytest.mark.parametrize("name,nsim", [
    ("01-hello.py", 4),
    ("02-broadcast.py", 4),
    ("03-reduce.py", 4),
    ("04-sendrecv.py", 4),
    ("05-ingraph.py", 8),
    ("06-jacobi.py", 4),
    ("07-overlap.py", 4),
    ("08-checkpoint.py", 4),
    ("09-partitioned.py", 2),
    ("14-ddp-train.py", 4),
])
def test_example_runs(name, nsim):
    env = dict(os.environ)
    env.pop("TPU_MPI_PROC_RANK", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-m", "tpu_mpi.launcher", "--sim", str(nsim),
         os.path.join(EXAMPLES, name)],
        capture_output=True, text=True, timeout=180, env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr


def test_serve_example_runs():
    # 12-serve.py hosts its own broker + tenants in one process, so it runs
    # under plain python rather than tpurun --sim
    env = dict(os.environ)
    env.pop("TPU_MPI_PROC_RANK", None)
    env.pop("TPU_MPI_SERVE_SOCKET", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "12-serve.py")],
        capture_output=True, text=True, timeout=180, env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr
    assert "two tenants, one warm pool" in res.stdout


def test_moe_serve_example_runs():
    # 13-moe-serve.py hosts broker + engine + tenants in one process too
    env = dict(os.environ)
    env.pop("TPU_MPI_PROC_RANK", None)
    env.pop("TPU_MPI_SERVE_SOCKET", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "13-moe-serve.py")],
        capture_output=True, text=True, timeout=180, env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr
    assert "batched and solo greedy decode agree bitwise" in res.stdout
