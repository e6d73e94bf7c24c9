"""chip_smoke.py on the CPU-sim substrate, and the no-fallback rules it
stands on (ISSUE 21).

The smoke itself only runs on a TPU. What can be checked here: its four leg
functions at toy sizes with the kernels in explicit interpret mode, that the
script refuses anything but a TPU, and that the library underneath fails
loudly where it used to fall back. (``capabilities()`` raising on an unknown
generation is covered where the table is tested, tests/test_config.py.)
"""

import hashlib
import os
import subprocess
import sys

import numpy as np
import pytest

import tpu_mpi as MPI
from tpu_mpi import _native, _runtime, collective, config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


def test_leg_host(no_folds_cached):
    facts = chip_smoke.leg_host(chip_smoke.TINY, "cpu")
    # rank i's operands and results lived on device i
    assert facts["rank_devices"] == [0, 1, 2, 3]
    # the legacy lane's compiled fold is XLA's own: no custom call
    assert facts["fold"] == "chain"


def test_leg_ingraph():
    facts = chip_smoke.leg_ingraph(chip_smoke.TINY, "cpu")
    assert facts["train"]["mesh"] == {"dp": 2, "tp": 2, "sp": 2}
    assert facts["train"]["losses"][-1] < facts["train"]["losses"][0]


def test_leg_kernels():
    facts = chip_smoke.leg_kernels(chip_smoke.TINY, "cpu")
    assert facts["interpret"] is True and facts["n"] == 8
    assert facts["oversize"] == "ValueError"
    assert "ring_allreduce[bfloat16]" in facts["seconds"]
    # the five ring kernels in two element types and the ring attention:
    # every kernel the leg compiles is one the program can still select
    assert {k.split("[")[0] for k in facts["seconds"]} == {
        "ring_allgather", "ring_allreduce", "ring_reduce_scatter",
        "pairwise_alltoall", "collective_permute", "ring_attention",
        "grouped_matmul", "grouped_row_sums", "causal_attention",
        "conv_silu", "delta_scan", "head_norm"}
    assert facts["row_sums_rel_err"] < 1e-5
    assert set(facts["conv_rel_err"]) == {"out", "dx", "dw", "dbias"}
    assert set(facts["channel_scan_rel_err"]) == {"o", "dq", "dk", "dv",
                                                  "dg", "dbeta"}
    assert set(facts["window_attention_rel_err"]) == {"out", "dq", "dk", "dv"}
    assert set(facts["head_norm_rel_err"]) == {
        "l2 out", "l2 dx", "gated out", "gated dx", "gated dscale",
        "gated dg", "gated dw"}
    # the grouped product ran (interpreted) against lax.ragged_dot; on the
    # CPU backend the program itself would select `lax.ragged_dot`
    assert set(facts["grouped_matmul_rel_err"]) == {"out", "d_lhs", "d_rhs"}
    assert facts["grouped_matmul"] == "ragged_dot"
    assert facts["expert_layer_custom_calls"] == 0


def test_leg_serve():
    facts = chip_smoke.leg_serve(chip_smoke.TINY, "cpu")
    assert facts["device_work"].startswith("none")
    assert facts["requests"] == 3


def test_smoke_refuses_without_a_tpu():
    """Plain ``python chip_smoke.py`` off the chip: non-zero, names the
    missing TPU, prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120, env=env)
    assert res.returncode != 0
    assert "'cpu', not 'tpu'" in res.stderr, res.stderr
    assert '"ok"' not in res.stdout


def test_backend_tpu_is_enforced(monkeypatch):
    """TPU_MPI_BACKEND=tpu on a CPU backend: the launch raises a typed
    error instead of running on whatever JAX fell back to."""
    monkeypatch.setenv("TPU_MPI_BACKEND", "tpu")
    config.load(refresh=True)
    monkeypatch.setattr(_runtime, "_jax_warmed", False)
    try:
        with pytest.raises(MPI.MPIError, match="default backend is 'cpu'") as e:
            MPI.spmd_run(lambda: None, 2)
        assert e.value.code == MPI.error.ERR_UNSUPPORTED_OPERATION
    finally:
        monkeypatch.undo()
        config.load(refresh=True)


def test_fold_compile_failure_propagates(monkeypatch, no_folds_cached):
    """A user operator that cannot be traced is the one documented reason a
    device fold is declined; a chain that traces and then fails to compile
    is the device's failure and must surface."""
    import jax
    import jax.numpy as jnp

    arrs = [jnp.arange(8, dtype=jnp.float32) + r for r in range(3)]
    host_only = MPI.Op(lambda a, b: np.add(np.asarray(a), np.asarray(b)))
    for _ in range(2):      # the second encounter is the one that compiles
        out = collective._reduce_arrays(arrs, host_only)
    np.testing.assert_array_equal(np.asarray(out), 3 * np.arange(8.0) + 3)

    def broken(fn, **k):
        assert fn.__name__ == "plain_fold", fn

        def compiled(*xs):
            raise RuntimeError("the compiler refused the fold")
        return compiled
    collective._reduce_arrays(arrs, MPI.SUM)        # first encounter: eager
    # the chain has traced (``_traceable``) by the time it is compiled
    monkeypatch.setattr(jax, "jit", broken)
    with pytest.raises(RuntimeError, match="refused the fold"):
        collective._reduce_arrays(arrs, MPI.SUM)


def test_compile_cache_dir(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert _runtime.compile_cache_dir() == "/some/dir"
    assert _runtime.enable_compile_cache() == "/some/dir"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert _runtime.compile_cache_dir() == os.path.join(REPO, ".jax_cache")
    # the CPU backend (this process) is left uncached, and nothing is set
    assert _runtime.enable_compile_cache() is None
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ


def test_native_library_follows_source_content(tmp_path, monkeypatch):
    """Staleness is keyed on transport.cc's bytes, not on file times: a copy
    of the tree with a different source can never load this one's library,
    and a library file newer than the source proves nothing."""
    lib = _native._lib_path()
    with open(_native._SRC, "rb") as f:
        src = f.read()
    assert hashlib.sha256(src).hexdigest()[:16] in os.path.basename(lib)
    other = tmp_path / "transport.cc"
    other.write_bytes(src + b"\n// another version\n")
    os.utime(other, (0, 0))         # older than any built library
    monkeypatch.setattr(_native, "_SRC", str(other))
    assert _native._lib_path() != lib


def test_device_buffer_refuses_narrowing(monkeypatch):
    """64-bit operands with jax_enable_x64 off (every process outside this
    suite): a typed error, not a silently narrowed device array."""
    import jax

    MPI.DeviceBuffer(np.arange(4, dtype=np.float64))    # x64 on: exact
    with jax.enable_x64(False):
        for make in (lambda: MPI.DeviceBuffer(np.zeros(4, np.float64)),
                     lambda: MPI.DeviceBuffer(np.zeros(4, np.int64)),
                     lambda: MPI.DeviceBuffer([1, 2], dtype=np.int64),
                     lambda: MPI.DeviceBuffer.empty(4)):
            with pytest.raises(MPI.MPIError, match="narrowed") as e:
                make()
            assert e.value.code == MPI.error.ERR_TYPE
        assert MPI.DeviceBuffer(np.zeros(4, np.float32)).dtype == np.float32
        assert MPI.DeviceBuffer([1.0, 2.0]).dtype == np.float32
