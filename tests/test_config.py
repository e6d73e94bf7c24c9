"""Config module: env + TOML precedence (reference: deps/build.jl:14-58
persisting JULIA_MPI_* to ~/.julia/prefs/MPI.toml)."""

import os

import pytest

import tpu_mpi
from tpu_mpi import config
from tpu_mpi.error import MPIError


@pytest.fixture
def clean_env(tmp_path, monkeypatch):
    for var in list(os.environ):
        if var.startswith("TPU_MPI_"):
            monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("TPU_MPI_CONFIG", str(tmp_path / "config.toml"))
    config.load(refresh=True)
    yield tmp_path
    config.load(refresh=True)


def test_defaults(clean_env):
    cfg = config.load(refresh=True)
    assert cfg.backend == "auto"
    assert cfg.deadlock_timeout == 60.0
    assert cfg.sim_devices == 8
    assert cfg.coordinator == ""


def test_env_overrides(clean_env, monkeypatch):
    monkeypatch.setenv("TPU_MPI_DEADLOCK_TIMEOUT", "12.5")
    monkeypatch.setenv("TPU_MPI_BACKEND", "cpu-sim")
    cfg = config.load(refresh=True)
    assert cfg.deadlock_timeout == 12.5
    assert cfg.backend == "cpu-sim"


def test_toml_then_env_precedence(clean_env, monkeypatch):
    path = clean_env / "config.toml"
    path.write_text('backend = "tpu"\nsim_devices = 4\nnprocs = 2\n')
    cfg = config.load(refresh=True)
    assert cfg.backend == "tpu" and cfg.sim_devices == 4 and cfg.nprocs == 2
    monkeypatch.setenv("TPU_MPI_SIM_DEVICES", "16")   # env wins over TOML
    cfg = config.load(refresh=True)
    assert cfg.sim_devices == 16
    assert cfg.backend == "tpu"


def test_persist_roundtrip(clean_env):
    out = config.persist(deadlock_timeout=30.0, coordinator="10.0.0.1:9999")
    assert os.path.exists(out)
    cfg = config.load(refresh=True)
    assert cfg.deadlock_timeout == 30.0
    assert cfg.coordinator == "10.0.0.1:9999"


def test_bad_value_rejected(clean_env, monkeypatch):
    monkeypatch.setenv("TPU_MPI_SIM_DEVICES", "not-a-number")
    with pytest.raises(MPIError):
        config.load(refresh=True)
    monkeypatch.delenv("TPU_MPI_SIM_DEVICES")
    config.load(refresh=True)


def test_serve_knobs(clean_env, monkeypatch):
    cfg = config.load(refresh=True)
    assert cfg.serve_socket == ""
    assert cfg.serve_max_tenants == 8
    assert cfg.serve_quota_bytes == 0
    assert cfg.session_token == ""
    monkeypatch.setenv("TPU_MPI_SERVE_SOCKET", "127.0.0.1:7900")
    monkeypatch.setenv("TPU_MPI_SERVE_MAX_TENANTS", "3")
    monkeypatch.setenv("TPU_MPI_SERVE_QUOTA_BYTES", "1048576")
    monkeypatch.setenv("TPU_MPI_SESSION_TOKEN", "s3cret")
    cfg = config.load(refresh=True)
    assert cfg.serve_socket == "127.0.0.1:7900"
    assert cfg.serve_max_tenants == 3
    assert cfg.serve_quota_bytes == 1048576
    assert cfg.session_token == "s3cret"
    # malformed values fail loudly, matching every other knob
    monkeypatch.setenv("TPU_MPI_SERVE_MAX_TENANTS", "many")
    with pytest.raises(MPIError):
        config.load(refresh=True)
    monkeypatch.setenv("TPU_MPI_SERVE_MAX_TENANTS", "3")
    monkeypatch.setenv("TPU_MPI_SERVE_QUOTA_BYTES", "a-lot")
    with pytest.raises(MPIError):
        config.load(refresh=True)
    monkeypatch.setenv("TPU_MPI_SERVE_QUOTA_BYTES", "0")
    config.load(refresh=True)


def test_decode_fastpath_knobs(clean_env, monkeypatch):
    cfg = config.load(refresh=True)
    assert cfg.infer_vectorized is True
    assert cfg.infer_spec_k == 0
    assert cfg.infer_prefill_chunk == 0
    assert cfg.kv_prefix_share is False
    monkeypatch.setenv("TPU_MPI_INFER_VECTORIZED", "0")
    monkeypatch.setenv("TPU_MPI_INFER_SPEC_K", "4")
    monkeypatch.setenv("TPU_MPI_INFER_PREFILL_CHUNK", "64")
    monkeypatch.setenv("TPU_MPI_KV_PREFIX_SHARE", "1")
    cfg = config.load(refresh=True)
    assert cfg.infer_vectorized is False
    assert cfg.infer_spec_k == 4
    assert cfg.infer_prefill_chunk == 64
    assert cfg.kv_prefix_share is True
    # malformed values fail loudly, matching every other knob
    monkeypatch.setenv("TPU_MPI_INFER_SPEC_K", "fast")
    with pytest.raises(MPIError):
        config.load(refresh=True)
    monkeypatch.setenv("TPU_MPI_INFER_SPEC_K", "4")
    monkeypatch.setenv("TPU_MPI_INFER_PREFILL_CHUNK", "a-few")
    with pytest.raises(MPIError):
        config.load(refresh=True)
    monkeypatch.setenv("TPU_MPI_INFER_PREFILL_CHUNK", "0")
    monkeypatch.setenv("TPU_MPI_KV_PREFIX_SHARE", "maybe")
    with pytest.raises(MPIError):
        config.load(refresh=True)
    monkeypatch.setenv("TPU_MPI_KV_PREFIX_SHARE", "0")
    config.load(refresh=True)


def test_runtime_deadlock_timeout_uses_env(clean_env, monkeypatch):
    from tpu_mpi._runtime import deadlock_timeout
    monkeypatch.setenv("TPU_MPI_DEADLOCK_TIMEOUT", "7")
    assert deadlock_timeout() == 7.0
    monkeypatch.delenv("TPU_MPI_DEADLOCK_TIMEOUT")
    config.load(refresh=True)
    assert deadlock_timeout() == 60.0


def test_capability_tables():
    from tpu_mpi.implementations import CAPABILITIES, capabilities
    for gen, row in CAPABILITIES.items():
        assert {"ici_gbps", "hbm_gbps", "hbm_gib", "cores", "bf16_tflops"} <= set(row)
    assert capabilities("v5e")["hbm_gbps"] == 819.0
    with pytest.raises(KeyError, match="no-such-chip"):
        capabilities("no-such-chip")    # an unknown device is not a v5e


def test_telemetry_knob_defaults(clean_env):
    cfg = config.load(refresh=True)
    assert cfg.trace_sample == 0.0          # tracing is opt-in
    assert cfg.flight_ring == 256           # flight recorder is always-on
    assert cfg.flight_dir == ""             # "" -> tempdir at dump time
    assert cfg.serve_slo_us == 0            # no fleet-wide objective


@pytest.mark.parametrize("var,bad", [
    ("TPU_MPI_TRACE_SAMPLE", "1.5"),
    ("TPU_MPI_TRACE_SAMPLE", "-0.1"),
    ("TPU_MPI_TRACE_SAMPLE", "yes"),
    ("TPU_MPI_FLIGHT_RING", "-1"),
    ("TPU_MPI_FLIGHT_RING", "many"),
    ("TPU_MPI_PVARS_HIST_BINS", "0"),
    ("TPU_MPI_PVARS_HIST_BINS", "-3"),
    ("TPU_MPI_SERVE_SLO_US", "-500"),
])
def test_telemetry_knobs_fail_loudly(clean_env, monkeypatch, var, bad):
    """Satellite: a bad telemetry knob is an MPIError at load, not a
    silently-ignored string — misconfigured observability must not look
    like observability."""
    monkeypatch.setenv(var, bad)
    with pytest.raises(MPIError):
        config.load(refresh=True)
    monkeypatch.delenv(var)
    config.load(refresh=True)               # and the cache recovers


def test_telemetry_knobs_good_values(clean_env, monkeypatch):
    monkeypatch.setenv("TPU_MPI_TRACE_SAMPLE", "0.25")
    monkeypatch.setenv("TPU_MPI_FLIGHT_RING", "0")      # 0 disables
    monkeypatch.setenv("TPU_MPI_SERVE_SLO_US", "2000")
    cfg = config.load(refresh=True)
    assert cfg.trace_sample == 0.25
    assert cfg.flight_ring == 0
    assert cfg.serve_slo_us == 2000
