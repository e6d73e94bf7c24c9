"""Test configuration: simulated 8-device CPU mesh, array-type parameterization.

Mirrors the reference CI shape (SURVEY.md §4): the whole suite runs in one
process on fake XLA devices; the same tests re-run on real TPU by unsetting
JAX_PLATFORMS. ArrayType parameterization follows test_allreduce.jl:4-9
(Array vs CuArray) — here numpy vs device-resident jax (DeviceBuffer).
"""

import os
import sys

# The CPU-sim test substrate: JAX on 8 fake CPU devices. This must run before
# any JAX *backend* is created.
if "TPU_MPI_TEST_REAL_TPU" not in os.environ:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    # Device arrays must hold 64-bit dtypes faithfully (the reference tests
    # CuArray{Int64}); without this jax silently downcasts to int32, which
    # byte-level paths (File I/O, RMA) would corrupt.
    jax.config.update("jax_enable_x64", True)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest

import tpu_mpi
from tpu_mpi.buffers import DeviceBuffer


class NumpyFactory:
    """ArrayType=Array analog."""
    name = "numpy"

    @staticmethod
    def array(data, dtype=None):
        return np.array(data, dtype=dtype)

    @staticmethod
    def empty(shape, dtype=np.float64):
        return np.empty(shape, dtype=dtype)

    @staticmethod
    def zeros(shape, dtype=np.float64):
        return np.zeros(shape, dtype=dtype)

    @staticmethod
    def full(shape, val, dtype=None):
        return np.full(shape, val, dtype=dtype)


class DeviceFactory:
    """ArrayType=CuArray analog: device-resident jax arrays in mutable cells."""
    name = "device"

    @staticmethod
    def array(data, dtype=None):
        return DeviceBuffer(np.array(data, dtype=dtype))

    @staticmethod
    def empty(shape, dtype=np.float64):
        return DeviceBuffer(np.zeros(shape, dtype=dtype))

    @staticmethod
    def zeros(shape, dtype=np.float64):
        return DeviceBuffer(np.zeros(shape, dtype=dtype))

    @staticmethod
    def full(shape, val, dtype=None):
        return DeviceBuffer(np.full(shape, val, dtype=dtype))


_param = os.environ.get("TPU_MPI_TEST_ARRAYTYPE", "")
if _param == "device":
    _FACTORIES = [DeviceFactory]
elif _param == "numpy":
    _FACTORIES = [NumpyFactory]
else:
    _FACTORIES = [NumpyFactory, DeviceFactory]


@pytest.fixture(params=_FACTORIES, ids=[f.name for f in _FACTORIES])
def AT(request):
    """Array-type factory fixture (the JULIA_MPI_TEST_ARRAYTYPE switch)."""
    return request.param


@pytest.fixture(autouse=True)
def _loaded_config_follows_the_environment():
    """A test that sets a `TPU_MPI_*` variable (`monkeypatch.setenv`) and
    reloads the config gets the variable restored but leaves the loaded
    config as it set it, and the next test in the worker inherits it:
    `test_perfvars.py`'s `TPU_MPI_REGISTERED_BUFFERS=0` case switched
    auto-arming off for whichever file the scheduler ran next (seen as 16
    failures of `test_left_fold.py` in one whole run and none in the next,
    PR 30). Torn down after the test's own fixtures: reload what the
    environment says now, and leave no trace where nothing had leaked."""
    yield
    from tpu_mpi import config
    loaded, generation = config._cached, config.GENERATION
    if loaded is not None and config.load(refresh=True) == loaded:
        with config._lock:
            config._cached, config.GENERATION = loaded, generation


@pytest.fixture
def nprocs():
    return int(os.environ.get("TPU_MPI_TEST_NPROCS", tpu_mpi.testing.DEFAULT_NPROCS))


@pytest.fixture
def no_folds_cached():
    """The legacy lane's fold caches empty before and after: a signature's
    first encounter folds eagerly and its second compiles, and what a test
    compiles there is its own."""
    from tpu_mpi import collective

    def clear():
        with collective._fold_lock:
            collective._fold_compiled.clear()
            collective._fold_seen.clear()
    clear()
    yield
    clear()


@pytest.fixture
def kernel_backend():
    """`tpu_mpi.xla.choice.backend`'s word, set for the time of a trace:
    "interpret" selects the Pallas kernels on this CPU (the interpret
    machine), "mosaic" selects them as a TPU would (for a test that lowers
    for the described chip, or asks a predicate), None selects none, as the
    CPU does. ``kernel_backend(word)`` sets it until the test ends; ``with
    kernel_backend(word):`` until the block does. The one place the tests
    steer the choice of a kernel: what a choice reads at trace time is in
    `choice.trace_key`, and the caches keyed on it follow the word.

    The interpret machine's host callbacks are ordered effects, which
    `jax.checkpoint` refuses in what it may recompute; a Mosaic kernel has
    none. A delta-rule layer recomputes its convolution
    (`_gdn_mixer`'s `scan_operands`), so for the time of the fixture the
    callbacks' effect is one a recomputation may hold: the machine only
    simulates, and running it twice gives the same values."""
    from jax._src import callback, effects
    from tpu_mpi.xla import choice
    at_start = choice.backend
    effects.remat_allowed_effects.add_type(callback.OrderedIOEffect)

    class set_word:
        def __init__(self, word):
            self.kept, choice.backend = choice.backend, lambda: word

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            choice.backend = self.kept

    yield set_word
    choice.backend = at_start
    effects.remat_allowed_effects._effect_types.discard(
        callback.OrderedIOEffect)
