"""One left fold on the host path (PR 27): every lane an ``MPI.Allreduce``
on device operands can take (eager, the compiled legacy lane, the armed
registered lane) computes the rank-ordered left chain, one definition of it
(``collective._left_chain``) compiled by XLA, and nothing a user sets chooses
another. On the CPU-sim mesh; nothing here is a timing."""

import ast
import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

import tpu_mpi as MPI
from tpu_mpi import SpmdContext, collective, config
from tpu_mpi.overlap import plans
from tpu_mpi.testing import run_spmd

N = 4
PKG = os.path.dirname(os.path.abspath(MPI.__file__))


# first encounter eager, second compiled: only with empty caches
pytestmark = pytest.mark.usefixtures("no_folds_cached")


def _operand(rank, count, dtype):
    """Values whose fold shows its order: floats over seven decades with
    either sign, any bits for integers."""
    rng = np.random.default_rng(77 + rank)
    if np.dtype(dtype).kind in "iu":
        return rng.integers(-2**31, 2**31 - 1, count).astype(dtype)
    x = rng.uniform(0.5, 2.0, count) * 10.0 ** rng.integers(-3, 4, count)
    return np.where(rng.random(count) < 0.5, -x, x).astype(dtype)


def _left_fold(fn, xs):
    acc = xs[0]
    for x in xs[1:]:
        acc = fn(acc, x)
    return acc


# the operators and element types the deleted kernel's tests held
LANES = {
    "sum-float32": (MPI.SUM, np.add, np.float32),
    "prod-float32": (MPI.PROD, np.multiply, np.float32),
    "min-float32": (MPI.MIN, np.minimum, np.float32),
    "max-bfloat16": (MPI.MAX, np.maximum, ml_dtypes.bfloat16),
    "sum-bfloat16": (MPI.SUM, np.add, ml_dtypes.bfloat16),
    "band-int32": (MPI.BAND, np.bitwise_and, np.int32),
    "bor-int32": (MPI.BOR, np.bitwise_or, np.int32),
    "bxor-int32": (MPI.BXOR, np.bitwise_xor, np.int32),
}


@pytest.mark.parametrize("placement", ["a-device-each", "one-device"])
@pytest.mark.parametrize("name", sorted(LANES))
def test_every_lane_of_an_allreduce_returns_the_left_folds_bytes(
        monkeypatch, name, placement):
    """Call 1 folds eagerly, call 2 compiles the legacy lane's fold, call 6
    runs armed (ranks on one device: the single-chip fold; a device each:
    the exchange). Each returns numpy's rank-ordered left fold, byte for
    byte."""
    op, fn, dtype = LANES[name]
    if placement == "one-device":           # a one-chip host
        monkeypatch.setattr(SpmdContext, "device_for",
                            lambda self, rank: jax.devices()[0])
    count, got = 1000, {}
    before = plans.stats()["auto"]

    def body():
        comm = MPI.COMM_WORLD
        r, dev = comm.rank(), comm.device
        send = MPI.DeviceBuffer(_operand(r, count, dtype), device=dev)
        recv = MPI.DeviceBuffer(jnp.zeros(count, dtype, device=dev),
                                device=dev)
        outs = {}
        for call in range(1, 7):
            MPI.Allreduce(send, recv, op, comm)
            if call in (1, 2, 6):
                outs[call] = (np.asarray(recv.value),
                              recv.value.devices() == {dev})
        got[r] = outs

    run_spmd(body, N)
    want = _left_fold(fn, [_operand(r, count, dtype) for r in range(N)])
    for r in range(N):
        for call, (out, home) in got[r].items():
            assert out.dtype == want.dtype, (r, call)
            assert out.tobytes() == want.tobytes(), (r, call)
            assert home, (r, call)
    # call 2 compiled the legacy lane's fold, and it is the chain
    key = (op.fn, "reduce", N, np.dtype(dtype).name, ((count,),) * N)
    fold = collective._fold_compiled[key]
    assert fold.__wrapped__.__name__ == "plain_fold"
    # every rank armed once and stayed armed: call 6 was an armed round
    after = plans.stats()["auto"]
    assert after["arms"] - before["arms"] == N
    assert after["hits"] - before["hits"] >= N


def test_the_legacy_and_the_registered_lane_compile_one_traced_function(
        monkeypatch):
    """``_jitted_fold`` in mode "reduce" and the registered lane's ``plain``
    are both ``jax.jit(_left_chain(op))``: equal jaxprs on the same
    operands, and both executables are ``jit_plain_fold``, the name the
    benchmark's fold readers look for."""
    made, chain_of = [], collective._left_chain

    def spy(op):
        made.append(chain_of(op))
        return made[-1]
    monkeypatch.setattr(collective, "_left_chain", spy)

    count, dev = 256, jax.devices()[0]
    arrs = [jnp.asarray(_operand(r, count, np.float32), device=dev)
            for r in range(N)]
    for _ in range(2):          # the second encounter is the one that compiles
        out = collective._reduce_arrays(arrs, MPI.SUM)
    key = (MPI.SUM.fn, "reduce", N, "float32", ((count,),) * N)
    legacy = collective._fold_compiled[key]
    combine = collective._registered_device_fold(
        MPI.SUM, count, np.float32, N, dev)
    closed = dict(zip(combine.__code__.co_freevars,
                      (c.cell_contents for c in combine.__closure__)))
    plain = closed["plain"]                 # the AOT executable a round runs

    assert len(made) == 2 and legacy.__wrapped__ is made[0]
    chain = str(jax.make_jaxpr(chain_of(MPI.SUM))(*arrs))
    assert [str(jax.make_jaxpr(f)(*arrs)) for f in made] == [chain, chain]
    assert str(legacy.trace(*arrs).jaxpr) == chain
    for text in (legacy.lower(*arrs).compile().as_text(), plain.as_text()):
        assert text.startswith("HloModule jit_plain_fold"), text[:80]
        assert "custom-call" not in text
    assert closed["donated"].as_text().startswith("HloModule jit_chain")
    want = np.asarray(out).tobytes()
    assert np.asarray(plain(*arrs)).tobytes() == want
    for _ in range(3):          # plain, then the donated chain in both slots
        assert np.asarray(combine(list(arrs))[0]).tobytes() == want


def _imports(tree, module):
    """Absolute names of everything ``tree`` (of ``module``) imports."""
    package = module.split(".")[:-1]
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level \
                else []
            stem = ".".join(base + ([node.module] if node.module else []))
            yield stem
            yield from (f"{stem}.{a.name}" for a in node.names)


def test_nothing_under_xla_imports_the_host_path():
    """The in-graph tier sits under ``collective.py``, which imports it
    (``_exchange_fold``); an import the other way is a cycle."""
    seen = 0
    for root, _, files in os.walk(os.path.join(PKG, "xla")):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(root, f)
            rel = os.path.relpath(path, os.path.dirname(PKG))[:-3]
            module = rel.replace(os.sep, ".")
            with open(path) as fh:
                tree = ast.parse(fh.read(), path)
            up = [m for m in _imports(tree, module)
                  if m == "tpu_mpi.collective"
                  or m.startswith("tpu_mpi.collective.")]
            assert not up, (path, up)
            seen += 1
    assert seen >= 4


def test_no_option_chooses_a_fold(monkeypatch):
    """The knob is gone, not ignored by name: an unknown key, no
    environment variable, and setting the old one changes nothing. (The
    names are spelled in halves so that a search of the tree for them finds
    nothing.)"""
    key = "fused" + "_fold"
    env = "TPU_MPI_" + key.upper()
    with pytest.raises(MPI.MPIError, match="unknown config key"):
        config.get(key)
    assert key not in config._ENV_MAP
    assert env not in config._ENV_MAP.values()
    assert not [k for k in config._ENV_MAP if "fold" in k]
    monkeypatch.setenv(env, "interp")
    try:
        assert not hasattr(config.load(refresh=True), key)
    finally:
        monkeypatch.undo()
        config.load(refresh=True)
