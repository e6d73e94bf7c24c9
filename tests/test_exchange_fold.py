"""The armed Allreduce of ranks that each sit on a device of their own
(PR 24): one executable over those devices (all-to-all, rank-ordered fold
of a slice, all-gather) instead of the star through rank 0's. The result
must be the star's, bit for bit, and on every rank's own device; ranks that
share a device keep the star. On the CPU-sim mesh (8 devices, rank i on
device i); nothing here is a timing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_mpi as MPI
from tpu_mpi import SpmdContext, config, perfvars
from tpu_mpi.testing import run_spmd

N, CALLS = 4, 10


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("TPU_MPI_PVARS", raising=False)
    config.load(refresh=True)
    perfvars.pcontrol(1)
    perfvars.reset()
    yield
    perfvars.reset()


def _operand(rank, count, dtype, seed=0):
    """Values whose fold depends on the order: magnitudes over seven
    decades for floats (a + b + c is not a + (b + c)), any bits for ints."""
    rng = np.random.default_rng(1000 * seed + rank)
    if np.dtype(dtype).kind in "iu":
        return rng.integers(-2**31, 2**31 - 1, count).astype(dtype)
    x = rng.uniform(0.5, 2.0, count) * 10.0 ** rng.integers(-3, 4, count)
    x = np.where(rng.random(count) < 0.5, -x, x)
    return x.astype(dtype) if np.dtype(dtype).kind != "c" \
        else (x + 1j * x[::-1]).astype(dtype)


def _left_fold(fn, xs):
    acc = xs[0]
    for x in xs[1:]:
        acc = fn(acc, x)
    return acc


def _folds(cid=None):
    return sum(c["ingraph_folds"] for c in perfvars.snapshot()["comms"]
               if cid is None or c["cid"] == cid)


OPS = {
    "sum": (MPI.SUM, np.add, np.float32),
    "prod": (MPI.PROD, np.multiply, np.float32),
    "max": (MPI.MAX, np.maximum, np.float32),
    "bxor": (MPI.BXOR, np.bitwise_xor, np.int32),
    # a user's traceable operator: no ufunc, so nothing says it acts per
    # element, and the executable folds whole operands after an all-gather
    "user-sub": (MPI.Op(lambda a, b: a - b, commutative=False),
                 np.subtract, np.float32),
}


@pytest.mark.parametrize("count", [1024, 1023, 3],
                         ids=["count-1024", "count-1023", "count-3"])
@pytest.mark.parametrize("name", sorted(OPS))
def test_armed_result_is_the_rank_ordered_left_fold(name, count):
    op, fn, dtype = OPS[name]
    got = {}

    def body():
        comm = MPI.COMM_WORLD
        r, dev = comm.rank(), comm.device
        send = MPI.DeviceBuffer(_operand(r, count, dtype), device=dev)
        recv = MPI.DeviceBuffer(jnp.zeros(count, dtype, device=dev),
                                device=dev)
        for _ in range(CALLS):
            MPI.Allreduce(send, recv, op, comm)
        got[r] = (np.asarray(recv.value), recv.value.devices() == {dev})

    run_spmd(body, N)
    want = _left_fold(fn, [_operand(r, count, dtype) for r in range(N)])
    if dtype is np.float32 and name != "max" and count > 1000:
        # among a thousand elements some show the order of the fold
        back = _left_fold(fn, [_operand(r, count, dtype)
                               for r in reversed(range(N))])
        assert want.tobytes() != back.tobytes()
    for r in range(N):
        out, home = got[r]
        assert out.dtype == want.dtype and out.tobytes() == want.tobytes()
        assert home, f"rank {r}'s result is not on its own device"
    assert CALLS - 4 <= _folds(cid=0) < CALLS


def test_split_half_folds_over_its_own_two_devices():
    count, got = 512, {}

    def body():
        world = MPI.COMM_WORLD
        wr = world.rank()
        comm = MPI.Comm_split(world, wr % 2, wr)    # {0, 2} and {1, 3}
        dev = comm.device
        send = MPI.DeviceBuffer(_operand(wr, count, np.float32), device=dev)
        recv = MPI.DeviceBuffer(jnp.zeros(count, jnp.float32, device=dev),
                                device=dev)
        for _ in range(CALLS):
            MPI.Allreduce(send, recv, MPI.SUM, comm)
        got[wr] = (np.asarray(recv.value), recv.value.devices(), dev)

    run_spmd(body, N)
    for wr in range(N):
        out, where, dev = got[wr]
        want = _left_fold(np.add, [_operand(w, count, np.float32)
                                   for w in (wr % 2, wr % 2 + 2)])
        assert out.tobytes() == want.tobytes()
        assert where == {dev} and dev == jax.devices()[wr]
    assert _folds() >= 2 * (CALLS - 4)


@pytest.mark.parametrize("case", ["nine-ranks-eight-devices",
                                  "every-rank-on-one-device",
                                  "buffers-on-one-device",
                                  "complex-operands"])
def test_ranks_that_share_a_device_register_the_star(monkeypatch, case):
    n = 9 if case == "nine-ranks-eight-devices" else N
    dtype = np.complex64 if case == "complex-operands" else np.float32
    if case == "every-rank-on-one-device":      # a one-chip host
        monkeypatch.setattr(SpmdContext, "device_for",
                            lambda self, rank: jax.devices()[0])
    count, got = 256, {}

    def body():
        comm = MPI.COMM_WORLD
        r = comm.rank()
        dev = jax.devices()[0] if case == "buffers-on-one-device" \
            else comm.device
        send = MPI.DeviceBuffer(_operand(r, count, dtype), device=dev)
        recv = MPI.DeviceBuffer(jnp.zeros(count, dtype, device=dev),
                                device=dev)
        for _ in range(CALLS):
            MPI.Allreduce(send, recv, MPI.SUM, comm)
        got[r] = (np.asarray(recv.value), recv.value.devices() == {dev})

    run_spmd(body, n)
    want = _left_fold(np.add, [_operand(r, count, dtype) for r in range(n)])
    for r in range(n):
        assert got[r][0].tobytes() == want.tobytes() and got[r][1]
    assert _folds() == 0
    from tpu_mpi.overlap import plans
    assert plans.stats()["auto"]["hits"] > 0        # and it did arm


@pytest.mark.parametrize("stray", ["host-array", "another-device"])
def test_a_round_with_a_stray_contribution_falls_back(stray):
    """Rank 2 contributes, on some rounds, what the executable over the
    devices cannot take: those rounds fold generically, and are right."""
    count, got = 512, {r: [] for r in range(N)}

    def body():
        comm = MPI.COMM_WORLD
        r, dev = comm.rank(), comm.device
        mine = [_operand(r, count, np.float32, seed=k) for k in range(CALLS)]
        recv = MPI.DeviceBuffer(jnp.zeros(count, jnp.float32, device=dev),
                                device=dev)
        if r == 2 and stray == "host-array":    # numpy all the way
            send, recv = mine[0].copy(), np.zeros(count, np.float32)
        else:
            send = MPI.DeviceBuffer(mine[0], device=dev)
        for k in range(CALLS):
            if isinstance(send, np.ndarray):
                send[:] = mine[k]
            elif r == 2 and k == CALLS - 2:     # once, late: on device 7
                send.value = jax.device_put(mine[k], jax.devices()[7])
            else:
                send.value = jax.device_put(mine[k], dev)
            MPI.Allreduce(send, recv, MPI.SUM, comm)
            got[r].append(np.asarray(getattr(recv, "value", recv)).copy())

    run_spmd(body, N)
    for k in range(CALLS):
        want = _left_fold(np.add, [_operand(r, count, np.float32, seed=k)
                                   for r in range(N)])
        for r in range(N):
            assert got[r][k].tobytes() == want.tobytes(), (r, k)
    if stray == "host-array":
        assert _folds() == 0
    else:
        assert 0 < _folds() < CALLS


def test_two_communicators_launch_over_the_same_devices_at_once():
    """Ranks 0-3 and ranks 8-11 of a 12-rank job sit on devices 0-3 (rank r
    on device r % 8) and fold on a communicator each: two executables over
    the same devices, launched from unrelated threads, a few hundred times.
    Unordered launches could reach two devices in opposite orders and
    wait for each other for good; the job's time limit would say so."""
    rounds, count, got = 300, 64, {}

    def body():
        world = MPI.COMM_WORLD
        wr = world.rank()
        comm = MPI.Comm_split(world, wr // 4, wr)
        dev = comm.device
        send = MPI.DeviceBuffer(_operand(wr, count, np.float32), device=dev)
        recv = MPI.DeviceBuffer(jnp.zeros(count, jnp.float32, device=dev),
                                device=dev)
        for _ in range(rounds):
            MPI.Allreduce(send, recv, MPI.SUM, comm)
        got[wr] = (np.asarray(recv.value), recv.value.devices() == {dev})

    run_spmd(body, 12, timeout=100.0)
    for wr in range(12):
        want = _left_fold(np.add, [_operand(w, count, np.float32)
                                   for w in range(wr // 4 * 4,
                                                  wr // 4 * 4 + 4)])
        assert got[wr][0].tobytes() == want.tobytes() and got[wr][1]
    assert _folds() >= 3 * (rounds - 4)


def test_floats_cross_as_integers_where_the_compiler_sums_to_move(
        monkeypatch):
    """On the CPU the compiler never builds an all-gather from an
    all-reduce; told that it did, the executable is compiled again with
    floats crossing as integers, and is still the left fold, signs of zero
    included."""
    from tpu_mpi import collective
    texts = []
    monkeypatch.setattr(collective, "_sums_to_move",
                        lambda hlo: texts.append(hlo) or True)
    count, got = 7, {}
    zeros = np.array([-0.0, 0.0, -0.0, 1.0, -1.0, 0.0, -0.0], np.float32)

    def body():
        comm = MPI.COMM_WORLD
        r, dev = comm.rank(), comm.device
        send = MPI.DeviceBuffer(zeros * (r + 1), device=dev)
        recv = MPI.DeviceBuffer(jnp.ones(count, jnp.float32, device=dev),
                                device=dev)
        for _ in range(CALLS):
            MPI.Allreduce(send, recv, MPI.SUM, comm)
        got[r] = np.asarray(recv.value)

    run_spmd(body, N)
    want = _left_fold(np.add, [zeros * (r + 1) for r in range(N)])
    assert np.signbit(want).tolist() == np.signbit(zeros).tolist()
    assert all(got[r].tobytes() == want.tobytes() for r in range(N))
    assert len(texts) == 1 and _folds() >= CALLS - 4


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    try:
        return list(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices)
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("count", [2**28, 2], ids=["1GiB", "8B"])
def test_compiled_for_the_v5e_only_integers_are_summed_to_move(
        v5e_2x2, count):
    """The exchange at the benchmark's size and at 8 B, compiled for four
    described v5e chips: at 1 GiB nothing but an all-to-all and an
    all-gather crosses chips and no operand is converted (each conversion
    is a 1 GiB copy there); at 8 B the compiler gathers by an all-reduce
    over zero padding, which only integers may take."""
    import re
    from tpu_mpi import collective
    run, _ = collective._exchange_fold(MPI.SUM, count, np.dtype(np.float32),
                                       v5e_2x2)
    hlo = run.as_text()
    crossing = set(re.findall(
        r"= (\S+) (all-to-all|all-gather|all-reduce|reduce-scatter|"
        r"collective-permute)[\w-]*\(", hlo))
    assert {kind for _, kind in crossing} >= {"all-to-all"}
    for shape, kind in crossing:
        if kind in ("all-reduce", "reduce-scatter"):
            assert "f32" not in shape and "u32" in shape, (shape, kind)
    if count == 2**28:
        assert {kind for _, kind in crossing} == {"all-to-all", "all-gather"}
        assert "bitcast-convert" not in hlo


@pytest.mark.parametrize("attention", ["plain", "fused"])
def test_compiled_for_the_v5e_the_expert_step_fits_one_chip(
        v5e_2x2, kernel_backend, attention):
    """The benchmark's OLMoE step (yardstick/configs/olmoe-1b-7b-1c.json:
    published widths, depth 4, batch 2 x 4096, parameters donated) compiled
    for one described v5e chip. With the plain attention (what this CPU
    backend selects) recomputed in the backward pass it needs 10.5 GB of
    the chip's 16 (15.4 GB with the scores of four layers kept, PR 25);
    with the fused kernel selected as on a TPU (PR 26) nothing is
    recomputed, no [b, h, s, s] buffer exists, it needs 9.4 GB (11.4 GB
    while the experts' weights were re-laid for `ragged-dot`, 10.2 GB while
    every layer kept its experts' permuted output for the router weights'
    gradient, until PR 31), and the
    kernel's calls carry their layer's `attn` scope, forward and backward.
    The experts' grouped multiplications are never a dense product over all
    64 experts: on the plain side the compiler's own `ragged-dot` kernel,
    and with the kernels selected as on a TPU (PR 29: one patch selects
    both) the grouped Pallas kernel in all nine products a layer, under the
    layer's `mlp/experts` scope, with no `ragged-dot` left and no copy that
    re-lays an expert weight for it."""
    import json
    import os
    import re
    if attention == "fused":
        kernel_backend("mosaic")
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tpu_mpi import xla
    from tpu_mpi.models.transformer import (TransformerConfig,
                                            transformer_init,
                                            transformer_train_step)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "yardstick", "configs",
                           "olmoe-1b-7b-1c.json")) as f:
        conf = json.load(f)
    fields = dict(conf["model"], max_seq=4096)
    fields["dtype"] = jnp.dtype(fields["dtype"])
    cfg = TransformerConfig(**fields)
    mesh = xla.make_mesh(dict(conf["mesh"]), devices=v5e_2x2[:1])
    step, specs = transformer_train_step(cfg, mesh, lr=conf["lr"], donate=True)
    shapes = jax.eval_shape(lambda k: transformer_init(k, cfg),
                            jax.random.key(0))
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        shapes, specs)
    tok = jax.ShapeDtypeStruct((2, 4096), jnp.int32,
                               sharding=NamedSharding(mesh, P("dp", "sp")))
    lowered = step.lower(params, tok, tok)
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    held = m.argument_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert m.alias_size_in_bytes > 3.7e9        # the parameters are reused
    hlo = compiled.as_text()
    calls = re.findall(r"(?m)^.*custom_call_target=\"tpu_custom_call\".*"
                       r"op_name=\"([^\"]*causal_attention[^\"]*)\"", hlo)
    if attention == "plain":
        assert 9e9 < held < 11e9, held
        assert "bf16[2,16,4096,4096]" in hlo and not calls
    else:
        assert 8.9e9 < held < 9.9e9, held
        assert ",4096,4096]" not in hlo
        assert all("/attn/" in c for c in calls)
        where = sorted(("transpose(" in c, int(re.search(
            r"jvp\(layer_(\d+)\)", c).group(1))) for c in calls)
        assert where == [(backward, i) for backward in (False, True)
                         for i in range(cfg.n_layers)]
    grouped = re.findall(r"(?m)^.*custom_call_target=\"tpu_custom_call\".*"
                         r"op_name=\"([^\"]*grouped_matmul_(\w+)/[^\"]*)\"", hlo)
    relaid = re.findall(r"copy\([^\n]*op_name=\"[^\"]*w_(?:gate|in|out)", hlo)
    if attention == "plain":
        assert hlo.count("ragged-dot") >= 9 * cfg.n_layers and not grouped
    else:
        assert "ragged-dot" not in hlo and not relaid
        assert all("/mlp/experts/" in name for name, _kind in grouped)
        assert sorted(kind for _name, kind in grouped) == sorted(
            ["fwd", "dlhs", "drhs"] * 3 * cfg.n_layers)
        # what the kernels cost at set-up (PR 29): the module the step is
        # lowered to defines each distinct kernel once, 3 kinds x 2 weight
        # shapes beside attention's two, and calls it from every layer
        kernels = re.findall(r"kernel_name = \"(\w+)\"", lowered.as_text())
        # and since PR 33 the embedding's gradient, its rows summed into
        # their places as a product: one kernel more, no scatter under
        # `embed`
        # and since PR 36 the whole-vector norm of q and of k with their
        # rotation, on the cut heads of 128 (one shape for both: a pair)
        assert sorted(kernels) == sorted(
            ["causal_attention_fwd", "causal_attention_bwd",
             "grouped_row_sums", "norm_rope_fwd", "norm_rope_bwd"]
            + ["grouped_matmul_fwd", "grouped_matmul_dlhs",
               "grouped_matmul_drhs"] * 2), kernels
        assert _row_scatters(hlo) == []
    assert "bf16[64,8192," not in hlo           # no [experts, tokens, ..] product


def _row_scatters(hlo: str) -> list:
    """The `op_name`s of the compiled scatters that lie under `dispatch`,
    `combine` or `embed`."""
    import re
    return [name for name in re.findall(
        r"(?m)^.* scatter\(.*op_name=\"([^\"]*)\"", hlo)
        if re.search(r"\b(dispatch|combine|embed)\b", name)]


def _no_row_is_scattered(hlo: str, cfg) -> None:
    """A held model's compiled step (PR 33): the rows of `combine`, of the
    transpose of `dispatch`'s gather and of the embedding's gradient are
    summed into their places by the product (`grouped_row_sums`, under
    those scopes: the first buffer's two a sparse layer, the further
    buffers' two, the embedding's one) and by no scatter."""
    import re
    sums = re.findall(r"(?m)^.*custom_call_target=\"tpu_custom_call\".*"
                      r"op_name=\"([^\"]*grouped_row_sums[^\"]*)\"", hlo)
    sparse = sum(cfg.layer_kind(i).sparse for i in range(cfg.n_layers))
    where = [re.findall(r"\b(dispatch|combine|embed)\b", name)[-1]
             for name in sums]
    assert sorted(where) == sorted(
        ["combine", "dispatch"] * 2 * sparse + ["embed"]), where
    assert _row_scatters(hlo) == []


def test_compiled_for_the_v5e_the_attention_kernel_at_the_flagships_shape(
        v5e_2x2):
    """The fused causal attention, forward and backward, at batch 8 x 16
    heads x seq 1024 x head 64 in bfloat16 (half a lane tile wide: the
    narrowest head the kernel's contract admits) lowers through Mosaic."""
    from jax.sharding import SingleDeviceSharding
    from tpu_mpi.xla import pallas_kernels as pk
    x = jax.ShapeDtypeStruct((8, 16, 1024, 64), jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e_2x2[0]))
    hlo = jax.jit(jax.grad(lambda q, k, v: pk.causal_attention(
        q, k, v, interpret=False).astype(jnp.float32).sum(),
        (0, 1, 2))).lower(x, x, x).compile().as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert ",1024,1024]" not in hlo


def test_compiled_for_the_v5e_the_layer_kind_step_fits_one_chip(
        v5e_2x2, kernel_backend):
    """The benchmark's K-EXAONE share (yardstick/configs/
    k-exaone-236b-a23b-1c.json: published widths, layers 0-4, 8 of 128
    experts held, batch 1 x 8192, parameters donated) compiled for one
    described v5e chip with the kernels selected as on a TPU: 2.504 B
    parameters, inside the chip's memory with the recomputation the file
    states (18.1 GB with none), no [b, h, t, t] buffer, the fused attention
    kernel forward and backward in every layer under its `attn` scope, the
    grouped kernel in the held experts' products under `mlp/experts`, and
    one kernel body a kind: two attention pairs (window, full), one set of
    grouped products a weight shape."""
    import json
    import os
    import re
    kernel_backend("mosaic")
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tpu_mpi import xla
    from tpu_mpi.models.transformer import (TransformerConfig,
                                            transformer_init,
                                            transformer_train_step)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "yardstick", "configs",
                           "k-exaone-236b-a23b-1c.json")) as f:
        conf = json.load(f)
    fields = dict(conf["model"], max_seq=8192)
    fields["dtype"] = jnp.dtype(fields["dtype"])
    cfg = TransformerConfig(**fields)
    mesh = xla.make_mesh(dict(conf["mesh"]), devices=v5e_2x2[:1])
    step, specs = transformer_train_step(cfg, mesh, lr=conf["lr"], donate=True)
    shapes = jax.eval_shape(lambda k: transformer_init(k, cfg),
                            jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 2_504_068_352
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        shapes, specs)
    tok = jax.ShapeDtypeStruct((1, 8192), jnp.int32,
                               sharding=NamedSharding(mesh, P("dp", "sp")))
    lowered = step.lower(params, tok, tok)
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    held = m.argument_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert m.alias_size_in_bytes > 5.0e9        # the parameters are reused
    assert 10e9 < held < 15e9, held
    hlo = compiled.as_text()
    # no [.., heads, t, t] scores (q's token-major row is [1, t, 64 x 128])
    assert not re.search(r"\[\d+,\d+,8192,8192\]", hlo)
    calls = re.findall(r"(?m)^.*custom_call_target=\"tpu_custom_call\".*"
                       r"op_name=\"([^\"]*causal_attention_(\w+)[^\"]*)\"", hlo)
    assert all("/attn/" in name for name, _d in calls)
    layers = sorted((d, int(re.search(r"layer_(\d+)", name).group(1)))
                    for name, d in calls)
    assert {(d, i) for d, i in layers} == {
        (d, i) for d in ("fwd", "bwd") for i in range(cfg.n_layers)}
    grouped = re.findall(r"(?m)^.*custom_call_target=\"tpu_custom_call\".*"
                         r"op_name=\"([^\"]*grouped_matmul_(\w+)/[^\"]*)\"", hlo)
    assert grouped and "ragged-dot" not in hlo
    # (a recomputed FFN half nests its scopes under `checkpoint`)
    assert all({"mlp", "experts"} <= set(name.split("/"))
               for name, _kind in grouped)
    assert {kind for _n, kind in grouped} == {"fwd", "dlhs", "drhs"}
    assert "bf16[128,8192," not in hlo and "bf16[8,65536," not in hlo
    kernels = re.findall(r"kernel_name = \"(\w+)\"", lowered.as_text())
    assert sorted(set(kernels)) == [
        "causal_attention_bwd", "causal_attention_fwd", "grouped_matmul_dlhs",
        "grouped_matmul_drhs", "grouped_matmul_fwd", "grouped_row_sums",
        "norm_rope_bwd", "norm_rope_fwd"]
    assert kernels.count("causal_attention_fwd") == \
        kernels.count("causal_attention_bwd") == 2, kernels
    # the window layers norm and rotate q's 64 heads and k's 8: two shapes,
    # a pair each; the full layer rotates nothing and norms as it did
    assert kernels.count("norm_rope_fwd") == \
        kernels.count("norm_rope_bwd") == 2, kernels
    _no_row_is_scattered(hlo, cfg)


def test_compiled_for_the_v5e_the_attention_kernel_with_a_window_and_groups(
        v5e_2x2):
    """The fused causal attention, forward and backward, at the drawn
    model's shape (64 query heads reading 8 key/value heads, seq 8192, head
    128, bfloat16) lowers through Mosaic with a window of 128 and without
    one; dk and dv come back with the key/value heads' shape."""
    from jax.sharding import SingleDeviceSharding
    from tpu_mpi.xla import pallas_kernels as pk
    one = SingleDeviceSharding(v5e_2x2[0])
    q = jax.ShapeDtypeStruct((1, 64, 8192, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 8, 8192, 128), jnp.bfloat16, sharding=one)
    for window in (128, 0):
        compiled = jax.jit(jax.grad(lambda q, k, v: pk.causal_attention(
            q, k, v, window=window, interpret=False).astype(
                jnp.float32).sum(), (0, 1, 2))).lower(q, kv, kv).compile()
        hlo = compiled.as_text()
        assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 2
        assert ",8192,8192]" not in hlo
        shapes = [o.shape for o in jax.tree.leaves(compiled.out_info)]
        assert shapes == [(1, 64, 8192, 128), (1, 8, 8192, 128),
                          (1, 8, 8192, 128)]


ROPE_ROWS = {      # (batch, tokens, heads, parts) of a train cell's row
    "flagship packed q|k|v of 64": (8, 1024, 16, ((64, True), (64, True),
                                                  (64, False))),
    "openPangu q row, 128 | 64 rotated": (1, 4096, 64, ((128, False),
                                                        (64, True))),
    "a grouped-query row of 128-wide heads": (1, 8192, 8, ((128, True),)),
}
ROPE_HEADS = {     # heads that are cut before they are normed and rotated
    "OLMoE q or k, the whole vector's norm": ((2, 16, 4096, 128), (16, 128),
                                              2048),
    "K-EXAONE q, 64 heads, a norm a head": ((1, 64, 8192, 128), (128,), 128),
    "K-EXAONE k, 8 heads, a norm a head": ((1, 8, 8192, 128), (128,), 128),
}


@pytest.mark.parametrize("heads", sorted(ROPE_HEADS))
def test_compiled_for_the_v5e_cut_heads_are_normed_and_rotated_by_one_kernel(
        v5e_2x2, heads):
    """`pallas_kernels.norm_rope` at a train cell's [batch, heads, tokens,
    128], bfloat16: the cell's norm of q and k and the rotation, one kernel
    forward, one backward, and no other pass over the heads; the scale's
    gradient is the sum of the blocks' partial sums."""
    import re
    from jax.sharding import SingleDeviceSharding
    from tpu_mpi.xla import pallas_kernels as pk
    one = SingleDeviceSharding(v5e_2x2[0])
    shape, scale_shape, denom = ROPE_HEADS[heads]
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
    table = jax.ShapeDtypeStruct((shape[2], 128), jnp.float32, sharding=one)
    scale = jax.ShapeDtypeStruct(scale_shape, jnp.bfloat16, sharding=one)

    def both(x, scale, cos, sin, cotangent):
        out, back = jax.vjp(lambda x, scale: pk.norm_rope(
            x, scale, cos, sin, eps=1e-6, denom=denom, interpret=False),
            x, scale)
        return out, back(cotangent)
    lowered = jax.jit(both).lower(x, scale, table, table, x)
    assert set(re.findall(r"kernel_name = \"(\w+)\"", lowered.as_text())) \
        == {"norm_rope_fwd", "norm_rope_bwd"}
    hlo = lowered.compile().as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 2
    # (beside them: the sum of the scale's partial sums and its rounding)
    assert not re.search(r" (?:transpose|concatenate|pad)\(", hlo)
    assert len(re.findall(r" fusion\(", hlo)) <= 2


@pytest.mark.parametrize("row", sorted(ROPE_ROWS))
def test_compiled_for_the_v5e_the_rotation_is_one_kernel_each_way(v5e_2x2,
                                                                  row):
    """`pallas_kernels.rope_heads` at a train cell's shape, bfloat16, lowers
    through Mosaic forward and backward (lane rotations, 64-lane pieces
    joined and cut): two kernels and no other computation, the
    parts come out [batch, heads, tokens, width] and the row's gradient
    token-major."""
    import re
    from jax.sharding import SingleDeviceSharding
    from tpu_mpi.xla import pallas_kernels as pk
    one = SingleDeviceSharding(v5e_2x2[0])
    b, t, heads, parts = ROPE_ROWS[row]
    lanes = pk.rope_heads_plan(parts)[0]
    width = heads * sum(w for w, _turned in parts)
    operands = (
        jax.ShapeDtypeStruct((b, t, width), jnp.bfloat16, sharding=one),
        jax.ShapeDtypeStruct((t, lanes), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((t, lanes), jnp.float32, sharding=one),
        tuple(jax.ShapeDtypeStruct((b, heads, t, w), jnp.bfloat16,
                                   sharding=one) for w, _turned in parts))

    def both(x, cos, sin, cotangents):
        outs, back = jax.vjp(lambda x: pk.rope_heads(
            x, cos, sin, heads, parts, interpret=False), x)
        return outs, back(cotangents)[0]
    compiled = jax.jit(both).lower(*operands).compile()
    hlo = compiled.as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 2
    # (a 64-wide part that is this program's own argument or result is
    # copied between the layout a kernel is handed and the entry's)
    assert not re.search(r" (?:fusion|transpose|concatenate|pad)\(", hlo)
    outs, d_row = compiled.out_info
    assert [o.shape for o in outs] == [(b, heads, t, w) for w, _t in parts]
    assert d_row.shape == (b, t, width) and d_row.dtype == jnp.bfloat16


def test_compiled_for_the_v5e_the_attention_kernel_with_two_term_scores(
        v5e_2x2):
    """The fused causal attention, forward and backward, at the latent
    layer's shape (64 heads of 128 unrotated + 64 rotated score dimensions
    beside 128-wide values, ONE rotary key for all heads, seq 4096,
    bfloat16) lowers through Mosaic: the triple (128 + 64, 128) is inside
    the contract as it stands, the shared key enters the kernels with its
    one head, and its gradient comes back with that shape."""
    from jax.sharding import SingleDeviceSharding
    from tpu_mpi.xla import pallas_kernels as pk
    one = SingleDeviceSharding(v5e_2x2[0])

    def shape(heads, width):
        return jax.ShapeDtypeStruct((1, heads, 4096, width), jnp.bfloat16,
                                    sharding=one)
    operands = (shape(64, 128), shape(64, 128), shape(64, 128),
                shape(64, 64), shape(1, 64))
    assert pk.causal_attention_blocks(4096, 128, 64, 128) == (512, 512)
    lowered = jax.jit(jax.grad(
        lambda q, k, v, q2, k2: pk.causal_attention(
            q, k, v, rope=(q2, k2), interpret=False).astype(
                jnp.float32).sum(), (0, 1, 2, 3, 4))).lower(*operands)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert ",4096,4096]" not in hlo
    # both kernels read the one-head key: nothing broadcast to 64 heads
    kernels = [line for line in hlo.splitlines()
               if "custom_call_target=\"tpu_custom_call\"" in line]
    assert all("bf16[1,1,4096,64]" in line for line in kernels)
    shapes = [o.shape for o in jax.tree.leaves(compiled.out_info)]
    assert shapes == [(1, 64, 4096, 128)] * 3 + [(1, 64, 4096, 64),
                                                 (1, 1, 4096, 64)]


def test_compiled_for_the_v5e_the_latent_step_fits_one_chip(v5e_2x2,
                                                            kernel_backend):
    """The benchmark's latent-attention share (yardstick/configs/
    openpangu-ultra-moe-718b-1c.json: published widths, layer 0 and four
    sparse layers, 64 of 128 heads and 8 of 256 experts held, batch 1 x
    4096, parameters donated) compiled for one described v5e chip with the
    kernels selected as on a TPU: 2.958 B parameters, inside the 14.9 GB
    the configuration's rule allows with the recomputation the file states,
    no [b, h, t, t] buffer, the two-term attention kernel forward and
    backward in every layer under its `attn` scope reading the ONE rotary
    key a token, the grouped kernel in the held experts' products, and one
    kernel body a kind."""
    import json
    import os
    import re
    kernel_backend("mosaic")
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tpu_mpi import xla
    from tpu_mpi.models.transformer import (TransformerConfig,
                                            transformer_init,
                                            transformer_train_step)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "yardstick", "configs",
                           "openpangu-ultra-moe-718b-1c.json")) as f:
        conf = json.load(f)
    fields = dict(conf["model"], max_seq=4096)
    fields["dtype"] = jnp.dtype(fields["dtype"])
    cfg = TransformerConfig(**fields)
    mesh = xla.make_mesh(dict(conf["mesh"]), devices=v5e_2x2[:1])
    step, specs = transformer_train_step(cfg, mesh, lr=conf["lr"], donate=True)
    shapes = jax.eval_shape(lambda k: transformer_init(k, cfg),
                            jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 2_958_302_720
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        shapes, specs)
    tok = jax.ShapeDtypeStruct((1, 4096), jnp.int32,
                               sharding=NamedSharding(mesh, P("dp", "sp")))
    lowered = step.lower(params, tok, tok)
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    held = m.argument_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert m.alias_size_in_bytes > 5.9e9        # the parameters are reused
    assert 12e9 < held < 14.9e9, held
    hlo = compiled.as_text()
    assert ",4096,4096]" not in hlo             # no [.., t, t] scores
    calls = re.findall(r"(?m)^(.*custom_call_target=\"tpu_custom_call\".*"
                       r"op_name=\"([^\"]*causal_attention_(\w+)[^\"]*)\".*)$",
                       hlo)
    assert all("/attn/" in name for _line, name, _d in calls)
    assert {(d, int(re.search(r"layer_(\d+)", name).group(1)))
            for _line, name, d in calls} == {
        (d, i) for d in ("fwd", "bwd") for i in range(cfg.n_layers)}
    # the kernels read the one-head rotary key: nothing broadcast to heads
    assert all("bf16[1,1,4096,64]" in line for line, _n, _d in calls)
    grouped = re.findall(r"(?m)^.*custom_call_target=\"tpu_custom_call\".*"
                         r"op_name=\"([^\"]*grouped_matmul_(\w+)/[^\"]*)\"", hlo)
    assert grouped and "ragged-dot" not in hlo
    assert {kind for _n, kind in grouped} == {"fwd", "dlhs", "drhs"}
    kernels = re.findall(r"kernel_name = \"(\w+)\"", lowered.as_text())
    assert sorted(set(kernels)) == [
        "causal_attention_bwd", "causal_attention_fwd", "grouped_matmul_dlhs",
        "grouped_matmul_drhs", "grouped_matmul_fwd", "grouped_row_sums",
        "rope_heads_bwd", "rope_heads_fwd"]
    _no_row_is_scattered(hlo, cfg)
    # two kinds of layer (dense, sparse), ONE attention kind: one pair, and
    # one pair for the query row's [128 unrotated | 64 rotated] heads
    assert kernels.count("causal_attention_fwd") == \
        kernels.count("causal_attention_bwd") == \
        kernels.count("rope_heads_fwd") == \
        kernels.count("rope_heads_bwd") == 1, kernels


def test_compiled_for_the_v5e_the_state_space_step_fits_one_chip(
        v5e_2x2, kernel_backend):
    """The benchmark's granite-4.0-h-micro stage (yardstick/configs/
    granite-4.0-h-micro-1c.json: published widths, layers 0-9, nine
    state-space layers to one attention layer, the whole 100352-row
    vocabulary, batch 1 x 8192, parameters donated) compiled for one
    described v5e chip with the kernels selected as on a TPU: 952 M
    parameters, at most 14.9 GB with nothing recomputed (PR 37: 12.81 GB
    with the scan's decay matrix computed again by XLA; PR 38: 12.92 with
    the scan's kernel pair, which computes it again in VMEM), no [.., t, t]
    buffer and no [.., chunk, chunk] one; the fused attention kernel
    forward and backward in layer 5 alone, under its `attn` scope; the five
    scopes of a mixer in the state-space layers, the scan's two kernels
    under `scan` in each; one trace of the block a mixer kind, and no kernel
    but attention's pair, the scan's pair and the embedding's row sums."""
    import json
    import os
    import re
    kernel_backend("mosaic")
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tpu_mpi import xla
    from tpu_mpi.models import transformer as tf
    from tpu_mpi.models.transformer import (TransformerConfig,
                                            transformer_init,
                                            transformer_train_step)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "yardstick", "configs",
                           "granite-4.0-h-micro-1c.json")) as f:
        conf = json.load(f)
    fields = dict(conf["model"], max_seq=8192)
    fields["dtype"] = jnp.dtype(fields["dtype"])
    cfg = TransformerConfig(**fields)
    mesh = xla.make_mesh(dict(conf["mesh"]), devices=v5e_2x2[:1])
    tf._block_traced_once.cache_clear()
    step, specs = transformer_train_step(cfg, mesh, lr=conf["lr"], donate=True)
    shapes = jax.eval_shape(lambda k: transformer_init(k, cfg),
                            jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 951_991_232
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        shapes, specs)
    tok = jax.ShapeDtypeStruct((1, 8192), jnp.int32,
                               sharding=NamedSharding(mesh, P("dp", "sp")))
    lowered = step.lower(params, tok, tok)
    assert tf._block_traced_once.cache_info().currsize == 2
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    held = m.argument_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert m.alias_size_in_bytes > 1.9e9        # the parameters are reused
    assert 11e9 < held <= 14.9e9, held
    hlo = compiled.as_text()
    assert not re.search(r"\[\d+,\d+,8192,8192\]", hlo)     # no [.., t, t]
    calls = re.findall(r"(?m)^.*custom_call_target=\"tpu_custom_call\".*"
                       r"op_name=\"([^\"]*causal_attention_(\w+)[^\"]*)\"", hlo)
    assert sorted((d, int(re.search(r"layer_(\d+)", name).group(1)))
                  for name, d in calls) == [("bwd", 5), ("fwd", 5)]
    assert all("/attn/" in name for name, _d in calls)
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for i in (0, 4, 6, 9):
        for scope in ("in_proj", "conv", "scan", "gate_norm", "out_proj"):
            assert [n for n in names if f"layer_{i})" in n
                    and f"/mixer/{scope}/" in n], (i, scope)
    assert not [n for n in names if "layer_5)" in n and "/mixer/" in n]
    assert not re.search(r"\[[\d,]*,256,256\]", hlo)     # no decay matrix
    scans = re.findall(r"(?m)^.*custom_call_target=\"tpu_custom_call\".*"
                       r"op_name=\"([^\"]*/mixer/scan/[^\"]*ssm_scan_(\w+)/"
                       r"[^\"]*)\"", hlo)
    assert sorted((d, int(re.search(r"layer_(\d+)", name).group(1)))
                  for name, d in scans) == [
        (d, i) for d in ("bwd", "fwd") for i in range(10) if i != 5]
    kernels = re.findall(r"kernel_name = \"(\w+)\"", lowered.as_text())
    assert sorted(kernels) == [
        "causal_attention_bwd", "causal_attention_fwd"] \
        + 3 * ["conv_silu_bwd"] + 3 * ["conv_silu_fwd"] + [
        "grouped_row_sums", "ssm_scan_bwd", "ssm_scan_fwd"], kernels
    _the_convolution_is_its_kernels(hlo, names, range(10), 3, skip=(5,))
    tf._block_traced_once.cache_clear()


def _the_convolution_is_its_kernels(hlo: str, names, layers, parts: int,
                                    again: int = 0, skip=()) -> None:
    """In a compiled step's text: under each recurrent layer's `mixer/conv`
    the convolution's kernel a part forward (and ``again`` of them once
    more, in what the backward pass recomputes) and backward, in no other
    scope, and nothing of the plain path there: no row filled up in front,
    no silu of XLA's."""
    import re
    calls = re.findall(r"(?m)^.*custom_call_target=\"tpu_custom_call\".*"
                       r"op_name=\"([^\"]*conv_silu_(\w+)/[^\"]*)\"", hlo)
    assert all("/mixer/" in name and "/conv/" in name for name, _d in calls)
    assert sorted((d, int(re.search(r"layer_(\d+)", name).group(1)))
                  for name, d in calls) == sorted(
        (d, i) for d, n in (("bwd", parts), ("fwd", parts + again))
        for i in layers if i not in skip for _ in range(n))
    under = [n for n in names if "/mixer/" in n and "/conv/" in n]
    assert not [n for n in under if n.endswith("/logistic")]
    assert not [n for n in under if n.endswith("/pad")
                and "transpose(" not in n]


def _the_norms_are_their_kernels(hlo: str, layers, forwards: dict) -> None:
    """In a compiled step's text: under each delta-rule layer's `mixer/prep`
    the L2 kernel, q's and k's, ``forwards["l2"]`` times forward and twice
    backward, under its `mixer/gate_norm` the gated kernel
    ``forwards["gated"]`` times forward and once backward, in no other
    scope; and no `copy` or `transpose` of an array as large as a [8192,
    2048] row's in those layers' `conv`, `prep`, `scan`, `gate_norm` or
    `out_proj`: the kernels hand one another the rows as they are."""
    import re
    calls = re.findall(r"(?m)^.*custom_call_target=\"tpu_custom_call\".*"
                       r"op_name=\"([^\"]*head_(\w+)_norm_(\w+)/[^\"]*)\"", hlo)
    assert all("/mixer/" in name and f"/{scope}/" in name
               for name, kind, _d in calls
               for scope in [{"l2": "prep", "gated": "gate_norm"}[kind]])
    assert sorted((kind, d, int(re.search(r"layer_(\d+)", name).group(1)))
                  for name, kind, d in calls) == sorted(
        (kind, d, i) for kind, back in (("l2", 2), ("gated", 1))
        for d, n in (("bwd", back), ("fwd", forwards[kind]))
        for i in layers for _ in range(n))
    moved = re.findall(
        r"(?m)^\s*(?:ROOT\s+)?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
        r"(?:copy|transpose|reshape)\(.*op_name=\"([^\"]*/mixer/[^\"]*)\"", hlo)
    assert not [(dims, name) for dims, name in moved
                if np.prod([int(d) for d in dims.split(",")]) >= 8192 * 2048
                and any(f"/{scope}/" in name for scope in (
                    "conv", "prep", "scan", "gate_norm", "out_proj"))]


def test_compiled_for_the_v5e_the_delta_rule_step_fits_one_chip(
        v5e_2x2, kernel_backend):
    """The benchmark's Qwen3-Next-80B-A3B share (yardstick/configs/
    qwen3-next-80b-a3b-1c.json: published widths, layers 0-7, six delta-rule
    layers and two gated attention layers at head 256, 64 of 512 experts and
    an eighth of the vocabulary, batch 1 x 8192, parameters donated)
    compiled for one described v5e chip with the kernels selected as on a
    TPU: 1.979 B parameters, at most 14.9 GB with every FFN half recomputed
    (PR 46: 12.67 GB, the scan's [chunk x chunk] temporaries gone with its
    loops; PR 45: 13.73 GB; 15.46 while the compiler kept float32 copies of z, q,
    k and the gated output and the convolution's outputs were kept, and
    20.18 with nothing recomputed); no [.., t, t] buffer: the fused
    attention kernel at (8192, 256) forward and backward in layers 3 and 7
    alone; the six scopes of a delta-rule mixer in the other six, the scan
    the delta-rule kernel pair under `scan` (PR 46) and no loop of XLA's; one
    trace of the block a mixer kind; the experts' products on the grouped kernel;
    and nothing the compiler chose to compute again to fit."""
    import json
    import os
    import re
    kernel_backend("mosaic")
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tpu_mpi import perfvars, xla
    from tpu_mpi.models import transformer as tf
    from tpu_mpi.models.transformer import (TransformerConfig,
                                            transformer_init,
                                            transformer_train_step)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "yardstick", "configs",
                           "qwen3-next-80b-a3b-1c.json")) as f:
        conf = json.load(f)
    fields = dict(conf["model"], max_seq=8192)
    fields["dtype"] = jnp.dtype(fields["dtype"])
    cfg = TransformerConfig(**fields)
    mesh = xla.make_mesh(dict(conf["mesh"]), devices=v5e_2x2[:1])
    tf._block_traced_once.cache_clear()
    perfvars.reset()
    step, specs = transformer_train_step(cfg, mesh, lr=conf["lr"], donate=True)
    shapes = jax.eval_shape(lambda k: transformer_init(k, cfg),
                            jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 1_978_847_360
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        shapes, specs)
    tok = jax.ShapeDtypeStruct((1, 8192), jnp.int32,
                               sharding=NamedSharding(mesh, P("dp", "sp")))
    lowered = step.lower(params, tok, tok)
    assert tf._block_traced_once.cache_info().currsize == 2
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    held = m.argument_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert m.alias_size_in_bytes > 3.9e9        # the parameters are reused
    assert 12e9 < held <= 14.9e9, held
    hlo = compiled.as_text()
    assert not re.search(r"\[\d+,\d+,8192,8192\]", hlo)     # no [.., t, t]
    assert ".remat" not in hlo          # the compiler recomputes nothing
    calls = re.findall(r"(?m)^.*custom_call_target=\"tpu_custom_call\".*"
                       r"op_name=\"([^\"]*causal_attention_(\w+)[^\"]*)\"", hlo)
    assert sorted((d, int(re.search(r"layer_(\d+)", name).group(1)))
                  for name, d in calls) == [("bwd", 3), ("bwd", 7),
                                            ("fwd", 3), ("fwd", 7)]
    assert all("/attn/" in name for name, _d in calls)
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for i in (0, 2, 4, 6):
        for scope in ("in_proj", "conv", "prep", "scan", "gate_norm",
                      "out_proj"):
            assert [n for n in names if f"layer_{i})" in n and "/mixer/" in n
                    and f"/{scope}/" in n], (i, scope)
        assert not [n for n in names if f"layer_{i})" in n and "/attn/" in n]
    for i in (3, 7):
        assert not [n for n in names if f"layer_{i})" in n and "/mixer/" in n]
        for scope in ("qk_norm", "out_gate"):
            assert [n for n in names if f"layer_{i})" in n
                    and f"/attn/{scope}/" in n], (i, scope)
    assert "ragged-dot" not in hlo
    kernels = re.findall(r"kernel_name = \"(\w+)\"", lowered.as_text())
    assert sorted(set(kernels)) == [
        "causal_attention_bwd", "causal_attention_fwd", "conv_silu_bwd",
        "conv_silu_fwd", "delta_scan_bwd", "delta_scan_fwd",
        "grouped_matmul_dlhs", "grouped_matmul_drhs", "grouped_matmul_fwd",
        "grouped_row_sums", "head_gated_norm_bwd", "head_gated_norm_fwd",
        "head_l2_norm_bwd", "head_l2_norm_fwd"]
    # q's, k's and v's; forward again for q and k alone, v is kept
    _the_convolution_is_its_kernels(hlo, names, (0, 1, 2, 4, 5, 6), 3,
                                    again=2)
    # q's and k's L2 norms and the gated norm once each way: what the
    # recomputed operands' norms give again nobody reads (the scan keeps
    # its operands, a norm's backward reads the convolution's output alone)
    _the_norms_are_their_kernels(hlo, (0, 1, 2, 4, 5, 6),
                                 {"l2": 2, "gated": 1})
    assert perfvars.snapshot()["head_norm_lowerings"] == {
        "kernel": 3, "plain": 0}        # one trace of the delta-rule block
    assert kernels.count("causal_attention_fwd") == \
        kernels.count("causal_attention_bwd") == 1, kernels
    assert kernels.count("delta_scan_fwd") == \
        kernels.count("delta_scan_bwd") == 1, kernels   # six layers, one trace
    scans = re.findall(r"(?m)^.*custom_call_target=\"tpu_custom_call\".*"
                       r"op_name=\"([^\"]*delta_scan_(\w+)/[^\"]*)\"", hlo)
    assert sorted((d, int(re.search(r"layer_(\d+)", name).group(1)))
                  for name, d in scans) == sorted(
        (d, i) for d in ("bwd", "fwd") for i in (0, 1, 2, 4, 5, 6))
    assert all("/mixer/scan/" in name for name, _d in scans)
    assert "while" not in "".join(n for n in names if "/mixer/scan/" in n)
    tf._block_traced_once.cache_clear()
    perfvars.reset()


def test_compiled_for_the_v5e_the_kda_step_fits_one_chip(v5e_2x2,
                                                         kernel_backend):
    """The benchmark's Kimi-Linear-48B-A3B share (yardstick/configs/
    kimi-linear-48b-a3b-1c.json: published widths, layers 1-8, six KDA layers
    whose decay is a number a key channel and two positionless latent
    attention layers, the dense FFN once and seven expert layers of 32 of 256
    experts, an eighth of the vocabulary, batch 1 x 8192, parameters donated)
    compiled for one described v5e chip with the kernels selected as on a
    TPU: 2.093 B parameters, at most 14.9 GB with every FFN half recomputed
    and a KDA layer's first half one recomputed function of the stream (PR
    49: 10.21 GB, the half keeping the scan's states before each chunk AND
    its output, so that the forward kernel runs once a layer; 9.70 with the
    states alone and 8.41 with neither, the forward kernel twice a layer
    either way; PR 48, the scan on the plain path: 13.82 GB; 16.6 with the
    half's products kept, 16.95 while the decayed products' [16, 16, 128]
    form a pair and channel was an array in HBM); no [.., t, t] buffer and
    no [.., r, r, key width] one; the two-term attention kernel at (8192,
    128 + 64, 128) forward and backward in layers 3 and 7 alone, nothing
    rotated; the seven scopes of a KDA mixer in the other six, the scan the
    delta-rule kernel pair for a decay a channel under `scan`, once each way
    a layer, and no loop of XLA's there; PR 47's convolution kernel a part;
    three traces of the block (KDA + dense, KDA + experts, latent +
    experts); the experts' products on the grouped kernel; and nothing the
    compiler chose to compute again to fit."""
    import json
    import os
    import re
    kernel_backend("mosaic")
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tpu_mpi import perfvars, xla
    from tpu_mpi.models import transformer as tf
    from tpu_mpi.models.transformer import (TransformerConfig,
                                            transformer_init,
                                            transformer_train_step)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "yardstick", "configs",
                           "kimi-linear-48b-a3b-1c.json")) as f:
        conf = json.load(f)
    fields = dict(conf["model"], max_seq=8192)
    fields["dtype"] = jnp.dtype(fields["dtype"])
    cfg = TransformerConfig(**fields)
    mesh = xla.make_mesh(dict(conf["mesh"]), devices=v5e_2x2[:1])
    tf._block_traced_once.cache_clear()
    perfvars.reset()
    step, specs = transformer_train_step(cfg, mesh, lr=conf["lr"], donate=True)
    shapes = jax.eval_shape(lambda k: transformer_init(k, cfg),
                            jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 2_092_548_288
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        shapes, specs)
    tok = jax.ShapeDtypeStruct((1, 8192), jnp.int32,
                               sharding=NamedSharding(mesh, P("dp", "sp")))
    lowered = step.lower(params, tok, tok)
    assert tf._block_traced_once.cache_info().currsize == 3
    snap = perfvars.snapshot()
    assert snap["delta_kernel_lowerings"] == {"kernel": 2, "plain": 0}
    assert snap["delta_decays"] == {"head": 0, "channel": 2}
    assert snap["head_norm_lowerings"] == {"kernel": 6, "plain": 0}
    assert snap["attn_kinds"] == {"latent": "fused"}
    assert snap["rope_forms"] == {"dense": 0, "halves": 0}
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    held = m.argument_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert m.alias_size_in_bytes > 4.1e9        # the parameters are reused
    assert 9.9e9 < held <= 14.9e9, held     # (under 9.9: o is not kept)
    hlo = compiled.as_text()
    assert not re.search(r"\[\d+,\d+,8192,8192\]", hlo)     # no [.., t, t]
    assert not re.search(r"\[[\d,]*16,16,128\]", hlo)    # nor [.., r, r, dk]
    assert ".remat" not in hlo          # the compiler recomputes nothing
    calls = re.findall(r"(?m)^.*custom_call_target=\"tpu_custom_call\".*"
                       r"op_name=\"([^\"]*causal_attention_(\w+)[^\"]*)\"", hlo)
    assert sorted((d, int(re.search(r"layer_(\d+)", name).group(1)))
                  for name, d in calls) == [("bwd", 3), ("bwd", 7),
                                            ("fwd", 3), ("fwd", 7)]
    assert all("/attn/" in name for name, _d in calls)
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for i in (0, 2, 4, 6):
        for scope in ("in_proj", "conv", "prep", "decay", "scan", "gate_norm",
                      "out_proj"):
            assert [n for n in names if f"layer_{i})" in n and "/mixer/" in n
                    and f"/{scope}/" in n], (i, scope)
        assert not [n for n in names if f"layer_{i})" in n and "/attn/" in n]
    for i in (3, 7):
        assert not [n for n in names if f"layer_{i})" in n and "/mixer/" in n]
        assert [n for n in names if f"layer_{i})" in n
                and "/attn/q_proj/" in n], i
        assert not [n for n in names if f"layer_{i})" in n
                    and "/attn/q_latent/" in n], i
    assert [n for n in names if "layer_0)" in n and "/mlp/dense/" in n]
    assert not [n for n in names if "layer_0)" in n and "/mlp/router/" in n]
    assert "ragged-dot" not in hlo
    kernels = re.findall(r"kernel_name = \"(\w+)\"", lowered.as_text())
    assert sorted(set(kernels)) == [
        "causal_attention_bwd", "causal_attention_fwd", "conv_silu_bwd",
        "conv_silu_fwd", "delta_channel_scan_bwd", "delta_channel_scan_fwd",
        "grouped_matmul_dlhs", "grouped_matmul_drhs", "grouped_matmul_fwd",
        "grouped_row_sums", "head_gated_norm_bwd", "head_gated_norm_fwd",
        "head_l2_norm_bwd", "head_l2_norm_fwd"]
    # q's, k's and v's; forward again for all three: the half is recomputed
    _the_convolution_is_its_kernels(hlo, names, (0, 1, 2, 4, 5, 6), 3,
                                    again=3)
    # and so are the norms: q's and k's twice forward each, the gated norm
    # twice, not three times (it is no recomputed function of its own)
    _the_norms_are_their_kernels(hlo, (0, 1, 2, 4, 5, 6),
                                 {"l2": 4, "gated": 2})
    assert kernels.count("causal_attention_fwd") == \
        kernels.count("causal_attention_bwd") == 1, kernels
    # the scan's two kernels once each a KDA layer (the recomputed half keeps
    # the forward one's two outputs: it does not run again), and no loop
    assert kernels.count("delta_channel_scan_fwd") == \
        kernels.count("delta_channel_scan_bwd") == 2, kernels   # two traces
    scans = re.findall(r"(?m)^.*custom_call_target=\"tpu_custom_call\".*"
                       r"op_name=\"([^\"]*delta_channel_scan_(\w+)/[^\"]*)\"",
                       hlo)
    assert sorted((d, int(re.search(r"layer_(\d+)", name).group(1)))
                  for name, d in scans) == sorted(
        (d, i) for d in ("bwd", "fwd") for i in (0, 1, 2, 4, 5, 6))
    assert all("/mixer/" in name and "/scan/" in name for name, _d in scans)
    assert "while" not in "".join(
        n for n in names if "/mixer/" in n and "/scan/" in n)
    tf._block_traced_once.cache_clear()
    perfvars.reset()


@pytest.mark.parametrize("what, width, gate", [
    ("kimi's q and k", 4096, None),
    ("qwen3-next's q and k", 2048, None),
    ("qwen3-next's gated norm", 4096, "silu"),
    ("kimi's gated norm", 4096, "sigmoid")])
def test_compiled_for_the_v5e_the_head_norm_is_one_kernel_each_way(
        what, width, gate, v5e_2x2):
    """The per-head norms' kernel pair at the widths of the benchmark's two
    delta-rule cells (one sequence of 8192 tokens, bfloat16: the L2 norm of
    Kimi's 32 and Qwen3-Next's 16 key heads, the gated RMSNorm of 32 value
    heads with z as rows and with the gate's 128-wide product inside the
    kernel) lowers through Mosaic forward and backward (a lane offset that
    is a loop's index, the product's three forms on the MXU, d w summed in
    its float32 output block): a kernel each way and no loop of XLA's, and
    the gradients come back with their operands' shapes and types."""
    from jax.sharding import SingleDeviceSharding
    from tpu_mpi.xla import head_norm_kernels as hk
    one = SingleDeviceSharding(v5e_2x2[0])
    bf16 = jnp.bfloat16
    rows = (1, 8192, width)
    shapes = {None: (rows,), "silu": (rows, (128,), rows),
              "sigmoid": (rows, (128,), (1, 8192, 128), (128, width))}[gate]
    operands = tuple(jax.ShapeDtypeStruct(shape, bf16, sharding=one)
                     for shape in shapes)

    def norm(x, *rest):
        if gate is None:
            return hk.l2_norm(x, scale=128 ** -0.5, interpret=False)
        return hk.gated_rms_norm(x, *rest, act=gate, eps=1e-6,
                                 interpret=False)

    def both(*a):
        out, back = jax.vjp(norm, *a)
        return out, back(jnp.ones_like(out))
    compiled = jax.jit(both).lower(*operands).compile()
    hlo = compiled.as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert " while(" not in hlo
    out, grads = compiled.out_info
    assert (out.shape, out.dtype) == (rows, bf16)
    assert [(o.shape, o.dtype) for o in grads] \
        == [(o.shape, o.dtype) for o in operands]


def test_compiled_for_the_v5e_the_attention_kernel_with_values_wider_than_scores(
        v5e_2x2):
    """The fused causal attention, forward and backward, at differential
    attention's shape in the benchmark's phi-4-mini-flash-reasoning stage (40
    query heads of 64 reading 20 key heads of 64 and 20 value heads of 128, a
    pair's values side by side; seq 8192, bfloat16, no second score term)
    lowers through Mosaic with the window of 512 and without one; dk and dv
    come back with the key/value heads' shapes."""
    from jax.sharding import SingleDeviceSharding
    from tpu_mpi.xla import pallas_kernels as pk
    one = SingleDeviceSharding(v5e_2x2[0])
    assert pk.causal_attention_blocks(8192, 64, 0, 128) == (512, 512)
    q = jax.ShapeDtypeStruct((1, 40, 8192, 64), jnp.bfloat16, sharding=one)
    k = jax.ShapeDtypeStruct((1, 20, 8192, 64), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((1, 20, 8192, 128), jnp.bfloat16, sharding=one)
    for window in (512, 0):
        compiled = jax.jit(jax.grad(lambda q, k, v: pk.causal_attention(
            q, k, v, window=window, interpret=False).astype(
                jnp.float32).sum(), (0, 1, 2))).lower(q, k, v).compile()
        hlo = compiled.as_text()
        assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 2
        assert ",8192,8192]" not in hlo
        shapes = [o.shape for o in jax.tree.leaves(compiled.out_info)]
        assert shapes == [(1, 40, 8192, 64), (1, 20, 8192, 64),
                          (1, 20, 8192, 128)]


def test_compiled_for_the_v5e_the_selective_scan_is_one_kernel_each_way(
        v5e_2x2):
    """The selective (Mamba-1) scan's kernel pair at the shape of the
    benchmark's phi-4-mini-flash-reasoning stage (one sequence of 8192
    tokens, 5120 channels over a state of 16, bfloat16 operands, float32
    dt, A and D) lowers through Mosaic forward and backward (dynamic lane
    rotations, sublane folds, the 128 x 128 turns, 17 MB of VMEM scratch):
    two kernels and no loop of XLA's, and the six gradients come back with
    their operands' shapes and types."""
    from jax.sharding import SingleDeviceSharding
    from tpu_mpi.xla import sel_scan_kernels as sk
    one = SingleDeviceSharding(v5e_2x2[0])
    b, t, ch, n = 1, 8192, 5120, sk.SEL_STATE
    bf16, f32 = jnp.bfloat16, jnp.float32
    operands = tuple(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one) for shape, dtype in (
            ((b, t, ch), bf16), ((b, t, ch), f32), ((ch, n), f32),
            ((b, t, n), bf16), ((b, t, n), bf16), ((ch,), f32)))
    compiled = jax.jit(jax.grad(
        lambda *a: sk.sel_scan(*a, interpret=False).astype(f32).sum(),
        tuple(range(6)))).lower(*operands).compile()
    hlo = compiled.as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert " while(" not in hlo
    assert [(o.shape, o.dtype) for o in jax.tree.leaves(compiled.out_info)] \
        == [(o.shape, o.dtype) for o in operands]


@pytest.mark.parametrize("what, columns, start, channels, cuts", [
    ("granite", 8512, 4096, 4352, (4096, 4224)),
    ("phi", 10240, 0, 5120, ()),
    ("qwen3-next", 8192, 0, 8192, (2048, 4096))])
def test_compiled_for_the_v5e_the_convolution_is_one_kernel_each_way_a_part(
        what, columns, start, channels, cuts, v5e_2x2):
    """The causal convolution's kernel pair at the shapes of the benchmark's
    three recurrent stages (one sequence of 8192 tokens, four taps,
    bfloat16; granite's 4352 channels from column 4096 of a row of 8512,
    phi's first 5120 of 10240, Qwen3-Next's 8192 cut into q, k and v) lowers
    through Mosaic forward and backward (unaligned loads of the float32 rows,
    a second window on x): a kernel a part each way and no loop of XLA's,
    and the gradients come back with their operands' shapes and types, dx
    the whole row's."""
    from jax.sharding import SingleDeviceSharding
    from tpu_mpi.xla import conv_kernels as ck
    one = SingleDeviceSharding(v5e_2x2[0])
    bf16 = jnp.bfloat16
    operands = tuple(jax.ShapeDtypeStruct(shape, bf16, sharding=one)
                     for shape in ((1, 8192, columns), (4, channels),
                                   (channels,)))

    def both(x, w, bias):
        out, back = jax.vjp(lambda *a: ck.conv_silu(
            *a, start=start, cuts=cuts, interpret=False), x, w, bias)
        return out, back(jax.tree.map(jnp.ones_like, out))
    compiled = jax.jit(both).lower(*operands).compile()
    hlo = compiled.as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") \
        == 2 * (len(cuts) + 1)
    assert " while(" not in hlo
    out, grads = compiled.out_info
    widths = [hi - lo for lo, hi in zip((0, *cuts), (*cuts, channels))]
    assert [(o.shape, o.dtype) for o in jax.tree.leaves(out)] \
        == [((1, 8192, n), bf16) for n in widths]
    assert [(o.shape, o.dtype) for o in grads] \
        == [(o.shape, o.dtype) for o in operands]
