"""chip_smoke.py — does the system still start on the chip?

Drives the four ways a user reaches the device, once each, through the
normal entry points, in ONE process (a chip belongs to one process; this
script starts no child that needs it):

1. host   — ``tpu_mpi.launcher.main(["-n", "4", <script>])`` (the ``tpurun``
   entry, rank threads): ``DeviceBuffer`` Float32[2^26] on ``comm.device``
   through Allreduce (eager first call, the compiled ``_jitted_fold``, the
   auto-armed registered lane: one left chain, compiled by XLA), a hand-armed
   ``Allreduce_init``/``Start``/``Wait`` (donated fold), Bcast,
   Reduce_scatter, Alltoall, a Sendrecv ring, one Win Put/Get epoch, Barrier;
2. ingraph — ``xla.allreduce/allgather/reduce_scatter/alltoall/sendrecv``
   under ``jit(shard_map)`` at Float32[2^26] per device, and
   ``transformer_train_step`` at the widest configuration the repo runs
   (benchmarks/flagship_probe.py), a few steps on a fixed batch;
3. kernels — the ring kernels of ``tpu_mpi.xla.pallas_kernels`` and the
   experts' grouped product compiled by Mosaic, numerics against the XLA
   collective, ``lax.ragged_dot`` (values and both gradients) or a jnp
   reference, the recurrent mixers' convolution against its plain path
   (values and three gradients), the delta-rule scan with a decay a key
   channel against its plain path (values and five gradients); an
   OLMoE-shaped expert layer takes the kernel's route and compiles to the
   kernels alone, and its rows' way into expert order and
   back (`parallel.ep.moe_dropless`) agrees with the plain form, forward
   and backward, the four passes timed;
4. serve  — ``serve.Broker(nranks=4, infer=True)`` answering three
   ``session.generate`` calls. The engine is host numpy by design (ROADMAP
   S3): this leg proves the broker, the event front door and the native
   transport build and answer on this machine, and says so.

Every result is checked against a closed form or a reference after a host
readback; on the chip every collective result is asserted to be a
``jax.Array`` resident on the TPU device that owns it. With four chips rank
i's buffers live on chip i, the train mesh is dp x tp x sp = 1 x 2 x 2 and
the ring kernels run at n = 4 with remote DMA.

Refuses to run (exit 2, one line on stderr, no result) unless JAX's default
backend is a TPU whose ``device_kind`` the capability table knows. It sets
``TPU_MPI_BACKEND=tpu``, so nothing underneath falls back to the CPU either.
``--tiny-cpu`` runs the same leg functions at toy sizes on the CPU with the
kernels in interpret mode; it exists for tests/test_chip_smoke.py and must
be asked for — the absence of a chip never selects it.

Per-leg compile and run seconds are printed as set-up facts, not metrics.
The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

NRANKS = 4
PERIOD = 251          # pattern period: coprime to every block size used

# What runs on the chip. The train configuration is the widest the repo
# runs (benchmarks/flagship_probe.py): nothing is cut.
FULL = {
    "n": 1 << 26,                 # Float32[2^26] per rank / per device
    "win": 1 << 20,               # RMA window elements (Put/Get go via host)
    "train": dict(vocab=32768, d_model=1024, n_heads=16, n_layers=8,
                  d_ff=4096, max_seq=1024, batch=8, steps=4, lr=0.01),
    "ring": 250_000,              # per-device elements of the ring kernels
    "attn": (2048, 128),          # per-device (seq, head_dim), bf16 causal
    "grouped": (8192, 2048, 1024, 16),  # rows, k, n, groups of one product
    "row_sums": (4096, 4096, 7680),     # rows summed into places x width
    # an expert layer at OLMoE's widths: d_model, d_ff, experts, top, seq
    "expert_layer": (2048, 1024, 64, 8, 4096),
    # a layer's row movement at OLMoE's shape: tokens, d_model, experts, top
    "expert_rows": (8192, 2048, 64, 8),
    # heads, key/value heads, seq, head_dim, window of one attention block
    "window_attn": (16, 2, 4096, 128, 128),
    # a recurrent mixer's convolution: seq, columns of the row it reads,
    # first column, channels, cuts (tiles of four lane tiles, two blocks)
    "conv": (2048, 2560, 512, 1536, (1024,)),
    # the delta-rule scan with a decay a key channel: seq, heads (of 128)
    "channel_scan": (1024, 4),
    # a delta-rule mixer's per-head norms: seq, heads (of 128), the rank of
    # the gate's product (two blocks of 256 tokens of the Kimi cell's rows)
    "head_norm": (512, 32, 128),
    "max_new": 8,
}
# Toy sizes for the tier-1 CPU test only.
TINY = {
    "n": 1 << 12,
    "win": 1 << 8,
    "train": dict(vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
                  max_seq=32, batch=4, steps=3, lr=0.02),
    "ring": 1000,                 # the interpreter stalls on larger rings
    "attn": (32, 64),
    "grouped": (256, 128, 128, 5),
    "row_sums": (256, 256, 128),
    "expert_layer": (128, 256, 4, 2, 64),
    "expert_rows": (64, 128, 4, 2),
    "window_attn": (4, 2, 256, 128, 100),
    "conv": (256, 448, 128, 256, (128,)),
    "channel_scan": (128, 2),
    "head_norm": (256, 2, 128),
    "max_new": 4,
}


def _log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# leg 1: the host MPI path
# ---------------------------------------------------------------------------

def _pattern_at(i: int, lo: int, blk: int, mul: int, add: int,
                step: int) -> float:
    """The closed form every host-leg operand and result follows, at one
    position, in plain Python: ``mul * ((lo + i % blk) % PERIOD) + add +
    (i // blk) * step``."""
    return float(mul * ((lo + i % blk) % PERIOD) + add + (i // blk) * step)


@functools.lru_cache(maxsize=None)
def _device_fns():
    """(pattern, verify), jitted once for every rank thread: the closed form
    as a device array, and a result's agreement with it (everywhere, as one
    bool, plus three sampled values for the host to judge itself)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=0)
    def pattern(cnt, lo, blk, mul, add, step):
        i = jnp.arange(cnt, dtype=jnp.int32)
        return (mul * ((lo + i % blk) % PERIOD) + add
                + (i // blk) * step).astype(jnp.float32)

    @jax.jit
    def verify(v, lo, blk, mul, add, step):
        want = pattern(v.size, lo, blk, mul, add, step)
        at = jnp.array([0, v.size // 2 + 1, v.size - 1])
        return jnp.array_equal(v, want), v[at]

    return pattern, verify


def _host_rank(outdir: str, n: int, win_n: int) -> None:
    """The SPMD program every rank runs (under ``tpurun`` as rank threads,
    or — by hand on the four-chip host — under ``tpurun --procs`` with one
    chip per process). Writes ``rank<r>.json`` into ``outdir``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import tpu_mpi as MPI
    from tpu_mpi import collective
    from tpu_mpi.overlap import plans

    MPI.Init()
    comm = MPI.COMM_WORLD
    r, size = comm.rank(), comm.size()
    dev = comm.device
    threads = os.environ.get("TPU_MPI_PROC_RANK") is None
    facts: dict = {"rank": r, "device": dev.id, "platform": dev.platform,
                   "tier": "threads" if threads else "procs"}
    chunk = n // size
    const = size * (size - 1) // 2
    left, right = (r - 1) % size, (r + 1) % size
    pattern, verify = _device_fns()

    def buf(cnt: int, *form) -> "MPI.DeviceBuffer":
        """A DeviceBuffer on this rank's chip: the pattern, or zeros."""
        return MPI.DeviceBuffer(pattern(cnt, *form) if form
                                else jnp.zeros(cnt, jnp.float32), device=dev)

    def check(what: str, x: "MPI.DeviceBuffer", *form) -> None:
        """The result is a jax.Array resident on this rank's chip and equals
        the closed form: everywhere (compared on the device, one bool read
        back) and at three positions read back and judged in Python."""
        v = x.value
        assert isinstance(v, jax.Array), (what, type(v))
        assert v.devices() == {dev}, (what, v.devices(), dev)
        ok, got = verify(v, *form)
        assert bool(ok), f"{what}: wrong values"
        at = (0, v.size // 2 + 1, v.size - 1)
        assert np.asarray(got).tolist() == \
            [_pattern_at(i, *form) for i in at], (what, got)

    whole = (0, n)                      # (lo, blk) of an unblocked array
    total = (*whole, size, const, 0)    # the sum over ranks of pattern + r
    with jax.default_device(dev):
        send = buf(n, *whole, 1, r, 0)
        out = buf(n)

        # -- plain Allreduce, repeated past the auto-arm threshold ----------
        times = []
        for k in range(8):
            out.fill(0)
            out.value.block_until_ready()
            t0 = time.perf_counter()
            MPI.Allreduce(send, out, MPI.SUM, comm)
            out.value.block_until_ready()
            times.append(time.perf_counter() - t0)
            check(f"Allreduce#{k}", out, *total)
        facts["allreduce_s"] = {"first_eager": times[0],
                                "second_compile": times[1],
                                "last_armed": times[-1]}
        if threads:
            assert plans.auto_hits > 0, "the auto-armed lane never ran"
            key = (MPI.SUM.fn, "reduce", size, "float32", ((n,),) * size)
            fold = collective._fold_compiled.get(key)
            assert fold is not None and fold is not collective._NOT_JITTABLE
            text = fold.lower(*[jax.ShapeDtypeStruct(
                (n,), jnp.float32)] * size).as_text()
            assert "custom_call" not in text, \
                "the compiled fold is not the plain left chain"
            facts["fold"] = "chain"

        # -- hand-armed persistent Allreduce: the donated fold --------------
        t0 = time.perf_counter()
        req = MPI.Allreduce_init(send, out, MPI.SUM, comm)
        for k in range(3):
            out.fill(0)
            MPI.Start(req)
            MPI.Wait(req)
            check(f"Allreduce_init#{k}", out, *total)
        facts["persistent_s"] = time.perf_counter() - t0

        # -- Bcast from rank 1 ----------------------------------------------
        root = 1 % size
        b = buf(n, *whole, 1, r, 0)
        MPI.Bcast(b, root, comm)
        check("Bcast", b, *whole, 1, root, 0)

        # -- Reduce_scatter: rank r keeps block r of the sum -----------------
        rs = buf(chunk)
        MPI.Reduce_scatter(send, rs, [chunk] * size, MPI.SUM, comm)
        check("Reduce_scatter", rs, r * chunk, chunk, size, const, 0)

        # -- Alltoall: block s of the result is sender s's block r -----------
        a2a = buf(n)
        MPI.Alltoall(send, a2a, chunk, comm)
        check("Alltoall", a2a, r * chunk, chunk, 1, 0, 1)

        # -- Sendrecv ring ----------------------------------------------------
        ring = buf(n)
        MPI.Sendrecv(send, right, 7, ring, left, 7, comm)
        check("Sendrecv", ring, *whole, 1, left, 0)

        # -- one RMA epoch: Put into the right neighbour, Get it back --------
        wbuf, got = buf(win_n), buf(win_n)
        win = MPI.Win_create(wbuf, comm)
        MPI.Win_fence(0, win)
        MPI.Put(buf(win_n, 0, win_n, 1, r, 0), right, win)
        MPI.Win_fence(0, win)
        MPI.Get(got, right, win)
        MPI.Win_fence(0, win)
        check("Put", wbuf, 0, win_n, 1, left, 0)
        check("Get", got, 0, win_n, 1, r, 0)

    MPI.Barrier(comm)
    MPI.Finalize()
    with open(os.path.join(outdir, f"rank{r}.json"), "w") as f:
        json.dump(facts, f)


def _host_rank_entry(argv: list) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--host-rank", dest="outdir", required=True)
    ap.add_argument("--n", type=int, default=FULL["n"])
    ap.add_argument("--win", type=int, default=FULL["win"])
    a = ap.parse_args(argv)
    os.makedirs(a.outdir, exist_ok=True)
    try:
        _host_rank(a.outdir, a.n, a.win)
    except BaseException:
        traceback.print_exc()       # tpurun reports only the message
        raise


def leg_host(sz: dict, platform: str) -> dict:
    import jax
    from tpu_mpi import launcher

    outdir = tempfile.mkdtemp(prefix="chip_smoke_host_")
    try:
        t0 = time.perf_counter()
        rc = launcher.main(["-n", str(NRANKS), os.path.abspath(__file__),
                            "--host-rank", outdir, "--n", str(sz["n"]),
                            "--win", str(sz["win"])])
        wall = time.perf_counter() - t0
        assert rc == 0, f"tpurun -n {NRANKS} exited {rc}"
        ranks = []
        for r in range(NRANKS):
            with open(os.path.join(outdir, f"rank{r}.json")) as f:
                ranks.append(json.load(f))
    finally:
        shutil.rmtree(outdir, ignore_errors=True)
    assert all(f["platform"] == platform for f in ranks), ranks
    devices = sorted({f["device"] for f in ranks})
    want = min(NRANKS, len(jax.devices()))
    assert len(devices) == want, \
        f"rank operands on devices {devices}, expected {want} distinct"
    return {"wall_s": round(wall, 2), "rank_devices": devices,
            "allreduce_s": {k: round(v, 4) for k, v in
                            ranks[0]["allreduce_s"].items()},
            "persistent_s": round(ranks[0]["persistent_s"], 3),
            "fold": ranks[0]["fold"]}


# ---------------------------------------------------------------------------
# leg 2: the in-graph tier
# ---------------------------------------------------------------------------

def _train_axes(ndev: int) -> dict:
    """dp x tp x sp over ndev devices: 1x1x1 on one chip, 1x2x2 on four."""
    tp = 2 if ndev % 2 == 0 else 1
    sp = 2 if (ndev // tp) % 2 == 0 else 1
    return {"dp": ndev // (tp * sp), "tp": tp, "sp": sp}


def leg_ingraph(sz: dict, platform: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    import tpu_mpi as MPI
    from tpu_mpi import xla
    from tpu_mpi.models.transformer import (TransformerConfig,
                                            transformer_init,
                                            transformer_train_step)

    devs = jax.devices()
    nd, n = len(devs), sz["n"]
    c = n // nd
    const = nd * (nd - 1) // 2
    mesh = xla.make_mesh({"x": nd})
    shard = NamedSharding(mesh, P("x"))
    facts: dict = {"devices": nd}

    def on_mesh(fn, length):
        """A global [length] array sharded over x, from its index formula."""
        return jax.jit(lambda: fn(jnp.arange(length, dtype=jnp.int32))
                       .astype(jnp.float32), out_shardings=shard)()

    # device d's shard is pattern(i) + d
    x = on_mesh(lambda j: (j % n) % PERIOD + j // n, nd * n)
    ring = [(d + 1) % nd for d in range(nd)]
    cases = {
        "allreduce": (lambda v: xla.allreduce(v, MPI.SUM, axis="x"),
                      lambda j: nd * ((j % n) % PERIOD) + const, nd * n),
        # device d's (nd, n) stack flattened: row s is source s's shard
        "allgather": (lambda v: xla.allgather(v, axis="x").reshape(-1),
                      lambda j: (j % n) % PERIOD + (j // n) % nd,
                      nd * nd * n),
        "reduce_scatter": (lambda v: xla.reduce_scatter(v, MPI.SUM,
                                                        axis="x"),
                           lambda j: nd * (j % PERIOD) + const, n),
        # device d, local i: source s = i // c sent its block d
        "alltoall": (lambda v: xla.alltoall(v, axis="x"),
                     lambda j: ((j // n) * c + (j % n) % c) % PERIOD
                     + (j % n) // c, nd * n),
        "sendrecv": (lambda v: xla.sendrecv(v, dest=ring, axis="x"),
                     lambda j: (j % n) % PERIOD + (j // n - 1) % nd, nd * n),
    }
    facts["collectives_s"] = {}
    for name, (fn, formula, length) in cases.items():
        f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("x"),
                                  out_specs=P("x")))
        t0 = time.perf_counter()
        compiled = f.lower(x).compile()
        t1 = time.perf_counter()
        y = compiled(x)
        y.block_until_ready()
        t2 = time.perf_counter()
        assert y.shape == (length,), (name, y.shape)
        assert {d.platform for d in y.devices()} == {platform}, name
        assert bool(jnp.array_equal(y, on_mesh(formula, length))), \
            f"xla.{name}: wrong values"
        for j in (0, length // 2 + 1, length - 1):     # host readback
            assert float(y[j]) == float(formula(np.int64(j))), (name, j)
        facts["collectives_s"][name] = {"compile": round(t1 - t0, 3),
                                        "run": round(t2 - t1, 4)}
        del y

    # -- the flagship train step, full width ----------------------------------
    tr = dict(sz["train"])
    batch, steps, lr = tr.pop("batch"), tr.pop("steps"), tr.pop("lr")
    axes = _train_axes(nd)
    cfg = TransformerConfig(dtype=jnp.bfloat16, **tr)
    tmesh = xla.make_mesh(axes)
    step, specs = transformer_train_step(cfg, tmesh, lr=lr)
    # inputs placed as the step shards them, so the compiled step's outputs
    # feed straight back in
    params = jax.device_put(
        transformer_init(jax.random.PRNGKey(0), cfg),
        jax.tree.map(lambda s: NamedSharding(tmesh, s), specs))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, cfg.max_seq),
                                0, cfg.vocab)
    tokens, labels = jax.device_put(
        (tokens, jnp.roll(tokens, -1, axis=1)),
        NamedSharding(tmesh, P("dp", "sp")))
    t0 = time.perf_counter()
    compiled = step.lower(params, tokens, labels).compile()
    compile_s = time.perf_counter() - t0
    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, loss = compiled(params, tokens, labels)
        losses.append(float(loss))                      # host readback
        step_s.append(time.perf_counter() - t0)
    assert all(np.isfinite(v) for v in losses), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    leaf = jax.tree_util.tree_leaves(params)[0]
    assert {d.platform for d in leaf.devices()} == {platform}
    facts["train"] = {"mesh": axes, "config": dict(sz["train"]),
                      "compile_s": round(compile_s, 2),
                      "step_s": [round(s, 4) for s in step_s],
                      "losses": [round(v, 4) for v in losses]}
    return facts


# ---------------------------------------------------------------------------
# leg 3: the Pallas kernels
# ---------------------------------------------------------------------------

def leg_kernels(sz: dict, platform: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    import tpu_mpi as MPI
    from tpu_mpi import xla
    from tpu_mpi.xla import pallas_kernels as pk

    interpret = platform != "tpu"       # explicit, either way
    devs = jax.devices()
    nd = len(devs)
    mesh = xla.make_mesh({"x": nd})
    shard = NamedSharding(mesh, P("x"))
    facts: dict = {"n": nd, "interpret": interpret, "seconds": {}}

    def timed(name, f, *args):
        t0 = time.perf_counter()
        compiled = f.lower(*args).compile()
        t1 = time.perf_counter()
        if not interpret:
            assert "tpu_custom_call" in compiled.as_text(), \
                f"{name}: no Mosaic custom call in the compiled module"
        out = compiled(*args)
        jax.block_until_ready(out)
        facts["seconds"][name] = {"compile": round(t1 - t0, 3),
                                  "run": round(time.perf_counter() - t1, 4)}
        return out

    def smap(fn, nargs=1):
        return jax.jit(jax.shard_map(fn, mesh=mesh,
                                     in_specs=(P("x"),) * nargs,
                                     out_specs=P("x"), check_vma=False))

    # -- the five RDMA collectives against their XLA counterparts --------------
    # per-device length divisible by nd (block collectives) but with a row
    # count that is not a multiple of any sublane tile, so the padding runs
    m = sz["ring"] // nd * nd
    perm = [(d + 1) % nd for d in range(nd)]
    for dtype in (jnp.float32, jnp.bfloat16):
        tag = jnp.dtype(dtype).name
        x = jax.jit(lambda: ((jnp.arange(nd * m, dtype=jnp.int32) % 13)
                             + jnp.arange(nd * m, dtype=jnp.int32) // m)
                    .astype(dtype), out_shardings=shard)()
        pairs = {
            "collective_permute": (
                lambda v: pk.collective_permute(v, perm, axis="x",
                                                interpret=interpret),
                lambda v: xla.sendrecv(v, dest=perm, axis="x")),
            "ring_allgather": (
                lambda v: pk.ring_allgather(v, axis="x",
                                            interpret=interpret).reshape(-1),
                lambda v: xla.allgather(v, axis="x").reshape(-1)),
            "ring_allreduce": (
                lambda v: pk.ring_allreduce(v, MPI.SUM, axis="x",
                                            interpret=interpret),
                lambda v: xla.allreduce(v, MPI.SUM, axis="x")),
            "ring_reduce_scatter": (
                lambda v: pk.ring_reduce_scatter(v, MPI.SUM, axis="x",
                                                 interpret=interpret),
                lambda v: xla.reduce_scatter(v, MPI.SUM, axis="x")),
            "pairwise_alltoall": (
                lambda v: pk.pairwise_alltoall(v, axis="x",
                                               interpret=interpret),
                lambda v: xla.alltoall(v, axis="x")),
        }
        for name, (kern, ref) in pairs.items():
            if nd == 1 and name not in ("collective_permute",
                                        "ring_allgather"):
                continue    # a ring of one returns its operand: no kernel
            got = timed(f"{name}[{tag}]", smap(kern), x)
            want = smap(ref)(x)
            assert {d.platform for d in got.devices()} == {platform}
            assert bool(jnp.array_equal(got, want)), f"{name}[{tag}] != XLA"
            assert np.array_equal(np.asarray(got[:8], np.float32),
                                  np.asarray(want[:8], np.float32))
    if nd == 1:
        facts["identity_at_n1"] = ["ring_allreduce", "ring_reduce_scatter",
                                   "pairwise_alltoall"]

    # an operand the ring kernels cannot hold in VMEM is refused by name
    big = jax.ShapeDtypeStruct((nd * (1 << 26),), jnp.float32, sharding=shard)
    try:
        smap(lambda v: pk.ring_allgather(v, axis="x",
                                         interpret=interpret)).lower(big)
    except ValueError as e:
        assert "VMEM" in str(e), e
        facts["oversize"] = "ValueError"
    else:
        raise AssertionError("a 256 MiB ring operand was not refused")

    # -- fused causal ring attention against a float32 jnp reference -----------
    t, d = sz["attn"]
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.device_put(
        jax.random.normal(kk, (nd * t, d), jnp.float32).astype(jnp.bfloat16),
        shard) for kk in keys)
    got = timed("ring_attention[bfloat16]", smap(
        lambda a, b, c: pk.ring_attention(a, b, c, axis="x", causal=True,
                                          interpret=interpret), 3), q, k, v)

    @jax.jit
    def reference(q, k, v):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        s = jnp.dot(q, k.T, precision="highest") / np.sqrt(d)
        s = jnp.where(jnp.tril(jnp.ones(s.shape, bool)), s, -jnp.inf)
        return jnp.dot(jax.nn.softmax(s, axis=-1), v, precision="highest")

    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - reference(q, k, v))))
    # bf16 operands and a bf16 result against float32 math: half a bf16 ulp
    # of O(1) outputs is 4e-3, the probabilities' rounding adds the rest
    assert err < 2e-2, f"ring_attention max abs err {err}"
    facts["attention_max_abs_err"] = err

    # -- the experts' grouped product against lax.ragged_dot -------------------
    # one product inside the kernel's contract, values and both gradients;
    # an uneven split with an empty group and a group smaller than a tile
    m, kdim, ndim, g = sz["grouped"]
    sizes = np.zeros(g, np.int64)
    sizes[1:] = np.arange(1, g) ** 2
    sizes = sizes * (m - 40) // sizes.sum()
    sizes[1] += m - sizes.sum()
    sizes = jnp.asarray(sizes, jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    rows, weights, dout = (
        jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16)
        for kk, shape in zip(keys, [(m, kdim), (g, kdim, ndim), (m, ndim)]))
    weights = (weights.astype(jnp.float32) * kdim ** -0.5).astype(jnp.bfloat16)

    def with_grads(product):
        def run(rows, weights, dout):
            out, vjp = jax.vjp(lambda r, w: product(r, w, sizes), rows,
                               weights)
            return (out,) + vjp(dout)
        return jax.jit(run)

    got = timed("grouped_matmul[bfloat16]", with_grads(
        lambda r, w, s: pk.grouped_matmul(r, w, s, interpret=interpret)),
        rows, weights, dout)
    want = with_grads(jax.lax.ragged_dot)(rows, weights, dout)
    facts["grouped_matmul_rel_err"] = {}
    for name, a, b in zip(("out", "d_lhs", "d_rhs"), got, want):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        # both round a float32 sum to bf16 once; the sums' orders differ
        assert rel < 1e-2, f"grouped_matmul {name} off by {rel}"
        facts["grouped_matmul_rel_err"][name] = rel
    # -- rows summed into indexed places ----------------------------------------
    # a held expert layer's combine at its widest (PR 33): weighed bf16 rows,
    # the last quarter masked out, sorted by place and summed in float32 by
    # `grouped_row_sums` (the weights' gradient kernel, a one-hot that
    # carries the weight as its left operand), against XLA's scatter-add
    from tpu_mpi.parallel import ep
    m, places, width = sz["row_sums"]
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    rows = jax.random.normal(keys[0], (m, width),
                             jnp.float32).astype(jnp.bfloat16)
    place = jax.random.randint(keys[1], (m,), 0, places, jnp.int32)
    scale = jax.random.uniform(keys[2], (m,), jnp.float32)
    live = jnp.arange(m) < 3 * m // 4
    got = timed("grouped_row_sums[float32]", jax.jit(
        lambda lhs, moved, counts: pk.grouped_row_sums(
            lhs, moved, counts, interpret=interpret)),
        *ep._by_place(rows, place, places, scale, live)).reshape(places,
                                                                 width)
    want = jnp.zeros((places, width), jnp.float32).at[place].add(
        jnp.where(live[:, None], rows.astype(jnp.float32) * scale[:, None], 0))
    rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert rel < 1e-5, f"grouped_row_sums off by {rel}"     # float32 both
    facts["row_sums_rel_err"] = rel
    # -- the local attention kernel under a window, grouped heads ---------------
    # one block as a layer kind with a window has it (query head j reads
    # key/value head j // group), forward and backward, against the plain
    # path (`parallel.ring.plain_attention`)
    from tpu_mpi.parallel import ring
    h, hk, t, dh, window = sz["window_attn"]
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    q, k, v, do = (
        jax.random.normal(kk, (1, n, t, dh), jnp.float32).astype(jnp.bfloat16)
        for kk, n in zip(keys, (h, hk, hk, h)))

    def attn_with_grads(attend):
        def run(q, k, v, do):
            out, vjp = jax.vjp(attend, q, k, v)
            return (out,) + vjp(do)
        return jax.jit(run)

    got = timed("causal_attention[window, grouped heads]", attn_with_grads(
        lambda q, k, v: pk.causal_attention(q, k, v, window=window,
                                            interpret=interpret)), q, k, v, do)
    want = attn_with_grads(
        lambda q, k, v: ring.plain_attention(q, k, v, window))(q, k, v, do)
    facts["window_attention_rel_err"] = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        # bf16 on both sides; the plain path rounds its probabilities and
        # sums a group's dk and dv in another order
        assert rel < 3e-2, f"causal_attention[window] {name} off by {rel}"
        facts["window_attention_rel_err"][name] = rel
    # -- the causal convolution and its silu, read in place and cut ------------
    # against the plain path in float32 on the same bfloat16 operands. (On
    # the chip alone does a tap's unaligned load read real VMEM: through a
    # view of one lane tile of a wider scratch it read the NEXT LANE TILE's
    # rows, which the interpret machine computed right: PERF.md, PR 47.)
    from tpu_mpi.parallel import ssm
    from tpu_mpi.xla import conv_kernels
    t, columns, start, channels, cuts = sz["conv"]
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    row, taps, bias, dout = (
        jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16)
        for kk, shape in zip(keys, ((2, t, columns), (4, channels),
                                    (channels,), (2, t, channels))))

    def conv_with_grads(conv, dtype):
        def run(*operands):
            out, vjp = jax.vjp(lambda *a: jnp.concatenate(conv(*a), -1),
                               *(o.astype(dtype) for o in operands[:3]))
            return (out,) + vjp(operands[3].astype(dtype))
        return jax.jit(run)

    got = timed("conv_silu[in place, cut]", conv_with_grads(
        lambda x, w, b: conv_kernels.conv_silu(
            x, w, b, start=start, cuts=cuts, interpret=interpret),
        jnp.bfloat16), row, taps, bias, dout)
    want = conv_with_grads(lambda x, w, b: jnp.split(jax.nn.silu(
        ssm.causal_conv(x[..., start:start + channels], w, b)), cuts, -1),
        jnp.float32)(row, taps, bias, dout)
    facts["conv_rel_err"] = {}
    for name, a, b in zip(("out", "dx", "dw", "dbias"), got, want):
        a = a.astype(jnp.float32)
        rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert rel < 1e-2, f"conv_silu {name} off by {rel}"  # one rounding
        facts["conv_rel_err"][name] = rel
    # -- the delta-rule scan with a decay a key channel -------------------------
    # against the plain path on the same bfloat16 operands at the Kimi
    # cell's head shape (heads of 128 in twos, chunks of 64, decays from a
    # thousandth to 1.6 a token, whose sums pass -100 inside a chunk): o and
    # the five gradients, each beside its limit. Both round the operands of
    # the same products; the kernel sums them in another order.
    from tpu_mpi.parallel import delta
    from tpu_mpi.xla import delta_kernels
    t, heads = sz["channel_scan"]
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    wide = (2, t, heads, delta_kernels.DELTA_WIDTH)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))
    bf16 = lambda x: x.astype(jnp.bfloat16)
    scan_in = (
        bf16(unit(jax.random.normal(keys[0], wide)) * wide[-1] ** -0.5),
        bf16(unit(jax.random.normal(keys[1], wide))),
        bf16(jax.random.normal(keys[2], wide)),
        -jnp.exp(jax.random.uniform(keys[3], wide, minval=np.log(1e-3),
                                    maxval=np.log(1.6))),
        jax.nn.sigmoid(jax.random.normal(keys[4], wide[:3])),
        bf16(jax.random.normal(keys[5], wide)))

    def scan_with_grads(scan):
        def run(*operands):
            out, vjp = jax.vjp(scan, *operands[:5])
            return (out,) + vjp(operands[5])
        return jax.jit(run)

    got = timed("delta_scan[a decay a channel]", scan_with_grads(
        lambda *a: delta_kernels.delta_scan(*a, interpret=interpret)),
        *scan_in)
    want = scan_with_grads(lambda *a: delta._chunked(
        *a, delta_kernels.DELTA_CHUNK))(*scan_in)
    facts["channel_scan_rel_err"], limit = {}, 3e-2
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert bool(jnp.isfinite(a).all()), f"delta_scan {name} not finite"
        rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        _log(f"kernels: delta_scan[a decay a channel] {name} off the plain "
             f"path by {rel:.3e} (limit {limit:.0e})")
        assert rel < limit, f"delta_scan[a decay a channel] {name} off by {rel}"
        facts["channel_scan_rel_err"][name] = rel
    # -- a delta-rule mixer's per-head norms over rows --------------------------
    # q's L2 norm with its scale, and the gated RMSNorm with the gate's
    # product inside the kernel, against the same float32 arithmetic on the
    # same bfloat16 operands: the result and every gradient. (On the chip
    # alone is a head's lane offset a loop's index into real VMEM.)
    from tpu_mpi.xla import head_norm_kernels
    t, heads, rank = sz["head_norm"]
    width = head_norm_kernels.HEAD_WIDTH
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    rows, dout, g_in, w_gate = (
        (jax.random.normal(kk, shape, jnp.float32) * size).astype(jnp.bfloat16)
        for kk, shape, size in zip(keys, (
            (2, t, heads * width), (2, t, heads * width), (2, t, rank),
            (rank, heads * width)), (1.0, 1.0, 1.0, rank ** -0.5)))
    leaf = (1.0 + 0.1 * jax.random.normal(keys[4], (width,))).astype(
        jnp.bfloat16)

    def plain_norm(x, scale, *gate_from, mean, eps):
        by_head = x.astype(jnp.float32).reshape(2, t, heads, width)
        squares = jnp.square(by_head)
        stat = jnp.mean(squares, -1, keepdims=True) if mean \
            else jnp.sum(squares, -1, keepdims=True)
        out = by_head * jax.lax.rsqrt(stat + eps) * scale.astype(jnp.float32)
        if gate_from:
            out = out * jax.nn.sigmoid(jnp.dot(
                *gate_from, preferred_element_type=jnp.float32)).reshape(
                    out.shape)
        return out.reshape(x.shape)

    def norm_with_grads(norm):
        def run(dout, *operands):
            out, vjp = jax.vjp(norm, *operands)
            return (out,) + vjp(dout.astype(out.dtype))
        return jax.jit(run)
    facts["head_norm_rel_err"] = {}
    for what, kernel, plain, operands in (
            ("l2", lambda x: head_norm_kernels.l2_norm(
                x, scale=width ** -0.5, interpret=interpret),
             lambda x: plain_norm(x, jnp.float32(width ** -0.5), mean=False,
                                  eps=1e-6), (rows,)),
            ("gated", lambda *a: head_norm_kernels.gated_rms_norm(
                *a, act="sigmoid", eps=1e-5, interpret=interpret),
             lambda *a: plain_norm(*a, mean=True, eps=1e-5),
             (rows, leaf, g_in, w_gate))):
        got = timed(f"head_norm[{what}]", norm_with_grads(kernel), dout,
                    *operands)
        want = norm_with_grads(plain)(dout, *operands)
        for name, a, b in zip(("out", "dx", "dscale", "dg", "dw"), got, want):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            # one rounding of the result; the product's cotangent enters
            # the MXU rounded to bfloat16, as the plain path's does
            assert rel < 2e-2, f"head_norm[{what}] {name} off by {rel}"
            facts["head_norm_rel_err"][f"{what} {name}"] = rel
    # which route the program itself gives an expert layer here, and what
    # its compiled forward and backward hold
    route, calls = _expert_layer_route(sz["expert_layer"])
    facts["grouped_matmul"] = route
    facts["expert_layer_custom_calls"] = calls
    if not interpret:
        # at OLMoE's widths on the chip: the kernel in all nine products
        assert (route, calls) == ("kernel", 9), (route, calls)
    facts["expert_rows"] = _expert_rows_way(sz["expert_rows"],
                                            on_chip=not interpret)
    return facts


def _expert_rows_way(sizes: tuple, on_chip: bool) -> dict:
    """One layer's row movement (tokens, d_model, experts, top-k; bfloat16):
    `parallel.ep.moe_dropless` around experts that only weigh their rows,
    value and the gradients to tokens and weights against the plain form
    (tokens[order // k], the rows permuted back, times weights, summed),
    and each of the four passes alone: milliseconds and GB/s over the
    bytes XLA's pass moves (R = slots x d x 2 bytes: a copy into expert
    order reads and writes R; a sum back writes the rows it gathered,
    reads them again and writes the tokens, 3.125 R at top-8). Off the
    chip the passes run once and their times are not reported."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from tpu_mpi.parallel import ep
    t, d, experts, k = sizes
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    tokens, c = (jax.random.normal(kk, (t, d), jnp.float32).astype(jnp.bfloat16)
                 for kk in keys[:2])
    weights, idx = lax.top_k(jax.nn.softmax(
        2.0 * jax.random.normal(keys[2], (t, experts))), k)
    weights, idx = weights.astype(jnp.bfloat16), idx.astype(jnp.int32)

    def weigh(rows, _sizes, scale):
        return rows * scale[:, None]

    def plain(tokens, weights):
        flat = idx.reshape(t * k)
        order = jnp.argsort(flat, stable=True)
        back = tokens[order // k][jnp.argsort(order)].reshape(t, k, d)
        return jnp.sum(back * weights[..., None], axis=1)

    def both(layer):
        def total(tokens, weights):
            y = layer(tokens, weights)
            return jnp.sum(y.astype(jnp.float32) * c.astype(jnp.float32)), y
        return jax.jit(jax.grad(total, argnums=(0, 1), has_aux=True))
    (gt, gw), y = both(lambda tokens, weights: ep.moe_dropless(
        tokens, idx, weights, weigh, experts)[0])(tokens, weights)
    (wt, ww), want = both(plain)(tokens, weights)
    out = {"rel_err": {}, "passes": {}}
    for name, a, b in zip(("out", "d_tokens", "d_weights"), (y, gt, gw),
                          (want, wt, ww)):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        # bf16 on both sides; the plain form rounds every product before it
        # sums, the layer sums in float32 and rounds once
        assert rel < 3e-2, f"moe_dropless {name} off by {rel}"
        out["rel_err"][name] = rel
    order, _scale = ep._by_expert(weights, idx.reshape(t * k))
    inverse = jnp.argsort(order).astype(jnp.int32).reshape(t, k)
    rows = jax.random.normal(keys[3], (t * k, d),
                             jnp.float32).astype(jnp.bfloat16)
    r = t * k * d * 2
    for name, fn, arg, nbytes in (
            ("dispatch forward", ep._rows_of_tokens, tokens, 2 * r),
            ("combine forward", ep._tokens_of_rows, rows, (3 + 1 / k) * r),
            ("combine backward", ep._rows_of_tokens, c, 2 * r),
            ("dispatch backward", ep._tokens_of_rows, rows, (3 + 1 / k) * r)):
        run = jax.jit(fn)
        jax.block_until_ready(run(arg, order, inverse))
        t0 = time.perf_counter()
        for _ in range(5):
            got = run(arg, order, inverse)
        jax.block_until_ready(got)
        ms = (time.perf_counter() - t0) / 5 * 1e3
        out["passes"][name] = {"ms": round(ms, 3), "GB/s": round(
            nbytes / ms / 1e6, 1)} if on_chip else "not measured"
    return out


def _expert_layer_route(sizes: tuple) -> tuple:
    """Compile one expert layer (d_model, d_ff, experts, top-k, seq; batch
    2), forward and backward, from shapes. Returns the route its three
    products took by the program's own counter (``kernel`` or
    ``ragged_dot``) and the number of Mosaic custom calls in its optimized
    HLO. On the kernel's route nothing of XLA's own grouped product is left:
    no `ragged-dot` instruction, no transposing copy of `w_gate`, `w_in` or
    `w_out`."""
    import re
    import jax
    import jax.numpy as jnp
    from tpu_mpi import perfvars
    from tpu_mpi.models import transformer as tf
    d, f, experts, top, seq = sizes
    cfg = tf.TransformerConfig(
        vocab=512, d_model=d, n_heads=d // 128, n_layers=1, d_ff=f,
        max_seq=seq, dtype=jnp.bfloat16, norm_eps=1e-5, qk_norm=True,
        n_experts=experts, experts_per_tok=top, router_aux_coef=0.01,
        tie_embeddings=False)
    layer = jax.eval_shape(lambda k: tf.transformer_init(k, cfg),
                           jax.random.key(0))["layers"][0]
    y = jax.ShapeDtypeStruct((2, seq, cfg.d_model), cfg.dtype)

    def summed(layer, y):
        out, _routed = tf._expert_ffn(cfg, layer, y)
        return jnp.sum(out.astype(jnp.float32) ** 2)
    before = perfvars.snapshot()["gmm_lowerings"]
    text = jax.jit(jax.value_and_grad(summed, argnums=(0, 1))).lower(
        layer, y).compile().as_text()
    after = perfvars.snapshot()["gmm_lowerings"]
    took = {k: after[k] - before[k] for k in after}
    assert sorted(took.values()) == [0, 3], took    # three products, one route
    route = max(took, key=took.get)
    if route == "kernel":
        assert "ragged-dot" not in text, "the expert layer still holds " \
            "XLA's ragged-dot"
        copies = re.findall(
            r"copy\([^\n]*op_name=\"[^\"]*w_(?:gate|in|out)", text)
        assert not copies, f"{len(copies)} copies of the experts' weights"
    return route, text.count("tpu_custom_call")


# ---------------------------------------------------------------------------
# leg 4: the server
# ---------------------------------------------------------------------------

def leg_serve(sz: dict, platform: str) -> dict:
    from tpu_mpi import serve

    token = "chip-smoke"
    t0 = time.perf_counter()
    broker = serve.Broker(nranks=NRANKS, token=token, infer=True)
    broker.run_in_thread()
    try:
        up = time.perf_counter() - t0
        s = serve.attach(broker.address, token=token, tenant="smoke")
        try:
            prompts = ([1, 2, 3, 4, 5, 6, 7], [1, 2, 3, 4, 5, 6, 7],
                       list(range(40, 56)))
            t0 = time.perf_counter()
            outs = [s.generate(p, max_new=sz["max_new"]) for p in prompts]
            gen = time.perf_counter() - t0
        finally:
            s.detach()
        vocab = broker.infer_engine.cfg.vocab
        transport = broker.transport
    finally:
        broker.close()
    assert all(len(o) == sz["max_new"] for o in outs), outs
    assert all(0 <= tok < vocab for o in outs for tok in o), outs
    assert outs[0] == outs[1], "identical prompts decoded differently"
    return {"device_work": "none — host engine", "transport": transport,
            "requests": len(outs), "broker_up_s": round(up, 3),
            "generate_s": round(gen, 3)}


LEGS = (("host", leg_host), ("ingraph", leg_ingraph),
        ("kernels", leg_kernels), ("serve", leg_serve))


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------

def _refuse(why: str) -> int:
    print(f"chip_smoke: refusing to run: {why}", file=sys.stderr)
    return 2


def main(argv: list) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny-cpu", action="store_true",
                    help="toy sizes on the CPU backend, kernels interpreted "
                         "(for tests/test_chip_smoke.py only)")
    args = ap.parse_args(argv)

    want = "cpu" if args.tiny_cpu else "tpu"
    if not args.tiny_cpu:
        os.environ["TPU_MPI_BACKEND"] = "tpu"
    from tpu_mpi import _native
    from tpu_mpi._runtime import enable_compile_cache
    from tpu_mpi.implementations import CAPABILITIES, tpu_generation
    # g++ is the one child process this script starts; it runs here, before
    # JAX is touched, so nothing is spawned once the chip is held
    _native.load()
    cache = enable_compile_cache()      # before the backend comes up
    import jax
    import jaxlib
    try:
        found = jax.default_backend()
    except RuntimeError as e:
        return _refuse(f"JAX could not initialize a backend ({e})")
    if found != want:
        return _refuse(f"JAX's default backend is {found!r}, not {want!r} "
                       f"(JAX_PLATFORMS="
                       f"{os.environ.get('JAX_PLATFORMS', '')!r})")
    dev = jax.devices()[0]
    if not args.tiny_cpu and tpu_generation() not in CAPABILITIES:
        return _refuse(f"device_kind {dev.device_kind!r} is not in "
                       f"tpu_mpi.implementations.CAPABILITIES")
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:       # noqa: BLE001 - a version string, nothing more
        libtpu = "unknown"
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    _log(f"platform: {dev.platform}")
    _log(f"device_kind: {dev.device_kind}")
    _log(f"device_count: {device['count']}")
    _log(f"versions: jax {jax.__version__} jaxlib {jaxlib.__version__} "
         f"libtpu {libtpu} python {sys.version.split()[0]}")
    _log(f"compile_cache: {cache}")

    sz = TINY if args.tiny_cpu else FULL
    for name, leg in LEGS:
        t0 = time.perf_counter()
        try:
            facts = leg(sz, dev.platform)
        except BaseException:
            traceback.print_exc()
            print(f"chip_smoke: leg {name} FAILED", file=sys.stderr)
            return 1
        _log(f"leg {name}: ok in {time.perf_counter() - t0:.1f}s "
             f"{json.dumps(facts, ensure_ascii=False)}")
    _log(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    if "--host-rank" in sys.argv[1:]:
        # a rank: run the one imported copy of this module (its jitted
        # helpers compile once for all rank threads) and return, never exit
        import chip_smoke
        chip_smoke._host_rank_entry(sys.argv[1:])
    else:
        sys.exit(main(sys.argv[1:]))
